"""Per-node launcher.

Reference: ``deepspeed/launcher/launch.py`` (``main`` :67) — decode the
world info, set ``MASTER_*``/rank env vars, spawn one process per local
accelerator, kill the pack if any child dies (:129-167).

TPU difference: one JAX process owns every chip of its host, and a chip
belongs to one process at a time, so on a TPU host the per-rank fan-out
is a single child — more than one (a hostfile with several slots for the
node, ``--procs_per_node`` or the runner's ``--num_gpus`` above 1) is
refused unless ``JAX_PLATFORMS=cpu`` keeps the children off the chips
(CPU-cluster and debug runs, the multi-process tests).  The contract
stays: env-var bootstrap (MASTER_ADDR/PORT, RANK, LOCAL_RANK, WORLD_SIZE
consumed by ``comm/distributed.init_distributed``), signal propagation,
non-zero exit on child failure.

Supervision (docs/resilience.md): children get ``DS_SUPERVISION_PORT``
(derived from ``master_port``) so the heartbeat side channel needs no
config edit.  The kill-on-failure contract becomes failure-domain
aware: a child dying to a SIGNAL (the hardware-loss signature —
SIGKILL, SIGSEGV, ...) opens a ``--peer_grace`` window in which the
surviving ranks may detect the death themselves, commit their verified
emergency tags, and exit ``43``/``44`` — only then is the pack killed.
A plain non-zero ``sys.exit`` still kills the pack immediately (a bug
is not a failure domain).  The final exit code prefers ``44`` ("a
survivor saved") over ``43`` over the crash code, and the per-rank exit
codes land in ``$DS_SUPERVISION_DIR/node<r>_status.json`` for the
runner's elastic restart to re-derive the surviving world from.
"""
from __future__ import annotations

import argparse
import base64
import json
import os
import signal
import subprocess
import sys
import time
from typing import Dict, List

from deepspeed_tpu.utils.logging import logger

EXIT_PREEMPTED_SAVED = 43
EXIT_PEER_FAILED_SAVED = 44
_SAVED_CODES = (EXIT_PREEMPTED_SAVED, EXIT_PEER_FAILED_SAVED)


def parse_args(args=None):
    parser = argparse.ArgumentParser(description="deepspeed_tpu per-node launcher")
    parser.add_argument("--node_rank", type=int, default=0)
    parser.add_argument("--master_addr", default="127.0.0.1", type=str)
    parser.add_argument("--master_port", default=29500, type=int)
    parser.add_argument("--world_info", default="e30=", type=str, help="base64 json {host: [slots]}")
    parser.add_argument("--procs_per_node", type=int, default=1)
    parser.add_argument(
        "--peer_grace", type=float, default=float(os.environ.get("DS_PEER_GRACE", "30")),
        help="seconds survivors get to emergency-save (exit 43/44) after a sibling "
             "dies to a signal, before the pack is killed",
    )
    parser.add_argument("training_script", type=str)
    parser.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return parser.parse_args(args=args)


def decode_world_info(encoded: str) -> dict:
    return json.loads(base64.urlsafe_b64decode(encoded).decode())


def main(args=None):
    args = parse_args(args)
    world_info = decode_world_info(args.world_info)
    hosts = list(world_info.keys())
    if hosts:
        # ranks come from the world info itself (supports heterogeneous
        # slot counts: rank = slots of earlier hosts + local_rank)
        slots = [len(v) for v in world_info.values()]
        world_size = sum(slots)
        procs_per_node = slots[args.node_rank]
        rank_offset = sum(slots[: args.node_rank])
    else:
        procs_per_node = max(1, args.procs_per_node)
        world_size = procs_per_node
        rank_offset = args.node_rank * procs_per_node

    if procs_per_node > 1 and os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise SystemExit(
            f"launch: {procs_per_node} processes asked for on this node, but one JAX "
            "process owns every local chip and a second one fails or hangs waiting "
            "for them.  Start one process per host (one hostfile slot, no --num_gpus), "
            "or set JAX_PLATFORMS=cpu for a multi-process CPU run."
        )

    children: List[subprocess.Popen] = []

    def kill_all(signum=None, frame=None):
        for p in children:
            if p.poll() is None:
                p.terminate()
        for p in children:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        if signum is not None:
            sys.exit(128 + signum)

    signal.signal(signal.SIGINT, kill_all)
    signal.signal(signal.SIGTERM, kill_all)

    # supervision side channel: every rank derives the same endpoint
    # from the launch args — no per-job config edit needed
    sup_port = os.environ.get("DS_SUPERVISION_PORT") or str(args.master_port + 17)
    sup_addr = os.environ.get("DS_SUPERVISION_ADDR") or args.master_addr

    for local_rank in range(procs_per_node):
        rank = rank_offset + local_rank
        env = os.environ.copy()
        env.update(
            MASTER_ADDR=args.master_addr,
            MASTER_PORT=str(args.master_port),
            RANK=str(rank),
            LOCAL_RANK=str(local_rank),
            WORLD_SIZE=str(world_size),
            DS_SUPERVISION_PORT=sup_port,
            DS_SUPERVISION_ADDR=sup_addr,
        )
        cmd = [sys.executable, "-u", args.training_script, *args.training_script_args]
        logger.info(f"launch: rank {rank}/{world_size} -> {' '.join(cmd)}")
        children.append(subprocess.Popen(cmd, env=env))

    # Reference behavior: the first plain non-zero exit kills every
    # sibling and propagates the code (launch.py:129-167).  Supervision
    # refinement: a SIGNAL death (rc < 0) instead opens a peer-grace
    # window so survivors can emergency-save and exit 43/44 themselves;
    # children exiting 43/44 never trigger the pack-kill at all (they
    # saved — their siblings are about to notice the departure and do
    # the same).
    codes: Dict[int, int] = {}
    crash_code = 0
    grace_deadline = None
    alive = set(range(len(children)))
    while alive:
        for i in list(alive):
            code = children[i].poll()
            if code is None:
                continue
            alive.discard(i)
            codes[i] = code
            if code == 0 or code in _SAVED_CODES:
                if code in _SAVED_CODES:
                    logger.warning(f"launch: rank process {i} exited {code} (saved-and-exited)")
                    # a saved-and-exited rank means its siblings are
                    # (or are about to be) wedged on the missing peer:
                    # arm the same bounded grace a signal death gets, so
                    # supervision-off packs cannot hang forever
                    if alive and grace_deadline is None:
                        grace_deadline = time.monotonic() + max(0.0, args.peer_grace)
                continue
            if code < 0:  # died to a signal: the hardware-loss signature
                sig = -code
                codes[i] = 128 + sig
                crash_code = crash_code or 128 + sig
                if grace_deadline is None:
                    grace_deadline = time.monotonic() + max(0.0, args.peer_grace)
                    logger.error(
                        f"launch: rank process {i} killed by signal {sig}; giving "
                        f"survivors {args.peer_grace:g}s to emergency-save before the pack-kill"
                    )
            else:
                logger.error(f"launch: rank process {i} exited with {code}; terminating job")
                crash_code = crash_code or code
                if grace_deadline is None:
                    # immediate pack-kill — but never SHORTEN a grace
                    # window a signal death already opened (exit 1 after
                    # a peer loss is the documented "save failed" code;
                    # other survivors may still be mid-emergency-save)
                    grace_deadline = time.monotonic()
        if alive and grace_deadline is not None and time.monotonic() >= grace_deadline:
            logger.error(f"launch: terminating {len(alive)} remaining rank process(es)")
            break
        if alive:
            # poll() above already reaps; a waitpid(-1) here would steal
            # exit statuses from Popen and break code propagation
            time.sleep(0.2)
    # survivors terminated at grace expiry were on HEALTHY hardware that
    # simply ran out of time — record them separately so the runner's
    # shrink does not drop their slots alongside the genuinely dead
    pack_killed = sorted(alive)
    if alive:
        kill_all()
        for i in alive:
            # kill_all waited: prefer the REAL exit code it reaped — a
            # survivor whose watchdog turned our SIGTERM into a saved
            # exit 43 must not be recorded as killed
            rc = children[i].returncode
            if rc is None:
                rc = 128 + signal.SIGTERM
            elif rc < 0:
                rc = 128 - rc
            codes.setdefault(i, rc)

    # exit-code aggregation (docs/resilience.md): a survivor that
    # certified a save outranks the crash that caused it — the runner's
    # --restarts keys off 43/44
    all_codes = list(codes.values())
    if any(c == EXIT_PEER_FAILED_SAVED for c in all_codes):
        exit_code = EXIT_PEER_FAILED_SAVED
    elif any(c == EXIT_PREEMPTED_SAVED for c in all_codes):
        exit_code = EXIT_PREEMPTED_SAVED
    else:
        exit_code = crash_code

    status_dir = os.environ.get("DS_SUPERVISION_DIR")
    if status_dir:
        try:
            os.makedirs(status_dir, exist_ok=True)
            status = {
                "node_rank": args.node_rank,
                "rank_offset": rank_offset,
                "codes": {str(rank_offset + i): codes.get(i, 0) for i in range(len(children))},
                "pack_killed": [rank_offset + i for i in pack_killed],
                "exit_code": exit_code,
            }
            tmp = os.path.join(status_dir, f".node{args.node_rank}_status.tmp")
            with open(tmp, "w") as f:
                json.dump(status, f)
            os.replace(tmp, os.path.join(status_dir, f"node{args.node_rank}_status.json"))
        except OSError as e:
            logger.warning(f"launch: could not write supervision status: {e}")
    sys.exit(exit_code)


if __name__ == "__main__":
    main()
