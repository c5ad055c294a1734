#!/usr/bin/env python3
"""Reads the two numbers every limit of ``correct`` is set from: the
largest value sound runs of the program give over a dozen seeds, and the
smallest the control gives — the reference put in the program's place
and computed in ``int8`` operands, the step below the bf16 the
configurations state.  One process, the cell's own size, on the chip:

    python3 benchmark/control.py --workload <cell> --seeds 12 --control-seeds 3 --out chiprun_out/control_<cell>.json

The benchmark's own runs never call this.  ``tests/bench/test_reference.py``
keeps the same comparison as a test at a size a test run can hold.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmark import build, checks, traffic  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.reference_gpt2 import BLOCK_MATRICES  # noqa: E402

def say(msg):
    print(f"[control] {msg}", file=sys.stderr, flush=True)


def _rms(a, b) -> float:
    return float(np.sqrt(np.mean((np.asarray(a) - np.asarray(b)) ** 2)))


def train_numbers(cfg, mix, seed, devices, with_control, forward_only):
    """The train runner's three numbers for the program, and for the
    int8 control in its place.  ``forward_only`` builds no engine: it
    reads ``logprob_rms_err`` alone (the program's loss path needs one
    device, whatever the cell's mesh), and the control in full."""
    ref = build.reference(cfg, seed)
    if forward_only:
        lp = build.loss_path(cfg, int(mix["seq"]), seed)
        probe = lp["probe"]
        out = {"program": {"logprob_rms_err": _rms(lp["nll_prog"], ref.nll(probe))}}
        del lp
    else:
        built = build.train_engine(cfg, int(mix["seq"]), seed, devices)
        out = {"program": build.first_step_numbers(built, build.first_step(built), ref)}
        probe = built["probe"]
        del built
    gc.collect()
    if with_control:
        nll_ref, ref_sweep = ref.nll_and_block_grads(probe)
        nll_ctl, ctl_sweep = build.reference(cfg, seed, precision="int8").nll_and_block_grads(probe)
        nll_ref, nll_ctl = np.asarray(nll_ref), np.asarray(nll_ctl)
        num = den = 0.0
        for (l, g), (l_ctl, g_ctl) in zip(ref_sweep, ctl_sweep):  # both from the last block down, one layer live
            assert l == l_ctl
            # in the program's place the control would have moved each weight by lr against its own gradient's sign
            for n in BLOCK_MATRICES:
                num += float(jnp.sum(jnp.sign(g_ctl[n]) * g[n]))
                den += float(jnp.sum(jnp.abs(g[n])))
        out["control_int8"] = {
            "loss_abs_err": abs(float(nll_ctl.mean()) - float(nll_ref.mean())),
            "logprob_rms_err": _rms(nll_ctl, nll_ref),
            "update_disagreement": 1.0 - num / den}
    return out


def serve_numbers(cfg, mix, seed, devices, with_control, requests):
    """``token_gap_mean``/``max`` for requests the engine serves, for the
    int8 control in its place (teacher-forced on the same contexts: at
    each generated position, the token the control's forward would have
    emitted), and for the program with its own int8 KV pool."""
    pad_to = cfg["serving"]["max_len"]
    ref = build.reference(cfg, seed)

    def served_by(kv_dtype):
        srv = build.serving_engine(cfg, seed, devices, kv_cache_dtype=kv_dtype)
        stream = traffic.request_stream(mix, seed, cfg["model"]["vocab_size"])
        reqs = [next(stream) for _ in range(requests)]
        ids = [srv.submit(r["prompt"], max_new_tokens=r["max_new"]) for r in reqs]
        done = srv.drain()
        out = [{"prompt": r["prompt"], "generated": list(done[i].generated)} for r, i in zip(reqs, ids)]
        del srv, done
        gc.collect()
        return out

    served = served_by("model")
    out = {"program": checks.token_gaps(ref, served, pad_to)}
    if with_control:
        ctl = build.reference(cfg, seed, precision="int8")
        gaps = []
        for r in served:
            context = np.concatenate([r["prompt"], np.asarray(r["generated"], np.int32)])
            n_p = len(r["prompt"])
            padded = np.zeros((1, pad_to), np.int32)
            padded[0, : len(context)] = context
            picks = np.asarray(jnp.argmax(ctl.logits(padded)[0], axis=-1))[n_p - 1 : len(context) - 1]
            # the f32 reference judges, at every generated position, the token the control picks in that context
            gaps += checks.position_gaps(ref, context, n_p, picks, pad_to)
        out["control_int8"] = checks.gap_summary(gaps)
        out["program_int8_kv"] = checks.token_gaps(ref, served_by("int8"), pad_to)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--requests", type=int, default=4, help="serve: requests served per seed")
    ap.add_argument("--forward-only", action="store_true",
                    help="train: no engine, one device: logprob_rms_err from the program's loss path, and the control")
    ap.add_argument("--out", required=True)
    ap.add_argument("--manifest", default=None, help="another BENCHMARK.json (the tests rehearse on a toy one)")
    args = ap.parse_args()
    m = Manifest(args.manifest) if args.manifest else Manifest()
    cell = m.cell(args.workload)
    cfg, mix = m.config(cell["config"]), m.traffic(cell["traffic"])
    devices = jax.devices()[: 1 if args.forward_only else cell["chips"]]
    if devices[0].platform != "tpu" and not os.environ.get("BENCH_CONTROL_ALLOW_CPU"):
        print("control: needs a TPU", file=sys.stderr)
        return 2
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        with_control = i < args.control_seeds
        if cfg["runner"] == "train":
            r = train_numbers(cfg, mix, seed, devices, with_control, args.forward_only)
        else:
            r = serve_numbers(cfg, mix, seed, devices, with_control, args.requests)
        r["seed"] = seed
        rows.append(r)
        say(json.dumps(r))
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": devices[0].device_kind, "rows": rows}, f, indent=1)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
