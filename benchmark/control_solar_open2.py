#!/usr/bin/env python3
"""Reads the numbers the Solar-Open2 cell's limits of ``correct`` are set
from, on the chip, at the cell's own size, in one process:

    python3 benchmark/control_solar_open2.py --workload serve-solar2-reasoning-backlog --seeds 3 --control-seeds 2 \\
        --requests 2 --out chiprun_out/control_solar2.json

Per seed: the engine as the cell builds it serves the first requests of
the cell's traffic, its slots' recurrent state read once on the way
(where the shortest answer is half written); then

* ``program``: ``token_gap_mean`` / ``token_gap_max`` of what it emitted
  and ``state_rel_err`` of what its slots held, against the float32
  reference (the comparisons the runner makes);
* ``control_bf16_state`` (the first ``--control-seeds`` seeds): **the
  program with its recurrent state kept in bfloat16** — the same engine,
  its pool's ``state["s"]`` cast before either program compiles (the
  decode step then takes the ``jnp`` recurrence: the kernel's state is
  float32) — serving the same requests, judged the same way.  The state
  is rounded once a token where the program carries float32;
* ``control_int8`` (the first ``--int8-seeds`` seeds): the reference with
  every matmul operand rounded to int8 put in the program's place,
  teacher-forced on the program's contexts — at each generated position
  the token the control's forward would have emitted, and the state its
  recurrence leaves, judged by the float32 reference.

Each goes through the runner's own ``judged`` with the configuration's
limits: ``correct`` must read true for ``program`` and **false for both
controls**.  The benchmark's own runs never call this.
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

from benchmark import build_solar_open2 as build  # noqa: E402
from benchmark import checks, traffic  # noqa: E402
from benchmark.control_deepseek_v2 import _pad, say  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402


def serve(cfg, seed, devices, reqs, state_dtype=None):
    """What the cell's engine emits for ``reqs``; ``state_dtype`` casts
    the pool's recurrent state before anything compiles."""
    srv = build.serving_engine(cfg, seed, devices, say=say)
    if state_dtype is not None:
        srv.pool.state = {**srv.pool.state, "s": srv.pool.state["s"].astype(state_dtype)}
    from benchmark.runners import serve_solar2

    ids = [srv.submit(r["prompt"], max_new_tokens=r["max_new"]) for r in reqs]
    live = [srv.result(i) for i in ids]
    half = min(r["max_new"] for r in reqs) // 2
    while srv.scheduler.has_work() and not all(q.status == "decode" and len(q.generated) >= half for q in live):
        srv.step()
    states = serve_solar2.state_samples(srv, live, len(live), np.random.default_rng(0))
    done = {**srv.pop_results(), **srv.drain()}
    st = srv.stats()
    served = [{"prompt": r["prompt"], "generated": list(done[i].generated)} for r, i in zip(reqs, ids)]
    notes = {k: st[k] for k in ("kda_decode_kernel", "kda_decode_fallback", "gqa_decode_kernel") if k in st}
    moe = {k: v for k, v in (st.get("moe") or {}).items() if k != "tokens_per_expert"}
    del srv, done, live
    gc.collect()
    return served, states, notes, moe


def verdict(lim, gaps, state, dropped=0):
    """The runner's own ``judged`` over one variant's numbers."""
    from benchmark.runners import serve_solar2

    checks_ = serve_solar2.judged(lim, 1, gaps, state, dropped)
    return {**gaps, **{k: state[k] for k in ("state_rel_err", "state_mantissa_bits", "by_slot_and_layer", "consumed")},
            "checks": checks_, "correct": all(c["ok"] for c in checks_)}


def numbers(cfg, mix, seed, devices, with_control, with_int8, requests):
    from benchmark.runners import serve_solar2  # the runner's own comparison

    lim = cfg["checks"]
    dims = build.dims_of(cfg)
    stream = traffic.request_stream(mix, seed, dims["vocab_size"])
    reqs = [next(stream) for _ in range(requests)]
    pad = int(lim["pad_multiple"])
    served, states, notes, moe = serve(cfg, seed, devices, reqs)
    say(f"seed {seed}: served {[len(s['prompt']) for s in served]} + {[len(s['generated']) for s in served]} tokens; {notes}")
    ref = build.reference(cfg, seed)
    out = {"program": verdict(lim, serve_solar2.served_gaps(ref, served, pad), serve_solar2.state_errors(ref, states, pad),
                              moe.get("dropped_assignments")), "forms": notes, "moe": moe}
    say(f"seed {seed}: program {json.dumps(out['program'])}")
    if with_control:
        low, low_states, low_notes, low_moe = serve(cfg, seed, devices, reqs, state_dtype=jnp.bfloat16)
        out["control_bf16_state"] = verdict(lim, serve_solar2.served_gaps(ref, low, pad),
                                            serve_solar2.state_errors(ref, low_states, pad), low_moe.get("dropped_assignments"))
        out["control_bf16_state"]["forms"] = low_notes
        out["control_bf16_state"]["tokens_differ"] = int(sum(
            int(np.sum(np.asarray(a["generated"]) != np.asarray(b["generated"]))) for a, b in zip(served, low)))
        say(f"seed {seed}: control_bf16_state {json.dumps(out['control_bf16_state'])}")
    if with_int8:
        ctl = build.reference(cfg, seed, precision="int8")
        gaps = []
        for r in served:
            context = np.concatenate([r["prompt"], np.asarray(r["generated"], np.int32)])
            n_p, pad_to = len(r["prompt"]), _pad(len(context), int(lim["pad_multiple"]))
            padded = np.zeros((1, pad_to), np.int32)
            padded[0, : len(context)] = context
            picks = np.asarray(jnp.argmax(ctl.logits(padded)[0], axis=-1))[n_p - 1: len(context) - 1]
            gaps += checks.position_gaps(ref, context, n_p, picks, pad_to)
        held = []  # the state the control's own recurrence leaves after the contexts the program's slots had consumed
        for s in states:
            padded = np.zeros((_pad(len(s["context"]), pad),), np.int32)
            padded[: len(s["context"])] = s["context"]
            held.append({"context": s["context"], "state": ctl.states(padded, len(s["context"]))})
        out["control_int8"] = verdict(lim, checks.gap_summary(gaps), serve_solar2.state_errors(ref, held, pad))
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--int8-seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--requests", type=int, default=2, help="requests served per seed")
    ap.add_argument("--out", required=True)
    ap.add_argument("--manifest", default=None, help="another BENCHMARK.json (the tests rehearse on a toy one)")
    args = ap.parse_args()
    m = Manifest(args.manifest) if args.manifest else Manifest()
    cell = m.cell(args.workload)
    cfg, mix = m.config(cell["config"]), m.traffic(cell["traffic"])
    devices = jax.devices()[:1]
    if devices[0].platform != "tpu" and not os.environ.get("BENCH_CONTROL_ALLOW_CPU"):
        print("control: needs a TPU", file=sys.stderr)
        return 2
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        r = numbers(cfg, mix, seed, devices, i < args.control_seeds, i < args.int8_seeds, args.requests)
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
