#!/usr/bin/env python3
"""Reads the numbers the Keye cell's limits of ``correct`` are set from,
on the chip, at the cell's own size, in one process:

    python3 benchmark/control_keye.py --workload serve-keye-32k-sparse-backlog --seeds 2 --control-seeds 1 \\
        --int8-seeds 1 --requests 2 --out chiprun_out/control_keye.json

Per seed: the engine as the cell builds it serves the first requests of
the cell's traffic, its slots' K, V and indexer-key page rows read once
on the way (where the shortest answer is half written) together with what
its newest decode step selected there (the runner's ``served_selection``:
the masks and the cut scores the served decode executable handed back); then

* ``program``: ``token_gap_mean`` of what it emitted, the rows' errors
  of what its pages held, ``selection_overlap_mean`` of what it selected
  and the bits of mantissa the index scores it cut at carry, against the
  float32 reference (the comparisons the runner makes);
* three controls, each **the reference's own variant put in the program's
  place**, teacher-forced on the program's contexts — at each generated
  position the token the control's forward would have emitted, and the
  rows, the selection and the cut scores its forward makes, judged by the
  float32 reference.  The program has no switch for any of them.
  ``control_recent`` (the first ``--control-seeds`` seeds) **attends to the
  most recent 2,048 positions** in place of the selected ones
  (``select="recent"``); ``control_bf16_indexer`` (the same seeds) **ranks
  with a bfloat16 indexer** where the configuration says float32
  (``index_precision="bfloat16"``: the indexer's operands rounded and each
  score held in bfloat16); ``control_int8`` (the first ``--int8-seeds``
  seeds) rounds every matmul operand to int8.

Each goes through the runner's own ``judged`` with the configuration's
limits: ``correct`` must read true for ``program`` and **false for each
control** — the recent window by ``selection_overlap_mean``, the bfloat16
indexer by ``index_score_mantissa_bits`` (its selected set reads like the
float32 program's: under a bf16 residual stream the two differ by a
thousandth of the set), int8 by ``token_gap_mean``.  The benchmark's
own runs never call this.
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
import numpy as np  # noqa: E402

from benchmark import build_keye as build  # noqa: E402
from benchmark import traffic  # noqa: E402
from benchmark.control_deepseek_v2 import say  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.runners import serve_keye as runner  # noqa: E402  (the runner's own comparison)

# each control: the reference's variant that takes the program's place
CONTROLS = {"control_recent": {"select": "recent"}, "control_bf16_indexer": {"index_precision": "bfloat16"}}


def serve(cfg, seed, devices, reqs):
    """What the cell's engine emits for ``reqs``, what its pages hold half way and what its newest decode step selected there."""
    srv = build.serving_engine(cfg, seed, devices, say=say)
    ids = [srv.submit(r["prompt"], max_new_tokens=r["max_new"]) for r in reqs]
    live = [srv.result(i) for i in ids]
    half = max(3, min(r["max_new"] for r in reqs) // 2)
    while srv.scheduler.has_work() and not all(q.status == "decode" and len(q.generated) >= half for q in live):
        srv.step()
    samples = runner.kv_samples(srv, live, len(live), np.random.default_rng(0), cfg["serving"]["prefill_chunk"])
    selected, cuts = runner.served_selection(srv, samples)
    done = {**srv.pop_results(), **srv.drain()}
    st = srv.stats()
    served = [{"prompt": r["prompt"], "generated": list(done[i].generated)} for r, i in zip(reqs, ids)]
    notes = {k: st[k] for k in ("dsa_index_form", "dsa_prefill_index_form", "dsa_select_form", "dsa_decode_kernel", "dsa_prefill_form",
                                "moe_router_form", "moe_grouped_kernel", "moe_grouped_fallback") if k in st}
    moe = {k: v for k, v in (st.get("moe") or {}).items() if k != "tokens_per_expert"}
    del srv, done, live
    gc.collect()
    return served, samples, selected, cuts, notes, moe


def verdict(lim, gaps, kv, theirs, selected, cuts, dropped=0):
    """The runner's own ``judged`` over one variant's numbers."""
    overlap = runner.selection_overlap(theirs, selected, cuts)
    checks_ = runner.judged(lim, 1, gaps, kv, overlap, dropped)
    return {**gaps, **kv, **{k: v for k, v in overlap.items() if k != "set_sizes"}, "checks": checks_,
            "correct": all(c["ok"] for c in checks_)}


def numbers(cfg, mix, seed, devices, with_control, with_int8, requests):
    lim = cfg["checks"]
    pad = int(lim["pad_multiple"])
    stream = traffic.request_stream(mix, seed, build.dims_of(cfg)["vocab_size"])
    reqs = [next(stream) for _ in range(requests)]
    served, samples, selected, cuts, notes, moe = serve(cfg, seed, devices, reqs)
    say(f"seed {seed}: served {[len(s['prompt']) for s in served]} + {[len(s['generated']) for s in served]} tokens; {notes}")
    ref = build.reference(cfg, seed)
    theirs = runner.reference_side(ref, samples, pad)  # once: the program and every control are held against it
    out = {"program": verdict(lim, runner.served_gaps(ref, served, pad), runner.kv_errors(theirs, samples), theirs, selected, cuts,
                              moe.get("dropped_assignments")), "forms": notes, "moe": moe}
    say(f"seed {seed}: program {json.dumps(out['program'])}")
    variants = ({**CONTROLS} if with_control else {}) | ({"control_int8": {"precision": "int8"}} if with_int8 else {})
    for name, how in variants.items():
        ctl = build.reference(cfg, seed, **how)
        held = runner.reference_side(ctl, samples, pad)  # the rows, the selection and the cuts the control's own forward makes over the contexts the program's slots had consumed
        out[name] = verdict(lim, runner.served_gaps(ref, served, pad, picker=ctl), runner.kv_errors(theirs, samples, held), theirs, held,
                            [c for h in held for c in h["cuts"]])
        say(f"seed {seed}: {name} {json.dumps(out[name])}")
        del ctl
        gc.collect()
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--control-seeds", type=int, default=1)
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
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": devices[0].device_kind, "rows": rows}, f, indent=1)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
