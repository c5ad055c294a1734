#!/usr/bin/env python3
"""Reads the numbers the ZAYA1 cell's limits of ``correct`` are set from,
on the chip, at the cell's own size, in one process:

    python3 benchmark/control_zaya1.py --workload serve-zaya1-longctx-backlog --seeds 2 --control-seeds 2 \\
        --int8-seeds 1 --requests 2 --out chiprun_out/control_zaya1.json

Per seed: the engine as the cell builds it serves the first requests of
the cell's traffic, its slots' K/V page rows read once on the way (where
the shortest answer is half written); then

* ``program``: ``token_gap_mean`` / ``token_gap_max`` of what it emitted
  and ``kv_boundary_rel_err`` of what its pages held, against the float32
  reference (the comparisons the runner makes);
* ``control_zero_tail`` (the first ``--control-seeds`` seeds): **the
  program with the slot's convolution tail and value shift zeroed at
  every chunk start** — the same engine, its family forward wrapped
  before either program compiles so that a prefill chunk reads its
  slot's rows of ``pool.state`` as zeros — serving the same requests,
  judged the same way.  The fault touches three positions a chunk;
* ``control_int8`` (the first ``--int8-seeds`` seeds): the reference with
  every matmul operand rounded to int8 put in the program's place,
  teacher-forced on the program's contexts — at each generated position
  the token the control's forward would have emitted, and the K/V rows
  its forward makes, judged by the float32 reference.

Each goes through the runner's own ``judged`` with the configuration's
limits: ``correct`` must read true for ``program`` and **false for both
controls**, the zeroed tail by ``kv_boundary_rel_err`` and int8 by
``token_gap_mean``.  The benchmark's own runs never call this.
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

from benchmark import build_zaya1 as build  # noqa: E402
from benchmark import traffic  # noqa: E402
from benchmark.control_deepseek_v2 import say  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.runners import serve_zaya1 as runner  # noqa: E402  (the runner's own comparison)


def forget_tail(srv) -> None:
    """Wrap the engine's family forward: a prefill chunk finds its
    slot's rows of the per-slot state zeroed, as if nothing had come
    before it.  Before anything compiles."""
    inner = srv._family_forward

    def fwd(params, tokens, k, v, pos, page_table, write_mask=None, row_valid=None, take=None, state=None, slot=None):
        if slot is not None:
            state = {name: buf.at[:, slot].set(0) for name, buf in state.items()}
        return inner(params, tokens, k, v, pos, page_table, write_mask=write_mask, row_valid=row_valid, take=take,
                     state=state, slot=slot)

    fwd.trace_notes = inner.trace_notes
    srv._family_forward = fwd


def serve(cfg, seed, devices, reqs, zero_tail=False):
    """What the cell's engine emits for ``reqs`` and what its pages hold half way."""
    srv = build.serving_engine(cfg, seed, devices, say=say)
    if zero_tail:
        forget_tail(srv)
    ids = [srv.submit(r["prompt"], max_new_tokens=r["max_new"]) for r in reqs]
    live = [srv.result(i) for i in ids]
    half = max(3, min(r["max_new"] for r in reqs) // 2)
    while srv.scheduler.has_work() and not all(q.status == "decode" and len(q.generated) >= half for q in live):
        srv.step()
    samples = runner.kv_samples(srv, live, len(live), np.random.default_rng(0), cfg["serving"]["prefill_chunk"])
    done = {**srv.pop_results(), **srv.drain()}
    st = srv.stats()
    served = [{"prompt": r["prompt"], "generated": list(done[i].generated)} for r, i in zip(reqs, ids)]
    notes = {k: st[k] for k in ("cca_decode_kernel", "cca_decode_fallback", "cca_prefill_form", "moe_router_form",
                                "moe_grouped_kernel", "moe_grouped_fallback") if k in st}
    moe = {k: v for k, v in (st.get("moe") or {}).items() if k != "tokens_per_expert"}
    del srv, done, live
    gc.collect()
    return served, samples, notes, moe


def verdict(lim, gaps, kv, dropped=0):
    """The runner's own ``judged`` over one variant's numbers."""
    checks_ = runner.judged(lim, 1, gaps, kv, dropped)
    return {**gaps, **kv, "checks": checks_, "correct": all(c["ok"] for c in checks_)}


def numbers(cfg, mix, seed, devices, with_control, with_int8, requests):
    lim = cfg["checks"]
    pad = int(lim["pad_multiple"])
    stream = traffic.request_stream(mix, seed, build.dims_of(cfg)["vocab_size"])
    reqs = [next(stream) for _ in range(requests)]
    served, samples, notes, moe = serve(cfg, seed, devices, reqs)
    say(f"seed {seed}: served {[len(s['prompt']) for s in served]} + {[len(s['generated']) for s in served]} tokens; {notes}")
    ref = build.reference(cfg, seed)
    out = {"program": verdict(lim, runner.served_gaps(ref, served, pad), runner.kv_errors(ref, samples, pad),
                              moe.get("dropped_assignments")), "forms": notes, "moe": moe}
    say(f"seed {seed}: program {json.dumps(out['program'])}")
    if with_control:
        low, low_samples, _, low_moe = serve(cfg, seed, devices, reqs, zero_tail=True)
        out["control_zero_tail"] = verdict(lim, runner.served_gaps(ref, low, pad), runner.kv_errors(ref, low_samples, pad),
                                           low_moe.get("dropped_assignments"))
        out["control_zero_tail"]["tokens_differ"] = int(sum(
            int(np.sum(np.asarray(a["generated"]) != np.asarray(b["generated"]))) for a, b in zip(served, low)))
        say(f"seed {seed}: control_zero_tail {json.dumps(out['control_zero_tail'])}")
    if with_int8:
        ctl = build.reference(cfg, seed, precision="int8")
        held = []  # the K/V rows the control's own forward makes over the contexts the program's slots had consumed
        for s in samples:
            rows: list = []
            ctl.hidden(runner._padded(s["context"], pad), kv_at=s["at"], kv=rows)
            held.append({"k": [k for k, _ in rows], "v": [v for _, v in rows]})
        out["control_int8"] = verdict(lim, runner.served_gaps(ref, served, pad, picker=ctl), runner.kv_errors(ref, samples, pad, held))
        say(f"seed {seed}: control_int8 {json.dumps(out['control_int8'])}")
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=2)
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
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": devices[0].device_kind, "rows": rows}, f, indent=1)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
