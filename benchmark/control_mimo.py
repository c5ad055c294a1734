#!/usr/bin/env python3
"""Reads the numbers the MiMo-V2-Flash cell's limits of ``correct`` are set
from, on the chip, at the cell's own size, in one process:

    python3 benchmark/control_mimo.py --workload serve-mimo-longctx-agent-backlog --seeds 3 --control-seeds 2 \\
        --requests 2 --out chiprun_out/control_mimo.json

Per seed: the engine as the cell builds it serves the first requests of
the cell's traffic whose contexts the reference is asked for (the first
of them past ``checks.wrapped_past`` positions: its ring of two pages has
lapped inside every chunk and across chunks), its newest decode step's
routing read once on the way (where the shortest answer is half
written); then

* ``program``: ``token_gap_mean`` of what it emitted, and its routers'
  ``router_overlap_mean`` / ``router_logit_mantissa_bits``, against the
  float32 reference (the comparisons the runner makes);
* the controls (the first ``--control-seeds`` seeds), each a **variant of
  the reference put in the program's place**, teacher-forced on the
  program's contexts — at each generated position the token the
  variant's forward would have emitted, and the experts and router
  logits its own routers give at the sampled positions, judged by the
  float32 reference: ``control_no_sink`` (the sink column left out of the
  window layers' softmax), ``control_window_129`` (a window of one more
  position), ``control_full_grouping`` (the window layers' query heads
  grouped 16 to a KV head over 4 of the 8: the full layers' grouping),
  ``control_no_value_scale`` (``attention_value_scale`` left out),
  ``control_theta_swapped`` (each kind rotated under the other's
  ``theta``), ``control_bf16_router`` (the router's operands, logits and
  sigmoid in bfloat16: the nearest precision below the float32 the
  configuration states).

Each goes through the runner's own ``judged`` with the configuration's
limits: ``correct`` must read true for ``program`` and **false for every
control**.  The benchmark's own runs never call this.
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

from benchmark import build_mimo as build  # noqa: E402
from benchmark import traffic  # noqa: E402
from benchmark.control_deepseek_v2 import say  # noqa: E402
from benchmark.control_laguna import Memo  # noqa: E402  (the float32 reference, each forward made once)
from benchmark.manifest import Manifest  # noqa: E402

CONTROLS = {"control_no_sink": {"no_sink": True}, "control_window_129": {"window": None},  # the configuration's window + 1
            "control_full_grouping": {"window_kv_heads": None},  # the full layers' KV heads: filled in from the configuration
            "control_no_value_scale": {"no_value_scale": True}, "control_theta_swapped": {"theta_swapped": True},
            "control_bf16_router": {"router": "bfloat16"}}


def serve(cfg, seed, devices, reqs):
    """What the cell's engine emits for ``reqs``, and what its routers chose half way."""
    from benchmark.runners import serve_mimo as runner

    srv = build.serving_engine(cfg, seed, devices, say=say)
    ids = [srv.submit(r["prompt"], max_new_tokens=r["max_new"]) for r in reqs]
    live = [srv.result(i) for i in ids]
    half = max(3, min(r["max_new"] for r in reqs) // 2)
    while srv.scheduler.has_work() and not all(q.status == "decode" and len(q.generated) >= half for q in live):
        srv.step()
    routed = runner.routing_samples(srv, live, len(live), np.random.default_rng(0), int(cfg["checks"]["max_context"]))
    done = {**srv.pop_results(), **srv.drain()}
    st = srv.stats()
    served = [{"prompt": r["prompt"], "generated": list(done[i].generated)} for r, i in zip(reqs, ids)]
    notes = {k: st[k] for k in ("swa_decode_form", "swa_chunk_form", "kv_write_form", "swa_ring_positions", "paged_decode_walk",
                                "gqa_prefill_form", "moe_router_form", "moe_grouped_kernel") if k in st}
    notes["groups"] = st["kvcache"].get("groups")
    moe = {k: v for k, v in (st.get("moe") or {}).items() if k != "tokens_per_expert"}
    del srv, done, live
    gc.collect()
    return served, routed, notes, moe


def verdict(lim, wrapped, gaps, routing, edge, dropped=0):
    """The runner's own ``judged`` over one variant's numbers."""
    from benchmark.runners import serve_mimo as runner

    checks_ = runner.judged(lim, 1, wrapped, gaps, routing, dropped, edge)
    return {**gaps, **routing, "window_edge_margin": edge, "checks": checks_, "correct": all(c["ok"] for c in checks_)}


def numbers(cfg, mix, seed, devices, with_controls, requests, only=()):
    from benchmark.runners import serve_mimo as runner  # the runner's own comparisons

    lim = cfg["checks"]
    dims = build.dims_of(cfg)
    pad, cap = int(lim["pad_multiple"]), int(lim["max_context"])
    wrapped_past = int(lim["wrapped_past"])
    stream = traffic.request_stream(mix, seed, dims["vocab_size"])
    reqs = []
    while len(reqs) < requests:
        r = next(stream)
        if len(r["prompt"]) + r["max_new"] <= cap and (reqs or len(r["prompt"]) > wrapped_past):  # the first one's ring has lapped
            reqs.append(r)
    served, routed, notes, moe = serve(cfg, seed, devices, reqs)
    pad_to = lambda t: runner._padded(t, pad)  # noqa: E731
    wrapped = sum(len(s["prompt"]) + len(s["generated"]) > wrapped_past for s in served)
    say(f"seed {seed}: served {[len(s['prompt']) for s in served]} + {[len(s['generated']) for s in served]} tokens; {notes}")
    ref = Memo(build.reference(cfg, seed))
    wide = Memo(build.reference(cfg, seed, window=int(dims["sliding_window"]) + 1))  # the window's edge: judged by a paired gap
    gaps = runner.served_gaps(ref, served, pad)
    out = {"program": verdict(lim, wrapped, gaps, runner.routing_numbers(ref, routed, pad), runner.window_edge(wide, served, pad, gaps),
                              moe.get("dropped_assignments")), "forms": notes, "moe": moe}
    say(f"seed {seed}: program {json.dumps(out['program'])}")
    for name, variant in CONTROLS.items() if with_controls else ():
        if only and name not in only:
            continue
        if "window_kv_heads" in variant:
            variant = {"window_kv_heads": int(dims["num_key_value_heads"])}
        if "window" in variant:
            variant = {"window": int(dims["sliding_window"]) + 1}
        ctl = build.reference(cfg, seed, **variant)
        own = [ctl.routings(pad_to(s["context"]), len(s["context"]) - 1) for s in routed["samples"]]
        held = {"experts": [e for e, _ in own], "logits": np.stack([g for _, g in own]) if own else np.zeros((0,), np.float32)}
        gaps = runner.served_gaps(ref, served, pad, picker=ctl)
        out[name] = verdict(lim, wrapped, gaps, runner.routing_numbers(ref, routed, pad, held=held),
                            runner.window_edge(wide, served, pad, gaps, picker=ctl))
        say(f"seed {seed}: {name} {json.dumps(out[name])}")
        del ctl
        gc.collect()
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--requests", type=int, default=2, help="requests served per seed")
    ap.add_argument("--controls", default="", help="only these controls, by name, comma-separated (default: all six)")
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
        r = numbers(cfg, mix, seed, devices, i < args.control_seeds, args.requests, tuple(n for n in args.controls.split(",") if n))
        r["seed"] = seed
        rows.append(r)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": devices[0].device_kind, "rows": rows}, f, indent=1)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
