#!/usr/bin/env python3
"""Reads the numbers the GigaChat3.5 cell's limits of ``correct`` are set
from, on the chip, at the cell's own size, in one process:

    python3 benchmark/control_gigachat35.py --workload serve-gigachat35-longreason-backlog --seeds 3 --control-seeds 1 \\
        --requests 2 --out chiprun_out/control_gigachat35.json

Per seed: the engine as the cell builds it serves the first requests of
the cell's traffic whose contexts the reference is asked for
(``checks.max_context``), both halves of its slots' caches read once on
the way (where the shortest answer is half written); then

* ``program``: ``token_gap_mean`` / ``token_gap_max`` of what it emitted,
  ``state_rel_err`` / ``state_mantissa_bits`` of the recurrent state and
  ``latent_boundary_rel_err`` of the latent rows its slots held, against
  the float32 reference (the comparisons the runner makes);
* three **controls** (the first ``--control-seeds`` seeds), each a
  **variant of the reference put in the program's place** — the program
  has no switch for any of them — teacher-forced on the program's
  contexts: at each generated position the token the variant's forward
  would have emitted, and the state and latent rows it leaves, judged by
  the float32 reference:
  ``control_int8`` every matmul operand rounded to int8;
  ``control_bf16_state`` the recurrent state rounded to bfloat16 between
  tokens; ``control_no_decay`` the decay left out (``g = 0``: a delta
  rule without its gate).

Each goes through the runner's own ``judged`` with the configuration's
limits: ``correct`` must read true for ``program`` and **false for all
three controls**.  The benchmark's own runs never call this.
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

from benchmark import build_gigachat35 as build  # noqa: E402
from benchmark import traffic  # noqa: E402
from benchmark.control_deepseek_v2 import say  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402

CONTROLS = {"control_int8": {"precision": "int8"}, "control_bf16_state": {"state_dtype": "bfloat16"},
            "control_no_decay": {"decay": False}}


class Memo:
    """The float32 reference, each forward made once: every variant is judged on the same contexts."""

    def __init__(self, ref):
        self.ref, self._logits, self._traces = ref, {}, {}

    def logits(self, tokens):
        key = np.asarray(tokens, np.int32).tobytes()
        if key not in self._logits:
            self._logits[key] = self.ref.logits(tokens)
        return self._logits[key]

    def traces(self, tokens, n, at):
        key = (np.asarray(tokens, np.int32).tobytes(), int(n), np.asarray(at, np.int32).tobytes())
        if key not in self._traces:
            self._traces[key] = self.ref.traces(tokens, n, at)
        return self._traces[key]


def serve(cfg, seed, devices, reqs):
    """What the cell's engine emits for ``reqs``, and what its slots' caches hold half way."""
    from benchmark.runners import serve_gigachat35 as runner

    srv = build.serving_engine(cfg, seed, devices, say=say)
    ids = [srv.submit(r["prompt"], max_new_tokens=r["max_new"]) for r in reqs]
    live = [srv.result(i) for i in ids]
    half = max(3, min(r["max_new"] for r in reqs) // 2)  # three generated at least: the sample reads the first two decoded positions
    while srv.scheduler.has_work() and not all(q.status == "decode" and len(q.generated) >= half for q in live):
        srv.step()
    samples = runner.cache_samples(srv, live, len(live), np.random.default_rng(0), cfg["serving"]["prefill_chunk"],
                                   int(cfg["checks"]["max_context"]))
    done = {**srv.pop_results(), **srv.drain()}
    st = srv.stats()
    served = [{"prompt": r["prompt"], "generated": list(done[i].generated)} for r, i in zip(reqs, ids)]
    notes = {k: st[k] for k in ("gdn_decode_kernel", "gdn_decode_fallback", "gdn_prefill_form", "mla_decode_kernel", "mla_prefill_form",
                                "moe_router_form", "moe_grouped_kernel") if k in st}
    notes["page_kind"] = st["kvcache"].get("page_kind")
    moe = {k: v for k, v in (st.get("moe") or {}).items() if k != "tokens_per_expert"}
    del srv, done, live
    gc.collect()
    return served, samples, notes, moe


def verdict(lim, gaps, cache, dropped=0):
    """The runner's own ``judged`` over one variant's numbers."""
    from benchmark.runners import serve_gigachat35 as runner

    checks_ = runner.judged(lim, 1, gaps, cache, dropped)
    return {**gaps, **cache, "checks": checks_, "correct": all(c["ok"] for c in checks_)}


def numbers(cfg, mix, seed, devices, with_controls, requests):
    from benchmark.runners import serve_gigachat35 as runner  # the runner's own comparisons

    lim = cfg["checks"]
    dims = build.dims_of(cfg)
    pad, cap = int(lim["pad_multiple"]), int(lim["max_context"])
    stream = traffic.request_stream(mix, seed, dims["vocab_size"])
    reqs = []
    while len(reqs) < requests:
        r = next(stream)
        if len(r["prompt"]) + r["max_new"] <= cap:
            reqs.append(r)
    served, samples, notes, moe = serve(cfg, seed, devices, reqs)
    say(f"seed {seed}: served {[len(s['prompt']) for s in served]} + {[len(s['generated']) for s in served]} tokens; {notes}")
    ref = Memo(build.reference(cfg, seed))
    out = {"program": verdict(lim, runner.served_gaps(ref, served, pad), runner.cache_errors(ref, samples, pad),
                              moe.get("dropped_assignments")), "forms": notes, "moe": moe}
    say(f"seed {seed}: program {json.dumps(out['program'])}")
    for name, variant in CONTROLS.items() if with_controls else ():
        ctl = build.reference(cfg, seed, **variant)
        held = []  # what the variant's own forward leaves after the contexts the program's slots had consumed
        for s in samples:
            state, latent = ctl.traces(runner._padded(s["context"], pad), len(s["context"]), s["at"])
            held.append({"state": state, "latent": latent})
        out[name] = verdict(lim, runner.served_gaps(ref, served, pad, picker=ctl), runner.cache_errors(ref, samples, pad, held=held))
        say(f"seed {seed}: {name} {json.dumps(out[name])}")
        del ctl
        gc.collect()
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--control-seeds", type=int, default=1)
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
        r = numbers(cfg, mix, seed, devices, i < args.control_seeds, args.requests)
        r["seed"] = seed
        rows.append(r)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": devices[0].device_kind, "rows": rows}, f, indent=1)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
