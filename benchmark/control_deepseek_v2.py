#!/usr/bin/env python3
"""Reads the numbers the DeepSeek-V2 cell's limits of ``correct`` are set
from, on the chip, at the cell's own size, in one process:

    python3 benchmark/control_deepseek_v2.py --workload serve-dsv2-longctx-backlog --seeds 3 --control-seeds 2 \\
        --requests 2 --out chiprun_out/control_dsv2.json

Per seed: the engine as the cell builds it serves the first requests of
the cell's traffic; then

* ``program``: ``token_gap_mean`` / ``token_gap_max`` of what it emitted
  against the float32 reference (the comparison the runner makes);
* ``control_int8`` (the first ``--control-seeds`` seeds): the reference
  with every matmul operand rounded to int8 put in the program's place,
  teacher-forced on the same contexts — at each generated position the
  token the control's forward would have emitted, judged by the float32
  reference.  The limits must fail it;
* ``routing_flip_share``: the share of (token, expert layer) pairs whose
  chosen expert set differs between the **program** (its own forward on
  its bf16 weights, chunk by chunk through a one-slot latent pool) and
  the float32 reference on the same contexts: a bf16 hidden state flips
  near-ties, and the limits have to sit above what that costs.

The benchmark's own runs never call this.
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

from benchmark import build_deepseek_v2 as build  # noqa: E402
from benchmark import checks, traffic  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402


def say(msg):
    print(f"[control] {msg}", file=sys.stderr, flush=True)


def _pad(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


def program_routing(srv, context: np.ndarray, chunk: int) -> np.ndarray:
    """The experts the program's own forward chooses for every token of
    ``context``: ``(expert layers, T, top_k)``."""
    from deepspeed_tpu.models import deepseek_v2 as ds

    mcfg, params, pool = srv.engine.model_config, srv.engine.params, srv.pool
    n_pages = pool.pages_per_slot
    buf = pool.kind.buffers(mcfg.n_layer, 1 + n_pages, pool.page_len)[0]
    table = jnp.arange(1, 1 + n_pages, dtype=jnp.int32)[None]

    @jax.jit
    def step(params, toks, buf, pos):
        sink = []
        _, buf, _ = ds.forward_with_cache(params, toks, buf, pos[None], mcfg, table, routing_sink=sink)
        return buf, jnp.stack(sink)

    out = []
    padded = np.zeros((_pad(len(context), chunk),), np.int32)
    padded[: len(context)] = context
    for start in range(0, len(padded), chunk):
        buf, idx = step(params, jnp.asarray(padded[None, start:start + chunk]), buf, jnp.int32(start))
        out.append(np.asarray(idx))
    return np.concatenate(out, axis=1)[:, : len(context)]


def numbers(cfg, mix, seed, devices, with_control, requests):
    lim, scfg = cfg["checks"], cfg["serving"]
    dims = build.dims_of(cfg)
    ref = build.reference(cfg, seed)
    srv = build.serving_engine(cfg, seed, devices, say=say)
    stream = traffic.request_stream(mix, seed, dims["vocab_size"])
    reqs = [next(stream) for _ in range(requests)]
    ids = [srv.submit(r["prompt"], max_new_tokens=r["max_new"]) for r in reqs]
    done = srv.drain()
    served = [{"prompt": r["prompt"], "generated": list(done[i].generated)} for r, i in zip(reqs, ids)]
    say(f"seed {seed}: served {[len(s['prompt']) for s in served]} + {[len(s['generated']) for s in served]} tokens")
    from benchmark.runners import serve_dsv2  # the runner's own comparison

    out = {"program": serve_dsv2.served_gaps(ref, served, int(lim["pad_multiple"])), "moe": srv.stats().get("moe", {})}
    out["moe"].pop("tokens_per_expert", None)
    # routing: program against reference, on the first served context
    context = np.concatenate([served[0]["prompt"], np.asarray(served[0]["generated"], np.int32)])
    prog = program_routing(srv, context, scfg["prefill_chunk"])
    keep: list = []
    ref.hidden(context, keep)
    moe_layers = [l for l in range(dims["num_hidden_layers"]) if not ref.is_dense(l)]
    differ = held_differ = 0
    first, count = dims["experts_held"]
    for j, (l, h) in enumerate(zip(moe_layers, keep)):
        want = np.sort(np.asarray(ref.routing(l, h)[0]), axis=-1)
        got = np.sort(prog[j], axis=-1)
        differ += int(np.any(want != got, axis=-1).sum())
        in_share = lambda a: np.where((a >= first) & (a < first + count), a, -1)  # noqa: E731
        held_differ += int(np.any(np.sort(in_share(want), -1) != np.sort(in_share(got), -1), axis=-1).sum())
    pairs = len(moe_layers) * len(context)
    out["routing_flip_share"] = differ / pairs
    out["routing_flip_share_held"] = held_differ / pairs  # pairs whose HELD experts differ: what this chip computes
    del srv, done, keep
    gc.collect()
    if with_control:
        ctl = build.reference(cfg, seed, precision="int8")
        gaps = []
        for r in served:
            context = np.concatenate([r["prompt"], np.asarray(r["generated"], np.int32)])
            n_p, pad_to = len(r["prompt"]), _pad(len(context), int(lim["pad_multiple"]))
            padded = np.zeros((1, pad_to), np.int32)
            padded[0, : len(context)] = context
            picks = np.asarray(jnp.argmax(ctl.logits(padded)[0], axis=-1))[n_p - 1: len(context) - 1]
            gaps += checks.position_gaps(ref, context, n_p, picks, pad_to)
        out["control_int8"] = checks.gap_summary(gaps)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--control-seeds", type=int, default=2)
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
        say(json.dumps(r))
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": devices[0].device_kind, "rows": rows}, f, indent=1)
    print(json.dumps({"workload": args.workload, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
