#!/usr/bin/env python3
"""One cell of the benchmark, once, in this process:

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Exits non-zero and prints no result
line when JAX finds no TPU or fewer chips than the cell asks for.  The
last line of standard output is the contract's JSON object.  XLA's
persistent compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says,
else at the fixed ``<checkout>/.jax_cache``.  ``BENCH_RUN`` is ignored.
"""
import time

_T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "deepspeed_tpu")):
        print(f"benchmark: no deepspeed_tpu package beside {ROOT}/benchmark — nothing to measure", file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    # cache every program, however quick its compile: the second run of
    # a cell has to find all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from benchmark import harness

    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), _T_START)
    except harness.NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
