#!/usr/bin/env python3
"""Finds, once, the highest rate an open-loop cell's system sustains:
runs the cell at each of a few fixed rates through the unchanged
harness (a scratch manifest whose only new files are copies of the
cell's traffic mix with another ``rate_rps``), one after the other in
one process, and writes the readings to a file.  The cell's own rate —
about four fifths of the knee — is then written into its traffic file
as a number.  The benchmark's own runs never call this.

    python3 benchmark/sweep.py --workload serve-xl-chat-open --rates 0.8,1.0,1.2,1.4,1.7,2.0 --seconds 30 --out chiprun_out/sweep.json
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", os.path.join(ROOT, ".jax_cache"))

from benchmark import harness, traffic  # noqa: E402
from benchmark.manifest import Manifest  # noqa: E402
from benchmark.stamps import pct  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=2_500_000_001)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    m = Manifest()
    cell = m.cell(args.workload)
    mix = m.traffic(cell["traffic"])
    scratch = os.path.join(ROOT, ".bench_scratch", "sweep")
    os.makedirs(os.path.join(scratch, "new", "traffic"), exist_ok=True)
    data = json.loads(json.dumps(m.data))
    data["paths"] = ["new"]
    for c in data["configs"]:
        c["file"] = os.path.relpath(os.path.join(m.root, c["file"]), scratch)
    rows = []
    answers = [a for _, a in traffic.length_pool(mix)]
    for rate in [float(r) for r in args.rates.split(",")]:
        name = f"{cell['traffic']}-r{rate:g}".replace(".", "p")
        with open(os.path.join(scratch, "new", "traffic", name + ".json"), "w") as f:
            json.dump({**mix, "rate_rps": rate}, f)
        wname = f"sweep-{name}"
        data["workloads"] = [{**cell, "name": wname, "traffic": name}]
        for e in data["end_to_end"] + data["per_layer"]:
            if "workloads" in e:
                e["workloads"] = [wname]
        with open(os.path.join(scratch, "BENCHMARK.json"), "w") as f:
            json.dump(data, f)
        out = harness.run_cell(wname, args.seed, args.seconds, False, time.perf_counter(),
                               manifest_path=os.path.join(scratch, "BENCHMARK.json"), scratch=scratch)
        w, c = out["record"]["window"], out["record"]["counters"]
        row = {"rate_rps": rate, "offered_tokens_per_s": rate * sum(answers) / len(answers),
               "serve_tokens_per_s": w["tokens_emitted"] / w["window_s"], "ttft_p50_ms": pct(w["ttft_ms"], 50),
               "ttft_p95_ms": pct(w["ttft_ms"], 95), "itl_p95_ms": pct(w["gaps_ms"], 95),
               "oldest_waiting_s": w["oldest_waiting_s"], "attempted": w["attempted"], "failed": w["failed"],
               "queue_depth_at_close": c["engine_stats"]["queue_depth_now"], "live_slots_mean": c["timeline"]["live_slots"],
               "queue_depth_mean": c["timeline"]["queue_depth"], "step_ms_mean": c["timeline"]["wall_ms"],
               "correct": out["result"]["correct"], "seconds": args.seconds, "seed": args.seed}
        rows.append(row)
        print("[sweep] " + json.dumps(row), file=sys.stderr, flush=True)
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "device": out["result"]["device"]["kind"], "rows": rows}, f, indent=1)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
