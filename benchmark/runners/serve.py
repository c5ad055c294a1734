"""Runner ``serve``: ``deepspeed_tpu.init_inference`` → ``ServingEngine``.

The runner drives ``srv.step()`` itself (a copy of the arrival loop of
``tools/bench_serving.py::run_load``, changed for a fixed window): it
submits what is due, steps the engine, and after every step stamps each
request's new tokens with that step's end time (:mod:`benchmark.stamps`).
Set-up: seeded bf16 weights on the device, the engine and its pool, one
small request drained (compiles the two executables), then a pre-roll
under the cell's own traffic so that the window opens on a system in
steady state.  Requests in flight at the close are abandoned, not
drained.  After the window, a seeded sample of the served requests is
checked against the plain reference.
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import build, checks, stamps, traffic
from benchmark.harness import check, memory_analysis

def run(ctx) -> Dict[str, Any]:
    cfg, mix = ctx.config, ctx.traffic
    dims, scfg = cfg["model"], cfg["serving"]
    ctx.say("imports done, device in hand")
    srv = build.serving_engine(cfg, ctx.seed, ctx.devices)
    ctx.say(f"engine ready: {scfg['num_slots']} slots x {scfg['max_len']}, pool {srv.pool.cache_bytes() / 1e9:.2f} GB")

    # compile both executables on a request of two chunks and a few tokens
    rng = np.random.default_rng([ctx.seed, 5])
    warm = srv.submit(rng.integers(1, dims["vocab_size"], scfg["prefill_chunk"] + 3, dtype=np.int32), max_new_tokens=4)
    srv.drain()
    srv.pop_results()
    ctx.say(f"warm request {warm} drained; {srv.prefill_compiles} prefill + {srv.decode_compiles} decode executable(s)")

    def pool_stats() -> Dict[str, Any]:
        return srv.pool.stats() if hasattr(srv.pool, "stats") else {}  # the slot-contiguous pool keeps none

    retired: Dict[int, Any] = {}  # what the engine retired in the step just taken

    def emitted(rid: int) -> int:
        r = retired.get(rid) or srv.result(rid)
        return len(r.generated) if r is not None else 0

    stamper = stamps.TokenStamper(emitted)
    stream = traffic.request_stream(mix, ctx.seed, dims["vocab_size"])
    prompts: Dict[int, np.ndarray] = {}
    served: List[Dict[str, Any]] = []
    clock = time.perf_counter

    late_s: List[float] = []  # how late the generator ran: submit time minus due time

    def submit(due: float) -> None:
        req = next(stream)
        late_s.append(clock() - due)
        try:
            rid = srv.submit(req["prompt"], max_new_tokens=req["max_new"])
        except Exception as e:  # refused, shed or errored: a failed request, never a crash of the run
            ctx.say(f"submit refused: {e!r}")
            stamper.offer(None, due, len(req["prompt"]), req["max_new"], refused=True)
            return
        prompts[rid] = req["prompt"]
        stamper.offer(rid, due, len(req["prompt"]), req["max_new"])

    open_loop = mix["kind"] == "open"
    gaps = traffic.arrival_gaps(mix) if open_loop else None
    t_begin = clock()
    t_open = t_begin + float(mix["preroll_s"])
    t_close = t_open + ctx.seconds
    next_due = t_begin + (next(gaps) if open_loop else 0.0)
    if not open_loop:
        with ctx.span("submit"):
            for _ in range(int(mix["clients"])):
                submit(t_begin)
    steps: List[Dict[str, Any]] = []
    opened = False
    win0: Dict[str, Any] = {}
    now = t_begin
    while now < t_close:
        if not opened and now >= t_open:
            # the window opens at a step boundary; everything before it was set-up
            opened, t_open = True, ctx.window_opens()
            t_close = t_open + ctx.seconds
            win0 = {"compiles": srv.prefill_compiles + srv.decode_compiles,
                    "alloc_waits": pool_stats().get("alloc_waits", 0)}
            srv.timeline.reset_window()
        if opened:
            ctx.maybe_start_trace(now, t_close)
        if open_loop and next_due <= now:
            with ctx.span("submit"):
                while next_due <= now:
                    submit(next_due)
                    next_due += next(gaps)
        if srv.scheduler.has_work():
            t0 = now
            with ctx.span("step"):
                srv.step()
            now = clock()
            retired.clear()
            retired.update(srv.pop_results())
            out = stamper.after_step(now, {rid: q.status == "done" for rid, q in retired.items()})
            steps.append({"t0": t0, "t1": now, "decode_fills": out["decode_fills"]})
            for rec in out["finished"]:
                q = retired.get(rec["id"])
                if q is not None and opened and not rec["errored"]:
                    served.append({"prompt": prompts[rec["id"]], "generated": list(q.generated)})
                prompts.pop(rec["id"], None)
                if not open_loop:
                    with ctx.span("submit"):
                        submit(now)
        else:
            with ctx.span("idle"):
                time.sleep(max(0.0, min(next_due, t_close) - clock()))
            now = clock()
    if not opened:
        raise RuntimeError("the window never opened: the pre-roll outlasted the run")
    ctx.window_closes()

    w = stamps.window_metrics(stamper.requests, t_open, t_close, float(mix.get("ttft_sample_share", 0.9)),
                              open_loop=open_loop)
    tl = srv.timeline.summary()
    kv = pool_stats()
    in_window = [s for s in steps if t_open <= s["t1"] < t_close]
    traced = [s for s in in_window if ctx.trace_t0 is not None and s["t0"] >= ctx.trace_t0]
    ctx.say(f"window: {w['tokens']} tokens counted of {w['tokens_emitted']} emitted / {w['window_s']:.1f}s, {len(in_window)} steps, "
            f"{w['attempted']} attempted, {w['failed']} failed, {len(served)} finished")

    # ---- correctness, outside the window ---------------------------------
    lim = cfg["checks"]
    pick = np.random.default_rng([ctx.seed, 6]).permutation(len(served))[: int(lim["sample_requests"])]
    g = checks.token_gaps(build.reference(cfg, ctx.seed), [served[int(i)] for i in pick], scfg["max_len"]) if len(pick) else None
    record_checks = [
        check("served_sample", float(len(pick)), ">=", 1.0),
        check("token_gap_mean", g["token_gap_mean"] if g else float("nan"), "<=", lim["token_gap_mean_max"]),
        check("token_gap_max", g["token_gap_max"] if g else float("nan"), "<=", lim["token_gap_max_max"]),
    ]
    ctx.say(f"checked {g['tokens'] if g else 0} tokens of {len(pick)} requests against the reference")

    e2e = {
        "serve_tokens_per_s": w["tokens"] / w["window_s"],
        "itl_p95_ms": stamps.pct(w["gaps_ms"], 95),
        "setup_s": t_open - ctx.t_start,
    }
    if ctx.trace:
        for which in ("prefill", "decode"):
            ctx.say(f"{which} step by the compiler: " + memory_analysis(srv.compiled_step(which)))
    page_len = scfg["kvcache"]["page_len"]
    fills = [f for s in traced for f in s["decode_fills"]]
    return {
        "end_to_end": e2e, "attempted": w["attempted"], "failed": w["failed"], "checks": record_checks,
        "window": {"t_open": t_open, "t_close": t_close, "steps": len(in_window),
                   "step_walls_s": [s["t1"] - s["t0"] for s in in_window], **w},
        "counters": {
            "compiles_in_window": srv.prefill_compiles + srv.decode_compiles - win0["compiles"],
            "kv_alloc_waits": kv.get("alloc_waits", 0) - win0["alloc_waits"],
            "kv_pages_live": kv.get("pages_live"), "kv_num_pages": kv.get("num_pages"),
            "timeline": tl, "num_slots": scfg["num_slots"], "generator_late_s_max": max(late_s),
            "engine_stats": {k: v for k, v in srv.stats().items() if isinstance(v, (int, float, str))},
        },
        "shapes": {"model": dims, "page_len": page_len, "decode_steps_traced": sum(1 for s in traced if s["decode_fills"]),
                   "decode_rows_traced": len(fills),
                   "decode_pages_traced": sum(-(-f // page_len) for f in fills)},
    }
