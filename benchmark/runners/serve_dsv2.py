"""Runner ``serve_dsv2``: DeepSeek-V2 behind ``deepspeed_tpu.init_inference``
→ ``ServingEngine``, on the paged latent cache.

The loop is ``runners/serve.py``'s (submit what is due, step the engine,
stamp each request's new tokens with the step's end time), and the
record carries every key the serve readers use.  What differs: the
engine and the reference come from :mod:`benchmark.build_deepseek_v2`;
token ids are drawn from the held slice of the vocabulary; the engine's
expert counters (``stats()["moe"]``) are started where the window opens
and kept in ``counters["moe"]``; and each checked request gets a
reference forward padded to the next multiple of ``pad_multiple`` (a
sequence of 8,192 positions costs the float32 reference seconds, a
served sample holds few of them).
"""
from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import build_deepseek_v2 as build
from benchmark import checks, stamps, traffic
from benchmark.harness import check, memory_analysis


def served_gaps(ref, served: List[Dict[str, Any]], pad_multiple: int) -> Dict[str, Any]:
    """``checks.token_gaps`` with each sequence padded to its own next
    multiple of ``pad_multiple`` instead of to the longest."""
    gaps: List[float] = []
    for r in served:
        context = np.concatenate([np.asarray(r["prompt"], np.int32), np.asarray(r["generated"], np.int32)])
        pad_to = -(-len(context) // pad_multiple) * pad_multiple
        gaps += checks.position_gaps(ref, context, len(r["prompt"]), context[len(r["prompt"]):], pad_to)
    return checks.gap_summary(gaps)


def run(ctx) -> Dict[str, Any]:
    cfg, mix = ctx.config, ctx.traffic
    scfg = cfg["serving"]
    dims = build.dims_of(cfg)
    ctx.say("imports done, device in hand")
    srv = build.serving_engine(cfg, ctx.seed, ctx.devices, say=ctx.say)
    ctx.say(f"engine ready: {scfg['num_slots']} slots x {scfg['max_len']}, pool {srv.pool.cache_bytes() / 1e9:.2f} GB")

    # compile both executables on a request of two chunks and a few tokens
    rng = np.random.default_rng([ctx.seed, 5])
    warm = srv.submit(rng.integers(1, dims["vocab_size"], scfg["prefill_chunk"] + 3, dtype=np.int32), max_new_tokens=4)
    srv.drain()
    srv.pop_results()
    ctx.say(f"warm request {warm} drained; {srv.prefill_compiles} prefill + {srv.decode_compiles} decode executable(s)")

    retired: Dict[int, Any] = {}  # what the engine retired in the step just taken

    def emitted(rid: int) -> int:
        r = retired.get(rid) or srv.result(rid)
        return len(r.generated) if r is not None else 0

    stamper = stamps.TokenStamper(emitted)
    stream = traffic.request_stream(mix, ctx.seed, dims["vocab_size"])  # ids 1 .. rows held - 1
    prompts: Dict[int, np.ndarray] = {}
    served: List[Dict[str, Any]] = []
    clock = time.perf_counter
    late_s: List[float] = []

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

    if mix["kind"] != "closed":
        raise ValueError("runner serve_dsv2 drives closed-loop traffic only")
    t_begin = clock()
    t_open = t_begin + float(mix["preroll_s"])
    t_close = t_open + ctx.seconds
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
                    "alloc_waits": srv.pool.stats().get("alloc_waits", 0)}
            srv.timeline.reset_window()
            srv.reset_moe_counters()
        if opened:
            ctx.maybe_start_trace(now, t_close)
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
            with ctx.span("submit"):
                submit(now)
    if not opened:
        raise RuntimeError("the window never opened: the pre-roll outlasted the run")
    ctx.window_closes()

    w = stamps.window_metrics(stamper.requests, t_open, t_close, float(mix.get("ttft_sample_share", 0.9)))
    stats = srv.stats()
    tl, kv, moe = srv.timeline.summary(), srv.pool.stats(), stats.get("moe")
    in_window = [s for s in steps if t_open <= s["t1"] < t_close]
    traced = [s for s in in_window if ctx.trace_t0 is not None and s["t0"] >= ctx.trace_t0]
    ctx.say(f"window: {w['tokens']} tokens / {w['window_s']:.1f}s, {len(in_window)} steps, "
            f"{w['attempted']} attempted, {w['failed']} failed, {len(served)} finished")

    # ---- correctness, outside the window ---------------------------------
    lim = cfg["checks"]
    pick = np.random.default_rng([ctx.seed, 6]).permutation(len(served))[: int(lim["sample_requests"])]
    g = served_gaps(build.reference(cfg, ctx.seed), [served[int(i)] for i in pick], int(lim["pad_multiple"])) if len(pick) else None
    record_checks = [
        check("served_sample", float(len(pick)), ">=", 1.0),
        check("token_gap_mean", g["token_gap_mean"] if g else float("nan"), "<=", lim["token_gap_mean_max"]),
        check("moe_dropped_assignments", float(moe["dropped_assignments"]) if moe else float("nan"), "<=", 0.0),
    ]
    # the largest gap is shown and not judged: the configuration file says why (checks.read_on_chip)
    ctx.say(f"checked {g['tokens'] if g else 0} tokens of {len(pick)} requests against the reference"
            + (f"; token_gap_max {g['token_gap_max']:.4f} (shown, not judged)" if g else ""))

    e2e = {"serve_tokens_per_s": w["tokens"] / w["window_s"], "setup_s": t_open - ctx.t_start}
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
            "moe": moe,
            "engine_stats": {k: v for k, v in stats.items() if isinstance(v, (int, float, str))},
        },
        "shapes": {"model": dims, "page_len": page_len, "decode_steps_traced": sum(1 for s in traced if s["decode_fills"]),
                   "decode_rows_traced": len(fills),
                   "decode_pages_traced": sum(-(-f // page_len) for f in fills)},
    }
