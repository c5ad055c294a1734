"""Runner ``serve_solar2``: Solar-Open2 behind ``deepspeed_tpu.init_inference``
→ ``ServingEngine``, on the hybrid cache (K/V pages + per-slot recurrent
state).

The loop and the record's keys are ``runners/serve_dsv2.py``'s (submit
what is due, step the engine, stamp each request's new tokens with the
step's end time; expert counters started where the window opens), so
every serve reader reads this cell too.  What differs: the engine and
the reference come from :mod:`benchmark.build_solar_open2`;
``counters["hybrid"]`` keeps the engine's ``stats()["hybrid"]``; and the
engine is **let go before the reference runs** — at 8,192 positions the
float32 reference's linear-attention layers hold ~3 GB of activations,
which does not fit beside 11.4 GB of weights and caches.  **The
recurrent state is judged too**: where the window closes, some decoding
slots' rows of ``pool.state["s"]`` are read off the timed engine with the
tokens they have consumed, and (``state_rel_err``) held against the state
the reference's recurrence leaves after the same tokens, and
(``state_mantissa_bits``) asked how many bits of mantissa they carry —
the emitted tokens, and the state's distance from the reference under
bf16 weights upstream, read a state rounded to bfloat16 once a token like
a float32 one (the configuration file's ``checks.read_on_chip``).  And the
stamper is answered from the engine's own request records, looked up once
a request: at 200 requests in flight ``srv.result`` for each, every step,
was 1.5 ms of a 25 ms step.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import build_solar_open2 as build
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


def state_samples(srv, requests, count: int, rng) -> List[Dict[str, Any]]:
    """The recurrent state of ``count`` decoding slots as the engine
    holds it now, each with the tokens it has consumed: the prompt and
    every generated token but the newest, which no step has read yet.
    ``requests``: the engine's own records of requests in flight."""
    rows = [q for q in requests if q.status == "decode" and q.slot is not None and q.generated]
    picked = [rows[int(i)] for i in rng.permutation(len(rows))[:count]]
    return [{"context": np.concatenate([np.asarray(q.prompt, np.int32), np.asarray(q.generated[:-1], np.int32)]),
             "state": np.asarray(srv.pool.state["s"][:, q.slot], np.float32)} for q in picked]


def mantissa_bits(x: np.ndarray) -> float:
    """Bits of mantissa the float32 numbers ``x`` carry, the median over
    the non-zero ones: 23 less the mantissa's trailing zeros.  A float32
    recurrence reads 22-23; numbers that were held in bfloat16 since they
    were last written read at most 7, in float16 at most 10."""
    x = np.ascontiguousarray(x, np.float32).reshape(-1)
    m = (x.view(np.uint32) & np.uint32(0x7FFFFF))[x != 0.0].astype(np.int64)
    if not m.size:
        return 0.0
    lowest = (m & -m).astype(np.float64)  # the lowest set bit; 0 where the mantissa is empty (a power of two)
    return float(np.median(np.where(m == 0, 0.0, 23.0 - np.log2(np.maximum(lowest, 1.0)))))


def state_errors(ref, samples: List[Dict[str, Any]], pad_multiple: int) -> Dict[str, Any]:
    """``|S - S_ref| / |S_ref|`` (Frobenius, a layer's whole state) of
    each sampled slot and linear-attention layer; ``state_rel_err`` is
    the largest, ``state_mantissa_bits`` the fewest bits a sampled
    slot's state carries."""
    by_layer: List[List[float]] = []
    for s in samples:
        n = len(s["context"])
        padded = np.zeros((-(-n // pad_multiple) * pad_multiple,), np.int32)
        padded[:n] = s["context"]
        want = ref.states(padded, n)
        by_layer.append([float(np.linalg.norm(got - w) / np.linalg.norm(w)) for got, w in zip(s["state"], want)])
    return {"state_rel_err": max(max(r) for r in by_layer), "by_slot_and_layer": by_layer,
            "state_mantissa_bits": min(mantissa_bits(s["state"]) for s in samples),
            "consumed": [len(s["context"]) for s in samples]}


def judged(lim: Dict[str, Any], sample: int, gaps, state, dropped) -> List[Dict[str, Any]]:
    """The cell's ``correct``: every number compared, beside its limit.
    ``control_solar_open2.py`` puts its controls through the same."""
    nan = float("nan")
    return [
        check("served_sample", float(sample), ">=", 1.0),
        check("token_gap_mean", gaps["token_gap_mean"] if gaps else nan, "<=", lim["token_gap_mean_max"]),
        check("state_rel_err", state["state_rel_err"] if state else nan, "<=", lim["state_rel_err_max"]),
        check("state_mantissa_bits", state["state_mantissa_bits"] if state else nan, ">=", lim["state_mantissa_bits_min"]),
        check("moe_dropped_assignments", nan if dropped is None else float(dropped), "<=", 0.0),
    ]


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
    live: Dict[int, Any] = {}  # the engine's own record of each request in flight, looked up once where it is submitted

    def emitted(rid: int) -> int:
        # 200 requests are asked every step: ``srv.result`` walks the 160 active slots for each (1.5 ms a step)
        r = retired.get(rid) or live.get(rid)
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
        prompts[rid], live[rid] = req["prompt"], srv.result(rid)
        stamper.offer(rid, due, len(req["prompt"]), req["max_new"])

    if mix["kind"] != "closed":
        raise ValueError("runner serve_solar2 drives closed-loop traffic only")
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
            live.pop(rec["id"], None)
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
    # the window's tokens by sixth: how steady the rate was inside the run (a stall, a slow stretch)
    sixth = np.zeros((6,), np.int64)
    for r in stamper.requests:
        at = np.asarray([s for s in r["stamps"] if t_open <= s < t_close], np.float64)
        np.add.at(sixth, np.minimum(((at - t_open) * 6.0 / (t_close - t_open)).astype(np.int64), 5), 1)
    ctx.say(f"window: {w['tokens']} tokens / {w['window_s']:.1f}s, {len(in_window)} steps, "
            f"{w['attempted']} attempted, {w['failed']} failed, {len(served)} finished; tokens by sixth {sixth.tolist()}")

    e2e = {"serve_tokens_per_s": w["tokens"] / w["window_s"], "setup_s": t_open - ctx.t_start}
    if ctx.trace:
        for which in ("prefill", "decode"):
            ctx.say(f"{which} step by the compiler: " + memory_analysis(srv.compiled_step(which)))

    compiles = srv.prefill_compiles + srv.decode_compiles - win0["compiles"]
    lim = cfg["checks"]
    sampled = state_samples(srv, live.values(), int(lim["state_sample_slots"]), np.random.default_rng([ctx.seed, 7]))

    # ---- correctness, outside the window, the engine let go first -------
    del srv  # the one reference: the closures above see an emptied cell
    live.clear()
    gc.collect()
    ref = build.reference(cfg, ctx.seed)
    pick = np.random.default_rng([ctx.seed, 6]).permutation(len(served))[: int(lim["sample_requests"])]
    g = served_gaps(ref, [served[int(i)] for i in pick], int(lim["pad_multiple"])) if len(pick) else None
    st = state_errors(ref, sampled, int(lim["pad_multiple"])) if sampled else None
    record_checks = judged(lim, len(pick), g, st, moe["dropped_assignments"] if moe else None)
    # the largest gap is shown and not judged: the configuration file says why (checks.read_on_chip)
    ctx.say(f"checked {g['tokens'] if g else 0} tokens of {len(pick)} requests against the reference"
            + (f"; token_gap_max {g['token_gap_max']:.4f} (shown, not judged)" if g else ""))
    ctx.say(f"recurrent state of {len(sampled)} decoding slots after {st['consumed'] if st else []} tokens: "
            f"relative error by slot and layer {st['by_slot_and_layer'] if st else []}, "
            f"mantissa bits {st['state_mantissa_bits'] if st else None}")

    page_len = scfg["kvcache"]["page_len"]
    fills = [f for s in traced for f in s["decode_fills"]]
    return {
        "end_to_end": e2e, "attempted": w["attempted"], "failed": w["failed"], "checks": record_checks,
        "window": {"t_open": t_open, "t_close": t_close, "steps": len(in_window),
                   "step_walls_s": [s["t1"] - s["t0"] for s in in_window], "tokens_by_sixth": sixth.tolist(), **w},
        "counters": {
            "compiles_in_window": compiles,
            "kv_alloc_waits": kv.get("alloc_waits", 0) - win0["alloc_waits"],
            "kv_pages_live": kv.get("pages_live"), "kv_num_pages": kv.get("num_pages"),
            "timeline": tl, "num_slots": scfg["num_slots"], "generator_late_s_max": max(late_s),
            "moe": moe, "hybrid": stats.get("hybrid"),
            "engine_stats": {k: v for k, v in stats.items() if isinstance(v, (int, float, str))},
        },
        "shapes": {"model": dims, "page_len": page_len, "decode_steps_traced": sum(1 for s in traced if s["decode_fills"]),
                   "decode_rows_traced": len(fills),
                   "decode_pages_traced": sum(-(-f // page_len) for f in fills)},
    }
