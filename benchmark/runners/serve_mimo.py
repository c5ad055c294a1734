"""Runner ``serve_mimo``: MiMo-V2-Flash behind ``deepspeed_tpu.init_inference``
→ ``ServingEngine``, on two page groups of unequal geometry in one pool
(pages by length for the full-attention layers' 4 KV heads, a ring of
pages a slot for the window layers' 8; keys 192 and values 128 wide).

A copy of ``runners/serve_laguna.py`` — the runner of the other family on
two page groups: a closed loop over an MoE family whose expert counters
are started where the window opens, the engine **let go before the
reference runs**, the routers judged on what the served decode program
itself left on the device (``routing_numbers`` / ``judged`` are that
runner's, imported; ``routing_samples`` is its too, asked for the
shortest contexts) — and the record carries every
key the serve readers use.  What differs: the engine and the reference
come from :mod:`benchmark.build_mimo`; the traced programs are read under
the scopes :data:`SCOPES` (``full.chunk`` beside ``swa.chunk``);
``shapes`` name the window layers' own KV geometry for this cell's two
work functions (``kernels/swa_sink_decode_paged.py``,
``kernels/asym_gqa_decode_paged.py``); and because a request here is ~21
chunks and ~1,200 steps — about as long as the window — **the sample the
reference is asked for is drawn from every request the timed path
finished since the traffic began**, the pre-roll's among them (the same
two executables, the same pool: of the pool's five contexts under
``checks.max_context`` the window alone finishes one or two), a context
past ``checks.wrapped_past`` positions first (``wrapped_contexts`` is
judged: its ring of two pages has lapped eight times inside every chunk
and across chunks; and **the window's edge is judged by a paired number**,
``window_edge_margin``: the same tokens' gap under the reference with a
window of one more position, less their gap under the reference as
configured — :func:`window_edge`), and the routers' sampled slots may hold contexts up
to ``checks.routing_max_context`` (a second padded length of the
reference: at the window's close a slot with a context under 8,192 is
not there on every seed).
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import build_mimo as build
from benchmark import scopes, stamps, traffic
from benchmark.harness import check, memory_analysis
from benchmark.runners.serve_gigachat35 import _padded, served_gaps  # noqa: F401  (the token gap over served requests, a control's picker: one function)
from benchmark.runners.serve_laguna import judged as _judged
from benchmark.runners.serve_laguna import routing_numbers, sample_served  # noqa: F401  (one function each)
from benchmark.runners.serve_laguna import routing_samples as _routing_samples

SCOPES = ("swa.chunk", "full.chunk", "moe.router")  # the named scopes inside the serve programs (docs/telemetry.md)


def routing_samples(srv, requests, count: int, rng, max_context: int) -> Dict[str, Any]:
    """``serve_laguna.routing_samples`` over the decoding rows with the **shortest** contexts: here most slots hold tens of
    thousands of positions, and a reference forward costs by its padded length — ``count`` of the ``count + 2``
    shortest are asked for (two to spare for a row the newest step did not hold), none past ``max_context``."""
    requests = list(requests)
    shortest = sorted(len(q.prompt) + len(q.generated) - 1 for q in requests if q.status == "decode" and q.slot is not None and len(q.generated) >= 2)
    # every decoding row's logits are read; only the sampled slots are capped
    return _routing_samples(srv, requests, count, rng, min([max_context] + shortest[count + 1: count + 2]))


def window_edge(wide_ref, picked, pad_multiple: int, gaps: Dict[str, Any], picker=None) -> float:
    """``window_edge_margin``: the emitted tokens' ``token_gap_mean`` under ``wide_ref`` — the float32 reference with a
    window of **one more position** — less their gap under the reference as configured (``gaps``), on the same tokens.
    The tokens of a program whose window is the configured one lie nearer the configured reference: the margin is
    positive by about what one more position moves the logits; a window of one more puts them at 0 under ``wide_ref``
    and the margin is minus its own gap.  A paired number: the rounding both gaps share cancels, which a limit on
    ``token_gap_mean`` alone cannot do (one position of 128 moves it by about as much as bfloat16 does)."""
    return float(served_gaps(wide_ref, picked, pad_multiple, picker=picker)["token_gap_mean"] - gaps["token_gap_mean"])


def judged(lim: Dict[str, Any], sample: int, wrapped: int, gaps, routing, dropped, edge) -> List[Dict[str, Any]]:
    """The cell's ``correct``: the Laguna runner's comparisons and the window's edge.  ``control_mimo.py`` puts its
    controls through the same."""
    return _judged(lim, sample, wrapped, gaps, routing, dropped) + [
        check("window_edge_margin", float("nan") if edge is None else edge, ">=", lim["window_edge_margin_min"])]


def run(ctx) -> Dict[str, Any]:
    cfg, mix = ctx.config, ctx.traffic
    scfg = cfg["serving"]
    dims = build.dims_of(cfg)
    ctx.say("imports done, device in hand")
    srv = build.serving_engine(cfg, ctx.seed, ctx.devices, say=ctx.say)
    ctx.say(f"engine ready: {scfg['num_slots']} slots x {scfg['max_len']}, pool {srv.pool.cache_bytes() / 1e9:.2f} GB "
            f"({srv.pool.shape_math()})")

    # compile both executables on a request of two chunks and a few tokens
    rng = np.random.default_rng([ctx.seed, 5])
    warm = srv.submit(rng.integers(1, dims["vocab_size"], scfg["prefill_chunk"] + 3, dtype=np.int32), max_new_tokens=4)
    srv.drain()
    srv.pop_results()
    ctx.say(f"warm request {warm} drained; {srv.prefill_compiles} prefill + {srv.decode_compiles} decode executable(s)")

    retired: Dict[int, Any] = {}  # what the engine retired in the step just taken
    live: Dict[int, Any] = {}  # the engine's own record of each request in flight, looked up once where it is submitted

    def emitted(rid: int) -> int:
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
        raise ValueError("runner serve_mimo drives closed-loop traffic only")
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
            if q is not None and not rec["errored"]:  # the pre-roll's too: the same executables on the same pool
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
            f"{w['attempted']} attempted, {w['failed']} failed, {len(served)} finished since the traffic began; tokens by sixth {sixth.tolist()}")

    e2e = {"serve_tokens_per_s": w["tokens"] / w["window_s"], "setup_s": t_open - ctx.t_start}
    if ctx.trace:
        scoped_ops = {}
        for which in ("prefill", "decode"):
            compiled = srv.compiled_step(which)
            ctx.say(f"{which} step by the compiler: " + memory_analysis(compiled))
            scoped_ops["jit_serve_" + which] = scopes.ops_by_scope(compiled.as_text(), SCOPES)
        # beside the trace: which instructions of each program were traced under which named scope (its events name the instruction only)
        scopes.keep(ctx.trace_dir, scoped_ops)
    compiles = srv.prefill_compiles + srv.decode_compiles - win0["compiles"]
    pool_bytes = srv.pool.cache_bytes()
    lim = cfg["checks"]
    routed = routing_samples(srv, live.values(), int(lim["routing_sample_slots"]), np.random.default_rng([ctx.seed, 7]), int(lim["routing_max_context"]))

    # ---- correctness, outside the window, the engine let go first -------
    del srv  # the one reference: the closures above see an emptied cell
    live.clear()
    gc.collect()
    wrapped_past = int(lim["wrapped_past"])
    picked = sample_served(served, int(lim["sample_requests"]), int(lim["max_context"]), wrapped_past, np.random.default_rng([ctx.seed, 6]))
    contexts = [len(r["prompt"]) + len(r["generated"]) for r in picked]
    ref = build.reference(cfg, ctx.seed)
    g = served_gaps(ref, picked, int(lim["pad_multiple"])) if picked else None
    rt = routing_numbers(ref, routed, int(lim["pad_multiple"])) if routed["samples"] else None
    wide = build.reference(cfg, ctx.seed, window=int(dims["sliding_window"]) + 1)
    edge = window_edge(wide, picked, int(lim["pad_multiple"]), g) if picked else None
    record_checks = judged(lim, len(picked), sum(c > wrapped_past for c in contexts), g, rt, moe["dropped_assignments"] if moe else None, edge)
    # the largest gap is shown and not judged: the configuration file says why (checks.read_on_chip)
    ctx.say(f"checked {g['tokens'] if g else 0} tokens of {len(picked)} requests (contexts {contexts}, the ring laps past {wrapped_past}) "
            "against the reference" + (f"; token_gap_max {g['token_gap_max']:.4f} (shown, not judged)" if g else ""))
    if rt:
        ctx.say(f"routers of the newest decode step: {rt['router_logits_read']} logits of {routed['rows']} rows carry "
                f"{rt['router_logit_mantissa_bits']} bits of mantissa; {len(routed['samples'])} slots after {rt['consumed']} tokens share "
                f"{rt['overlap_by_slot_and_layer']} of the reference's experts by slot and layer")

    page_len, window = scfg["kvcache"]["page_len"], int(dims["sliding_window"])
    fills = [f for s in traced for f in s["decode_fills"]]
    return {
        "end_to_end": e2e, "attempted": w["attempted"], "failed": w["failed"], "checks": record_checks,
        "window": {"t_open": t_open, "t_close": t_close, "steps": len(in_window),
                   "step_walls_s": [s["t1"] - s["t0"] for s in in_window], "tokens_by_sixth": sixth.tolist(), **w},
        "counters": {
            "compiles_in_window": compiles,
            "kv_alloc_waits": kv.get("alloc_waits", 0) - win0["alloc_waits"],
            "kv_pages_live": kv.get("pages_live"), "kv_num_pages": kv.get("num_pages"),
            "kv_groups": kv.get("groups"), "kv_cache_bytes": pool_bytes,
            "timeline": tl, "num_slots": scfg["num_slots"], "generator_late_s_max": max(late_s),
            "moe": moe, "hybrid": stats.get("hybrid"),
            "engine_stats": {k: v for k, v in stats.items() if isinstance(v, (int, float, str))},
        },
        # ``model`` names both kinds' geometry under the published keys (``num_key_value_heads`` / ``swa_num_key_value_heads`` ...);
        # the full layers' work is ``decode_pages_traced``, the window layers' ``decode_window_positions_traced``
        "shapes": {"model": dims, "page_len": page_len, "decode_steps_traced": sum(1 for s in traced if s["decode_fills"]),
                   "decode_rows_traced": len(fills),
                   "decode_pages_traced": sum(-(-f // page_len) for f in fills),
                   "window": window, "window_heads": dims["swa_num_attention_heads"],
                   "decode_window_positions_traced": sum(min(f, window) for f in fills)},
    }
