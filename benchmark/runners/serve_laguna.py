"""Runner ``serve_laguna``: Laguna behind ``deepspeed_tpu.init_inference``
→ ``ServingEngine``, on two page groups in one pool (pages by length for
the full-attention layers, a ring of pages a slot for the window layers).

A copy of ``runners/serve_dsv2.py`` — the smallest runner that fits: a
closed loop over an MoE family whose expert counters are started where
the window opens — and the record carries every key the serve readers
use (``served_gaps`` is ``serve_gigachat35.py``'s, imported).  What differs: the engine and the reference come from
:mod:`benchmark.build_laguna`; the stamper is answered from the engine's
own request records (``serve_solar2.py``'s); a traced run keeps, beside
its trace, which instructions of the two programs were traced under the
named scopes :data:`SCOPES` (``serve_zaya1.py``'s); ``counters`` keep the
pool's two groups (``kv_groups``: layers, pages or positions a slot,
bytes) and ``shapes`` the traced decode rows' **window positions**
(``min(fill, window)`` summed: the work of ``swa_decode_paged``) beside
the filled pages of the full group; the engine is **let go before the
reference runs** (13.4 GB of weights and caches leave no room for the
float32 forward of 8,192 positions); and the sample of served requests
the reference is asked for holds, on every seed, **a context past
``window + prefill_chunk`` positions** — one whose ring has lapped, in a
chunk and across chunks — first (``wrapped_contexts`` is judged).  **The
routers are judged too**, on what the served decode program itself chose:
the family's forward says ``decode_keeps``, so the engine leaves the
newest decode step's chosen experts and their router logits on the
device (``ServingEngine.decode_kept``); where the window closes they are
read for every decoding row — ``router_logit_mantissa_bits``: float32
logits carry 21–23 bits of mantissa, logits that were ever held in
bfloat16 at most 7 — and, for some decoding slots with the tokens they
have consumed, held against the experts the reference's routers choose
at the same position (``router_overlap_mean``).  The reference is asked for contexts up to ``checks.max_context`` only, every
one padded to ``checks.pad_multiple`` (one length is one compile);
longer requests are served and timed and not sampled.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import numpy as np

from benchmark import build_laguna as build
from benchmark import checks, scopes, stamps, traffic
from benchmark.harness import check, memory_analysis
from benchmark.runners.serve_gigachat35 import _padded, served_gaps  # noqa: F401  (the token gap over served requests, a control's picker: one function)
from benchmark.runners.serve_solar2 import mantissa_bits

SCOPES = ("swa.chunk", "moe.router")  # the named scopes inside the serve programs (docs/telemetry.md)


def sample_served(served: List[Dict[str, Any]], count: int, max_context: int, wrapped_past: int, rng) -> List[Dict[str, Any]]:
    """``count`` of the served requests whose context the reference is
    asked for, in a seeded order — those whose context passes
    ``wrapped_past`` positions (the ring has lapped) first."""
    fits = [r for r in served if len(r["prompt"]) + len(r["generated"]) <= max_context]
    order = sorted(rng.permutation(len(fits)), key=lambda i: len(fits[int(i)]["prompt"]) + len(fits[int(i)]["generated"]) <= wrapped_past)
    return [fits[int(i)] for i in order[:count]]


def routing_samples(srv, requests, count: int, rng, max_context: int) -> Dict[str, Any]:
    """What the routers of the engine's newest decode step — the served
    executable itself — chose (``ServingEngine.decode_kept``): ``logits``
    the chosen experts' router logits of every decoding row and sparse
    layer, and ``samples``, for ``count`` decoding slots whose context
    fits ``max_context``, the tokens the slot has consumed (the prompt
    and every generated token but the newest, which no step has read
    yet) with the experts chosen at its newest consumed position
    ``(sparse layers, top_k)``.  ``requests``: the engine's own records
    of requests in flight."""
    kept = srv.decode_kept
    if kept is None:
        raise RuntimeError("the engine kept nothing of its newest decode step: the family's forward does not say decode_keeps")
    pos, experts, logits = np.asarray(kept["pos"]), np.asarray(kept["experts"]), np.asarray(kept["router_logits"], np.float32)
    rows = [q for q in requests if q.status == "decode" and q.slot is not None and len(q.generated) >= 2
            and int(pos[q.slot]) == len(q.prompt) + len(q.generated) - 2]  # the rows of that step: each at its newest consumed position
    fits = [q for q in rows if len(q.prompt) + len(q.generated) - 1 <= max_context]
    samples = [{"context": np.concatenate([np.asarray(q.prompt, np.int32), np.asarray(q.generated[:-1], np.int32)]),
                "experts": experts[:, q.slot]} for q in [fits[int(i)] for i in rng.permutation(len(fits))[:count]]]
    return {"logits": logits[:, [q.slot for q in rows]], "rows": len(rows), "samples": samples}


def routing_numbers(ref, routing: Dict[str, Any], pad_multiple: int, held=None) -> Dict[str, Any]:
    """``router_logit_mantissa_bits`` of the kept logits, and
    ``router_overlap_mean``: the share of the reference's chosen experts
    that the program chose too, by sampled slot and sparse layer, at the
    slot's newest consumed position.  ``held`` replaces the program's
    numbers with a control's own (``{"logits", "experts": one a sample}``)."""
    got = held if held is not None else {"logits": routing["logits"], "experts": [s["experts"] for s in routing["samples"]]}
    by = []
    for s, mine in zip(routing["samples"], got["experts"]):
        theirs, _ = ref.routings(_padded(s["context"], pad_multiple), len(s["context"]) - 1)
        by.append([round(len(np.intersect1d(a, b)) / len(a), 4) for a, b in zip(theirs, mine)])
    flat = [x for row in by for x in row]
    return {"router_overlap_mean": float(np.mean(flat)) if flat else float("nan"), "overlap_by_slot_and_layer": by,
            "router_logit_mantissa_bits": mantissa_bits(got["logits"]) if np.size(got["logits"]) else float("nan"),
            "router_logits_read": int(np.size(got["logits"])), "consumed": [len(s["context"]) for s in routing["samples"]]}


def judged(lim: Dict[str, Any], sample: int, wrapped: int, gaps, routing, dropped) -> List[Dict[str, Any]]:
    """The cell's ``correct``: every number compared, beside its limit.
    ``control_laguna.py`` puts its controls through the same."""
    nan = float("nan")
    of = lambda k: routing[k] if routing else nan  # noqa: E731
    return [
        check("served_sample", float(sample), ">=", 1.0),
        check("wrapped_contexts", float(wrapped), ">=", 1.0),
        check("token_gap_mean", gaps["token_gap_mean"] if gaps else nan, "<=", lim["token_gap_mean_max"]),
        check("router_overlap_mean", of("router_overlap_mean"), ">=", lim["router_overlap_mean_min"]),
        check("router_logit_mantissa_bits", of("router_logit_mantissa_bits"), ">=", lim["router_logit_mantissa_bits_min"]),
        check("moe_dropped_assignments", nan if dropped is None else float(dropped), "<=", 0.0),
    ]


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
        raise ValueError("runner serve_laguna drives closed-loop traffic only")
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
    routed = routing_samples(srv, live.values(), int(lim["routing_sample_slots"]), np.random.default_rng([ctx.seed, 7]), int(lim["max_context"]))

    # ---- correctness, outside the window, the engine let go first -------
    del srv  # the one reference: the closures above see an emptied cell
    live.clear()
    gc.collect()
    wrapped_past = int(dims["sliding_window"]) + int(scfg["prefill_chunk"])
    picked = sample_served(served, int(lim["sample_requests"]), int(lim["max_context"]), wrapped_past, np.random.default_rng([ctx.seed, 6]))
    contexts = [len(r["prompt"]) + len(r["generated"]) for r in picked]
    ref = build.reference(cfg, ctx.seed)
    g = served_gaps(ref, picked, int(lim["pad_multiple"])) if picked else None
    rt = routing_numbers(ref, routed, int(lim["pad_multiple"])) if routed["samples"] else None
    record_checks = judged(lim, len(picked), sum(c > wrapped_past for c in contexts), g, rt, moe["dropped_assignments"] if moe else None)
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
        # ``model`` names the full layers' heads (``num_attention_heads``: what ``gqa_decode_paged`` counts under
        # ``flash_decode_paged``); the window layers' are ``window_heads``, their work ``decode_window_positions_traced``
        "shapes": {"model": dims, "page_len": page_len, "decode_steps_traced": sum(1 for s in traced if s["decode_fills"]),
                   "decode_rows_traced": len(fills),
                   "decode_pages_traced": sum(-(-f // page_len) for f in fills),
                   "window": window, "window_heads": max(dims["num_attention_heads_per_layer"]),
                   "decode_window_positions_traced": sum(min(f, window) for f in fills)},
    }
