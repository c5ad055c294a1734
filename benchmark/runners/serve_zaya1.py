"""Runner ``serve_zaya1``: ZAYA1 behind ``deepspeed_tpu.init_inference`` →
``ServingEngine``, on the hybrid cache in which every layer has K/V pages
and a per-slot convolution tail.

The loop and the record's keys are ``runners/serve_solar2.py``'s (submit
what is due, step the engine, stamp each request's new tokens with the
step's end time; expert counters started where the window opens), so
every serve reader reads this cell too.  What differs: the engine and
the reference come from :mod:`benchmark.build_zaya1`; the engine is let
go before the reference runs (a forward over 8,192 positions beside 11 GB
of weights and pages does not fit); the head's logits are computed for
the generated positions only (``(8192, 131136)`` float32 is 4.3 GB); and
**the cache rows are judged too**: where the window closes, some decoding
slots' K and V page rows are read off the timed engine at the positions
where a lost convolution carry or value shift would show — 0, 1, 2, each
chunk boundary ``c, c + 1, c + 2``, the last prompt position and the
first two decoded — and held against the reference's mixed, normalised,
rotated ``k`` and shifted ``v`` there (``kv_boundary_rel_err``: the
largest relative error of a row).  Inside ``token_gap_mean`` such a fault
would hide: it touches three positions a chunk.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import build_zaya1 as build
from benchmark import checks, scopes, stamps, traffic
from benchmark.harness import check, memory_analysis

HEAD_ROWS = 512  # positions whose logits over the rows held exist at a time
SCOPES = ("cca.mix", "cca.attend", "moe.router")  # the named scopes inside both serve programs (docs/telemetry.md)


def _padded(context: np.ndarray, pad_multiple: int) -> np.ndarray:
    out = np.zeros((-(-len(context) // pad_multiple) * pad_multiple,), np.int32)
    out[: len(context)] = context
    return out


def position_gaps(ref, hidden, n_prompt: int, n: int, chosen: np.ndarray) -> List[float]:
    """For the generated positions ``n_prompt .. n - 1`` of one sequence
    whose final hidden states are ``hidden``: the reference's largest
    logit minus its logit of the token ``chosen`` there."""
    import jax.numpy as jnp

    gaps: List[float] = []
    for a in range(n_prompt - 1, n - 1, HEAD_ROWS):  # the positions that predicted each generated token
        rows = ref.head(hidden[a: min(a + HEAD_ROWS, n - 1)])
        picked = jnp.take_along_axis(rows, jnp.asarray(chosen[a - n_prompt + 1: a - n_prompt + 1 + rows.shape[0]], jnp.int32)[:, None], axis=-1)
        gaps += [float(g) for g in np.asarray(jnp.max(rows, axis=-1) - picked[:, 0])]
    return gaps


def served_gaps(ref, served: List[Dict[str, Any]], pad_multiple: int, picker=None) -> Dict[str, Any]:
    """``token_gap_mean`` / ``token_gap_max`` of served requests, each
    sequence padded to its own next multiple of ``pad_multiple`` (causal:
    the padding cannot reach back).  ``picker``, another reference, is
    put in the program's place: the tokens judged are those *its* forward
    over the same context would have emitted (the int8 control)."""
    import jax.numpy as jnp

    gaps: List[float] = []
    for r in served:
        n_prompt = len(r["prompt"])
        context = np.concatenate([np.asarray(r["prompt"], np.int32), np.asarray(r["generated"], np.int32)])
        padded, chosen = _padded(context, pad_multiple), context[n_prompt:]
        if picker is not None:
            theirs = picker.hidden(padded)
            chosen = np.concatenate([np.asarray(jnp.argmax(picker.head(theirs[a: min(a + HEAD_ROWS, len(context) - 1)]), axis=-1))
                                     for a in range(n_prompt - 1, len(context) - 1, HEAD_ROWS)])
        gaps += position_gaps(ref, ref.hidden(padded), n_prompt, len(context), chosen)
    return checks.gap_summary(gaps)


def boundary_positions(n_prompt: int, consumed: int, chunk: int) -> np.ndarray:
    """Where a lost carry would show: the sequence's first three
    positions, the first three of every later prefill chunk, the last
    prompt position and the first two decoded — those the slot has consumed."""
    at = [0, 1, 2] + [c + i for c in range(chunk, n_prompt, chunk) for i in range(3)] + [n_prompt - 1, n_prompt, n_prompt + 1]
    return np.asarray(sorted({p for p in at if 0 <= p < consumed}), np.int32)


def kv_samples(srv, requests, count: int, rng, chunk: int) -> List[Dict[str, Any]]:
    """The K and V page rows of ``count`` decoding slots as the engine
    holds them now, at :func:`boundary_positions`, each with the tokens
    the slot has consumed: the prompt and every generated token but the
    newest, which no step has read yet.  Slots whose prompt spans several
    chunks first.  ``requests``: the engine's own records of requests in flight."""
    import jax

    rows = [q for q in requests if q.status == "decode" and q.slot is not None and len(q.generated) >= 3]
    order = sorted(rng.permutation(len(rows)), key=lambda i: len(rows[int(i)].prompt) <= chunk + 2)
    gather = jax.jit(lambda buf, pages, offs: buf[:, pages, :, offs, :])  # (n, layers, kv heads, head_dim)
    out = []
    for q in [rows[int(i)] for i in order[:count]]:
        context = np.concatenate([np.asarray(q.prompt, np.int32), np.asarray(q.generated[:-1], np.int32)])
        at = boundary_positions(len(q.prompt), len(context), chunk)
        page_len = srv.pool.page_len
        pages, offs = np.asarray(srv.pool.table(q.slot))[at // page_len], at % page_len
        out.append({"context": context, "n_prompt": len(q.prompt), "at": at,
                    "k": np.asarray(gather(srv.pool.k, pages, offs), np.float32).transpose(1, 0, 2, 3),
                    "v": np.asarray(gather(srv.pool.v, pages, offs), np.float32).transpose(1, 0, 2, 3)})
    return out


def kv_errors(ref, samples: List[Dict[str, Any]], pad_multiple: int, held=None) -> Dict[str, Any]:
    """``|row - row_ref| / |row_ref|`` of every sampled position, layer
    and buffer (a row: all KV heads of one position); ``kv_boundary_rel_err``
    is the largest.  ``held`` replaces the engine's rows (a control's own)."""
    worst, where, by_layer = 0.0, None, []
    for i, s in enumerate(samples):
        want: List = []
        ref.hidden(_padded(s["context"], pad_multiple), kv_at=s["at"], kv=want)
        got = held[i] if held is not None else s
        layers = []
        for l, (wk, wv) in enumerate(want):
            errs = {name: np.linalg.norm((g - w).reshape(len(s["at"]), -1), axis=1) / np.linalg.norm(w.reshape(len(s["at"]), -1), axis=1)
                    for name, g, w in (("k", got["k"][l], wk), ("v", got["v"][l], wv))}
            for name, e in errs.items():
                if float(e.max()) > worst:
                    worst, where = float(e.max()), {"sample": i, "layer": l, "buffer": name, "position": int(s["at"][int(e.argmax())])}
            layers.append(round(float(max(e.max() for e in errs.values())), 5))
        by_layer.append(layers)
    return {"kv_boundary_rel_err": worst, "worst_at": where, "by_sample_and_layer": by_layer,
            "positions": [s["at"].tolist() for s in samples]}


def judged(lim: Dict[str, Any], sample: int, gaps, kv, dropped) -> List[Dict[str, Any]]:
    """The cell's ``correct``: every number compared, beside its limit.
    ``control_zaya1.py`` puts its controls through the same."""
    nan = float("nan")
    return [
        check("served_sample", float(sample), ">=", 1.0),
        check("token_gap_mean", gaps["token_gap_mean"] if gaps else nan, "<=", lim["token_gap_mean_max"]),
        check("kv_boundary_rel_err", kv["kv_boundary_rel_err"] if kv else nan, "<=", lim["kv_boundary_rel_err_max"]),
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
        raise ValueError("runner serve_zaya1 drives closed-loop traffic only")
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
    lim = cfg["checks"]
    sampled = kv_samples(srv, live.values(), int(lim["kv_sample_slots"]), np.random.default_rng([ctx.seed, 7]), scfg["prefill_chunk"])

    # ---- correctness, outside the window, the engine let go first -------
    del srv  # the one reference: the closures above see an emptied cell
    live.clear()
    gc.collect()
    ref = build.reference(cfg, ctx.seed)
    pad = int(lim["pad_multiple"])
    pick = np.random.default_rng([ctx.seed, 6]).permutation(len(served))[: int(lim["sample_requests"])]
    g = served_gaps(ref, [served[int(i)] for i in pick], pad) if len(pick) else None
    kv_err: Optional[Dict[str, Any]] = kv_errors(ref, sampled, pad) if sampled else None
    record_checks = judged(lim, len(pick), g, kv_err, moe["dropped_assignments"] if moe else None)
    # the largest gap is shown and not judged: the configuration file says why (checks.read_on_chip)
    ctx.say(f"checked {g['tokens'] if g else 0} tokens of {len(pick)} requests against the reference"
            + (f"; token_gap_max {g['token_gap_max']:.4f} (shown, not judged)" if g else ""))
    ctx.say(f"K/V rows of {len(sampled)} decoding slots at {kv_err['positions'] if kv_err else []}: largest relative error of a row "
            f"by slot and layer {kv_err['by_sample_and_layer'] if kv_err else []}, worst at {kv_err['worst_at'] if kv_err else None}")

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
