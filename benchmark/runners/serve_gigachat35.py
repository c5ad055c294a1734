"""Runner ``serve_gigachat35``: GigaChat3.5 behind
``deepspeed_tpu.init_inference`` → ``ServingEngine``, on the hybrid cache
over latent pages (a latent buffer for the latent-attention layer + per
slot a float32 recurrent state for the delta-rule layers).

The loop and the record's keys are ``runners/serve_solar2.py``'s (submit
what is due, step the engine, stamp each request's new tokens with the
step's end time; expert counters started where the window opens; the
stamper answered from the engine's own request records), so every serve
reader reads this cell too.  What differs: the engine and the reference
come from :mod:`benchmark.build_gigachat35`; a traced run keeps, beside
its trace, which instructions of the two programs were traced under the
named scopes :data:`SCOPES`; and **both halves of the cache are judged**
on what the timed engine holds where the window closes, the engine let
go before the reference runs (9.5 GB of weights do not fit beside its
activations).  For some decoding slots, each with the tokens it has
consumed:

* ``state_rel_err`` / ``state_mantissa_bits`` — the slot's rows of
  ``pool.state["s"]`` against the state the reference's recurrence leaves
  after the same tokens, a layer's whole state in the Frobenius norm, and
  the bits of mantissa they carry (``runners/serve_solar2.py``'s numbers,
  here on four layers);
* ``latent_boundary_rel_err`` — the rows ``[c_kv | k_pe]`` the slot's
  pages hold at :func:`boundary_positions` (where a lost carry, a page
  mapped wrong or a chunk written past its end would show) against the
  reference's, the largest relative error of a row.

The reference is asked for contexts up to ``checks.max_context`` only,
every one padded to the one length ``checks.pad_multiple`` (its float32
activations of 16,384 channels a delta-rule layer are 0.5 GB a thousand
positions, and every padded length compiles its own dozen programs: one
length is one compile); longer requests are served and timed and not
sampled.
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import build_gigachat35 as build
from benchmark import checks, scopes, stamps, traffic
from benchmark.harness import check, memory_analysis
from benchmark.runners.serve_solar2 import mantissa_bits

SCOPES = ("gdn.conv", "gdn.chunk", "gdn.step", "mla.attend", "moe.router")


def _padded(context: np.ndarray, multiple: int) -> np.ndarray:
    out = np.zeros((-(-len(context) // multiple) * multiple,), np.int32)
    out[: len(context)] = context
    return out


def served_gaps(ref, served: List[Dict[str, Any]], pad_multiple: int, picker=None) -> Dict[str, Any]:
    """``token_gap_mean`` over ``served``, each sequence padded to its
    own next multiple of ``pad_multiple`` (causal: the padding cannot
    reach back).  ``picker``, another reference, is put in the program's
    place: the tokens judged are those *its* forward over the same
    context would have emitted (a control)."""
    import jax.numpy as jnp

    gaps: List[float] = []
    for r in served:
        n_prompt = len(r["prompt"])
        context = np.concatenate([np.asarray(r["prompt"], np.int32), np.asarray(r["generated"], np.int32)])
        padded, chosen = _padded(context, pad_multiple), context[n_prompt:]
        if picker is not None:
            chosen = np.asarray(jnp.argmax(picker.logits(padded[None])[0], axis=-1))[n_prompt - 1: len(context) - 1]
        gaps += checks.position_gaps(ref, context, n_prompt, chosen, len(padded))
    return checks.gap_summary(gaps)


def boundary_positions(n_prompt: int, consumed: int, page_len: int) -> np.ndarray:
    """Where a lost carry or a wrong page would show: the sequence's
    first three positions, every page boundary and the positions either
    side of it (a chunk boundary is a page boundary too), the last prompt
    position and the first two decoded — those the slot has consumed."""
    at = [0, 1, 2] + [b + i for b in range(page_len, consumed, page_len) for i in (-1, 0, 1)] + [n_prompt - 1, n_prompt, n_prompt + 1]
    return np.asarray(sorted({p for p in at if 0 <= p < consumed}), np.int32)


def cache_samples(srv, requests, count: int, rng, chunk: int, max_context: int) -> List[Dict[str, Any]]:
    """What the engine holds now for ``count`` decoding slots, each with
    the tokens it has consumed (the prompt and every generated token but
    the newest, which no step has read yet): the recurrent state of every
    delta-rule layer and the latent rows at :func:`boundary_positions`.
    Slots whose prompt spans several chunks first; contexts past
    ``max_context`` are not sampled.  ``requests``: the engine's own
    records of requests in flight."""
    import jax

    rows = [q for q in requests if q.status == "decode" and q.slot is not None and len(q.generated) >= 3
            and len(q.prompt) + len(q.generated) - 1 <= max_context]
    order = sorted(rng.permutation(len(rows)), key=lambda i: len(rows[int(i)].prompt) <= chunk + 2)
    gather = jax.jit(lambda buf, pages, offs: buf[:, pages, :, offs])  # (n, latent layers, width)
    page_len = srv.pool.page_len
    out = []
    for q in [rows[int(i)] for i in order[:count]]:
        context = np.concatenate([np.asarray(q.prompt, np.int32), np.asarray(q.generated[:-1], np.int32)])
        at = boundary_positions(len(q.prompt), len(context), page_len)
        pages, offs = np.asarray(srv.pool.table(q.slot))[at // page_len], at % page_len
        out.append({"context": context, "n_prompt": len(q.prompt), "at": at,
                    "state": np.asarray(srv.pool.state["s"][:, q.slot], np.float32),
                    "latent": np.asarray(gather(srv.pool.k, pages, offs), np.float32).transpose(1, 0, 2)})
    return out


def cache_errors(ref, samples: List[Dict[str, Any]], pad_multiple: int, held=None) -> Dict[str, Any]:
    """Each sampled slot against one reference forward over its context:
    ``state_rel_err`` the largest ``|S - S_ref| / |S_ref|`` of a layer's
    whole state, ``state_mantissa_bits`` the fewest bits a sampled slot's
    state carries, ``latent_boundary_rel_err`` the largest relative error
    of a cached row (``latent_median_rel_err``, the median row's, is
    shown beside it).  ``held`` replaces the engine's numbers (a control's
    own: ``{"state", "latent"}`` a sample)."""
    by_state: List[List[float]] = []
    by_latent: List[List[float]] = []
    worst, where, median = 0.0, None, 0.0
    bits = []
    for i, s in enumerate(samples):
        want_s, want_l = ref.traces(_padded(s["context"], pad_multiple), len(s["context"]), s["at"])
        got = held[i] if held is not None else s
        by_state.append([float(np.linalg.norm(g - w) / np.linalg.norm(w)) for g, w in zip(got["state"], want_s)])
        bits.append(mantissa_bits(got["state"]))
        layers = []
        for l, (g, w) in enumerate(zip(got["latent"], want_l)):
            e = np.linalg.norm(g - w, axis=1) / np.linalg.norm(w, axis=1)
            if float(e.max()) > worst:
                worst, where = float(e.max()), {"sample": i, "latent_layer": l, "position": int(s["at"][int(e.argmax())])}
            layers.append(round(float(e.max()), 5))
            median = max(median, float(np.median(e)))
        by_latent.append(layers)
    return {"state_rel_err": max(max(r) for r in by_state), "state_by_slot_and_layer": by_state, "state_mantissa_bits": min(bits),
            "latent_boundary_rel_err": worst, "latent_worst_at": where, "latent_by_slot_and_layer": by_latent,
            "latent_median_rel_err": median,  # shown, not judged: the typical row, beside the largest
            "consumed": [len(s["context"]) for s in samples], "positions_a_slot": [len(s["at"]) for s in samples]}


def judged(lim: Dict[str, Any], sample: int, gaps, cache, dropped) -> List[Dict[str, Any]]:
    """The cell's ``correct``: every number compared, beside its limit.
    ``control_gigachat35.py`` puts its controls through the same."""
    nan = float("nan")
    of = lambda k: cache[k] if cache else nan  # noqa: E731
    return [
        check("served_sample", float(sample), ">=", 1.0),
        check("token_gap_mean", gaps["token_gap_mean"] if gaps else nan, "<=", lim["token_gap_mean_max"]),
        check("state_rel_err", of("state_rel_err"), "<=", lim["state_rel_err_max"]),
        check("state_mantissa_bits", of("state_mantissa_bits"), ">=", lim["state_mantissa_bits_min"]),
        check("latent_boundary_rel_err", of("latent_boundary_rel_err"), "<=", lim["latent_boundary_rel_err_max"]),
        check("moe_dropped_assignments", nan if dropped is None else float(dropped), "<=", 0.0),
    ]


def sample_served(served: List[Dict[str, Any]], count: int, max_context: int, rng) -> List[Dict[str, Any]]:
    """``count`` of the served requests whose context the reference is asked for, in a seeded order."""
    fits = [r for r in served if len(r["prompt"]) + len(r["generated"]) <= max_context]
    return [fits[int(i)] for i in rng.permutation(len(fits))[:count]]


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
        raise ValueError("runner serve_gigachat35 drives closed-loop traffic only")
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

    # the window's longest steps by the engine's own timeline: where a run that reads low lost its time (a stall of the host's, a slow stretch)
    recs = srv.timeline.records
    if recs:
        med = float(np.median([r["wall"] for r in recs]))
        longest = sorted(range(len(recs)), key=lambda i: -recs[i]["wall"])[:4]
        ctx.say(f"steps over twice the median wall ({med * 1e3:.1f} ms): {sum(r['wall'] > 2 * med for r in recs)} of {len(recs)}, "
                f"{sum(r['wall'] - med for r in recs if r['wall'] > 2 * med):.2f} s over it in all; the longest: "
                + "; ".join(f"step {i}: " + ", ".join(f"{k} {recs[i][k] * 1e3:.0f}" for k in ("wall", "stage", "dispatch", "wait", "note", "other"))
                            for i in longest))
    if moe:
        # the held experts' share of the window's assignments is the seed's (the router's draw): device work that differs run to run
        ctx.say(f"held experts: {moe['assignments_computed']} assignments computed in the window, "
                f"load max over mean {moe['load_max_over_mean']:.2f}, by layer {[sum(layer) for layer in moe['tokens_per_expert']]}")
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
    pad, cap = int(lim["pad_multiple"]), int(lim["max_context"])
    sampled = cache_samples(srv, live.values(), int(lim["cache_sample_slots"]), np.random.default_rng([ctx.seed, 7]),
                            scfg["prefill_chunk"], cap)

    # ---- correctness, outside the window, the engine let go first -------
    del srv  # the one reference: the closures above see an emptied cell
    live.clear()
    gc.collect()
    ref = build.reference(cfg, ctx.seed)
    picked = sample_served(served, int(lim["sample_requests"]), cap, np.random.default_rng([ctx.seed, 6]))
    g = served_gaps(ref, picked, pad) if picked else None
    cache: Optional[Dict[str, Any]] = cache_errors(ref, sampled, pad) if sampled else None
    record_checks = judged(lim, len(picked), g, cache, moe["dropped_assignments"] if moe else None)
    # the largest gap is shown and not judged: the configuration file says why (checks.read_on_chip)
    ctx.say(f"checked {g['tokens'] if g else 0} tokens of {len(picked)} requests "
            f"(prompts {[len(r['prompt']) for r in picked]}) against the reference"
            + (f"; token_gap_max {g['token_gap_max']:.4f} (shown, not judged)" if g else ""))
    if cache:
        ctx.say(f"caches of {len(sampled)} decoding slots after {cache['consumed']} tokens: recurrent state's relative error by slot and "
                f"layer {cache['state_by_slot_and_layer']}, mantissa bits {cache['state_mantissa_bits']}; latent rows at "
                f"{cache['positions_a_slot']} positions a slot, largest relative error of a row by slot and layer "
                f"{cache['latent_by_slot_and_layer']}, worst at {cache['latent_worst_at']}, the median row {cache['latent_median_rel_err']:.4f}")

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
            "kv_page_kind": kv.get("page_kind"), "kv_page_leaves": kv.get("page_leaves"), "kv_state_leaves": kv.get("state_leaves"),
            "timeline": tl, "num_slots": scfg["num_slots"], "generator_late_s_max": max(late_s),
            "moe": moe, "hybrid": stats.get("hybrid"),
            "engine_stats": {k: v for k, v in stats.items() if isinstance(v, (int, float, str))},
        },
        "shapes": {"model": dims, "page_len": page_len, "decode_steps_traced": sum(1 for s in traced if s["decode_fills"]),
                   "decode_rows_traced": len(fills),
                   "decode_pages_traced": sum(-(-f // page_len) for f in fills)},
    }
