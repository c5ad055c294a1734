"""Runner ``serve_keye``: Keye-VL-2.0's language model behind
``deepspeed_tpu.init_inference`` → ``ServingEngine``, on the cache kind
whose pages carry K, V and an indexer key a position.

The loop and the record's keys are ``runners/serve_zaya1.py``'s (submit
what is due, step the engine, stamp each request's new tokens with the
step's end time; expert counters started where the window opens), so
every serve reader reads this cell too.  What differs: the engine and the
reference come from :mod:`benchmark.build_keye`; the engine is let go
before the reference runs (a float32 forward over up to 33,792 positions
beside 9 GB of weights and pages does not fit); **the cache rows of all
three leaves are judged** at chunk and page boundaries
(``kv_first_layer_rel_err``, ``kv_layer_median_rel_err``: relative errors
of K, V and indexer-key rows, :func:`kv_errors`); and **the selection itself is judged,
as the timed decode executable made it**: the family's decode program
hands back, beside its tokens, each layer's selection mask and the index
score it was cut at, and the engine leaves them on the device until its
next step (``ServingEngine.decode_kept``).  Where the window closes the
newest decode step's are read for some decoding slots
(:func:`served_selection`: no program of the runner's own) and held
against the reference's selection at the same position
(``selection_overlap_mean``: the share of the reference's set that the
program's holds, over the sampled slots and all layers), and the scores
that step's selections were cut at — every live row's, every layer's — are
asked how many bits of mantissa they carry
(``index_score_mantissa_bits``: the selected set itself reads a bfloat16
indexer like a float32 one — under a bf16 residual stream the two differ
by a thousandth of the set, the configuration file's
``checks.read_on_chip``).
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmark import build_keye as build
from benchmark import scopes, stamps, traffic
from benchmark.harness import check, memory_analysis
from benchmark.runners.serve_solar2 import mantissa_bits  # the bits of mantissa a float32 array carries
from benchmark.runners.serve_zaya1 import _padded, served_gaps  # noqa: F401  (token_gap_mean of served requests, as the ZAYA1 cell reads it; chip_smoke.py and control_keye.py take them from here)

SCOPES = ("dsa.qkv", "dsa.index", "dsa.select", "dsa.attend", "moe.router")  # the named scopes inside both serve programs (docs/telemetry.md)


def boundary_positions(n_prompt: int, consumed: int, chunk: int, page_len: int) -> np.ndarray:
    """Where a fault of the writes would show: the sequence's first two
    positions, the last and first of every prefill chunk boundary, a page
    boundary inside the first chunk, the last prompt position and the
    first two decoded — those the slot has consumed."""
    at = [0, 1, page_len - 1, page_len] + [c + i for c in range(chunk, n_prompt, chunk) for i in (-1, 0, 1)] + [n_prompt - 1, n_prompt, n_prompt + 1]
    return np.asarray(sorted({p for p in at if 0 <= p < consumed}), np.int32)


def _decoding(requests) -> List[Any]:
    return [q for q in requests if q.status == "decode" and q.slot is not None and len(q.generated) >= 3]


def kv_samples(srv, requests, count: int, rng, chunk: int) -> List[Dict[str, Any]]:
    """The K, V and indexer-key rows of ``count`` decoding slots as the
    engine holds them now, at :func:`boundary_positions`, each with the
    tokens the slot has consumed: the prompt and every generated token but
    the newest, which no step has read yet.  ``requests``: the engine's own
    records of requests in flight."""
    import jax

    rows = _decoding(requests)
    order = rng.permutation(len(rows))
    heads = jax.jit(lambda buf, pages, offs: buf[:, pages, :, offs, :])  # (n, layers, kv heads, head_dim)
    flat = jax.jit(lambda buf, pages, offs: buf[:, pages, :, offs].transpose(1, 0, 2))  # (layers, n, index dim): a page's positions lie along its last dim
    out = []
    for q in [rows[int(i)] for i in order[:count]]:
        context = np.concatenate([np.asarray(q.prompt, np.int32), np.asarray(q.generated[:-1], np.int32)])
        page_len = srv.pool.page_len
        at = boundary_positions(len(q.prompt), len(context), chunk, page_len)
        pages, offs = np.asarray(srv.pool.table(q.slot))[at // page_len], at % page_len
        out.append({"context": context, "n_prompt": len(q.prompt), "at": at, "slot": int(q.slot),
                    "k": np.asarray(heads(srv.pool.k["k"], pages, offs), np.float32).transpose(1, 0, 2, 3),
                    "v": np.asarray(heads(srv.pool.v, pages, offs), np.float32).transpose(1, 0, 2, 3),
                    "idx": np.asarray(flat(srv.pool.k["idx"], pages, offs), np.float32)})
    return out


def reference_side(ref, samples: List[Dict[str, Any]], pad_multiple: int) -> List[Dict[str, Any]]:
    """What ``ref`` (the reference, or a control put in the program's
    place) makes of each sample's context: every layer's ``(k, v, kI)``
    rows at the sampled positions (``rows``), and at the newest consumed
    position its selection, layer by layer (``selected``), with the index
    score each was cut at (``cuts``)."""
    out = []
    for s in samples:
        rows: List = []
        sel: List = []
        cuts: List = []
        ref.hidden(_padded(s["context"], pad_multiple), kv_at=s["at"], kv=rows, selected_at=[len(s["context"]) - 1], selected=sel, cuts=cuts)
        out.append({"rows": rows, "selected": [np.flatnonzero(m[0]) for m in sel], "cuts": [float(c[0]) for c in cuts]})
    return out


def kv_errors(theirs: List[Dict[str, Any]], samples: List[Dict[str, Any]], held=None) -> Dict[str, Any]:
    """``|row - row_ref| / |row_ref|`` of every sampled position, layer
    and leaf (a row: all KV heads of one position, or its indexer key),
    against ``theirs`` (the reference's :func:`reference_side`).
    ``kv_first_layer_rel_err`` is the largest of layer 0 (whose inputs are
    the embedding's rows: nothing but the writes and a bf16 rounding stands
    between the two); ``kv_layer_median_rel_err`` the largest, over slots,
    layers and leaves, of the **median over the sampled positions** — a
    write that lands in another layer's or slot's pages moves every
    position of a layer, where the rounding that now and then moves a
    position's heaviest attended key across the selection's threshold
    moves a tenth of them; ``kv_boundary_rel_err``, the largest of all, is
    shown.  ``held`` replaces the engine's rows (a control's own
    :func:`reference_side`)."""
    worst, where, by_layer, medians = 0.0, None, [], []
    for i, s in enumerate(samples):
        got = [(s["k"][l], s["v"][l], s["idx"][l]) for l in range(len(theirs[i]["rows"]))] if held is None else held[i]["rows"]
        layers, mids = [], []
        for l, want in enumerate(theirs[i]["rows"]):
            errs = {name: np.linalg.norm((g - w).reshape(len(s["at"]), -1), axis=1) / np.linalg.norm(w.reshape(len(s["at"]), -1), axis=1)
                    for name, g, w in zip(("k", "v", "idx"), got[l], want)}
            for name, e in errs.items():
                if float(e.max()) > worst:
                    worst, where = float(e.max()), {"sample": i, "layer": l, "leaf": name, "position": int(s["at"][int(e.argmax())])}
            layers.append(round(float(max(e.max() for e in errs.values())), 5))
            mids.append(round(float(max(np.median(e) for e in errs.values())), 5))
        by_layer.append(layers)
        medians.append(mids)
    return {"kv_boundary_rel_err": worst, "kv_first_layer_rel_err": max((row[0] for row in by_layer), default=float("nan")),
            "kv_layer_median_rel_err": max((x for row in medians for x in row), default=float("nan")),
            "worst_at": where, "by_sample_and_layer": by_layer, "median_by_sample_and_layer": medians,
            "positions": [s["at"].tolist() for s in samples]}


def served_selection(srv, samples: List[Dict[str, Any]]):
    """What the engine's newest decode step — the served executable
    itself, at all its rows — selected for the sampled slots
    (``ServingEngine.decode_kept``): per sample each layer's selected
    positions (``selected``) and the index score it was cut at (``cuts``);
    and, second, the cuts of every live row and layer of that step.  A
    sampled slot's row of that step is the query at its newest consumed
    position, or this raises."""
    kept = srv.decode_kept
    if kept is None:
        raise RuntimeError("the engine kept nothing of its newest decode step: the family's forward does not say decode_keeps")
    pos, cuts = np.asarray(kept["pos"]), np.asarray(kept["threshold"], np.float32)  # (slots,), (layers, slots)
    out = []
    for s in samples:
        t = len(s["context"]) - 1
        if int(pos[s["slot"]]) != t:
            raise RuntimeError(f"slot {s['slot']}: the newest decode step's row stood at {int(pos[s['slot']])}, the slot's newest consumed position is {t}")
        masks = np.asarray(kept["selected"][:, s["slot"]])  # (layers, positions)
        out.append({"selected": [np.flatnonzero(m) for m in masks], "cuts": cuts[:, s["slot"]].tolist()})
    return out, cuts[np.isfinite(cuts)]


def selection_overlap(theirs: List[Dict[str, Any]], ours: List[Dict[str, Any]], cuts) -> Dict[str, Any]:
    """The share of the reference's selected set that the program's holds,
    by sample and layer: the mean (judged) and the smallest (shown: where a
    sampled position's hidden state took one of the rare steps away from
    the reference's, a deep layer's sets part by a third); and the bits of
    mantissa that ``cuts``, the index scores the program's selections were
    cut at, carry (float32 scores read 21-23, scores that were ever held
    in bfloat16 at most 7)."""
    by = [[round(len(np.intersect1d(a, b)) / max(1, len(a)), 5) for a, b in zip(want["selected"], got["selected"])]
          for want, got in zip(theirs, ours)]
    flat = [x for row in by for x in row]
    cuts = np.asarray(cuts, np.float32)
    return {"selection_overlap_mean": float(np.mean(flat)) if flat else float("nan"), "selection_overlap_min": min(flat, default=float("nan")),
            "overlap_by_sample_and_layer": by,
            "index_score_mantissa_bits": mantissa_bits(cuts[np.isfinite(cuts)]) if np.isfinite(cuts).any() else float("nan"),
            "index_scores_read": int(np.isfinite(cuts).sum()),
            "set_sizes": [[len(a) for a in want["selected"]] for want in theirs]}


def judged(lim: Dict[str, Any], sample: int, gaps, kv, overlap, dropped) -> List[Dict[str, Any]]:
    """The cell's ``correct``: every number compared, beside its limit.
    ``control_keye.py`` puts its controls through the same."""
    nan = float("nan")
    return [
        check("served_sample", float(sample), ">=", 1.0),
        check("token_gap_mean", gaps["token_gap_mean"] if gaps else nan, "<=", lim["token_gap_mean_max"]),
        check("kv_layer_median_rel_err", kv["kv_layer_median_rel_err"] if kv else nan, "<=", lim["kv_layer_median_rel_err_max"]),
        check("kv_first_layer_rel_err", kv["kv_first_layer_rel_err"] if kv else nan, "<=", lim["kv_first_layer_rel_err_max"]),
        check("selection_overlap_mean", overlap["selection_overlap_mean"] if overlap else nan, ">=", lim["selection_overlap_mean_min"]),
        check("index_score_mantissa_bits", overlap["index_score_mantissa_bits"] if overlap else nan, ">=", lim["index_score_mantissa_bits_min"]),
        check("moe_dropped_assignments", nan if dropped is None else float(dropped), "<=", 0.0),
    ]


def run(ctx) -> Dict[str, Any]:
    cfg, mix = ctx.config, ctx.traffic
    scfg = cfg["serving"]
    dims = build.dims_of(cfg)
    build.model_config(cfg)  # a checkout without the family stops here, before the device is asked for anything
    ctx.say("imports done, device in hand")
    srv = build.serving_engine(cfg, ctx.seed, ctx.devices, say=ctx.say)
    ctx.say(f"engine ready: {scfg['num_slots']} slots x {scfg['max_len']}, pool {srv.pool.cache_bytes() / 1e9:.2f} GB "
            f"({srv.pool.shape_math()})")

    # compile both executables on a request of two chunks and a few tokens
    rng = np.random.default_rng([ctx.seed, 5])
    t_warm = time.perf_counter()
    warm = srv.submit(rng.integers(1, dims["vocab_size"], scfg["prefill_chunk"] + 3, dtype=np.int32), max_new_tokens=4)
    srv.drain()
    srv.pop_results()
    ctx.say(f"warm request {warm} drained in {time.perf_counter() - t_warm:.1f}s; {srv.prefill_compiles} prefill + "
            f"{srv.decode_compiles} decode executable(s)")

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
        raise ValueError("runner serve_keye drives closed-loop traffic only")
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
    ours, cuts = served_selection(srv, sampled) if sampled else ([], [])

    # ---- correctness, outside the window, the engine let go first -------
    del srv  # the one reference: the closures above see an emptied cell
    live.clear()
    gc.collect()
    ref = build.reference(cfg, ctx.seed)
    pad = int(lim["pad_multiple"])
    pick = np.random.default_rng([ctx.seed, 6]).permutation(len(served))[: int(lim["sample_requests"])]
    g = served_gaps(ref, [served[int(i)] for i in pick], pad) if len(pick) else None
    theirs = reference_side(ref, sampled, pad)
    kv_err: Optional[Dict[str, Any]] = kv_errors(theirs, sampled) if sampled else None
    overlap = selection_overlap(theirs, ours, cuts) if sampled else None
    record_checks = judged(lim, len(pick), g, kv_err, overlap, moe["dropped_assignments"] if moe else None)
    # the largest gap is shown and not judged: the configuration file says why (checks.read_on_chip)
    ctx.say(f"checked {g['tokens'] if g else 0} tokens of {len(pick)} requests against the reference"
            + (f"; token_gap_max {g['token_gap_max']:.4f} (shown, not judged)" if g else ""))
    ctx.say(f"K, V and indexer-key rows of {len(sampled)} decoding slots at {kv_err['positions'] if kv_err else []}: largest relative "
            f"error of a row by slot and layer {kv_err['by_sample_and_layer'] if kv_err else []} (kv_boundary_rel_err "
            f"{kv_err['kv_boundary_rel_err'] if kv_err else float('nan'):.4f}, shown, not judged; worst at {kv_err['worst_at'] if kv_err else None}), "
            f"the median over positions {kv_err['median_by_sample_and_layer'] if kv_err else []}")
    ctx.say(f"selection of the newest decode step at those slots against the reference's at the same position, by slot and layer: "
            f"{overlap['overlap_by_sample_and_layer'] if overlap else []} of sets of {overlap['set_sizes'] if overlap else []} "
            f"(selection_overlap_min {overlap['selection_overlap_min'] if overlap else float('nan'):.4f}, shown, not judged); "
            f"{overlap['index_scores_read'] if overlap else 0} index scores that step's selections were cut at carry "
            f"{overlap['index_score_mantissa_bits'] if overlap else float('nan'):.1f} bits of mantissa; at the sampled slots "
            f"{[o['cuts'] for o in ours]} beside the reference's {[t['cuts'] for t in theirs]}")

    page_len, topk = scfg["kvcache"]["page_len"], int(dims["sa_config"]["topk"])
    fills = [f for s in traced for f in s["decode_fills"]]
    return {
        "end_to_end": e2e, "attempted": w["attempted"], "failed": w["failed"], "checks": record_checks,
        "window": {"t_open": t_open, "t_close": t_close, "steps": len(in_window),
                   "step_walls_s": [s["t1"] - s["t0"] for s in in_window], "tokens_by_sixth": sixth.tolist(), **w},
        "counters": {
            "compiles_in_window": compiles,
            "kv_alloc_waits": kv.get("alloc_waits", 0) - win0["alloc_waits"],
            "kv_pages_live": kv.get("pages_live"), "kv_num_pages": kv.get("num_pages"), "kv_page_leaves": kv.get("page_leaves"),
            "timeline": tl, "num_slots": scfg["num_slots"], "generator_late_s_max": max(late_s),
            "moe": moe,
            "engine_stats": {k: v for k, v in stats.items() if isinstance(v, (int, float, str))},
        },
        "shapes": {"model": dims, "page_len": page_len, "decode_steps_traced": sum(1 for s in traced if s["decode_fills"]),
                   "decode_rows_traced": len(fills),
                   "decode_pages_traced": sum(-(-f // page_len) for f in fills),
                   "decode_positions_traced": sum(fills),
                   "decode_selected_traced": sum(min(f, topk) for f in fills),
                   # what the selection's kernel computed and what it had to read, over the window (the engine's host counters)
                   "select": {"slots": scfg["num_slots"], "prefill_chunk": scfg["prefill_chunk"],
                              "decode_steps": stats.get("dsa_decode_steps", 0), "chunks": stats.get("dsa_chunks", 0),
                              "decode_positions_attendable": stats.get("dsa_positions_attendable", 0),
                              "chunk_positions_attendable": stats.get("dsa_chunk_positions_attendable", 0)}},
    }
