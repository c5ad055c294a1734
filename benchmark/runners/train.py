"""Runner ``train``: ``deepspeed_tpu.initialize`` → ``engine.train_batch``.

Set-up: seeded weights on the device, the program's loss path on the
probe sequences, the engine, its first step (which compiles; its loss
and weights are kept on the host), two more warm-up steps.  The plain
reference's sweep, which that first step is checked against, runs after
the window has closed, so set-up times the program and not the
benchmark's own float32 model.  Window: whole optimizer
steps, each closed by ``block_until_ready``, until ``--seconds`` have
passed; the rate is the tokens of those steps over the time they took
(the window closes with the step that crosses ``--seconds``, so no
partial step is counted and no step is cut).
"""
from __future__ import annotations

import time
from typing import Any, Dict

import jax
import numpy as np

from benchmark import build, traffic
from benchmark.harness import check, memory_analysis
from benchmark.stats import train_flops_per_token


def run(ctx) -> Dict[str, Any]:
    cfg, mix = ctx.config, ctx.traffic
    dims, n_dev, seq = cfg["model"], len(ctx.devices), int(mix["seq"])
    ctx.say(f"imports done, {n_dev} device(s) in hand")
    built = build.train_engine(cfg, seq, ctx.seed, ctx.devices, say=ctx.say)
    engine, rows = built["engine"], built["rows"]
    ctx.say(f"engine ready: {rows} rows x {seq} = {rows * seq} tokens a step on {n_dev} device(s)")
    stepped = build.first_step(built)
    ctx.say(f"first step (compiles) done, loss {stepped['loss']:.4f}; its weights kept on the host for the check")

    batches = traffic.token_batches(mix, ctx.seed, dims["vocab_size"], rows)
    for _ in range(int(cfg.get("warmup_steps", 2))):
        jax.block_until_ready(engine.train_batch(next(batches)))
    compiles0 = engine.compilation_count

    walls, losses = [], []
    t_open = ctx.window_opens()
    t_close = t_open + ctx.seconds
    now = t_open
    while now < t_close:
        ctx.maybe_start_trace(now, t_close)
        t0 = now
        with ctx.span("data"):
            batch = next(batches)
        with ctx.span("step"):
            loss = engine.train_batch(batch)
            jax.block_until_ready(loss)
        now = time.perf_counter()
        walls.append(now - t0)
        losses.append(loss)
    elapsed = now - t_open
    ctx.window_closes()
    steps = len(walls)
    last_loss = float(losses[-1])

    # ---- correctness, outside the window: the first step against the reference ----
    numbers = build.first_step_numbers(built, stepped, build.reference(cfg, ctx.seed))
    lim = cfg["checks"]
    record_checks = [check(name, value, "<=", lim[name + "_max"]) for name, value in numbers.items()]
    record_checks.append(check("last_loss_finite", float(np.isfinite(last_loss)), ">=", 1.0))
    ctx.say("first step checked: " + ", ".join(f"{c['name']}={c['value']:.3e}" for c in record_checks))
    tokens_per_s_chip = steps * rows * seq / elapsed / n_dev
    if ctx.trace:
        ctx.say("train step by the compiler: " + memory_analysis(engine.train_step_executable()))
    ctx.say(f"window: {steps} steps in {elapsed:.3f}s, {tokens_per_s_chip:.2f} tokens/s/chip, last loss {last_loss:.4f}")
    return {
        "end_to_end": {"train_tokens_per_s": tokens_per_s_chip, "setup_s": t_open - ctx.t_start},
        "attempted": steps, "failed": 0, "checks": record_checks,
        "window": {"t_open": t_open, "t_close": now, "steps": steps, "step_walls_s": walls,
                   "tokens_per_step": rows * seq},
        "counters": {"compiles_in_window": engine.compilation_count - compiles0},
        "shapes": {"model": dims, "seq": seq, "rows_per_device": rows // n_dev, "n_devices": n_dev,
                   "flops_per_token": train_flops_per_token(dims, seq)},
    }
