"""``moe_grouped_matmul`` (ops/kernels/grouped_matmul.py) in interpret
mode under ``DS_KERNELS=1``: the kernel against ``jax.lax.ragged_dot``
and against a plain per-expert loop in float32; the two forms of
``dropless_held_experts``, even and skewed; the dispatch rule and the
note it leaves in ``ServingEngine.stats()``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import deepseek_v2 as ds
from deepspeed_tpu.moe import layer as moe
from deepspeed_tpu.ops.kernels.grouped_matmul import group_metadata, grouped_matmul, grouped_matmul_supported
from deepspeed_tpu.serving import ServingEngine

K, N = 128, 256  # two column blocks of 128


@pytest.fixture
def armed(monkeypatch):
    monkeypatch.setenv("DS_KERNELS", "1")


def _per_expert_loop(x, w, sizes):
    """Group by group in float32, NumPy: rows past ``sum(sizes)`` stay NaN."""
    out = np.full((x.shape[0], w.shape[2]), np.nan, np.float32)
    at = 0
    for e, n in enumerate(sizes):
        out[at:at + n] = np.asarray(x[at:at + n], np.float32) @ np.asarray(w[e], np.float32)
        at += n
    return out


# row tiles of 512 (M = 1024: two) or the rows whole (M = 192), windows of 128 rows from a 16-aligned start
CASES = {
    "even_groups":                    dict(M=1024, sizes=[128] * 8),
    "empty_groups_first_last_middle": dict(M=1024, sizes=[0, 0, 37, 0, 90, 0, 0, 200, 5, 0]),
    "one_hot_expert_several_tiles":   dict(M=1024, sizes=[0, 0, 700, 0]),
    "hot_expert_among_small_ones":    dict(M=1024, sizes=[3, 600, 1, 0, 2, 19]),
    "boundaries_straddle_row_tiles":  dict(M=1024, sizes=[500, 24, 100, 300, 100]),
    "window_clamped_at_a_tiles_end":  dict(M=1024, sizes=[450, 62, 1]),
    "rows_past_the_sum":              dict(M=1024, sizes=[19, 20, 0, 18, 21]),
    "every_row_held":                 dict(M=512, sizes=[100, 0, 300, 112]),
    "decode_rows_whole":              dict(M=192, sizes=[1, 0, 2, 0, 0, 3, 40, 0, 1, 1]),
    "nobody_held":                    dict(M=256, sizes=[0, 0, 0]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_is_ragged_dot_and_the_per_expert_loop_on_the_held_rows(case, dtype):
    M, sizes = CASES[case]["M"], CASES[case]["sizes"]
    rng = np.random.default_rng(len(case))
    x = jnp.asarray(rng.standard_normal((M, K)), dtype)
    w = jnp.asarray(rng.standard_normal((len(sizes), K, N)) * 0.1, dtype)
    s = jnp.asarray(sizes, jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda x, w, s: grouped_matmul(x, w, s, tn=128, interpret=True))(x, w, s), np.float32)
        ragged = np.asarray(jax.lax.ragged_dot(x, w, s), np.float32)
    held = sum(sizes)
    tol = dict(atol=1e-4) if dtype == jnp.float32 else dict(atol=0.05, rtol=0.02)  # one bf16 rounding of the output
    np.testing.assert_allclose(got[:held], ragged[:held], **tol)
    np.testing.assert_allclose(got[:held], _per_expert_loop(x, w, sizes)[:held], **tol)
    assert got.dtype == np.float32 and np.isfinite(got[:held]).all()


def test_group_metadata_visits_each_nonempty_group_once_a_row_tile_it_reaches():
    sizes = jnp.asarray([500, 24, 0, 100, 300, 100, 0], jnp.int32)  # offsets 0 500 524 524 624 924 1024
    offsets, group_of, tile_of, visits = group_metadata(sizes, 1024, 512)
    assert offsets.tolist() == [0, 500, 524, 524, 624, 924, 1024, 1024]
    assert int(visits) == 6 and group_of.shape == (7 + 2 - 1,)
    assert list(zip(group_of[:6].tolist(), tile_of[:6].tolist())) == [(0, 0), (1, 0), (1, 1), (3, 1), (4, 1), (5, 1)]
    assert int(group_metadata(jnp.zeros((5,), jnp.int32), 1024, 512)[3]) == 0


@pytest.mark.parametrize("shape,served", [
    ((3072, 5120, 3072, jnp.bfloat16), True),   # the cell's chunk, gate-up
    ((3072, 1536, 5120, jnp.bfloat16), True),   # ... and down
    ((192, 5120, 3072, jnp.bfloat16), True),    # its decode step: 32 rows x top-6, the rows whole
    ((512, 256, 256, jnp.float32), True),
    ((200, 128, 128, jnp.bfloat16), False),     # a row count no tile divides and no sublane tile either
    ((1000, 128, 128, jnp.bfloat16), False),
    ((64, 128, 128, jnp.bfloat16), False),      # fewer rows than one window
    ((8, 5120, 3072, jnp.bfloat16), False),
    ((512, 64, 256, jnp.bfloat16), False),      # DEEPSEEK_V2_TINY's widths fill no lane tile
    ((512, 128, 256, jnp.float16), False),
])
def test_supported_shapes(shape, served):
    assert grouped_matmul_supported(*shape) is served


def test_an_unsupported_call_is_refused_not_computed_wrong():
    with pytest.raises(ValueError, match="unsupported call"):
        grouped_matmul(jnp.zeros((200, 128)), jnp.zeros((2, 128, 128)), jnp.zeros((2,), jnp.int32), interpret=True)


# ---------------------------------------------------------------------------
# through dropless_held_experts
# ---------------------------------------------------------------------------

def _routed_layer(seed, tokens, D, F, experts, held, top_k, hot=None, dtype=jnp.float32):
    """A routed layer's inputs: ``hot`` sends every token's first choice
    to that expert (a skewed router)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((tokens, D)), dtype)
    idx = np.stack([rng.permutation(experts)[:top_k] for _ in range(tokens)]).astype(np.int32)
    if hot is not None:
        idx[:, 0] = hot
        for k in range(1, top_k):
            idx[:, k] = np.where(idx[:, k] == hot, (hot + 1 + k) % experts, idx[:, k])
    weight = jnp.asarray(rng.random((tokens, top_k)), jnp.float32)
    w_gu = jnp.asarray(rng.standard_normal((held[1], D, 2 * F)) * 0.1, dtype)
    w_down = jnp.asarray(rng.standard_normal((held[1], F, D)) * 0.1, dtype)
    valid = jnp.asarray(rng.random(tokens) < 0.9)
    return x, jnp.asarray(idx), weight, w_gu, w_down, valid


@pytest.mark.parametrize("hot", [None, 5], ids=["even_router", "one_hot_expert"])
def test_kernel_form_is_the_ragged_dot_form_counts_alike_and_nothing_dropped(hot, monkeypatch):
    held = (4, 8)  # experts 4..11 of 16 are held here
    x, idx, weight, w_gu, w_down, valid = _routed_layer(3, tokens=64, D=128, F=128, experts=16, held=held, top_k=4, hot=hot)
    with jax.default_matmul_precision("highest"):
        want, want_counts = moe.dropless_held_experts(x, idx, weight, w_gu, w_down, held, valid, trace_notes=(off := {}))
        monkeypatch.setenv("DS_KERNELS", "1")
        got, counts = moe.dropless_held_experts(x, idx, weight, w_gu, w_down, held, valid, trace_notes=(on := {}))
    assert off == {"moe_grouped_kernel": "", "moe_grouped_fallback": "256: kernel suite not armed"}
    assert on == {"moe_grouped_kernel": "256", "moe_grouped_fallback": ""}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    assert np.isfinite(np.asarray(got)).all()  # what the kernel left in the rows of absent experts is masked
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts))
    assert int(counts[-1]) == int(counts[:-1].sum()) > 0  # routed to held == computed: nothing dropped
    if hot is not None:
        assert int(counts[hot - held[0]]) == int(valid.sum())  # the hot expert took every real token


def test_a_row_count_no_tile_divides_takes_ragged_dot_bit_for_bit(armed):
    """33 tokens x top-4 = 132 assignment rows: the armed suite still
    answers with ``ragged_dot``, and says why."""
    held = (0, 8)
    x, idx, weight, w_gu, w_down, valid = _routed_layer(7, tokens=33, D=128, F=128, experts=16, held=held, top_k=4)
    assert not grouped_matmul_supported(132, 128, 256, x.dtype)
    got, counts = moe.dropless_held_experts(x, idx, weight, w_gu, w_down, held, valid, trace_notes=(notes := {}))
    assert notes["moe_grouped_kernel"] == "" and notes["moe_grouped_fallback"].startswith("132: unsupported shape")
    # the parent's lines, to the letter
    local = idx - held[0]
    key = jnp.where((local >= 0) & (local < held[1]), local, held[1]).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held[1] + 1)[:held[1]].astype(jnp.int32)
    gu = jax.lax.ragged_dot(jnp.take(x, order // 4, axis=0), w_gu, sizes)
    g, u = jnp.split(gu, 2, axis=-1)
    ys = jax.lax.ragged_dot(jax.nn.silu(g) * u, w_down, sizes)
    ws = jnp.take(jnp.where((local >= 0) & (local < held[1]), weight, 0.0).reshape(-1), order)
    ys = jnp.where((jnp.arange(132) < jnp.sum(sizes))[:, None], ys * ws[:, None], 0.0)
    want = jnp.take(ys, jnp.argsort(order), axis=0).reshape(33, 4, -1).sum(axis=1)
    assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("tokens,top_k", [(64, 1), (25, 4), (3, 1)])
def test_fewer_rows_than_one_window_are_handed_to_the_kernel_as_a_whole_window(armed, monkeypatch, tokens, top_k):
    """A top-1 decode step of 64 rows (and 100 rows, and 3): under the
    kernel's 128-row window the call is padded to one window with rows
    that belong to no group, takes the kernel, and gives what
    ``ragged_dot`` gives on the rows alone; the counters see the real rows."""
    held = (0, 8)
    x, idx, weight, w_gu, w_down, valid = _routed_layer(11, tokens=tokens, D=128, F=128, experts=16, held=held, top_k=top_k)
    got, counts = moe.dropless_held_experts(x, idx, weight, w_gu, w_down, held, valid, trace_notes=(notes := {}))
    assert notes == {"moe_grouped_kernel": str(tokens * top_k), "moe_grouped_fallback": ""}
    monkeypatch.setenv("DS_KERNELS", "0")  # the reference: the same call on ragged_dot
    want, want_counts = moe.dropless_held_experts(x, idx, weight, w_gu, w_down, held, valid, trace_notes=(off := {}))
    assert off["moe_grouped_kernel"] == ""
    assert got.shape == (tokens, 128) and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    assert np.array_equal(np.asarray(counts), np.asarray(want_counts)) and int(counts[-1]) == int(counts[:-1].sum())


def test_notes_keep_one_answer_a_row_count():
    notes = {}
    moe.note_grouped_form(notes, 3072, "")
    moe.note_grouped_form(notes, 192, "traced for a multi-device mesh")
    moe.note_grouped_form(notes, 3072, "")
    assert notes == {"moe_grouped_kernel": "3072", "moe_grouped_fallback": "192: traced for a multi-device mesh"}
    moe.note_grouped_form(notes, 192, "")  # traced again, another answer: the last one stands
    assert notes == {"moe_grouped_kernel": "192,3072", "moe_grouped_fallback": ""}


# ---------------------------------------------------------------------------
# the note reaches ServingEngine.stats()
# ---------------------------------------------------------------------------

def _serve(cfg, **kw):
    inf = deepspeed_tpu.init_inference(model_config=cfg, dtype=jnp.float32, max_out_tokens=64, seed=3)
    srv = ServingEngine(inf, config={"num_slots": 2, "max_len": 64, "max_new_tokens": 4,
                                     "kvcache": {"enabled": True, "page_len": 16}, **kw})
    assert "moe_grouped_kernel" not in srv.stats()  # nothing traced yet
    srv.submit(np.arange(1, 40, dtype=np.int32), max_new_tokens=2)
    srv.drain(max_steps=50)
    return srv.stats()


def test_stats_say_on_the_cpu_that_both_programs_took_ragged_dot():
    stats = _serve(ds.DEEPSEEK_V2_TINY, prefill_chunk=16)  # 16 x top-4 = 64 rows a chunk, 2 x 4 = 8 a decode step
    assert stats["moe_grouped_kernel"] == ""
    assert stats["moe_grouped_fallback"] == "8: kernel suite not armed; 64: kernel suite not armed"
    assert stats["moe"]["dropped_assignments"] == 0


def test_stats_say_which_program_took_the_kernel_when_the_suite_is_armed(armed):
    """Widths of whole lane tiles: the chunk's 32 x top-4 = 128 rows
    take the kernel, and so do the decode step's 8 (handed over as one
    window of 128, the rest nobody's)."""
    cfg = dataclasses.replace(ds.DEEPSEEK_V2_TINY, hidden_size=128, moe_intermediate_size=128)
    stats = _serve(cfg, prefill_chunk=32)
    assert stats["moe_grouped_kernel"] == "8,128" and stats["moe_grouped_fallback"] == ""
    assert stats["moe"]["dropped_assignments"] == 0 and stats["moe"]["assignments_computed"] > 0
