"""``mla_prefill`` (ops/kernels/mla_prefill.py) in interpret mode: the
kernel path of ``expanded_attention`` against its ``jnp`` body and
against ``absorbed_attention_reference`` position by position; the
dispatch rule and the note it leaves in ``ServingEngine.stats()``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import deepseek_v2 as ds
from deepspeed_tpu.ops.kernels.mla_prefill import NEG_INF, STAT_LANES, mla_prefill, mla_prefill_supported
from deepspeed_tpu.ops.transformer import latent_attention as la
from deepspeed_tpu.serving import ServingEngine

SCALE = 0.11
PAGE = 128


def _setup(seed, B, T, H, dn, dr, dv, pages_per_slot, c=32, dtype=jnp.float32):
    """Queries, a filled latent pool (one layer of interest, 1) and
    page tables that scatter each slot's pages over the pool."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + B * pages_per_slot
    pool = jnp.asarray(rng.standard_normal((2, n_pages, c + dr, PAGE)), dtype)
    table = jnp.asarray(1 + rng.permutation(B * pages_per_slot).reshape(B, pages_per_slot), jnp.int32)
    q_nope = jnp.asarray(rng.standard_normal((B, T, H, dn)) * 0.5, dtype)
    q_pe = jnp.asarray(rng.standard_normal((B, T, H, dr)) * 0.5, dtype)
    w_kvb = jnp.asarray(rng.standard_normal((c, H, dn + dv)) * 0.2, dtype)
    return q_nope, q_pe, pool, table, w_kvb


def _by_position(q_nope, q_pe, pool, table, pos, w_kvb, dn):
    """The absorbed reference, every (row, query) a decode step of its own."""
    B, T, H, _ = q_nope.shape
    q_abs = jnp.einsum("bthn,chn->bthc", q_nope, w_kvb[..., :dn]).reshape(B * T, H, -1)
    at = (pos[:, None] + jnp.arange(T)[None, :]).reshape(B * T)
    out = la.absorbed_attention_reference(q_abs, q_pe.reshape(B * T, H, -1), pool, 1, jnp.repeat(table, T, axis=0), at, SCALE)
    return jnp.einsum("nhc,chv->nhv", out, w_kvb[..., dn:]).reshape(B, T, H, -1)


def _parent_expanded_attention(q_nope, q_pe, pool, layer, page_table, pos, w_kvb, nope, sm_scale, block_pages=8):
    """``expanded_attention`` as it stood before the kernel (5964d55), to
    the letter: what every unsupported shape must still compute."""
    B, T, H, _ = q_nope.shape
    P, page_len = page_table.shape[1], pool.shape[3]
    c = w_kvb.shape[0]
    dv = w_kvb.shape[-1] - nope
    while P % block_pages:
        block_pages -= 1
    S = block_pages * page_len
    dt = q_nope.dtype
    q_pos = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    n_blocks = jnp.minimum((jnp.max(pos) + T + S - 1) // S, P // block_pages)

    def body(j, carry):
        m, l, acc = carry
        pages = jax.lax.dynamic_slice_in_dim(page_table, j * block_pages, block_pages, axis=1)
        rows = la._gather_slot(pool, layer, pages).astype(dt)
        kv = jnp.einsum("bsc,chx->bshx", rows[..., :c], w_kvb.astype(dt))
        s = jnp.einsum("bthn,bshn->bhts", q_nope, kv[..., :nope], preferred_element_type=jnp.float32)
        s = s + jnp.einsum("bthr,bsr->bhts", q_pe, rows[..., c:], preferred_element_type=jnp.float32)
        k_pos = j * S + jnp.arange(S, dtype=jnp.int32)
        ok = k_pos[None, None, None, :] <= q_pos[:, None, :, None]
        s = jnp.where(ok, s * sm_scale, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhts,bshv->bhtv", p.astype(dt), kv[..., nope:],
                                                  preferred_element_type=jnp.float32)
        return m_new, l, acc

    init = (jnp.full((B, H, T), NEG_INF, jnp.float32), jnp.zeros((B, H, T), jnp.float32),
            jnp.zeros((B, H, T, dv), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, init)
    return (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).transpose(0, 2, 1, 3).astype(dt)


# blocks of 3 pages = 384 keys = three key tiles of 128; 6 pages a slot = two blocks = 768 positions
CHUNKS = {
    "starts_at_0":                  dict(T=384, pos=[0]),
    "mid_context_on_a_block_edge":  dict(T=256, pos=[384]),
    "mid_context_off_a_block_edge": dict(T=384, pos=[200]),
    "context_of_exactly_one_block": dict(T=256, pos=[128]),
    "ends_the_pools_last_block":    dict(T=384, pos=[384]),
    "two_rows_at_different_pos":    dict(T=128, pos=[517, 30]),
    "one_query_tile_of_256":        dict(T=256, pos=[73]),
}


@pytest.mark.parametrize("case", CHUNKS)
def test_kernel_path_is_the_jnp_body_and_the_absorbed_reference_position_by_position(case):
    T, pos = CHUNKS[case]["T"], jnp.asarray(CHUNKS[case]["pos"], jnp.int32)
    H, dn, dr, dv = 2, 128, 64, 128  # the published head dims, few heads
    q_nope, q_pe, pool, table, w_kvb = _setup(len(case), len(pos), T, H, dn, dr, dv, pages_per_slot=6)
    notes = {}
    with jax.default_matmul_precision("highest"):
        got = la.expanded_attention(q_nope, q_pe, pool, 1, table, pos, w_kvb, dn, SCALE, block_pages=3,
                                    use_kernel=True, trace_notes=notes)
        body = la.expanded_attention(q_nope, q_pe, pool, 1, table, pos, w_kvb, dn, SCALE, block_pages=3, use_kernel=False)
        want = _by_position(q_nope, q_pe, pool, table, pos, w_kvb, dn)
    assert notes == {"mla_prefill_kernel": True, "mla_prefill_fallback": ""}
    np.testing.assert_allclose(np.asarray(got), np.asarray(body), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_kernel_path_in_bfloat16_strays_from_the_jnp_body_by_roundings_of_p_only():
    H, dn, dr, dv, T = 2, 128, 64, 128, 256
    q_nope, q_pe, pool, table, w_kvb = _setup(5, 2, T, H, dn, dr, dv, pages_per_slot=6, dtype=jnp.bfloat16)
    pos = jnp.asarray([300, 0], jnp.int32)
    got = la.expanded_attention(q_nope, q_pe, pool, 1, table, pos, w_kvb, dn, SCALE, block_pages=3, use_kernel=True)
    body = la.expanded_attention(q_nope, q_pe, pool, 1, table, pos, w_kvb, dn, SCALE, block_pages=3, use_kernel=False)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(body, np.float32), atol=2e-2)


def test_a_row_whose_keys_are_all_masked_gives_zero_not_nan():
    """Row 0's queries sit before position 0: every key tile is skipped
    for it, ``l`` stays 0 and the output is 0; row 1 is untouched by it."""
    H, dn, dr, dv, T = 2, 128, 64, 128, 128
    q_nope, q_pe, pool, table, w_kvb = _setup(9, 2, T, H, dn, dr, dv, pages_per_slot=6)
    pos = jnp.asarray([-T, 5], jnp.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(la.expanded_attention(q_nope, q_pe, pool, 1, table, pos, w_kvb, dn, SCALE, block_pages=3, use_kernel=True))
        body = np.asarray(la.expanded_attention(q_nope, q_pe, pool, 1, table, pos, w_kvb, dn, SCALE, block_pages=3, use_kernel=False))
    assert np.isfinite(got).all() and not got[0].any()
    np.testing.assert_allclose(got[1], body[1], atol=2e-5)


@pytest.mark.parametrize("k_start,pos", [(0, [0, 3]), (16, [9, 40]), (32, [5, 20])],
                         ids=["first_block", "diagonal_and_whole_tiles", "a_block_no_query_reaches"])
def test_kernel_at_tiny_dims_folds_one_block_as_the_jnp_lines_do(k_start, pos):
    """The call itself, at sizes no tile divides (one tile a dimension):
    one block folded into a carry that already holds a block."""
    B, H, T, S, dn, dr, dv = 2, 3, 8, 16, 8, 4, 8
    rng = np.random.default_rng(k_start)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    qn, qp, kn, kp, v = f(B, H, T, dn), f(B, H, T, dr), f(B, H, S, dn), f(B, S, dr), f(B, H, S, dv)
    m, l, acc = f(B, H, T), jnp.abs(f(B, H, T)) + 1.0, f(B, H, T, dv)
    pos = jnp.asarray(pos, jnp.int32)
    with jax.default_matmul_precision("highest"):
        rep = lambda x: jnp.broadcast_to(x[..., None], x.shape + (STAT_LANES,))  # noqa: E731
        m2, l2, acc2 = mla_prefill(qn, qp, kn, kp, v, pos, k_start, (rep(m), rep(l), acc), SCALE, interpret=True)
        s = (jnp.einsum("bhtn,bhsn->bhts", qn, kn) + jnp.einsum("bhtr,bsr->bhts", qp, kp)) * SCALE
        ok = (k_start + jnp.arange(S))[None, None, None, :] <= (pos[:, None] + jnp.arange(T))[:, None, :, None]
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        l_new = jnp.exp(m - m_new) * l + p.sum(-1)
        acc_new = acc * jnp.exp(m - m_new)[..., None] + jnp.einsum("bhts,bhsv->bhtv", p, v)
    for got, want in ((m2, rep(m_new)), (l2, rep(l_new)), (acc2, acc_new)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("shape,served", [
    ((128, 512, 1024, 128, 64, 128), True),    # the published model's chunk
    ((8, 128, 512, 32, 64, 32), True),         # chip_smoke's tiny model
    ((4, 16, 32, 16, 8, 16), False),           # DEEPSEEK_V2_TINY: tiles of no whole 128-row run
    ((128, 512, 1024, 128, 64, 120), False),   # a head dimension that fills no sublane tile
    ((128, 500, 1024, 128, 64, 128), False),
])
def test_supported_shapes(shape, served):
    assert mla_prefill_supported(*shape) is served


@pytest.mark.parametrize("use_kernel", [True, False, None], ids=["asked_for", "refused", "left_to_the_suite"])
def test_an_unsupported_shape_takes_the_jnp_body_bit_for_bit_the_parents(use_kernel):
    cfg = ds.DEEPSEEK_V2_TINY
    H, dn, dr, dv, c = 4, 16, 8, 16, 32
    rng = np.random.default_rng(1)
    pool = jnp.asarray(rng.standard_normal((1, 6, c + dr, 16)), jnp.float32)
    table = jnp.asarray([[2, 5, 1, 0], [4, 3, 0, 0]], jnp.int32)
    pos = jnp.asarray([40, 17], jnp.int32)
    q_nope = jnp.asarray(rng.standard_normal((2, 8, H, dn)), jnp.float32)
    q_pe = jnp.asarray(rng.standard_normal((2, 8, H, dr)), jnp.float32)
    w_kvb = jnp.asarray(rng.standard_normal((c, H, dn + dv)) * 0.2, jnp.float32)
    notes = {}
    got = la.expanded_attention(q_nope, q_pe, pool, 0, table, pos, w_kvb, dn, cfg.softmax_scale, block_pages=2,
                                use_kernel=use_kernel, trace_notes=notes)
    want = _parent_expanded_attention(q_nope, q_pe, pool, 0, table, pos, w_kvb, dn, cfg.softmax_scale, block_pages=2)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert notes["mla_prefill_kernel"] is False
    assert notes["mla_prefill_fallback"].startswith("unsupported shape" if use_kernel else "kernel suite not armed")


def test_stats_say_which_form_the_prefill_program_compiled():
    inf = deepspeed_tpu.init_inference(model_config=ds.DEEPSEEK_V2_TINY, dtype=jnp.float32, max_out_tokens=64, seed=3)
    srv = ServingEngine(inf, config={"num_slots": 2, "max_len": 64, "prefill_chunk": 16, "max_new_tokens": 4,
                                     "kvcache": {"enabled": True, "page_len": 16}})
    assert "mla_prefill_kernel" not in srv.stats()  # nothing traced yet
    srv.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=2)
    srv.drain(max_steps=50)
    stats = srv.stats()
    assert stats["mla_prefill_kernel"] is False and stats["mla_prefill_fallback"] == "kernel suite not armed"
