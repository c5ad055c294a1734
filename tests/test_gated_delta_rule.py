"""The gated delta rule (ops/transformer/linear_attention.py at a scalar
decay a head, fewer query / key heads than value heads): the recurrence,
the chunked form and the Mosaic decode kernel ``gdn_decode`` (interpret
mode) are one function, and KDA's forms are what they were."""
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.kernels import kda_decode as kd
from deepspeed_tpu.ops.transformer import linear_attention as la


def _inputs(B, T, Hk, Hv, dk, dv, seed=0):
    """``q, k`` a key head, ``v``, a scalar log-decay ``g`` and ``beta`` in (0, 1) a value head."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k = la.l2norm(f(B, T, Hk, dk)) * dk ** -0.5, la.l2norm(f(B, T, Hk, dk))
    g = -jnp.asarray(rng.uniform(0.001, 1.6, (B, T, Hv)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (B, T, Hv)), jnp.float32)
    return q, k, f(B, T, Hv, dv), g, beta, f(B, Hv, dk, dv)


def _recurrent(S, q, k, v, g, beta):
    """Token by token, as written: value head h reads query / key head h // (Hv / Hk)."""
    Hv = v.shape[2]
    outs = []
    for t in range(q.shape[1]):
        o, S = la.recurrent_step(S, la.share_heads(q[:, t], Hv, 1), la.share_heads(k[:, t], Hv, 1), v[:, t],
                                 g[:, t][..., None], beta[:, t])
        outs.append(o)
    return jnp.stack(outs, axis=1), S


def _plain(S, q, k, v, g, beta):
    """The recurrence in numpy, head by head, from the equations: S <- exp(g) S; S <- S + k (beta (v - S^T k))^T; o = S^T q."""
    S = np.array(S, np.float64)
    B, T, Hv = v.shape[:3]
    rep = Hv // q.shape[2]
    out = np.zeros(v.shape, np.float64)
    for b in range(B):
        for h in range(Hv):
            for t in range(T):
                kt, qt = np.asarray(k[b, t, h // rep], np.float64), np.asarray(q[b, t, h // rep], np.float64)
                S[b, h] *= np.exp(float(g[b, t, h]))
                S[b, h] += np.outer(kt, float(beta[b, t, h]) * (np.asarray(v[b, t, h], np.float64) - S[b, h].T @ kt))
                out[b, t, h] = S[b, h].T @ qt
    return out, S


def test_the_recurrent_step_with_a_broadcast_decay_is_the_equations_head_by_head():
    q, k, v, g, beta, S0 = _inputs(2, 7, 2, 4, 8, 8, seed=1)
    got_o, got_S = _recurrent(S0, q, k, v, g, beta)
    want_o, want_S = _plain(S0, q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(got_o), want_o, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_S), want_S, atol=1e-5)
    # consecutive pairs, not interleaved: the other reading differs
    other, _ = _plain(S0, q[:, :, ::-1], k[:, :, ::-1], v, g, beta)
    assert np.abs(other - want_o).max() > 1e-3


@pytest.mark.parametrize("T,chunk", [(64, 64), (128, 64), (48, 16), (5, 64)])
def test_chunked_with_a_scalar_decay_is_the_recurrence_across_chunk_boundaries(T, chunk):
    q, k, v, g, beta, S0 = _inputs(2, T, 2, 4, 16, 16, seed=T)
    want_o, want_S = _recurrent(S0, q, k, v, g, beta)
    got_o, got_S = la.chunked(S0, la.share_heads(q, 4, 2), la.share_heads(k, 4, 2), v, g[..., None], beta, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S), atol=2e-5)
    # the scalar path is KDA's per-channel path at a constant channel vector
    full = jnp.broadcast_to(g[..., None], g.shape + (16,))
    kda_o, kda_S = la.chunked(S0, la.share_heads(q, 4, 2), la.share_heads(k, 4, 2), v, full, beta, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(kda_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(kda_S), atol=2e-5)


def test_chunked_with_a_scalar_decay_survives_strong_decay_and_a_padded_tail():
    q, k, v, _, beta, S0 = _inputs(1, 128, 1, 2, 16, 16, seed=3)
    g = -jnp.asarray(np.random.default_rng(4).uniform(2.0, 8.0, (1, 128, 2)), jnp.float32)
    want_o, want_S = _recurrent(S0, q, k, v, g, beta)
    got_o, got_S = la.chunked(S0, la.share_heads(q, 2, 2), la.share_heads(k, 2, 2), v, g[..., None], beta)
    assert np.isfinite(np.asarray(got_o)).all() and np.isfinite(np.asarray(got_S)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S), atol=2e-5)
    valid = jnp.arange(128) < 77
    _, want_S = _recurrent(S0, q[:, :77], k[:, :77], v[:, :77], g[:, :77], beta[:, :77])
    _, got_S = la.chunked(S0, la.share_heads(q, 2, 2), la.share_heads(k, 2, 2), v, jnp.where(valid[None, :, None], g, 0.0)[..., None],
                          jnp.where(valid[None, :, None], beta, 0.0))
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S), atol=2e-5)


@pytest.mark.parametrize("mask", [[True, True, True], [True, False, True], [False, False, False]])
def test_gdn_decode_kernel_in_interpret_mode_is_the_recurrence_at_two_value_heads_a_key_head(mask):
    B, Hk, Hv, d = 3, 8, 16, 128
    q, k, v, g, beta, S0 = _inputs(B, 1, Hk, Hv, d, d, seed=7)
    state = jnp.stack([S0 * 0.5, S0])  # two layers; the kernel works on layer 1
    m = jnp.asarray(mask)
    got_o, got = kd.gdn_decode(state, 1, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], m, interpret=True)
    want_o, want_S = _recurrent(S0, q, k, v, g, beta)
    for b in range(B):
        if mask[b]:
            np.testing.assert_allclose(np.asarray(got_o[b]), np.asarray(want_o[b, 0]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(got[1, b]), np.asarray(want_S[b]), atol=1e-5)
        else:  # neither read nor written: the state bit for bit, the output 0
            assert np.array_equal(np.asarray(got[1, b]), np.asarray(S0[b])) and not np.asarray(got_o[b]).any()
    assert np.array_equal(np.asarray(got[0]), np.asarray(state[0]))  # the other layer
    with pytest.raises(ValueError, match="whole groups"):
        kd.gdn_decode(state, 1, q[:, 0, :5], k[:, 0, :5], v[:, 0], g[:, 0], beta[:, 0], m, interpret=True)


def test_decode_step_takes_gdn_decode_for_a_scalar_decay_and_all_three_forms_agree():
    B, Hk, Hv, d = 2, 8, 16, 128
    q, k, v, g, beta, S0 = _inputs(B, 1, Hk, Hv, d, d, seed=9)
    state, m = S0[None], jnp.asarray([True, True])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], m)
    notes_k, notes_j = {}, {}
    o_k, s_k = la.decode_step(state, 0, *args, use_kernel=True, trace_notes=notes_k)
    o_j, s_j = la.decode_step(state, 0, *args, use_kernel=False, trace_notes=notes_j)
    o_c, s_c = la.chunked(S0, la.share_heads(q, Hv, 2), la.share_heads(k, Hv, 2), v, g[..., None], beta)  # a chunk of one token
    for o, s in ((o_j, s_j), (o_c[:, 0], s_c[None])):
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o), atol=1e-5)
        np.testing.assert_allclose(np.asarray(s_k), np.asarray(s), atol=1e-5)
    assert notes_k == {"gdn_decode_kernel": True, "gdn_decode_fallback": ""}  # KDA's keys are not touched
    assert notes_j["gdn_decode_kernel"] is False and "not armed" in notes_j["gdn_decode_fallback"]
    small = {}
    la.decode_step(jnp.zeros((1, 2, 4, 16, 16)), 0, q[:, 0, :2, :16], k[:, 0, :2, :16], v[:, 0, :4, :16], g[:, 0, :4], beta[:, 0, :4], m,
                   use_kernel=True, trace_notes=small)
    assert small["gdn_decode_kernel"] is False and "unsupported shape" in small["gdn_decode_fallback"]


def test_gdn_decode_is_kda_decode_at_a_constant_decay_column_and_repeated_heads():
    """The two program names share a body: the same numbers, bit for bit."""
    B, Hk, Hv, d = 2, 8, 16, 128
    q, k, v, g, beta, S0 = _inputs(B, 1, Hk, Hv, d, d, seed=11)
    m = jnp.asarray([True, True])
    o_g, s_g = kd.gdn_decode(S0[None], 0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], m, interpret=True)
    full = jnp.broadcast_to(g[:, 0][..., None], (B, Hv, d))
    o_k, s_k = kd.kda_decode(S0[None], 0, la.share_heads(q[:, 0], Hv, 1), la.share_heads(k[:, 0], Hv, 1), v[:, 0], full, beta[:, 0], m,
                             interpret=True)
    assert np.array_equal(np.asarray(o_g), np.asarray(o_k)) and np.array_equal(np.asarray(s_g), np.asarray(s_k))
