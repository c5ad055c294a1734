"""KDA (ops/transformer/linear_attention.py): the recurrence, the
chunked form and the Mosaic decode kernel (interpret mode) are one
function; the convolution's carried state; what a mask leaves alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.kernels import kda_decode as kd
from deepspeed_tpu.ops.transformer import linear_attention as la


def _inputs(B, T, H, dk, dv, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k = la.l2norm(f(B, T, H, dk)) * dk ** -0.5, la.l2norm(f(B, T, H, dk))
    g = -jnp.asarray(rng.uniform(0.001, 0.6, (B, T, H, dk)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (B, T, H)), jnp.float32)  # up to 2: negative eigenvalues allowed
    return q, k, f(B, T, H, dv), g, beta, f(B, H, dk, dv)


def _recurrent(S, q, k, v, g, beta):
    outs = []
    for t in range(q.shape[1]):
        o, S = la.recurrent_step(S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    return jnp.stack(outs, axis=1), S


@pytest.mark.parametrize("T,chunk", [(64, 64), (128, 64), (48, 16), (5, 64)])
def test_chunked_is_the_recurrence_across_chunk_boundaries(T, chunk):
    q, k, v, g, beta, S0 = _inputs(2, T, 3, 16, 16, seed=T)
    want_o, want_S = _recurrent(S0, q, k, v, g, beta)
    got_o, got_S = la.chunked(S0, q, k, v, g, beta, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S), atol=2e-5)


def test_chunked_survives_strong_decay_without_overflow():
    """Per-channel decays down to exp(-8) a token: the cumulative decay
    of a chunk underflows, its inverse would overflow; only differences
    G_t - G_s with s <= t are ever exponentiated."""
    q, k, v, _, beta, S0 = _inputs(1, 128, 2, 16, 16, seed=3)
    g = -jnp.asarray(np.random.default_rng(4).uniform(2.0, 8.0, (1, 128, 2, 16)), jnp.float32)
    want_o, want_S = _recurrent(S0, q, k, v, g, beta)
    got_o, got_S = la.chunked(S0, q, k, v, g, beta)
    assert np.isfinite(np.asarray(got_o)).all() and np.isfinite(np.asarray(got_S)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o), atol=2e-5)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S), atol=2e-5)


def test_a_padded_tail_with_beta_and_g_zero_leaves_the_state_untouched():
    q, k, v, g, beta, S0 = _inputs(1, 32, 2, 16, 16, seed=5)
    valid = jnp.arange(32) < 21
    _, want_S = _recurrent(S0, q[:, :21], k[:, :21], v[:, :21], g[:, :21], beta[:, :21])
    got_o, got_S = la.chunked(S0, q, k, v, jnp.where(valid[None, :, None, None], g, 0.0),
                              jnp.where(valid[None, :, None], beta, 0.0), chunk=16)
    np.testing.assert_allclose(np.asarray(got_S), np.asarray(want_S), atol=2e-5)
    assert np.isfinite(np.asarray(got_o)).all()


@pytest.mark.parametrize("mask", [[True, True, True], [True, False, True], [False, False, False]])
def test_kda_decode_kernel_in_interpret_mode_is_the_recurrence_and_skips_masked_rows(mask):
    B, H, d = 3, 16, 128
    q, k, v, g, beta, S0 = _inputs(B, 1, H, d, d, seed=7)
    state = jnp.stack([S0 * 0.5, S0])  # two layers; the kernel works on layer 1
    m = jnp.asarray(mask)
    assert kd.kda_decode_supported(H, d, d) and not kd.kda_decode_supported(4, 16, 16)
    got_o, got = kd.kda_decode(state, 1, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], m, interpret=True)
    want_o, want_S = la.recurrent_step(S0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
    for b in range(B):
        if mask[b]:
            np.testing.assert_allclose(np.asarray(got_o[b]), np.asarray(want_o[b]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(got[1, b]), np.asarray(want_S[b]), atol=1e-5)
        else:  # neither read nor written: the state bit for bit, the output 0
            assert np.array_equal(np.asarray(got[1, b]), np.asarray(S0[b])) and not np.asarray(got_o[b]).any()
    assert np.array_equal(np.asarray(got[0]), np.asarray(state[0]))  # the other layer


def test_decode_step_dispatch_kernel_and_jnp_forms_agree_and_say_which():
    B, H, d = 2, 16, 128
    q, k, v, g, beta, S0 = _inputs(B, 1, H, d, d, seed=9)
    state, m = S0[None], jnp.asarray([True, False])
    args = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], m)
    notes_k, notes_j = {}, {}
    o_k, s_k = la.decode_step(state, 0, *args, use_kernel=True, trace_notes=notes_k)
    o_j, s_j = la.decode_step(state, 0, *args, use_kernel=False, trace_notes=notes_j)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_j), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_j), atol=1e-5)
    assert notes_k == {"kda_decode_kernel": True, "kda_decode_fallback": ""}
    assert notes_j["kda_decode_kernel"] is False and "not armed" in notes_j["kda_decode_fallback"]
    small = {}
    la.decode_step(jnp.zeros((1, 2, 4, 16, 16)), 0, *(t[..., :4, :16] for t in args[:4]), args[4][:, :4], m,
                   use_kernel=True, trace_notes=small)
    assert small["kda_decode_kernel"] is False and "unsupported shape" in small["kda_decode_fallback"]


def test_compact_rows_lists_the_decoding_rows_first_and_pack_columns_transposes():
    rows, n = kd.compact_rows(jnp.asarray([False, True, False, True, True]))
    assert int(n[0]) == 3 and list(np.asarray(rows)) == [1, 3, 4, 4, 4]
    rows, n = kd.compact_rows(jnp.zeros((4,), bool))
    assert int(n[0]) == 0 and len(set(np.asarray(rows).tolist())) == 1
    x = jnp.arange(2 * 32 * 8, dtype=jnp.float32).reshape(2, 32, 8)
    cols = kd.pack_columns(x, x + 1000, x + 2000, x + 3000)
    assert cols.shape == (2, 2, 8, 128)
    assert float(cols[1, 1, 5, 8 * 3 + 1]) == float(x[1, 16 + 3, 5]) + 1000  # tile 1, head 16 + 3, column k


def test_short_conv_carries_its_last_inputs_and_forgets_a_padded_tail():
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((2, 12, 6)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
    zero = jnp.zeros((2, 3, 6), jnp.float32)
    whole, _ = la.short_conv(x, w, zero)
    # by hand: y_t = silu(sum_j w_j x_{t-3+j}) with zeros before the start
    ext = np.concatenate([np.zeros((2, 3, 6), np.float32), np.asarray(x)], axis=1)
    pre = sum(ext[:, j:j + 12] * np.asarray(w)[j] for j in range(4))
    np.testing.assert_allclose(np.asarray(whole), pre / (1 + np.exp(-pre)), atol=1e-5)
    # two pieces, the first with a padded tail of 3, equal the whole
    first = jnp.concatenate([x[:, :5], jnp.full((2, 3, 6), 9.0)], axis=1)
    y1, st = la.short_conv(first, w, zero, n_valid=jnp.asarray([5, 5]))
    np.testing.assert_array_equal(np.asarray(st), np.asarray(x[:, 2:5]))
    y2, st2 = la.short_conv(x[:, 5:], w, st)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1[:, :5], y2], axis=1)), np.asarray(whole), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(st2), np.asarray(x[:, 9:]))
    # a one-token chunk keeps two of the old inputs
    _, st3 = la.short_conv(x[:, 5:6], w, st, n_valid=jnp.asarray([1, 1]))
    np.testing.assert_array_equal(np.asarray(st3), np.asarray(x[:, 3:6]))
