"""Learned sparse attention (``ops/transformer/sparse_attention.py``): the
exact top-k as a mask, three-stream rotary, the routing function, and each
Mosaic kernel of ``ops/kernels/sparse_decode.py`` in interpret mode
against its ``jnp`` form."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe.layer import group_limited_topk, softmax_topk
from deepspeed_tpu.ops.kernels import sparse_decode as kern
from deepspeed_tpu.ops.transformer import sparse_attention as dsa


def plain_topk_mask(scores, k, valid):
    """Sort by (score descending, index ascending) among the valid; the first k."""
    out = np.zeros(scores.shape, bool)
    for r in np.ndindex(scores.shape[:-1]):
        idx = np.flatnonzero(valid[r])
        order = idx[np.lexsort((idx, -scores[r][idx].astype(np.float64)))]
        out[r][order[:k]] = True
    return out


@pytest.mark.parametrize("n,k", [(8, 16), (16, 16), (200, 16), (33, 1), (1000, 128)])
def test_topk_mask_is_the_exact_top_k_with_ties_to_the_lower_position(n, k):
    rng = np.random.default_rng(n * 31 + k)
    scores = rng.standard_normal((3, 5, n)).astype(np.float32)
    scores[0] = np.round(scores[0] * 2) / 2  # many ties, negative and positive, and zeros of both signs
    scores[1, :, ::3] = 0.0
    scores[1, :, 1::3] = -0.0
    valid = rng.random((3, 5, n)) < 0.8
    valid[2, 0] = False  # nothing valid selects nothing
    valid[2, 1] = True
    got, cut = (np.asarray(x) for x in jax.jit(dsa.topk_mask, static_argnums=1)(jnp.asarray(scores), k, jnp.asarray(valid)))
    want = plain_topk_mask(np.where(scores == 0, 0.0, scores), k, valid)  # -0.0 ranks with 0.0 in the plain form
    zeros = (scores == 0).any()
    if not zeros:
        np.testing.assert_array_equal(got, want)
    assert (got <= valid).all() and (got.sum(-1) == np.minimum(k, valid.sum(-1))).all()
    # every selected score is >= every valid unselected one, and among equals the lower index is the one selected
    for r in np.ndindex(scores.shape[:-1]):
        sel, rest = np.flatnonzero(got[r]), np.flatnonzero(valid[r] & ~got[r])
        # the threshold handed back is the k-th largest valid score itself, bit for bit; NaN where fewer than k are valid
        if valid[r].sum() >= k:
            assert cut[r].view(np.uint32) == scores[r][sel].min().view(np.uint32) or (cut[r] == 0 and scores[r][sel].min() == 0)
        else:
            assert np.isnan(cut[r])
        if len(sel) and len(rest):
            lo = scores[r][sel].min()
            assert lo >= scores[r][rest].max()
            tied_out = rest[scores[r][rest] == lo]
            tied_in = sel[scores[r][sel] == lo]
            # -0.0 orders below +0.0 in the bits: only compare indices where the bit patterns agree
            same = lambda a: a[np.signbit(scores[r][a]) == np.signbit(lo)]  # noqa: E731
            if len(same(tied_out)) and len(same(tied_in)):
                assert same(tied_in).max() < same(tied_out).min()


def test_topk_mask_agrees_with_lax_top_k_on_untied_scores():
    s = jax.random.normal(jax.random.PRNGKey(0), (4, 3000), jnp.float32)
    valid = jnp.arange(3000)[None, :] <= jnp.asarray([5, 100, 2047, 2999])[:, None]
    got = np.asarray(dsa.topk_mask(s, 256, valid)[0])
    _, idx = jax.lax.top_k(jnp.where(valid, s, -jnp.inf), 256)
    for r in range(4):
        n = min(256, int(valid[r].sum()))
        assert set(np.flatnonzero(got[r])) == set(np.asarray(idx[r][:n]).tolist())


@pytest.mark.parametrize("n,k", [(128, 16), (384, 16), (1024, 128), (256, 300)])
def test_dsa_select_threshold_in_interpret_mode_against_the_jnp_form(n, k):
    rng = np.random.default_rng(n + k)
    scores = rng.standard_normal((2, 8, n)).astype(np.float32)
    scores[0] = np.round(scores[0] * 2) / 2  # ties at the threshold, of both signs
    scores[1, 3] = 0.0                       # a row of one value: the lowest k positions
    valid = rng.random((2, 8, n)) < 0.8
    valid[1, 0] = False
    valid[1, 1, 5:] = False                  # fewer valid than k
    want, want_cut = (np.asarray(x) for x in dsa.topk_mask(jnp.asarray(scores), k, jnp.asarray(valid), use_kernel=False))
    got, cut = (np.asarray(x) for x in dsa.topk_mask(jnp.asarray(scores), k, jnp.asarray(valid), use_kernel=True))  # interpret mode on the CPU
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(cut.view(np.uint32), want_cut.view(np.uint32))
    assert (got.sum(-1) == np.minimum(k, valid.sum(-1))).all()
    # rows that are no multiple of the kernel's 8 take the lax form
    assert not kern.select_supported(12, n, True) and kern.select_supported(16, n, True) and not kern.select_supported(16, n + 1, True)


def test_topk_mask_upto_is_the_same_mask_whichever_bucket_holds_the_context(monkeypatch):
    monkeypatch.setattr(dsa, "SELECT_BUCKET", 64)
    s = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 200), jnp.float32)
    for n_ctx in (5, 64, 65, 130, 200):
        valid = jnp.broadcast_to(jnp.arange(200) < n_ctx, (2, 3, 200))
        got, cut = jax.jit(lambda s, v, n: dsa.topk_mask_upto(s, 16, v, n))(s, valid, jnp.int32(n_ctx))
        want, want_cut = dsa.topk_mask(s, 16, valid)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(cut).view(np.uint32), np.asarray(want_cut).view(np.uint32))


def test_mrope_with_equal_streams_is_plain_rotary_and_unequal_streams_turn_their_own_pairs():
    pos = jnp.asarray([[0, 3, 7, 100]], jnp.int32)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 4, 2, 16), jnp.float32)
    plain = dsa.rotate(x, dsa.mrope_angles(pos, 8, 1e4))
    three = dsa.rotate(x, dsa.mrope_angles(jnp.broadcast_to(pos, (3, 1, 4)), 8, 1e4, (2, 3, 3)))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(three), rtol=1e-6, atol=1e-6)
    streams = jnp.stack([pos, pos * 0 + 5, pos * 2])
    mixed = np.asarray(dsa.rotate(x, dsa.mrope_angles(streams, 8, 1e4, (2, 3, 3))))
    for sec, (a, b) in zip(streams, ((0, 2), (2, 5), (5, 8))):
        alone = np.asarray(dsa.rotate(x, dsa.mrope_angles(sec, 8, 1e4)))
        np.testing.assert_allclose(mixed[..., a:b], alone[..., a:b], rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(mixed[..., 8 + a: 8 + b], alone[..., 8 + a: 8 + b], rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        dsa.mrope_angles(streams, 8, 1e4, (2, 3, 4))


@pytest.mark.parametrize("renorm", [True, False])
def test_softmax_topk_is_a_plain_top_k_of_the_softmax_and_group_limited_at_one_group(renorm):
    logits = jax.random.normal(jax.random.PRNGKey(2), (40, 128), jnp.float32) * 2
    idx, w = softmax_topk(logits, 8, renorm)
    p = np.asarray(jax.nn.softmax(logits, -1), np.float64)
    want_idx = np.argsort(-p, axis=-1, kind="stable")[:, :8]
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    want_w = np.take_along_axis(p, want_idx, -1)
    want_w = want_w / want_w.sum(-1, keepdims=True) if renorm else want_w
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    gi, gw = group_limited_topk(jax.nn.softmax(logits, -1), 1, 1, 8, renormalize=renorm)
    np.testing.assert_array_equal(np.asarray(gi), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(gw), np.asarray(w), rtol=1e-6)


def _paged(rng, B, P, page_len, NP):
    table = np.stack([rng.permutation(np.arange(1, NP))[:P] for _ in range(B)]).astype(np.int32)
    return jnp.asarray(table)


def _k_pages(NP, Hkv=2, page_len=128, d=128):
    """The K pages both kernels' span is read off: 2 KV heads x 128 x 128 bf16 = 128 KB of K + V a page."""
    return jax.ShapeDtypeStruct((NP, Hkv, page_len, d), jnp.bfloat16)


@pytest.mark.parametrize("shape,dtype,P,span", [
    ((8, 4225, 4, 128, 128), jnp.bfloat16, 264, 4),   # Keye's pool: 256 KB a page, four an item, a mebibyte a grid step
    ((20, 2, 128, 128), jnp.bfloat16, 3, 1),          # three pages a slot: no span but 1 divides
    ((20, 2, 128, 128), jnp.bfloat16, 6, 2),
    ((20, 2, 128, 128), jnp.bfloat16, 4, 4),
    ((20, 2, 128, 128), jnp.bfloat16, 16, 8),         # 128 KB a page: eight fit the budget
    ((20, 8, 128, 128), jnp.float32, 16, 1),          # a page of a mebibyte: one
], ids=["keye", "three_pages", "six_pages", "four_pages", "small_pages", "large_pages"])
def test_the_span_of_both_kernels_is_the_paged_tiles(shape, dtype, P, span):
    """One span rule: ``span_of`` is ``flash_decode.paged_tile``'s, and
    ``work_list`` is ``paged_work_list`` under it — what the indexer's
    scores and the attention both walk."""
    from deepspeed_tpu.ops.kernels import flash_decode as fd

    pages = jax.ShapeDtypeStruct(shape, dtype)
    assert kern.span_of(pages, P) == fd.paged_tile(pages, P)[1] == span
    pos, live = jnp.asarray([5, 130, P * 128 - 1], jnp.int32), jnp.asarray([True, False, True])
    got, want = kern.work_list(pos, live, pages, P), fd.paged_work_list(pos, live, 128, P, span)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    assert got[0].shape == (3 * P // span,) and int(got[2][0]) == 1 + P // span


@pytest.mark.parametrize("P", [3, 4, 6, 8])  # spans of 1, 4, 2 and 8 pages a grid step
@pytest.mark.parametrize("live", [None, [True, False, True]])
def test_dsa_index_scores_paged_in_interpret_mode_against_the_jnp_form(live, P):
    rng = np.random.default_rng(5)
    B, Hi, di, L, NP, page_len, layer = 3, 4, 64, 2, 20, 128, 1
    assert kern.span_of(_k_pages(NP), P) == {3: 1, 4: 4, 6: 2, 8: 8}[P]
    pool = jnp.asarray(rng.standard_normal((L, NP, di, page_len)), jnp.bfloat16)
    qi, w = jnp.asarray(rng.standard_normal((B, Hi, di)), jnp.float32), jnp.asarray(rng.standard_normal((B, Hi)), jnp.float32)
    table, pos = _paged(rng, B, P, page_len, NP), jnp.asarray([5, 130, 383], jnp.int32)
    work = kern.work_list(pos, None if live is None else jnp.asarray(live), _k_pages(NP), P)
    got = np.asarray(kern.dsa_index_scores_paged(qi, w, pool, layer, table, pos, work, interpret=True))
    want = np.asarray(dsa.index_scores(qi[:, None], w[:, None], dsa.index_context(pool, layer, table))[:, 0])
    for b in range(B):
        if live is None or live[b]:
            n = (int(pos[b]) // page_len + 1) * page_len  # the filled pages: what the list visits
            np.testing.assert_allclose(got[b, :n], want[b, :n], rtol=1e-5, atol=1e-5)
    if live is None:  # no list given: the filled pages, one an item
        alone = np.asarray(kern.dsa_index_scores_paged(qi, w, pool, layer, table, pos, interpret=True))
        for b in range(B):
            n = (int(pos[b]) // page_len + 1) * page_len
            np.testing.assert_array_equal(alone[b, :n], got[b, :n])


SPANS = {1: 3, 2: 6, 4: 12, 8: 16}  # pages an item: pages a slot that give it at 128 KB a page, two items a slot at the least


def _selection_case(rng, span, group, Hkv=2, d=128, page_len=128):
    """Four rows on the merged body: one whose last span reaches past its
    position, one whose **first item holds nothing selected**, one that
    selects nothing, one filled to the slot's last position but 77."""
    P = SPANS[span]
    B, H, NP = 4, Hkv * group, 1 + 4 * P
    kc = jnp.asarray(rng.standard_normal((NP, Hkv, page_len, d)), jnp.bfloat16)
    vc = jnp.asarray(rng.standard_normal((NP, Hkv, page_len, d)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, H, 1, d)), jnp.bfloat16)
    item = span * page_len
    pos = np.asarray([5, item + 2, P * page_len - 1, P * page_len - 78], np.int32)
    mask = (np.arange(P * page_len)[None, :] <= pos[:, None]) & (rng.random((B, P * page_len)) < 0.3)
    mask[0, 3] = True
    mask[1, :item] = False   # the row's first item adds nothing: its running maximum is still NEG_INF when the second starts
    mask[1, item + 1] = True
    mask[2] = False          # a row that selects nothing reads 0
    return q, kc, vc, _paged(rng, B, P, page_len, NP), jnp.asarray(pos), mask, P


@pytest.mark.parametrize("group", [1, 8])
@pytest.mark.parametrize("span", list(SPANS))
@pytest.mark.parametrize("live", [None, [True, True, True, False]])
def test_dsa_sparse_decode_in_interpret_mode_against_the_jnp_form(live, span, group):
    """``flash_decode_paged`` under a selection (the body
    ``dsa_sparse_decode`` hands over to) against the gathered rows under
    the mask, at every span the tile rule gives and at multi-head and
    grouped queries."""
    q, kc, vc, table, pos, mask, P = _selection_case(np.random.default_rng(6 + span + group), span, group)
    assert kern.span_of(kc, P) == span
    lv = None if live is None else jnp.asarray(live)
    work = kern.work_list(pos, lv, kc, P)
    got = np.asarray(kern.dsa_sparse_decode(q, kc, vc, table, pos, jnp.asarray(mask), None, work, interpret=True), np.float32)
    if live is not None:
        mask = mask & np.asarray(live)[:, None]
    want = np.asarray(dsa.selected_decode_reference(q, kc, vc, table, jnp.asarray(mask), 128 ** -0.5), np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    assert got[0].any() and got[1].any() and not got[2].any()  # a row that selects nothing reads 0
    if live is not None:
        assert not got[3].any()  # a row that does not decode reads 0
    else:  # no list given: the rows' filled spans, every row live
        alone = np.asarray(kern.dsa_sparse_decode(q, kc, vc, table, pos, jnp.asarray(mask), interpret=True), np.float32)
        np.testing.assert_array_equal(alone, got)


@pytest.mark.parametrize("form", ["float32_strip", "int8_strip"])
def test_the_selection_may_come_as_a_strip_of_ones_where_selected(form):
    """The operand is the mask as int8, whatever it came as: a strip that is
    1 where a position is selected and 0 elsewhere, float32 or int8, reads
    as the bool mask does, bit for bit."""
    q, kc, vc, table, pos, mask, P = _selection_case(np.random.default_rng(11), 4, 8)
    want = np.asarray(kern.dsa_sparse_decode(q, kc, vc, table, pos, jnp.asarray(mask), interpret=True), np.float32)
    strip = jnp.asarray(mask, {"float32_strip": jnp.float32, "int8_strip": jnp.int8}[form])
    got = np.asarray(kern.dsa_sparse_decode(q, kc, vc, table, pos, strip, interpret=True), np.float32)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("window", [None, 200])
def test_flash_decode_paged_without_a_mask_has_no_selection_operand(window):
    """What keeps the other families' decode programs what they were: the
    selection is a branch of Python, so a call without one traces the
    Mosaic call without the operand, its mask and its name."""
    from deepspeed_tpu.ops.kernels import flash_decode as fd

    q, kc, vc, table, pos, mask, P = _selection_case(np.random.default_rng(12), 4, 8)
    span = fd.paged_tile(kc, P)[1]

    def call_of(**kw):
        jaxpr = jax.make_jaxpr(lambda *a: fd.flash_decode_paged(*a, interpret=True, **kw))(q, kc, vc, table, pos)
        (eqn,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
        return eqn, str(eqn)

    bare, text = call_of(window=window)
    # the grid's traced bound, the five prefetched scalars, q, a span of K pages and one of V pages
    assert len(bare.invars) == 1 + 5 + 1 + 2 * span and not any(v.aval.dtype == jnp.int8 for v in bare.invars)
    assert ("swa_decode_paged" if window else "flash_decode_paged") in text and "dsa_sparse_decode" not in text
    under, text = call_of(window=window, mask=jnp.asarray(mask))
    assert len(under.invars) == len(bare.invars) + 1 and "dsa_sparse_decode" in text
    strip = under.invars[-1].aval
    assert strip.dtype == jnp.int8 and strip.shape == (4, P // span, 1, span * 128)
    # and the unmasked trace does not depend on a masked one having been traced before it
    assert str(call_of(window=window)[0].params["jaxpr"]) == str(bare.params["jaxpr"])


def test_chunk_attention_under_a_selection_mask_is_masked_softmax_attention():
    from deepspeed_tpu.ops.transformer.inference import paged_chunk_attention

    rng = np.random.default_rng(7)
    B, H, Hkv, d, NP, page_len, P, T = 1, 4, 2, 128, 9, 8, 8, 16
    kc = jnp.asarray(rng.standard_normal((NP, Hkv, page_len, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((NP, Hkv, page_len, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, T, d)), jnp.float32)
    table, pos = _paged(rng, B, P, page_len, NP), jnp.asarray([40], jnp.int32)
    S = P * page_len
    causal = np.arange(S)[None, None, :] <= (40 + np.arange(T))[None, :, None]
    mask = causal & (rng.random((B, T, S)) < 0.2)
    mask[0, :, 0] = True
    mask[0, 3, :32] = False  # a query that selects nothing in the walk's first block
    got = np.asarray(paged_chunk_attention(q, kc, vc, table, pos, extra_mask=jnp.asarray(mask)))
    from deepspeed_tpu.ops.transformer.inference import paged_gather

    gk, gv = np.asarray(paged_gather(kc, table))[0], np.asarray(paged_gather(vc, table))[0]  # (Hkv, S, d)
    for h in range(H):
        s = np.einsum("td,sd->ts", np.asarray(q)[0, h], gk[h // 2]) * d ** -0.5
        s = np.where(mask[0], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ gv[h // 2]
        np.testing.assert_allclose(got[0, h], want, rtol=2e-4, atol=2e-4)
    plain = np.asarray(paged_chunk_attention(q, kc, vc, table, pos))
    np.testing.assert_allclose(np.asarray(paged_chunk_attention(q, kc, vc, table, pos, extra_mask=jnp.asarray(causal))), plain, rtol=1e-5, atol=1e-5)
