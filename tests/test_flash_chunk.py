"""``flash_chunk_paged`` (ops/kernels/flash_chunk.py) in interpret mode
against the ``jnp`` form of ``paged_chunk_attention`` (the suite unarmed):
the serve cells' head layouts, rows at unequal positions, a chunk that
starts mid-page, a shuffled page table, the selection mask; the dispatch
rule and the notes it leaves in ``ServingEngine.stats()``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.ops.kernels.flash_chunk import chunk_tile, flash_chunk_paged, flash_chunk_supported
from deepspeed_tpu.ops.transformer import inference as inf
from deepspeed_tpu.serving import ServingEngine

PAGE, D = 128, 128


def _setup(seed, B, H, Hkv, T, P, dtype, d=D, page_len=PAGE):
    """Queries, filled K / V pools (page 0 the garbage page) and a page
    table that scatters each slot's pages over the pool."""
    rng = np.random.default_rng(seed)
    n_pages = 1 + B * P
    k, v = (jnp.asarray(rng.standard_normal((n_pages, Hkv, page_len, d)), dtype) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, H, T, d)), dtype)
    table = jnp.asarray(1 + rng.permutation(B * P).reshape(B, P), jnp.int32)
    return q, k, v, table


def _selection(seed, B, T, S, pos, share=0.12):
    """A per-query selection of the context inside the causal bound, as
    Keye's indexer leaves it — and, of row 0, query 5 selects nothing at
    all, query 9 nothing in the first 256 positions, query 11 nothing past them."""
    rng = np.random.default_rng(seed)
    reach = np.arange(S)[None, None, :] <= (np.asarray(pos)[:, None] + np.arange(T)[None, :])[:, :, None]
    mask = (rng.random((B, T, S)) < share) & reach
    mask[0, 5] = False
    mask[0, 9, :256] = False
    mask[0, 11, 256:] = False
    return jnp.asarray(mask)


def _gap(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(1.0, float(np.max(np.abs(want)))))


LAYOUTS = {"keye_32_over_4": (32, 4), "laguna_48_over_8": (48, 8), "zaya1_8_over_2": (8, 2), "solar_open2_64_over_8": (64, 8)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("masked", [False, True], ids=["causal", "under_a_selection"])
@pytest.mark.parametrize("layout", LAYOUTS)
def test_the_kernel_is_the_jnp_form_at_the_cells_head_layouts(layout, masked, dtype):
    """Two rows at unequal positions, one starting mid-page, pages in
    shuffled order, through ``paged_chunk_attention``'s own dispatch."""
    H, Hkv = LAYOUTS[layout]
    B, T, P = 2, 128, 4
    q, k, v, table = _setup(len(layout), B, H, Hkv, T, P, dtype)
    pos = jnp.asarray([300, 37], jnp.int32)  # row 0 ends the slot's third page but 84, row 1 starts inside its first
    mask = _selection(H, B, T, P * PAGE, pos) if masked else None
    notes = {}
    got = inf.paged_chunk_attention(q, k, v, table, pos, extra_mask=mask, use_kernel=True, trace_notes=notes)
    want = inf.paged_chunk_attention(q, k, v, table, pos, extra_mask=mask, use_kernel=False, block_pages=1)
    assert notes == {"chunk_attention_kernel": True, "chunk_attention_fallback": ""}
    assert got.shape == q.shape and got.dtype == q.dtype
    assert _gap(got, want) <= (2e-5 if dtype == jnp.float32 else 2e-2)  # bf16: p is rounded under another block's maximum
    if masked:
        assert float(jnp.abs(got[0, :, 5].astype(jnp.float32)).max()) == 0.0  # selected nothing: l == 0 reads 0


# tiles of (tq, pages a block) on 6 pages a slot = 768 positions
WALKS = {
    "starts_at_0":                        dict(T=384, pos=[0], tile=(128, 1)),
    "starts_mid_page":                    dict(T=256, pos=[200], tile=(128, 2)),
    "starts_on_a_block_edge":             dict(T=256, pos=[256], tile=(128, 2)),
    "ends_the_slots_last_page":           dict(T=384, pos=[384], tile=(128, 3)),
    "two_rows_the_first_far_ahead":       dict(T=128, pos=[517, 30], tile=(128, 1)),
    "two_rows_the_second_far_ahead":      dict(T=128, pos=[3, 640], tile=(128, 2)),
    "one_query_tile_of_256":              dict(T=256, pos=[73], tile=(256, 1)),
    "a_block_of_the_whole_slot":          dict(T=256, pos=[130], tile=(128, 6)),
    "a_last_block_past_the_slots_pages":  dict(T=256, pos=[512], tile=(128, 4)),
    "the_tile_the_shapes_give":           dict(T=256, pos=[411], tile=None),
}


@pytest.mark.parametrize("masked", [False, True], ids=["causal", "under_a_selection"])
@pytest.mark.parametrize("walk", WALKS)
def test_the_walk_skips_what_the_causal_bound_empties_and_ends_where_the_furthest_query_does(walk, masked):
    T, pos, tile = WALKS[walk]["T"], jnp.asarray(WALKS[walk]["pos"], jnp.int32), WALKS[walk]["tile"]
    B, H, Hkv, P = len(WALKS[walk]["pos"]), 4, 2, 6
    q, k, v, table = _setup(len(walk), B, H, Hkv, T, P, jnp.float32)
    mask = _selection(T, B, T, P * PAGE, pos) if masked else None
    got = flash_chunk_paged(q, k, v, table, pos, extra_mask=mask, tile=tile)
    want = inf.paged_chunk_attention(q, k, v, table, pos, extra_mask=mask, use_kernel=False, block_pages=3)
    assert _gap(got, want) <= 2e-5
    # what lies past the furthest query is never read: garbage there changes nothing
    reach = int(jnp.max(pos)) + T
    span = (tile or chunk_tile(H // Hkv, T, P, PAGE))[1]
    first_unread = -(-reach // (span * PAGE)) * span  # the first page past the furthest query's last block
    if first_unread < P:
        poisoned = k.at[table[:, first_unread:].reshape(-1)].set(jnp.nan)
        assert np.array_equal(np.asarray(flash_chunk_paged(q, poisoned, v, table, pos, extra_mask=mask, tile=tile)), np.asarray(got))


def test_a_tile_is_sized_from_the_shapes_alone():
    assert chunk_tile(8, 2048, 264, 128) == (256, 8)    # Keye: 8 heads a KV head stack 2,048 rows against 1,024 keys: 8 MB of scores
    assert chunk_tile(6, 1024, 168, 128) == (256, 8)    # Laguna's full layers: 1,536 rows
    assert chunk_tile(4, 1024, 64, 128) == (512, 8)     # ZAYA1
    assert chunk_tile(8, 512, 64, 128) == (256, 8)      # Solar-Open2
    assert chunk_tile(1, 384, 7, 256) == (128, 4)       # pages need not divide the slot; T / 128 odd: one run of 128
    assert chunk_tile(16, 256, 6, 128) == (128, 6)      # a short slot is one block, a wide group keeps one run of 128 a head
    assert chunk_tile(2, 2048, 4, 2048) == (512, 1)     # a page longer than a block is a block


def _int8_pool(k):
    scale = jnp.max(jnp.abs(k), axis=-1, keepdims=True) / 127.0
    return {"q": jnp.round(k / scale).astype(jnp.int8), "s": scale.astype(jnp.float32)}


REFUSALS = {
    "a_head_of_64":             dict(d=64, T=128, quant=False, use_kernel=True, says="head dim 64 is narrower than the 128 lanes"),
    "an_int8_pool":             dict(d=128, T=128, quant=True, use_kernel=True, says="int8 pool"),
    "a_chunk_of_64":            dict(d=128, T=64, quant=False, use_kernel=True, says="chunk of 64 on pages of 128: not whole 128-row tiles"),
    "pages_of_16":              dict(d=128, T=128, quant=False, use_kernel=True, page_len=16, says="chunk of 128 on pages of 16: not whole 128-row tiles"),
    "an_unarmed_suite":         dict(d=128, T=128, quant=False, use_kernel=None, says="kernel suite not armed"),
}


@pytest.mark.parametrize("case", REFUSALS)
def test_what_the_kernel_does_not_serve_takes_the_jnp_form_to_the_bit_and_says_why(case):
    c = REFUSALS[case]
    page_len = c.get("page_len", PAGE)
    q, k, v, table = _setup(len(case), 2, 4, 2, c["T"], 512 // page_len, jnp.float32, d=c["d"], page_len=page_len)
    if c["quant"]:
        k, v = _int8_pool(k), _int8_pool(v)
    pos = jnp.asarray([130, 9], jnp.int32)
    notes = {}
    got = inf.paged_chunk_attention(q, k, v, table, pos, use_kernel=c["use_kernel"], trace_notes=notes)
    want = inf.paged_chunk_attention(q, k, v, table, pos, use_kernel=False)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    assert notes["chunk_attention_kernel"] is False and notes["chunk_attention_fallback"].startswith(c["says"])
    assert not flash_chunk_supported(c["T"], c["d"], page_len, c["quant"]) or c["use_kernel"] is None
    assert inf.chunk_attention_note(notes) == f"blockwise jnp (paged_chunk_attention): {notes['chunk_attention_fallback']}"


def test_the_kernel_refuses_a_shape_its_predicate_does_not_pass():
    q, k, v, table = _setup(0, 1, 4, 2, 64, 4, jnp.float32)
    with pytest.raises(ValueError, match="flash_chunk_supported"):
        flash_chunk_paged(q, k, v, table, jnp.zeros((1,), jnp.int32))


@pytest.mark.parametrize("armed", [False, True], ids=["unarmed", "armed"])
def test_stats_say_which_form_a_gpt2_prefill_program_compiled_and_why(armed, monkeypatch):
    """GPT-2's heads are narrower than the lanes: armed or not, its chunk
    keeps the ``jnp`` form, and ``stats()`` say which reason held."""
    from deepspeed_tpu.models import gpt2

    monkeypatch.setenv("DS_KERNELS", "1" if armed else "0")
    engine = deepspeed_tpu.init_inference(model_config=gpt2.GPT2_TINY, dtype=jnp.float32, max_out_tokens=64, seed=3)
    srv = ServingEngine(engine, config={"num_slots": 2, "max_len": 64, "prefill_chunk": 16, "max_new_tokens": 4,
                                        "kvcache": {"enabled": True, "page_len": 16}})
    assert "chunk_attention_kernel" not in srv.stats()  # nothing traced yet
    srv.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=2)
    srv.drain(max_steps=50)
    stats = srv.stats()
    assert stats["chunk_attention_kernel"] is False
    assert stats["chunk_attention_fallback"].startswith("head dim" if armed else "kernel suite not armed")
    assert stats["prefill_attend_form"] == f"blockwise (paged_chunk_attention): {stats['chunk_attention_fallback']}"


def test_a_keye_layer_attends_its_chunk_through_the_kernel_under_the_selection_mask():
    """``sparse_attention.attention`` on a chunk with the kernels asked
    for: the chunk's attention is ``flash_chunk_paged`` under the mask the
    selection made, equal to the ``jnp`` form's, and the form note says so."""
    from deepspeed_tpu.ops.transformer import sparse_attention as sa

    sz = sa.Sizes(heads=4, kv_heads=2, head_dim=128, index_heads=2, index_dim=64, index_rot=32, topk=96, theta=1e4, sections=(16, 24, 24))
    rng = np.random.default_rng(3)
    w = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.1, jnp.float32)  # noqa: E731
    lp = {"qkv": w(64, (4 + 2 + 2) * 128), "q_norm": 1 + w(128), "k_norm": 1 + w(128), "index_q": w(64, 2 * 64), "index_k": w(64, 64),
          "index_w": w(64, 2), "index_k_gain": 1 + w(64), "index_k_bias": w(64)}
    B, T, P, pages = 1, 128, 3, 4
    k_pool = {"k": jnp.zeros((1, pages, 2, PAGE, 128), jnp.float32), "idx": jnp.zeros((1, pages, 64, PAGE), jnp.float32)}
    v_pool = jnp.zeros((1, pages, 2, PAGE, 128), jnp.float32)
    table = jnp.asarray([[2, 1, 3]], jnp.int32)
    pos3 = lambda pos: jnp.broadcast_to(pos[None, :, None] + jnp.arange(T)[None, None, :], (3, B, T))  # noqa: E731
    outs, notes = {}, {}
    for use_kernel in (False, True):
        kp, vp = k_pool, v_pool
        for chunk in range(2):  # the second chunk attends the first's pages
            pos = jnp.asarray([chunk * T], jnp.int32)
            u = jnp.asarray(np.random.default_rng(chunk).standard_normal((B, T, 64)), jnp.float32)
            notes[use_kernel] = {}
            o, kp, vp = sa.attention(sz, lp, u, kp, vp, 0, pos, pos3(pos), table, use_kernel=use_kernel, trace_notes=notes[use_kernel])
        outs[use_kernel] = o
    assert notes[True]["chunk_attention_kernel"] is True and notes[False]["chunk_attention_fallback"] == "kernel suite not armed"
    assert notes[True]["dsa_prefill_form"] == ("paged_chunk_attention, dense under the selection mask: "
                                               "flash_chunk_paged (the slot's pages where they lie, scores in VMEM)")
    assert notes[False]["dsa_prefill_form"].startswith("paged_chunk_attention, dense under the selection mask: blockwise jnp: kernel suite")
    assert _gap(outs[True], outs[False]) <= 2e-5
