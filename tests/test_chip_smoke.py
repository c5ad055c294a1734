"""What can be known about the chip path without a chip.

* every Pallas kernel the smoke arms lowers for the TPU at the smoke's
  shapes (``jax.export`` runs the Pallas→Mosaic lowering on the CPU; the
  Mosaic compiler itself only runs where libtpu compiles, i.e. in
  ``chip_smoke.py``) — this is the test that would have caught the fused
  LAMB partial-norm blocks;
* ``chip_smoke.py``'s phases, driven at toy sizes on the CPU mesh, for
  control flow only; the script itself refuses to run off a TPU;
* the compile-cache rule: where ``JAX_COMPILATION_CACHE_DIR`` is set,
  no code sets another directory;
* the multi-device wrapper the compiled kernels run under.
"""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import chip_smoke
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.utils import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FULL = chip_smoke.FULL


def _lowers_for_tpu(fn, *shapes):
    exported = jax.export.export(jax.jit(fn), platforms=["tpu"])(*shapes)
    assert "tpu_custom_call" in exported.mlir_module()


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# (a) lowering at the smoke's shapes
# ---------------------------------------------------------------------------

def test_flash_attention_lowers_for_tpu_at_smoke_shapes():
    from deepspeed_tpu.ops.attention.flash_attention import flash_attention

    c = FULL.train_cfg
    qkv = _sds((FULL.micro, c.n_head, FULL.seq, c.head_dim), jnp.bfloat16)

    def fwd_bwd(q, k, v):
        return jax.grad(
            lambda *a: flash_attention(*a, causal=True, interpret=False).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    _lowers_for_tpu(fwd_bwd, qkv, qkv, qkv)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_flash_decode_lowers_for_tpu_at_smoke_shapes(kv):
    from deepspeed_tpu.ops.kernels.flash_decode import flash_decode, flash_decode_paged, paged_tile, paged_work_list

    mcfg = gpt2.PRESETS[FULL.serve_model]
    B, H, d, S, PL = FULL.slots, mcfg.n_head, mcfg.head_dim, FULL.max_len, FULL.page_len
    P = S // PL

    def cache(rows_shape):
        if kv == "int8":
            return {"q": _sds(rows_shape, jnp.int8), "s": _sds(rows_shape[:-1] + (1,), jnp.float32)}
        return _sds(rows_shape, jnp.bfloat16)

    q, pos = _sds((B, H, 1, d), jnp.bfloat16), _sds((B,), jnp.int32)
    pages = cache((1 + B * P, H, PL, d))
    _lowers_for_tpu(
        lambda q, k, v, t, p: flash_decode_paged(q, k, v, t, p, interpret=False),
        q, pages, pages, _sds((B, P), jnp.int32), pos,
    )
    # as a decode program calls it: the work list of the rows that decode, its length the grid's traced bound.
    # Multi-head attention with all 25 heads of a page in one program, ``(d, page_len)`` tiles (int8: two pages an item)
    span = paged_tile(pages, P)[1]
    assert paged_tile(pages, P) == (H, 2 if kv == "int8" else 1)
    _lowers_for_tpu(
        lambda q, k, v, t, p, m: flash_decode_paged(q, k, v, t, p, work=paged_work_list(p, m, PL, P, span), interpret=False),
        q, pages, pages, _sds((B, P), jnp.int32), pos, _sds((B,), jnp.bool_),
    )
    # ... and at ZAYA1's shapes: 2 KV heads x 4 query heads of 128, 64 slots of 64 pages: a span of eight pages an item
    zB, zP = 64, 64
    zq, zpages = _sds((zB, 8, 1, 128), jnp.bfloat16), cache((1 + 2048, 2, PL, 128))
    assert paged_tile(zpages, zP) == (2, 8)
    _lowers_for_tpu(
        lambda q, k, v, t, p, m: flash_decode_paged(q, k, v, t, p, work=paged_work_list(p, m, PL, zP, 8), interpret=False),
        zq, zpages, zpages, _sds((zB, zP), jnp.int32), _sds((zB,), jnp.int32), _sds((zB,), jnp.bool_),
    )
    slots = cache((B, H, S, d))
    _lowers_for_tpu(lambda q, k, v, p: flash_decode(q, k, v, p, interpret=False), q, slots, slots, pos)
    _lowers_for_tpu(
        lambda q, k, v, p, m: flash_decode(q, k, v, p, key_padding_mask=m, interpret=False),
        q, slots, slots, pos, _sds((B, S), jnp.bool_),
    )


@pytest.mark.parametrize("opt_name", ["adam", "lamb"])
def test_fused_update_lowers_for_tpu_at_smoke_shapes(opt_name):
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
    from deepspeed_tpu.ops.kernels.fused_update import engine_update
    from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb

    c = FULL.train_cfg
    opt = FusedAdam(lr=1e-4) if opt_name == "adam" else FusedLamb(lr=1e-4)
    tree = {"qkv_w": _sds((c.n_layer, c.n_embd, 3 * c.n_embd), jnp.float32)}
    state = jax.eval_shape(opt.init, tree)
    _lowers_for_tpu(
        lambda g, st, p: engine_update(opt, g, st, p, jnp.float32(1e-4), None, interpret=False),
        tree, state, tree,
    )


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described, not attached, v5e: libtpu compiles for
    it here.  Described inside a fixture and in this file only — a
    process keeps libtpu once it has loaded it."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("cell,slots,pages_per_slot,pool,heads,tile", [
    ("gpt2_xl", 16, 8, (129, 25, 128, 64), 25, (25, 1)),          # 25 heads a program, (d, page_len) tiles
    ("solar_open2", 160, 64, (2561, 8, 128, 128), 64, (8, 2)),    # 8 KV heads x 2 pages: 16 operands' worth a step
    ("zaya1", 64, 64, (2049, 2, 128, 128), 8, (2, 8)),            # 2 KV heads x 8 pages, each page an operand of its own
    ("laguna_full", 24, 168, (2305, 8, 128, 128), 48, (8, 2)),    # 6 query heads a KV head: a 48-row tile
    ("keye_selected", 16, 264, (8 * 4225, 4, 128, 128), 32, (4, 4)),  # under the rows' selection: an int8 strip of 512 positions an item
])
def test_flash_decode_paged_compiles_for_v5e_under_the_tile_of_each_serve_cell(cell, slots, pages_per_slot, pool, heads, tile, v5e_chip):
    """The paged decode kernel at the serve cells' own shapes, by the
    chip's compiler without the chip: Mosaic takes the tile the pool's
    shape gives (a mebibyte of K + V a grid step, twice over in VMEM) —
    Keye's under a selection, where the call's name is ``dsa_sparse_decode``."""
    from deepspeed_tpu.ops.kernels.flash_decode import flash_decode_paged, paged_tile, paged_work_list

    on_chip = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    assert paged_tile(on_chip(pool), pages_per_slot) == tile
    selected = cell == "keye_selected"

    def call(q, k, v, t, p, m, chosen=None):
        work = paged_work_list(p, m, pool[2], pages_per_slot, tile[1])
        return flash_decode_paged(q, k, v, t, p, work=work, interpret=False, mask=chosen)

    compiled = jax.jit(call).lower(on_chip((slots, heads, 1, pool[3])), on_chip(pool), on_chip(pool),
                                   on_chip((slots, pages_per_slot), jnp.int32), on_chip((slots,), jnp.int32),
                                   on_chip((slots,), jnp.bool_),
                                   *([on_chip((slots, pages_per_slot * pool[2]), jnp.bool_)] if selected else [])).compile()
    assert chip_smoke.mosaic_kernels(compiled.as_text()) == {"dsa_sparse_decode" if selected else "flash_decode_paged": 1}


@pytest.mark.parametrize("form,kv_heads,slots,pool_pages,tile", [("swa_decode_paged", 8, 32, 65, (8, 1)), ("flash_decode_paged", 4, 32, 7169, (4, 2))])
def test_the_mimo_decode_kernels_compile_for_v5e_with_keys_in_the_lanes_form_and_values_row_major(form, kv_heads, slots, pool_pages, tile, v5e_chip):
    """``chip_smoke.check_mimo_decode_paged``'s shadow: the paged decode kernel at MiMo-V2-Flash's two geometries (64 query heads
    on 8 / 4 KV heads, keys 192 and values 128 wide, slots of 544 pages), by the chip's compiler without the chip — the window
    form with its sink operand on the ring of 2 pages, the full form over pages by length; the K pool lies with its positions
    in the lanes, unpadded, and no copy of a pool stands in front of the call."""
    from deepspeed_tpu.ops.kernels.flash_decode import flash_decode_paged, paged_tile, paged_work_list
    from deepspeed_tpu.ops.transformer import inference as inf

    on_chip = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    k, v = on_chip((pool_pages, kv_heads, 128, 192)), on_chip((pool_pages, kv_heads, 128, 128))
    assert paged_tile(k, 544, v) == tile
    window = 128 if form == "swa_decode_paged" else None

    def call(q, k, v, t, p, m, sink):
        table = inf.ring_table(jnp.arange(slots), 2, 544) if window else t
        work = paged_work_list(p, m, 128, 544, tile[1], window)
        return flash_decode_paged(q, k, v, table, p, work=work, interpret=False, window=window, sink=sink if window else None)

    compiled = jax.jit(call).lower(on_chip((slots, 64, 1, 192)), k, v, on_chip((slots, 544), jnp.int32), on_chip((slots,), jnp.int32),
                                   on_chip((slots,), jnp.bool_), on_chip((64,), jnp.float32)).compile()
    assert chip_smoke.mosaic_kernels(compiled.as_text()) == {form: 1}
    assert "bf16[%d,%d,128,192]{2,3,1,0" % (pool_pages, kv_heads) in compiled.as_text()  # page_len in the lanes: 192 is not padded to 256
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 20


@pytest.mark.parametrize("cell,heads,kv_heads,chunk,pages_per_slot,masked,tile", [
    ("keye", 32, 4, 2048, 264, True, (256, 8)),          # 8 query heads a KV head stacked: 2,048 rows against 1,024 keys, under the selection
    ("laguna_full", 48, 8, 1024, 168, False, (256, 8)),  # 6 a KV head: 1,536 rows
    ("zaya1", 8, 2, 1024, 64, False, (512, 8)),          # 4 a KV head
    ("solar_open2", 64, 8, 512, 64, False, (256, 8)),
    ("a_slot_its_blocks_do_not_divide", 8, 2, 256, 21, True, (256, 8)),  # the last block reaches 3 pages past the slot, the mask's with it
])
def test_flash_chunk_paged_compiles_for_v5e_under_the_tile_of_each_serve_cell(cell, heads, kv_heads, chunk, pages_per_slot, masked, tile, v5e_chip):
    """The chunk's attention kernel at the serve cells' own shapes, by the
    chip's compiler without the chip: Mosaic takes the tile the shapes
    give, and nothing the size of a block's float32 scores is left in
    the program beside it."""
    from deepspeed_tpu.ops.kernels.flash_chunk import chunk_tile, flash_chunk_paged

    on_chip = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    assert chunk_tile(heads // kv_heads, chunk, pages_per_slot, 128) == tile
    pool = (1 + 2 * pages_per_slot, kv_heads, 128, 128)
    args = [on_chip((1, heads, chunk, 128)), on_chip(pool), on_chip(pool), on_chip((1, pages_per_slot), jnp.int32), on_chip((1,), jnp.int32)]
    if masked:
        args.append(on_chip((1, chunk, pages_per_slot * 128), jnp.bool_))
    compiled = jax.jit(lambda q, k, v, t, p, m=None: flash_chunk_paged(q, k, v, t, p, extra_mask=m, interpret=False)).lower(*args).compile()
    assert chip_smoke.mosaic_kernels(compiled.as_text()) == {"flash_chunk_paged": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < heads * chunk * tile[1] * 128 * 4


@pytest.mark.parametrize("opt_name", ["adam", "lamb"])
def test_fused_update_compiles_for_v5e_with_no_pass_beside_the_kernels(opt_name, v5e_chip):
    """The check chip_smoke makes on the chip, made by the chip's
    compiler without the chip: Mosaic takes the blocks the leaf's shape
    gives, and XLA puts no copy, reshape or transpose of a weight leaf
    round the calls (the (rows, 256) view cost seven: PERF.md, PR 27)."""
    from deepspeed_tpu.ops.adam.fused_adam import FusedAdam
    from deepspeed_tpu.ops.kernels.fused_update import engine_update
    from deepspeed_tpu.ops.lamb.fused_lamb import FusedLamb

    opt = FusedAdam(lr=1e-4) if opt_name == "adam" else FusedLamb(lr=1e-4)
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=v5e_chip), t)
    tree = on_chip({"cell_fc_w": _sds(FULL.update_leaf, jnp.float32)})
    state = on_chip(jax.eval_shape(opt.init, tree))
    hlo = (
        jax.jit(
            lambda g, st, p: engine_update(opt, g, st, p, jnp.float32(1e-4), None, interpret=False)[::-1],
            donate_argnums=(1, 2),
        ).lower(tree, state, tree).compile().as_text()
    )
    chip_smoke.expect_kernels(chip_smoke.mosaic_kernels(hlo), chip_smoke.UPDATE_KERNELS[opt_name], opt_name)
    assert chip_smoke.leaf_sized_moves(hlo, int(np.prod(FULL.update_leaf))) == []


@pytest.mark.parametrize("H,T,pages,dn,dr,dv,c", [
    (128, 512, 64, 128, 64, 128, 512),  # the DeepSeek-V2 cell's chunk: context blocks of 8 pages
    (8, 128, 4, 32, 64, 32, 128),       # chip_smoke's tiny DeepSeek-V2
], ids=["published_dims", "smoke_dims"])
def test_expanded_attention_compiles_for_v5e_with_the_scores_in_the_kernel(H, T, pages, dn, dr, dv, c, v5e_chip, monkeypatch):
    """A prefill chunk's attention, by the chip's compiler without the
    chip: Mosaic takes ``mla_prefill`` at these tiles, and no float32
    ``(H, T, S)`` score block is left in the program round it."""
    from deepspeed_tpu.ops.kernels import mla_prefill
    from deepspeed_tpu.ops.transformer import latent_attention as la

    monkeypatch.setattr(mla_prefill, "pallas_interpret_default", lambda: False)  # this process's platform is the CPU
    on_chip = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    hlo = jax.jit(
        lambda qn, qp, pool, table, pos, w: la.expanded_attention(qn, qp, pool, 1, table, pos, w, dn, 0.11, use_kernel=True)
    ).lower(
        on_chip((1, T, H, dn), jnp.bfloat16), on_chip((1, T, H, dr), jnp.bfloat16),
        on_chip((2, 1 + pages, c + dr, 128), jnp.bfloat16), on_chip((1, pages), jnp.int32), on_chip((1,), jnp.int32),
        on_chip((c, H, dn + dv), jnp.bfloat16),
    ).compile().as_text()
    assert chip_smoke.mosaic_kernels(hlo) == {"mla_prefill": 1}
    S = min(pages, 8) * 128
    assert f"f32[1,{H},{T},{S}]" not in hlo and f"f32[{H},{T},{S}]" not in hlo


@pytest.mark.parametrize("tokens", [512, 32], ids=["a_chunk_of_512", "a_decode_step_of_32_rows"])
def test_held_experts_compile_for_v5e_with_the_weights_read_where_they_are(tokens, v5e_chip, monkeypatch):
    """The DeepSeek-V2 cell's routed layer (40 experts held, top-6,
    5120 / 1536), by the chip's compiler without the chip: Mosaic takes
    ``moe_grouped_matmul`` for both grouped matmuls, and XLA puts no
    copy, transpose or fusion of a layer's expert weights round them
    (1.26 GB a call if it did: PERF.md, PR 27 and PR 28)."""
    from deepspeed_tpu.moe.layer import dropless_held_experts
    from deepspeed_tpu.ops.kernels import grouped_matmul

    monkeypatch.setenv("DS_KERNELS", "1")
    monkeypatch.setattr(grouped_matmul, "pallas_interpret_default", lambda: False)  # this process's platform is the CPU
    on_chip = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    E, D, F, top_k = 40, 5120, 1536, 6
    hlo = jax.jit(lambda x, idx, w, gu, down: dropless_held_experts(x, idx, w, gu, down, (0, E))).lower(
        on_chip((tokens, D), jnp.bfloat16), on_chip((tokens, top_k), jnp.int32), on_chip((tokens, top_k), jnp.float32),
        on_chip((E, D, 2 * F), jnp.bfloat16), on_chip((E, F, D), jnp.bfloat16),
    ).compile().as_text()
    assert chip_smoke.mosaic_kernels(hlo) == {"moe_grouped_matmul": 2}
    assert "ragged-dot" not in hlo
    assert chip_smoke.leaf_sized_moves(hlo, E * D * 2 * F) == [] and chip_smoke.leaf_sized_moves(hlo, E * F * D) == []


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_solar_open2_steps_compile_for_v5e_with_both_caches_updated_in_place(which, v5e_chip, monkeypatch):
    """One period of Solar-Open2 at the published widths on the hybrid
    cache (16 slots, 2,049 pages), by the chip's compiler
    without the chip: the decode step holds ``kda_decode`` once a KDA
    layer, the grouped ``flash_decode_paged`` and the experts' kernel;
    and neither step leaves a copy of the K/V pools or of the recurrent
    state in the program (an XLA scatter for the K/V write copied both
    pools every step: PERF.md, PR 32)."""
    from deepspeed_tpu.models import solar_open2 as so
    from deepspeed_tpu.ops.kernels import flash_chunk, flash_decode, grouped_matmul, kda_decode

    monkeypatch.setenv("DS_KERNELS", "1")
    for mod in (flash_chunk, flash_decode, grouped_matmul, kda_decode):
        monkeypatch.setattr(mod, "pallas_interpret_default", lambda: False)  # this process's platform is the CPU
    slots, pages, chunk = 16, 2049, 512
    cfg = so.SolarOpen2Config(num_hidden_layers=4, gqa_layers=(0,), experts_held=(0, 40), vocab_held=24576)
    on_chip = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    params = jax.tree.map(lambda sh: on_chip(sh, jnp.bfloat16), so.param_shapes(cfg), is_leaf=lambda sh: isinstance(sh, tuple))
    kind = so.cache_kind(cfg, jnp.bfloat16)
    k, v = (on_chip(a.shape, a.dtype) for a in jax.eval_shape(lambda: kind.buffers(4, pages, 128)))
    state = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(lambda: kind.state_buffers(slots)))
    if which == "decode":
        def step(p, t, pos, table, wm, k, v, st):
            return so.forward_with_cache(p, t[:, None], k, v, st, pos, cfg, table, write_mask=wm, row_valid=wm[:, None])
        args = (params, on_chip((slots,), jnp.int32), on_chip((slots,), jnp.int32), on_chip((slots, 64), jnp.int32),
                on_chip((slots,), jnp.bool_), k, v, state)
    else:
        def step(p, t, table, slot, pos, k, v, st):
            return so.forward_with_cache(p, t, k, v, st, pos[None], cfg, table[None], slot=slot[None])
        args = (params, on_chip((1, chunk), jnp.int32), on_chip((64,), jnp.int32), on_chip((), jnp.int32), on_chip((), jnp.int32),
                k, v, state)
    compiled = jax.jit(step, donate_argnums=(len(args) - 3, len(args) - 2, len(args) - 1)).lower(*args).compile()
    found = chip_smoke.mosaic_kernels(compiled.as_text())
    if which == "decode":
        assert found == {"kda_decode": 3, "flash_decode_paged": 1, "moe_grouped_matmul": 8}
    else:
        assert found == {"flash_chunk_paged": 1, "moe_grouped_matmul": 8}  # the GQA layer's chunk walks its pages in the kernel
    m = compiled.memory_analysis()
    cache_bytes = 2 * int(np.prod(k.shape)) * 2 + sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert m.alias_size_in_bytes >= cache_bytes               # all three groups come back in place
    assert m.temp_size_in_bytes < int(np.prod(k.shape)) * 2   # and no temporary is as large as one pool


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_zaya_steps_compile_for_v5e_with_pages_and_tail_updated_in_place(which, v5e_chip, monkeypatch):
    """Two layers of ZAYA1-8B at the published widths on the cell's
    hybrid cache (64 slots, 2,433 pages, every layer with pages *and* a
    slot tail), by the chip's compiler without the chip: a decode step
    holds the grouped ``flash_decode_paged`` once a layer (2 KV heads x
    group 4 x 128, reading the stacked pool through a merged leading dim
    and an offset page table) and the experts' kernel at 2048 / 2048 for
    its 64 top-1 rows (padded to one MXU window); a chunk of 1,024 holds
    the experts' kernel; neither leaves a copy of the pools or the tail."""
    from deepspeed_tpu.models import zaya
    from deepspeed_tpu.ops.kernels import flash_chunk, flash_decode, grouped_matmul

    monkeypatch.setenv("DS_KERNELS", "1")
    for mod in (flash_chunk, flash_decode, grouped_matmul):
        monkeypatch.setattr(mod, "pallas_interpret_default", lambda: False)  # this process's platform is the CPU
    layers, slots, pages, chunk = 2, 64, 2433, 1024
    cfg = zaya.ZayaConfig(num_hidden_layers=layers, experts_held=(0, 8), vocab_held=131136)
    on_chip = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    params = jax.tree.map(lambda sh: on_chip(sh, jnp.bfloat16), zaya.param_shapes(cfg), is_leaf=lambda sh: isinstance(sh, tuple))
    kind = zaya.cache_kind(cfg, jnp.bfloat16)
    k, v = (on_chip(a.shape, a.dtype) for a in jax.eval_shape(lambda: kind.buffers(layers, pages, 128)))
    state = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(lambda: kind.state_buffers(slots)))
    notes = {}
    if which == "decode":
        def step(p, t, pos, table, wm, k, v, st):
            return zaya.forward_with_cache(p, t[:, None], k, v, st, pos, cfg, table, write_mask=wm, row_valid=wm[:, None],
                                           trace_notes=notes)
        args = (params, on_chip((slots,), jnp.int32), on_chip((slots,), jnp.int32), on_chip((slots, 64), jnp.int32),
                on_chip((slots,), jnp.bool_), k, v, state)
    else:
        def step(p, t, table, slot, pos, rv, k, v, st):
            return zaya.forward_with_cache(p, t, k, v, st, pos[None], cfg, table[None], slot=slot[None], row_valid=rv,
                                           trace_notes=notes)
        args = (params, on_chip((1, chunk), jnp.int32), on_chip((64,), jnp.int32), on_chip((), jnp.int32), on_chip((), jnp.int32),
                on_chip((1, chunk), jnp.bool_), k, v, state)
    compiled = jax.jit(step, donate_argnums=(len(args) - 3, len(args) - 2, len(args) - 1)).lower(*args).compile()
    found = chip_smoke.mosaic_kernels(compiled.as_text())
    if which == "decode":
        assert found == {"flash_decode_paged": layers, "moe_grouped_matmul": 2 * layers}
        assert notes["cca_decode_kernel"] is True and notes["moe_grouped_kernel"] == "64"
    else:
        assert found == {"flash_chunk_paged": layers, "moe_grouped_matmul": 2 * layers} and notes["moe_grouped_kernel"] == "1024"
        assert notes["chunk_attention_kernel"] is True and notes["cca_prefill_form"].startswith("flash_chunk_paged")
    assert notes["moe_grouped_fallback"] == ""
    m = compiled.memory_analysis()
    cache_bytes = 2 * int(np.prod(k.shape)) * 2 + sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert m.alias_size_in_bytes >= cache_bytes                    # pages and tail come back in place
    assert m.temp_size_in_bytes < int(np.prod(k.shape)) * 2 // 2   # and no temporary is half of one pool


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_gigachat35_steps_compile_for_v5e_with_latent_pages_and_state_updated_in_place(which, v5e_chip, monkeypatch):
    """The cell's five layers of GigaChat3.5 at the published widths on
    its hybrid cache over latent pages (96 slots of 280 pages, 8,193
    latent pages of one layer, a 17 MB state a slot), by the chip's
    compiler without the chip: a decode step holds ``gdn_decode`` once a
    delta-rule layer, ``mla_decode_paged`` once and the experts' kernel
    twice an expert layer; a chunk of 1,024 holds ``mla_prefill`` and the
    experts' kernel; neither leaves a copy of the latent pool or of the
    recurrent state in the program."""
    from deepspeed_tpu.models import gigachat35 as gc
    from deepspeed_tpu.ops.kernels import grouped_matmul, kda_decode, mla_decode, mla_prefill

    monkeypatch.setenv("DS_KERNELS", "1")
    for mod in (grouped_matmul, kda_decode, mla_decode, mla_prefill):
        monkeypatch.setattr(mod, "pallas_interpret_default", lambda: False)  # this process's platform is the CPU
    slots, pages, chunk, per_slot = 96, 8193, 1024, 280
    cfg = gc.GigaChat35Config(num_hidden_layers=5, first_k_dense_replace=1, full_attention_layers=(4,), experts_held=(0, 16),
                              vocab_held=16032)
    on_chip = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    params = jax.tree.map(lambda sh: on_chip(sh, jnp.bfloat16), gc.param_shapes(cfg), is_leaf=lambda sh: isinstance(sh, tuple))
    kind = gc.cache_kind(cfg, jnp.bfloat16)
    pool = jax.eval_shape(lambda: kind.buffers(5, pages, 128)[0])
    pool = on_chip(pool.shape, pool.dtype)
    state = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(lambda: kind.state_buffers(slots)))
    notes = {}
    if which == "decode":
        def step(p, t, pos, table, wm, k, st):
            return gc.forward_with_cache(p, t[:, None], k, st, pos, cfg, table, write_mask=wm, row_valid=wm[:, None], trace_notes=notes)
        args = (params, on_chip((slots,), jnp.int32), on_chip((slots,), jnp.int32), on_chip((slots, per_slot), jnp.int32),
                on_chip((slots,), jnp.bool_), pool, state)
    else:
        def step(p, t, table, slot, pos, rv, k, st):
            return gc.forward_with_cache(p, t, k, st, pos[None], cfg, table[None], slot=slot[None], row_valid=rv, trace_notes=notes)
        args = (params, on_chip((1, chunk), jnp.int32), on_chip((per_slot,), jnp.int32), on_chip((), jnp.int32), on_chip((), jnp.int32),
                on_chip((1, chunk), jnp.bool_), pool, state)
    compiled = jax.jit(step, donate_argnums=(len(args) - 2, len(args) - 1)).lower(*args).compile()
    found = chip_smoke.mosaic_kernels(compiled.as_text())
    if which == "decode":
        assert found == {"gdn_decode": 4, "mla_decode_paged": 1, "moe_grouped_matmul": 8}
        assert notes["gdn_decode_kernel"] is True and notes["mla_decode_kernel"] is True and notes["moe_grouped_kernel"] == "768"
    else:
        assert found == {"mla_prefill": 1, "moe_grouped_matmul": 8}
        assert notes["mla_prefill_kernel"] is True and notes["moe_grouped_kernel"] == "8192" and notes["gdn_prefill_form"].startswith("chunked")
    assert notes["moe_grouped_fallback"] == ""
    m = compiled.memory_analysis()
    cache_bytes = int(np.prod(pool.shape)) * 2 + sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert cache_bytes > 2.8e9 and m.alias_size_in_bytes >= cache_bytes   # the latent pool and both state leaves come back in place
    assert m.temp_size_in_bytes < int(np.prod(pool.shape)) * 2            # and no temporary is as large as the latent pool (1.2 GB)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_laguna_steps_compile_for_v5e_with_both_page_groups_updated_in_place(which, v5e_chip, monkeypatch):
    """The cell's twelve layers of Laguna-S-2.1 at the published widths on
    its two page groups (24 slots of 168 pages: 2,305 pages of 3 full
    layers, a ring of 5 pages a slot of 9 window layers), by the chip's
    compiler without the chip: a decode step holds ``flash_decode_paged``
    once a full layer (6 query heads a KV head), ``swa_decode_paged`` once
    a window layer (9 a KV head: Mosaic takes the 72-row tile as it is,
    neither padded nor re-laid) and the experts' kernel twice a sparse
    layer; a chunk of 1,024 holds the experts' kernel; neither leaves a
    copy of a group in the program."""
    from deepspeed_tpu.models import laguna as lg
    from deepspeed_tpu.ops.kernels import flash_chunk, flash_decode, grouped_matmul

    monkeypatch.setenv("DS_KERNELS", "1")
    for mod in (flash_chunk, flash_decode, grouped_matmul):
        monkeypatch.setattr(mod, "pallas_interpret_default", lambda: False)  # this process's platform is the CPU
    slots, pages, chunk, per_slot = 24, 2305, 1024, 168
    cfg = lg.LagunaConfig.from_hf({}, num_hidden_layers=12, experts_held=(0, 32), vocab_held=12544)
    on_chip = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    params = jax.tree.map(lambda sh: on_chip(sh, jnp.bfloat16), lg.param_shapes(cfg), is_leaf=lambda sh: isinstance(sh, tuple))
    kind = lg.cache_kind(cfg, jnp.bfloat16)
    k, v = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(lambda: kind.buffers(12, pages, 128)))
    state = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(lambda: kind.state_buffers(slots, 128, chunk)))
    assert k.shape == (3, pages, 8, 128, 128) and state["wk"].shape == (9, 1 + slots * 5, 8, 128, 128)  # 640 positions a slot, whatever max_len
    notes = {}
    if which == "decode":
        def step(p, t, pos, table, wm, k, v, st):
            return lg.forward_with_cache(p, t[:, None], k, v, st, pos, cfg, table, write_mask=wm, row_valid=wm[:, None], trace_notes=notes)
        args = (params, on_chip((slots,), jnp.int32), on_chip((slots,), jnp.int32), on_chip((slots, per_slot), jnp.int32),
                on_chip((slots,), jnp.bool_), k, v, state)
    else:
        def step(p, t, table, slot, pos, rv, k, v, st):
            return lg.forward_with_cache(p, t, k, v, st, pos[None], cfg, table[None], slot=slot[None], row_valid=rv, trace_notes=notes)
        args = (params, on_chip((1, chunk), jnp.int32), on_chip((per_slot,), jnp.int32), on_chip((), jnp.int32), on_chip((), jnp.int32),
                on_chip((1, chunk), jnp.bool_), k, v, state)
    compiled = jax.jit(step, donate_argnums=(len(args) - 3, len(args) - 2, len(args) - 1)).lower(*args).compile()
    found = chip_smoke.mosaic_kernels(compiled.as_text())
    if which == "decode":
        assert found == {"flash_decode_paged": 3, "swa_decode_paged": 9, "moe_grouped_matmul": 22}
        assert notes["swa_decode_form"].startswith("swa_decode_paged") and "9 query heads a KV head" in notes["swa_decode_form"]
        assert notes["paged_decode_walk"] == "work list, 8 heads x 2 pages" and notes["moe_grouped_kernel"] == "240"
    else:
        assert found == {"flash_chunk_paged": 3, "moe_grouped_matmul": 22} and notes["moe_grouped_kernel"] == "10240"
        assert notes["swa_chunk_form"].startswith("banded jnp") and notes["gqa_prefill_form"].startswith("flash_chunk_paged")
        assert notes["chunk_attention_kernel"] is True and notes["chunk_attention_fallback"] == ""
    assert notes["moe_grouped_fallback"] == "" and notes["swa_ring_positions"] == 640
    m = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(a.shape)) * 2 for a in (k, v, *jax.tree.leaves(state)))
    assert 4.1e9 < cache_bytes < 4.3e9 and m.alias_size_in_bytes >= cache_bytes  # both groups come back in place
    assert m.temp_size_in_bytes < int(np.prod(state["wk"].shape)) * 2 * 2         # and no temporary is as large as the window group (0.57 GB)
    assert m.argument_size_in_bytes + m.temp_size_in_bytes < 14.0e9               # weights 8.65 GB + caches 4.2 GB + the step's own


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_keye_steps_compile_for_v5e_with_all_three_leaves_updated_in_place(which, v5e_chip, monkeypatch):
    """Two layers of Keye-VL-2.0's language model at the published widths
    on the cell's three-leaf cache (16 slots of 264 pages, 3,329 pages: K,
    V and an indexer key a position), by the chip's compiler without the
    chip: a decode step holds ``dsa_index_scores_paged`` and
    ``dsa_sparse_decode`` once a layer (reading the stacked leaves through
    a merged leading dim and an offset page table) and the experts' kernel
    at 2048 / 768 for its 16 x 8 assignment rows; a chunk of 2,048 holds
    the experts' kernel; neither leaves a copy of a leaf."""
    from deepspeed_tpu.models import keye
    from deepspeed_tpu.ops.kernels import flash_chunk, flash_decode, grouped_matmul, sparse_decode

    monkeypatch.setenv("DS_KERNELS", "1")
    for mod in (flash_chunk, flash_decode, sparse_decode, grouped_matmul):
        monkeypatch.setattr(mod, "pallas_interpret_default", lambda: False)  # this process's platform is the CPU
    layers, slots, pages, per_slot, chunk = 2, 16, 3329, 264, 2048
    cfg = keye.KeyeConfig(num_hidden_layers=layers, experts_held=(0, 16), vocab_held=18992)
    on_chip = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    params = jax.tree.map(lambda sh: on_chip(sh, jnp.bfloat16), keye.param_shapes(cfg), is_leaf=lambda sh: isinstance(sh, tuple))
    kind = keye.cache_kind(cfg, jnp.bfloat16)
    k, v = jax.tree.map(lambda a: on_chip(a.shape, a.dtype), jax.eval_shape(lambda: kind.buffers(layers, pages, 128)))
    notes = {}
    if which == "decode":
        def step(p, t, pos, table, wm, k, v):
            return keye.forward_with_cache(p, t[:, None], k, v, pos, cfg, table, write_mask=wm, row_valid=wm[:, None], trace_notes=notes)
        args = (params, on_chip((slots,), jnp.int32), on_chip((slots,), jnp.int32), on_chip((slots, per_slot), jnp.int32),
                on_chip((slots,), jnp.bool_), k, v)
    else:
        def step(p, t, table, pos, rv, k, v):
            return keye.forward_with_cache(p, t, k, v, pos[None], cfg, table[None], row_valid=rv, trace_notes=notes)
        args = (params, on_chip((1, chunk), jnp.int32), on_chip((per_slot,), jnp.int32), on_chip((), jnp.int32),
                on_chip((1, chunk), jnp.bool_), k, v)
    compiled = jax.jit(step, donate_argnums=(len(args) - 2, len(args) - 1)).lower(*args).compile()
    found = chip_smoke.mosaic_kernels(compiled.as_text())
    buckets = -(-per_slot * 128 // 4096)  # the selection's threshold kernel stands once a bucket of context a layer (one is taken)
    assert found.pop("dsa_select_threshold") == layers * buckets
    if which == "decode":
        assert found == {"dsa_index_scores_paged": layers, "dsa_sparse_decode": layers, "moe_grouped_matmul": 2 * layers}
        assert notes["dsa_decode_kernel"].startswith("dsa_sparse_decode") and notes["dsa_index_form"].startswith("dsa_index_scores_paged")
        assert notes["moe_grouped_kernel"] == "128" and "dsa_select_threshold" in notes["dsa_select_form"]
    else:
        assert found == {"flash_chunk_paged": layers, "moe_grouped_matmul": 2 * layers} and notes["moe_grouped_kernel"] == "16384"
        assert notes["dsa_prefill_form"].startswith("paged_chunk_attention") and "dsa_select_threshold" in notes["dsa_prefill_select_form"]
        assert notes["chunk_attention_kernel"] is True and "flash_chunk_paged" in notes["dsa_prefill_form"]
    assert notes["moe_grouped_fallback"] == ""
    m = compiled.memory_analysis()
    cache_bytes = sum(int(np.prod(a.shape)) * 2 for a in jax.tree.leaves((k, v)))
    assert m.alias_size_in_bytes >= cache_bytes                         # all three leaves come back in place
    # no temporary is a leaf of pages: a decode step's stay under half of one; a chunk's are its (2,048 x 33,792) scores,
    # their ordered bits and the masks (~0.9 GB whatever the number of layers; a leaf of the cell's 8 layers is 3.5 GB)
    assert m.temp_size_in_bytes < (int(np.prod(v.shape)) * 2 // 2 if which == "decode" else 1.2e9)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_gpt2_xl_paged_steps_compile_for_v5e_with_the_pool_read_and_written_where_it_lies(which, v5e_chip, monkeypatch):
    """Four layers of GPT-2 XL at the published widths on the GPT-2
    serve cells' paged pool (16 slots, 80 pages of 128 x 64: a head of
    half a lane row, which the TPU stores with ``page_len`` in the
    lanes), by the chip's compiler without the chip: a decode step holds
    ``flash_decode_paged`` and the K and the V pool's ``paged_kv_write``
    in its layer loop, each handed ``(d, page_len)`` tiles of the pool's
    own bytes — no slice update of the pool is left in it (1,536 of them
    were half the step: PERF.md, PR 49), and the writes' plan is built
    outside the loop; both steps hand the pool back aliased;
    and neither holds an array the size of the pool or of a layer of it
    other than the pool itself and a chunk's in-place slice updates (the
    scatter, the scan over the pool and the relayout in front of the
    kernel made nine such operations, 3 s of a traced 5: PERF.md, PR 40).
    The carry is pinned to the layout the pool has on the chip, as the
    engine pins it from ``pool.k.format``."""
    from jax.experimental.layout import Layout

    from deepspeed_tpu.ops.kernels import flash_decode, paged_kv_write
    from deepspeed_tpu.ops.transformer import inference as inf

    monkeypatch.setenv("DS_KERNELS", "1")
    for kernel in (flash_decode, paged_kv_write):
        monkeypatch.setattr(kernel, "pallas_interpret_default", lambda: False)  # this process's platform is the CPU
    lanes_hold_page_len = Layout(major_to_minor=(0, 1, 2, 4, 3))  # what a v5e gives bf16[.., 128, 64]: the entry layout below
    mcfg = gpt2.PRESETS["gpt2-xl"]
    L, slots, pages, page_len, chunk = 4, 16, 80, 128, 64
    H, d, E, V = mcfg.n_head, mcfg.head_dim, mcfg.n_embd, mcfg.vocab_size
    on_chip = lambda shape, dt=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dt, sharding=v5e_chip)  # noqa: E731
    block = {"ln1_g": (E,), "ln1_b": (E,), "qkv_w": (E, 3 * E), "qkv_b": (3 * E,), "proj_w": (E, E), "proj_b": (E,),
             "ln2_g": (E,), "ln2_b": (E,), "fc_w": (E, 4 * E), "fc_b": (4 * E,), "fc_proj_w": (4 * E, E), "fc_proj_b": (E,)}
    params = {"wte": on_chip((V, E)), "wpe": on_chip((mcfg.n_positions, E)), "lnf_g": on_chip((E,)), "lnf_b": on_chip((E,)),
              "blocks": {name: on_chip((L,) + shape) for name, shape in block.items()}}
    icfg = inf.DeepSpeedInferenceConfig(hidden_size=E, heads=H, dtype=jnp.bfloat16, max_out_tokens=mcfg.n_positions)
    pool = on_chip((L, pages, H, page_len, d))
    P = mcfg.n_positions // page_len
    if which == "decode":
        def step(p, t, pos, table, wm, k, v):
            return inf.forward_with_cache(p, t[:, None], k, v, pos, icfg, page_table=table, write_mask=wm,
                                          pool_layout=lanes_hold_page_len)
        args = (params, on_chip((slots,), jnp.int32), on_chip((slots,), jnp.int32), on_chip((slots, P), jnp.int32),
                on_chip((slots,), jnp.bool_), pool, pool)
    else:
        def step(p, t, table, pos, src, dst, k, v):
            k, v = inf.page_copy(k, src, dst), inf.page_copy(v, src, dst)  # the pending copy-on-write, as the engine has it
            return inf.forward_with_cache(p, t, k, v, pos[None], icfg, page_table=table[None], pool_layout=lanes_hold_page_len)
        args = (params, on_chip((1, chunk), jnp.int32), on_chip((P,), jnp.int32), on_chip((), jnp.int32),
                on_chip((), jnp.int32), on_chip((), jnp.int32), pool, pool)
    compiled = jax.jit(step, donate_argnums=(len(args) - 2, len(args) - 1)).lower(*args).compile()
    hlo = compiled.as_text()
    assert chip_smoke.mosaic_kernels(hlo) == ({"flash_decode_paged": 1, "paged_kv_write": 2} if which == "decode" else {})
    assert "bf16[%d,%d,%d,%d,%d]{3,4,2,1,0" % (L, pages, H, page_len, d) in hlo.split("\n", 1)[0]  # the entry layout is the pinned one
    if which == "decode":
        assert chip_smoke.first_output_dims(hlo, "flash_decode_paged") == (slots, H, 1, d)
    pool_elems = L * pages * H * page_len * d
    # a chunk's slices, in place; a decode step's layer loop and nothing else, its sorts (the work list's, the write plan's) outside it
    assert set(chip_smoke.leaf_sized_moves(hlo, pool_elems)) <= ({"while"} if which == "decode" else {"dynamic-update-slice", "fusion", "while"})
    if which == "decode":
        assert "dynamic-update-slice(" not in hlo and all("while/body" not in line for line in hlo.split("\n") if " sort(" in line)
    assert chip_smoke.leaf_sized_moves(hlo, pool_elems // L) == []                                 # nothing a layer's size
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 2 * pool_elems * 2
    # beside the pool: the tied head's weights transposed (XLA's choice, 161 MB) and a step's activations
    assert m.temp_size_in_bytes < 2 * (pool_elems // L) * 2 + V * E * 2 + (32 << 20)


# ---------------------------------------------------------------------------
# (b) the smoke's control flow, and its refusal to run off a TPU
# ---------------------------------------------------------------------------

TOY = chip_smoke.Smoke(
    train_cfg=dataclasses.replace(
        gpt2.GPT2_TINY, remat=True, xent_chunk_size=64,
        remat_save_names=FULL.train_cfg.remat_save_names,
    ),
    seq=128, micro=2, global_batch=8, steps=2,
    serve_model="tiny", slots=2, max_len=64, page_len=16, prefill_chunk=16,
    prompt_lens=(4, 40), new_tokens=4, requests=3, update_leaf=(2, 128, 512), mosaic=False,
)


def test_chip_smoke_phases_at_toy_size_on_the_cpu_mesh():
    chip_smoke.run(TOY, jax.devices()[:4])


_HLO = """
  %flash_attention_fwd.1 = (bf16[64,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[64,8,1024]{2,1,0}) custom-call(%a, %b, %c), custom_call_target="tpu_custom_call", operand_layout_constraints={}, backend_config={"custom_call_config":{"body":"TUzv"}}
  %fused_adam.17 = (f32[180,256]{1,0}) custom-call(%s, %p), custom_call_target="tpu_custom_call", backend_config={}
  %fused_adam.18 = (f32[180,256]{1,0}) custom-call(%s, %p), custom_call_target="tpu_custom_call", backend_config={}
  %custom-call.3 = bf16[4,16]{1,0} custom-call(%x), custom_call_target="ConcatBitcast"
  %all-gather-start.2 = (bf16[4,1024,1024]{2,1,0}, bf16[16,1024,1024]{2,1,0}) all-gather-start(%y), dimensions={0}
  %all-gather.5 = s32[16,1024,1]{1,2,0} all-gather(%ids), dimensions={0}
  %p.1 = f32[36,1280,5120]{2,1,0:T(8,128)} parameter(3), metadata={op_name="p"}
  %reshape.98.remat2 = f32[921600,256]{1,0:T(8,128)} reshape(%p.1), metadata={op_name="jit(f)/reshape"}
  %fused_adam.14 = (f32[921600,256]{1,0:T(8,128)}, f32[921600,256]{1,0:T(8,128)}) custom-call(%s, %reshape.98.remat2), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4]{0}, f32[921600,256]{1,0}}
  %pallas_call.58 = f32[921600,256]{1,0:T(8,128)} get-tuple-element(%fused_adam.14), index=1
  %bitcast.9 = f32[1,46080,5120]{2,1,0:T(8,128)} bitcast(%pallas_call.58)
  ROOT %copy.115 = f32[36,1280,5120]{2,1,0:T(8,128)S(1)} copy(%bitcast.9), backend_config={"flag_configs":[]}
"""


def test_smoke_reads_kernels_and_gathers_from_optimized_hlo():
    assert chip_smoke.mosaic_kernels(_HLO) == {"flash_attention_fwd": 1, "fused_adam": 3}
    # a pass over a leaf beside the kernel that was meant to be the only one
    assert chip_smoke.leaf_sized_moves(_HLO, 36 * 1280 * 5120) == ["reshape", "copy"]
    assert chip_smoke.leaf_sized_moves(_HLO, 180 * 256) == []
    assert chip_smoke.first_output_dims(_HLO, "flash_attention_fwd") == (64, 1024, 64)
    assert chip_smoke.gathered_float_shapes(_HLO) == [(4, 1024, 1024), (16, 1024, 1024)]
    chip_smoke.expect_kernels(chip_smoke.mosaic_kernels(_HLO), ["fused_adam", "flash_attention_fwd"], "t")
    # a kernel that quietly dispatched to its lax path is a failure, not a pass
    with pytest.raises(AssertionError, match="flash_attention_bwd"):
        chip_smoke.expect_kernels(
            chip_smoke.mosaic_kernels(_HLO), ["fused_adam", "flash_attention_fwd", "flash_attention_bwd"], "t")


def test_chip_smoke_refuses_to_run_off_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO,
    )
    assert res.returncode != 0
    assert "platform 'cpu'" in res.stderr
    assert '"ok"' not in res.stdout


# ---------------------------------------------------------------------------
# (c) the compile cache is placed from outside
# ---------------------------------------------------------------------------

def test_cache_env_set_means_no_code_sets_a_directory(monkeypatch, tmp_path):
    from deepspeed_tpu.ops.kernels.autotune import default_cache_path

    monkeypatch.delenv("DS_KERNEL_AUTOTUNE_CACHE", raising=False)
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    # even on a TPU the helper leaves jax.config alone when the env names the place
    monkeypatch.setattr(device, "on_tpu_backend", lambda: True)
    assert device.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    # the autotune cache rides beside the compile cache, never in ~/.cache
    assert default_cache_path() == str(tmp_path / "kernel_autotune.json")


def test_cache_env_unset_is_checkout_dir_on_tpu_and_none_on_cpu(monkeypatch):
    from deepspeed_tpu.ops.kernels.autotune import default_cache_path

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("DS_KERNEL_AUTOTUNE_CACHE", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert device.setup_compile_cache() is None  # CPU: no persistent cache
    assert jax.config.jax_compilation_cache_dir == before
    fixed = os.path.join(REPO, ".jax_cache")
    assert default_cache_path() == os.path.join(fixed, "kernel_autotune.json")
    monkeypatch.setattr(device, "on_tpu_backend", lambda: True)
    try:
        assert device.setup_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------------------
# the wrapper compiled kernels run under on a multi-device mesh
# ---------------------------------------------------------------------------

def test_flash_kernel_over_mesh_matches_single_device():
    """Batch over the dp grid, heads over tp, a broadcast bias dim left
    whole, the indivisible batch left whole — same numbers as one
    device (interpret mode stands in for the compiled kernel)."""
    from deepspeed_tpu.ops.attention import flash_attention as fa
    from deepspeed_tpu.ops.kernels.sharded import dim_spec, free_mesh_axes
    from deepspeed_tpu.parallel.sequence import ambient_mesh
    from deepspeed_tpu.sharding.mesh import MESH_AXES

    shape = {"data": 2, "fsdp": 2, "model": 2}
    mesh = Mesh(np.asarray(jax.devices()).reshape([shape.get(a, 1) for a in MESH_AXES]), MESH_AXES)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((4, 4, 256, 16)), jnp.float32) for _ in range(3))
    bias = jnp.asarray(rng.standard_normal((4, 1, 1, 256)), jnp.float32)

    def kernel(q, k, v, bias, drop_seed):
        return fa._flash_attention(q, k, v, bias, None, drop_seed, True, 0.25, 128, 128, True, 1.0, None, None)

    want = kernel(q, k, v, bias, None)
    with ambient_mesh(mesh):
        sizes = free_mesh_axes()
        assert {a: n for a, n in sizes.items() if n > 1} == shape
        got = jax.jit(lambda *a: fa._over_mesh(kernel, *a, None, sizes))(q, k, v, bias)
        grads = jax.jit(jax.grad(lambda q: fa._over_mesh(kernel, q, k, v, bias, None, sizes).sum()))(q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(
        np.asarray(grads), np.asarray(jax.grad(lambda q: kernel(q, k, v, bias, None).sum())(q)),
        atol=2e-4, rtol=2e-4,
    )
    # a batch of 3 does not split over data x fsdp = 4: it stays whole
    assert dim_spec((3, 4, 256, 16), {0: ("data", "fsdp"), 1: "model"}, sizes) == \
        jax.sharding.PartitionSpec(None, "model", None, None)
    assert free_mesh_axes() == {}  # no ambient mesh: one device's worth
