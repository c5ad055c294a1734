"""The plain reference, and the control that has to fail.

At a size a test run can hold (the chip's readings at the cells' own
sizes are in PERF.md §2): the program's model code in bf16 stays close
to the float32 reference; the same reference with int8 operands — the
control, in the program's place — is several times farther, on the
forward (per-position log-probabilities), on the backward (the sign of
the update against the reference's gradient) and on served tokens (the
reference's logit gap of the emitted token).  With an outlier channel in
the seeded weights the program's own int8 KV pool fails too."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


from benchmark import checks, weights
from benchmark.reference_gpt2 import BLOCK_MATRICES, Reference

DIMS = {"vocab_size": 512, "n_positions": 128, "n_embd": 128, "n_layer": 3, "n_head": 4, "layer_norm_epsilon": 1e-5}
SEEDS = [3, 2 ** 31 + 9, 4_000_000_123]
LR = 1e-4


def _gpt2_config(**options):
    from deepspeed_tpu.models import gpt2

    return gpt2.GPT2Config(**{k: DIMS[k] for k in ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head")}, **options)


def _program_loss(params, batch, rng):
    """The loss function the engine would be handed (remat, chunked
    cross-entropy), on a bf16 copy of the weights."""
    from deepspeed_tpu.models import gpt2

    model_fn, _, _ = gpt2.make_model(_gpt2_config(remat=True, xent_chunk_size=64))
    return model_fn(jax.tree.map(lambda a: a.astype(jnp.bfloat16), params), batch, rng)


def _rms(a, b):
    return float(np.sqrt(np.mean((np.asarray(a) - np.asarray(b)) ** 2)))


def test_stacked_weights_are_the_per_layer_weights():
    p = weights.stacked_params(SEEDS[1], DIMS)
    ref = Reference(DIMS, SEEDS[1])
    for l in range(DIMS["n_layer"]):
        one = ref.layer_init(l)
        for name, leaf in one.items():
            # one float32 ulp apart at most: the scaling by the init std fuses differently under vmap
            np.testing.assert_allclose(np.asarray(p["blocks"][name][l]), np.asarray(leaf), rtol=1e-6, atol=0)
    assert p["wte"].shape == (512, 128) and float(jnp.std(p["blocks"]["qkv_w"])) == pytest.approx(0.02, rel=0.05)
    other = weights.stacked_params(SEEDS[2], DIMS)
    assert not np.array_equal(np.asarray(other["wte"]), np.asarray(p["wte"]))


def test_reference_matches_the_programs_model_in_float32():
    """Same equations: the repo's ``gpt2.apply`` at float32 and the
    reference agree to float32 rounding."""
    from deepspeed_tpu.models import gpt2

    cfg = gpt2.GPT2Config(**{k: DIMS[k] for k in ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head")},
                          remat=False, use_flash_attention=False)
    tokens = checks.check_sequences(SEEDS[0], DIMS["vocab_size"], 128)
    with jax.default_matmul_precision("highest"):
        prog = gpt2.apply(weights.stacked_params(SEEDS[0], DIMS), jnp.asarray(tokens), cfg)
    np.testing.assert_allclose(np.asarray(prog), np.asarray(Reference(DIMS, SEEDS[0]).logits(tokens)), atol=2e-4)


@pytest.mark.parametrize("seed", SEEDS)
def test_forward_control_fails_where_the_program_passes(seed):
    tokens = checks.check_sequences(seed, DIMS["vocab_size"], 128)
    ref = np.asarray(Reference(DIMS, seed).nll(tokens))
    prog = checks.program_nll(_program_loss, weights.stacked_params(seed, DIMS), tokens)
    ctl = np.asarray(Reference(DIMS, seed, precision="int8").nll(tokens))
    # read at this size: program 0.0015-0.0017, control 0.0091-0.0100 (three seeds)
    assert _rms(prog, ref) < 0.003 < 0.006 < _rms(ctl, ref)
    assert _rms(ctl, ref) > 3 * _rms(prog, ref)


def test_loss_path_reading_is_each_positions_own_term():
    """``program_nll`` reads per-position terms off a scalar masked-mean
    loss through its gradient in the mask: on the program's model code
    they are the log-softmax terms themselves."""
    from deepspeed_tpu.models import gpt2

    tokens = checks.check_sequences(SEEDS[1], DIMS["vocab_size"], 128)
    params = weights.stacked_params(SEEDS[1], DIMS)
    model_fn, _, _ = gpt2.make_model(_gpt2_config(remat=True, xent_chunk_size=64))
    got = checks.program_nll(model_fn, params, tokens)
    logits = gpt2.apply(params, jnp.asarray(tokens), _gpt2_config(remat=False)).astype(jnp.float32)
    want = -jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1], -1), jnp.asarray(tokens)[:, 1:, None], -1)[..., 0]
    assert got.shape == (2, 127)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-5)


def _disagreement(seed, precision):
    """What ``update_disagreement`` reads for a first Adam step taken
    against the gradient signs of the reference at ``precision``."""
    tokens = checks.check_sequences(seed, DIMS["vocab_size"], 128)
    ref = Reference(DIMS, seed)
    _, sweep = Reference(DIMS, seed, precision=precision).nll_and_block_grads(tokens)
    stepped = {l: g for l, g in sweep}
    after = {n: np.stack([np.asarray(ref.layer_init(l)[n] - LR * jnp.sign(stepped[l][n])) for l in range(DIMS["n_layer"])])
             for n in BLOCK_MATRICES}
    _, ref_sweep = ref.nll_and_block_grads(tokens)
    return checks.update_disagreement(ref, ref_sweep, after, LR), stepped


@pytest.mark.parametrize("seed", SEEDS[:2])
def test_backward_control_fails_where_a_sound_update_passes(seed):
    """A sound first Adam step moves every weight by lr against the sign
    of its (bf16-computed) gradient; the control's gradient — int8
    operands and int8 cotangents in every matmul of the backward — gets
    markedly more of the signs wrong.  Its gradients are real numbers,
    not the zeros that differentiating through a rounding would give."""
    sound, _ = _disagreement(seed, "bfloat16")
    control, grads = _disagreement(seed, "int8")
    assert _disagreement(seed, "float32")[0] == pytest.approx(0.0, abs=1e-5)
    # read at this size: bf16 1.2e-5-1.3e-5, int8 0.027-0.034 (two seeds)
    assert sound < 1e-4 < 0.01 < control < 0.2
    zeros = np.mean([float(jnp.mean(g[n] == 0)) for g in grads.values() for n in BLOCK_MATRICES])
    assert zeros < 0.1  # 0.7 % at this size; differentiating through jnp.round gave 99.97 %


def test_float32_reference_gradients_are_plain_autodiff():
    """The rounded-cotangent rule of ``_dot`` is the ordinary derivative
    when nothing is rounded."""
    from benchmark import reference_gpt2 as rg

    a, b = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8)), jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    f = lambda x, y: jnp.sum(jnp.tanh(rg._dot("btd,de->bte", x, y, "float32")))
    g = lambda x, y: jnp.sum(jnp.tanh(jnp.einsum("btd,de->bte", x, y, precision="highest")))
    for got, want in zip(jax.grad(f, (0, 1))(a, b), jax.grad(g, (0, 1))(a, b)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)
    da = jax.grad(lambda x: jnp.sum(rg._dot("btd,de->bte", x, b, "int8")))(a)
    assert float(jnp.mean(da == 0)) < 0.05 and float(jnp.abs(da).mean()) > 0.1


def test_update_check_reads_flat_padded_leaves():
    leaf = jnp.arange(24.0).reshape(2, 3, 4)
    flat = jnp.pad(leaf.reshape(-1), (0, 8))
    np.testing.assert_array_equal(np.asarray(checks.natural(flat, (2, 3, 4))), np.asarray(leaf))
    assert checks.natural(leaf, (2, 3, 4)) is leaf


def test_token_gap_is_zero_for_the_references_own_choice_and_positive_for_another():
    seed = SEEDS[0]
    ref = Reference(DIMS, seed)
    prompt = checks.check_sequences(seed, DIMS["vocab_size"], 20, n=1)[0]
    padded = np.zeros((1, 128), np.int32)
    padded[0, :20] = prompt
    best = int(jnp.argmax(ref.logits(padded)[0, 19]))
    assert checks.token_gaps(ref, [{"prompt": prompt, "generated": [best]}], 128)["token_gap_max"] == 0.0
    worse = checks.token_gaps(ref, [{"prompt": prompt, "generated": [(best + 1) % 512]}], 128)
    assert worse["token_gap_mean"] > 0 and worse["tokens"] == 1


def test_unknown_precision_is_refused():
    with pytest.raises(ValueError):
        Reference(DIMS, 1, precision="fp4").nll(checks.check_sequences(1, 512, 128))


OUTLIER = 96.0


def test_outlier_channel_changes_nothing_in_exact_arithmetic():
    """``kv_outlier``: a constant on every key shifts a query's scores
    alike and the constant value channel is read by no one — the block
    computes what it computes with those biases at 0."""
    from benchmark.reference_gpt2 import block

    dims = {**DIMS, "kv_outlier": OUTLIER}
    lp = weights.layer_params(weights.seed_key(SEEDS[0]), 1, dims)
    d, first = DIMS["n_embd"], np.arange(DIMS["n_head"]) * (DIMS["n_embd"] // DIMS["n_head"])
    assert np.asarray(lp["qkv_b"])[d + first].tolist() == [OUTLIER] * 4 == np.asarray(lp["qkv_b"])[2 * d + first].tolist()
    assert float(jnp.abs(lp["qkv_b"]).sum()) == 8 * OUTLIER and float(jnp.abs(lp["proj_w"][first]).sum()) == 0.0
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, d))
    with_outlier = block(lp, x, DIMS["n_head"], 1e-5, "float32")
    without = block({**lp, "qkv_b": jnp.zeros_like(lp["qkv_b"])}, x, DIMS["n_head"], 1e-5, "float32")
    np.testing.assert_allclose(np.asarray(with_outlier), np.asarray(without), atol=2e-5)
    # ... and the program lays its heads out as the reference does
    from deepspeed_tpu.models import gpt2

    tokens = checks.check_sequences(SEEDS[0], DIMS["vocab_size"], 64)
    with jax.default_matmul_precision("highest"):
        prog = gpt2.apply(weights.stacked_params(SEEDS[0], dims), jnp.asarray(tokens), _gpt2_config(remat=False, use_flash_attention=False))
    np.testing.assert_allclose(np.asarray(prog), np.asarray(Reference(dims, SEEDS[0]).logits(tokens)), atol=5e-4)


def test_int8_kv_pool_fails_on_served_tokens_where_the_bf16_pool_passes():
    """The program's own lower-precision path as the control: the serving
    engine on seeded weights with the outlier channel, its pool in the
    model's bf16 and in int8.  Read at this size (two seeds, 176 tokens
    each): bf16 3.7e-5 and 0, int8 6.6e-4 and 0 — the chip's readings at
    the cell's size are in PERF.md section 2."""
    from benchmark import build, traffic

    cfg = {"model": {"vocab_size": 16384, "n_positions": 256, "n_embd": 256, "n_layer": 4, "n_head": 4},
           "weights": {"kv_outlier": OUTLIER},
           "serving": {"num_slots": 4, "max_len": 256, "kv_cache_dtype": "model", "prefill_chunk": 32, "max_queue": 100,
                       "max_new_tokens": 64, "degrade_max_new_tokens": 0,
                       "kvcache": {"enabled": True, "page_len": 32, "num_pages": 33}}}
    mix = {"pool": 8, "prompt": {"dist": "uniform", "min": 32, "max": 160},
           "answer": {"dist": "uniform", "min": 24, "max": 64}, "max_total": 256}
    seed = 5
    ref = build.reference(cfg, seed)
    gap = {}
    for kv in ("model", "int8"):
        srv = build.serving_engine(cfg, seed, jax.devices()[:1], kv_cache_dtype=kv)
        stream = traffic.request_stream(mix, seed, 16384)
        reqs = [next(stream) for _ in range(4)]
        ids = [srv.submit(r["prompt"], max_new_tokens=r["max_new"]) for r in reqs]
        done = srv.drain()
        gap[kv] = checks.token_gaps(ref, [{"prompt": r["prompt"], "generated": list(done[i].generated)}
                                          for r, i in zip(reqs, ids)], 256)["token_gap_mean"]
    assert gap["model"] < 1e-4 < 3e-4 < gap["int8"]
