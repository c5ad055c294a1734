"""The family seam and the one ladder (docs/serving.md §Model families): a config class names its family in
``models/__init__.py`` and nowhere else, the family's module names its partition-rule table, and every causal family —
GPT-2 among them — is served through ``cache_kind`` / ``serving_forward`` by the one body a program that
``serving/engine.py`` holds."""
import ast
import inspect

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from deepspeed_tpu import models
from deepspeed_tpu.models import bert, deepseek_v2, gigachat35, gpt2, keye, laguna, mimo_v2, solar_open2, zaya
from deepspeed_tpu.sharding.rules import rules_for_config

TINY = {"GPT2Config": gpt2.GPT2_TINY, "BertConfig": bert.BERT_TINY, "DeepseekV2Config": deepseek_v2.DEEPSEEK_V2_TINY,
        "SolarOpen2Config": solar_open2.SOLAR_OPEN2_TINY, "ZayaConfig": zaya.ZAYA_TINY, "KeyeConfig": keye.KEYE_TINY,
        "GigaChat35Config": gigachat35.GIGACHAT35_TINY, "LagunaConfig": laguna.LAGUNA_TINY, "MiMoV2Config": mimo_v2.MIMO_V2_TINY}


def test_every_built_in_config_class_has_a_tiny_configuration_here():
    assert set(TINY) == set(models._CONFIG_FAMILIES)


@pytest.mark.parametrize("klass", sorted(TINY))
def test_a_config_class_resolves_to_the_table_its_family_names_and_to_berts_only_if_it_is_bert(klass):
    cfg = TINY[klass]
    family = models.family_of(cfg)
    assert family.__name__ == "deepspeed_tpu.models." + models._CONFIG_FAMILIES[klass]
    rules = rules_for_config(cfg)
    assert rules.name == family.PARTITION_RULES
    assert (rules.name == "bert") == (family is bert)


@pytest.mark.parametrize("family", [keye, gigachat35, laguna, mimo_v2], ids=["keye", "gigachat35", "laguna", "mimo_v2"])
def test_the_two_newest_families_shard_held_experts_over_expert_and_embedding_and_head_over_the_vocabulary(family):
    cfg = TINY[[k for k, v in models._CONFIG_FAMILIES.items() if family.__name__.endswith("." + v)][0]]
    rules, shapes = rules_for_config(cfg), family.param_shapes(cfg)
    moe = next(i for i, layer in enumerate(shapes["layers"]) if "experts_gu" in layer)
    for name in ("experts_gu", "experts_down"):
        assert rules.spec(f"layers/{moe}/{name}", shapes["layers"][moe][name]) == P("expert", None, None)
    for name in ("embed", "head"):  # whichever dim of the leaf is the vocabulary
        vocab = shapes[name].index(cfg.vocab_rows)
        assert rules.spec(name, shapes[name]) == P(*("model" if d == vocab else None for d in range(2)))
    assert rules.spec(f"layers/{moe}/router", shapes["layers"][moe]["router"]) in (None, P())


def test_a_built_in_family_without_a_table_is_an_error_and_a_config_outside_the_classes_too(monkeypatch):
    monkeypatch.delattr(zaya, "PARTITION_RULES")
    with pytest.raises(ValueError, match="names no partition-rule table"):
        rules_for_config(zaya.ZAYA_TINY)
    with pytest.raises(ValueError, match="no built-in partition rules"):
        rules_for_config(object())


def test_the_inference_engine_falls_back_to_berts_table_only_outside_the_built_in_classes():
    from deepspeed_tpu.inference.engine import InferenceEngine

    src = inspect.getsource(InferenceEngine.__init__)
    at = src.index("rules_for_config(self.model_config)")
    assert "except ValueError" not in src[at - 400:at + 400]
    assert 'if family is not None else rules_for_family("bert")' in src[at:at + 400]


POOLS = {"slot": {}, "paged": {"kvcache": {"enabled": True, "page_len": 16}},
         "paged-int8": {"kvcache": {"enabled": True, "page_len": 16}, "kv_cache_dtype": "int8"}}


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_gpt2_is_served_through_the_seam_on_every_pool_by_one_program_each(pool):
    import deepspeed_tpu
    from deepspeed_tpu.serving import ServingEngine
    from deepspeed_tpu.serving.kvcache import PerHeadKV

    inf = deepspeed_tpu.init_inference(model_config=gpt2.GPT2_TINY, dtype=jnp.float32, max_out_tokens=128, seed=3)
    srv = ServingEngine(inf, config={"num_slots": 2, "max_len": 128, "prefill_chunk": 16, **POOLS[pool]})
    kind = gpt2.cache_kind(gpt2.GPT2_TINY, "int8" if pool == "paged-int8" else jnp.float32)
    assert isinstance(kind, PerHeadKV) and (kind.heads, kind.head_dim) == (4, 16)
    assert callable(srv._family_forward) and callable(srv._family_forward.bind)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, n).astype(np.int32) for n in (21, 5, 33)]  # more requests than slots: a slot is used twice
    ids = [srv.submit(p, max_new_tokens=6) for p in prompts]
    done = srv.drain()
    for i, p in zip(ids, prompts):
        want = np.asarray(inf.generate(p[None], max_new_tokens=6, do_sample=False))[0, len(p):]
        if pool != "paged-int8":  # the quantized cache's tokens are its own; the pools' equality tests hold them
            assert done[i].generated == want.tolist()
        assert len(done[i].generated) == 6
    st = srv.stats()
    assert st["prefill_compiles"] == st["decode_compiles"] == 1
    assert st["kv_dtype"] == ("int8" if pool == "paged-int8" else "float32")
    notes = {"kv_write_form", "prefill_attend_form"}
    assert (notes <= set(st)) == (pool != "slot") and "moe" not in st and "hybrid" not in st


@pytest.mark.parametrize("kind", ["PerHeadKV", "PerHeadKV-int8", "LatentKV", "IndexedKV", "HybridKV"])
def test_every_cache_kind_copies_a_page_of_every_leaf_and_is_the_identity_on_a_page_itself(kind):
    import jax

    from deepspeed_tpu.serving.kvcache import pages

    made = {"PerHeadKV": lambda: pages.PerHeadKV(2, 8, jnp.float32), "PerHeadKV-int8": lambda: pages.PerHeadKV(2, 8, "int8"),
            "LatentKV": lambda: pages.LatentKV(12, jnp.float32), "IndexedKV": lambda: pages.IndexedKV(2, 8, 4, jnp.float32),
            "HybridKV": lambda: pages.HybridKV(2, pages.PerHeadKV(2, 8, jnp.float32), {"s": (1, (3,), jnp.float32)})}[kind]()
    rng = np.random.default_rng(1)
    pool = jax.tree.map(lambda a: jnp.asarray(rng.integers(-9, 9, a.shape), a.dtype), made.buffers(3, 6, 4))
    for tree in pool:
        out = jax.jit(made.copy_page)(tree, jnp.int32(2), jnp.int32(5))
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(b)[:, 5], np.asarray(a)[:, 2])
            np.testing.assert_array_equal(np.delete(np.asarray(b), 5, axis=1), np.delete(np.asarray(a), 5, axis=1))
        same = made.copy_page(tree, jnp.int32(0), jnp.int32(0))
        assert all((np.asarray(a) == np.asarray(b)).all() for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(same)))


def _functions(tree, name):
    return [n for n in ast.walk(tree) if isinstance(n, ast.FunctionDef) and n.name == name]


def test_the_serving_engine_holds_one_body_a_program_and_asks_no_question_about_who_the_model_is():
    from deepspeed_tpu.serving import engine

    src = inspect.getsource(engine)
    tree = ast.parse(src)
    assert len(_functions(tree, "serve_prefill")) == len(_functions(tree, "serve_decode")) == 1
    assert "forward_with_cache" not in src and "_family_forward is" not in src
    for builder in ("_get_prefill", "_get_decode"):
        (fn,) = _functions(tree, builder)
        assert "self._paged" not in ast.get_source_segment(src, fn)


@pytest.mark.parametrize("module", ["deepspeed_tpu.models.gpt2", "deepspeed_tpu.ops.transformer.inference"])
def test_the_seam_below_the_engine_imports_nothing_of_it_but_the_cache_kinds(module):
    tree = ast.parse(inspect.getsource(__import__(module, fromlist=["x"])))
    above = [n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom) and n.module
             and n.module.startswith(("deepspeed_tpu.serving", "deepspeed_tpu.inference"))]
    assert set(above) <= {"deepspeed_tpu.serving.kvcache.pages"}
