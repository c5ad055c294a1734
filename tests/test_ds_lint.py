"""ds_lint (deepspeed_tpu.analysis) tests.

Every shipped rule has at least one failing fixture and one clean
fixture; plus suppression syntax, baseline round-trips, CLI exit codes,
and the self-run gate (the linter must be clean on deepspeed_tpu/ with
the checked-in baseline, in well under the 15s budget).
"""
import functools
import json
import os
import textwrap
import time

import pytest

from deepspeed_tpu.analysis import Severity, all_rules, lint_paths
from deepspeed_tpu.analysis import baseline as baseline_mod
from deepspeed_tpu.analysis.cli import cli_main

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_src(tmp_path, src, rule=None, name="mod.py", **kw):
    p = tmp_path / name
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(src))
    kw.setdefault("use_baseline", False)
    return lint_paths([str(p)], select=[rule] if rule else None, **kw)


def rule_ids(result):
    return [f.rule for f in result.findings]


@functools.lru_cache(maxsize=1)
def _repo_self_run():
    """One full-package lint shared by every test that needs the repo's
    current findings (each full pass costs ~8s of tier-1 time).  The
    time returned is the pass's own CPU time (it is single-threaded): the
    wall clock of a tier-1 run also counts what five other xdist workers
    take from it, and read 16s for the same 8s of work."""
    start = time.process_time()
    res = lint_paths(
        [os.path.join(REPO_ROOT, "deepspeed_tpu")],
        baseline_path=os.path.join(REPO_ROOT, ".ds_lint_baseline.json"),
    )
    return res, time.process_time() - start


# ---------------------------------------------------------------------------
# registry sanity
# ---------------------------------------------------------------------------


def test_rule_catalog_shape():
    rules = all_rules()
    assert len(rules) >= 10
    assert all(r.tier in (Severity.A, Severity.B, Severity.C) for r in rules.values())
    assert all(r.description for r in rules.values())
    # the rules named in the issue all exist
    for rid in (
        "host-sync-in-jit", "print-under-trace", "np-random-under-trace",
        "global-mutation-under-trace", "unhashable-static-arg",
        "donated-buffer-reuse", "float64-promotion", "config-key-drift",
        "bare-jit", "missing-sharding-constraint",
        "non-atomic-checkpoint-write",  # PR 2 resilience tier-B rule
        "unfenced-timing",  # PR 3 overlap tier-C rule
        "unguarded-collective-barrier",  # PR 5 supervision tier-B rule
        "raw-collective-outside-comm-layer",  # PR 6 comm-layer tier-B rule
        "hand-built-partition-spec",  # PR 8 partition-rule-engine tier-B rule
        "raw-metric-emit",  # PR 9 telemetry-plane tier-C rule
        "raw-pallas-call-outside-kernels",  # PR 12 kernel-seam tier-B rule
    ):
        assert rid in rules, rid


# ---------------------------------------------------------------------------
# host-sync-in-jit
# ---------------------------------------------------------------------------


class TestHostSync:
    def test_flags_syncs_in_jitted_function(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax
            import numpy as np

            @jax.jit
            def step(state, g):
                h = np.array(g)
                s = float(h.sum())
                v = state.item()
                jax.device_get(state)
                state.block_until_ready()
                return s
            """,
            "host-sync-in-jit",
        )
        msgs = " ".join(f.message for f in res.findings)
        assert len(res.findings) == 5
        assert all(f.severity == Severity.A for f in res.findings)
        assert "numpy.array" in msgs and "device_get" in msgs and "block_until_ready" in msgs

    def test_flags_through_jit_call_and_scan_body(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def body(carry, x):
                return carry, float(x)

            def outer(xs):
                return jax.lax.scan(body, 0.0, xs)
            """,
            "host-sync-in-jit",
        )
        assert rule_ids(res) == ["host-sync-in-jit"]

    def test_flags_helper_called_from_traced(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def helper(x):
                return x.item()

            @jax.jit
            def step(x):
                return helper(x)
            """,
            "host-sync-in-jit",
        )
        assert rule_ids(res) == ["host-sync-in-jit"]

    def test_dotted_import_does_not_shadow_root_alias(self, tmp_path):
        # `import jax.numpy` binds the root name `jax`; it must not make
        # `jax.device_get` resolve as jax.numpy.device_get
        res = lint_src(
            tmp_path,
            """
            import jax
            import jax.numpy

            @jax.jit
            def step(x):
                return jax.device_get(x)
            """,
            "host-sync-in-jit",
        )
        assert rule_ids(res) == ["host-sync-in-jit"]

    def test_clean_host_path_and_jnp_code(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax
            import jax.numpy as jnp
            import numpy as np

            def host_apply(grads):
                # not traced: host optimizer path, syncs are the point
                g = np.array(jax.device_get(grads))
                return float(g.sum())

            @jax.jit
            def step(state):
                return jnp.sum(state) * 2
            """,
            "host-sync-in-jit",
        )
        assert res.findings == []

    def test_host_annotated_helper_not_traced(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def threshold(keep_prob: float) -> int:
                return int(keep_prob * 4294967296.0)

            @jax.jit
            def step(x):
                t = threshold(0.9)
                return x * t
            """,
            "host-sync-in-jit",
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# print-under-trace / np-random-under-trace / global-mutation-under-trace
# ---------------------------------------------------------------------------


class TestSideEffects:
    def test_print_flagged(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            @jax.jit
            def step(x):
                print("loss", x)
                return x
            """,
            "print-under-trace",
        )
        assert rule_ids(res) == ["print-under-trace"]
        assert res.findings[0].severity == Severity.B

    def test_print_clean_with_debug_print_and_host(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            @jax.jit
            def step(x):
                jax.debug.print("loss {}", x)
                return x

            def report(x):
                print("host-side is fine", x)
            """,
            "print-under-trace",
        )
        assert res.findings == []

    def test_np_random_flagged(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax
            import numpy as np

            @jax.jit
            def dropout(x):
                mask = np.random.rand(*x.shape) > 0.5
                return x * mask
            """,
            "np-random-under-trace",
        )
        assert rule_ids(res) == ["np-random-under-trace"]
        assert "constant" in res.findings[0].message

    def test_np_random_clean(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax
            import numpy as np

            def make_batch(rng):
                return np.random.rand(4, 4)  # host data pipeline: fine

            @jax.jit
            def dropout(x, key):
                mask = jax.random.bernoulli(key, 0.5, x.shape)
                return x * mask
            """,
            "np-random-under-trace",
        )
        assert res.findings == []

    def test_global_mutation_flagged(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            _step_count = 0

            @jax.jit
            def step(self, x):
                global _step_count
                _step_count += 1
                self.cache = x
                return x
            """,
            "global-mutation-under-trace",
        )
        assert rule_ids(res) == ["global-mutation-under-trace"] * 2
        msgs = " ".join(f.message for f in res.findings)
        assert "global" in msgs and "self.cache" in msgs

    def test_global_mutation_clean_outside_trace(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            class Engine:
                def set_mesh(self, mesh):
                    self.mesh = mesh  # plain host method: fine
            """,
            "global-mutation-under-trace",
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# unhashable-static-arg
# ---------------------------------------------------------------------------


class TestStaticArgs:
    def test_direct_call_with_list(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def f(x, cfg):
                return x

            y = jax.jit(f, static_argnums=(1,))(1, [2, 3])
            """,
            "unhashable-static-arg",
        )
        assert rule_ids(res) == ["unhashable-static-arg"]

    def test_wrapped_name_call_and_mutable_default(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def f(x, cfg={}):
                return x

            g = jax.jit(f, static_argnums=(1,))
            y = g(1, {"a": 1})
            """,
            "unhashable-static-arg",
        )
        assert len(res.findings) == 2  # default dict + call-site dict

    def test_clean_with_tuple(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def f(x, cfg):
                return x

            g = jax.jit(f, static_argnums=(1,))
            y = g(1, (2, 3))
            """,
            "unhashable-static-arg",
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# donated-buffer-reuse
# ---------------------------------------------------------------------------


class TestDonation:
    def test_read_after_donation(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def train(step_fn, state):
                step = jax.jit(step_fn, donate_argnums=(0,))
                new_state = step(state)
                return state, new_state  # state's buffer is gone
            """,
            "donated-buffer-reuse",
        )
        assert rule_ids(res) == ["donated-buffer-reuse"]
        assert "donate_argnums=0" in res.findings[0].message

    def test_inline_jit_donation(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def train(step_fn, state):
                out = jax.jit(step_fn, donate_argnums=(0,))(state)
                loss = state["loss"]
                return out, loss
            """,
            "donated-buffer-reuse",
        )
        assert rule_ids(res) == ["donated-buffer-reuse"]

    def test_rebind_is_clean(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def train(step_fn, state):
                step = jax.jit(step_fn, donate_argnums=(0,))
                state = step(state)   # engine idiom: rebind
                state = step(state)
                return state
            """,
            "donated-buffer-reuse",
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# float64-promotion
# ---------------------------------------------------------------------------


class TestFloat64:
    def test_flags_explicit_f64(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax.numpy as jnp

            def init(n):
                a = jnp.zeros(n, dtype=jnp.float64)
                b = jnp.arange(n, dtype="float64")
                c = jnp.ones(n, dtype=float)
                return a.astype("float64") + b + c
            """,
            "float64-promotion",
        )
        assert len(res.findings) == 4
        assert all(f.severity == Severity.B for f in res.findings)

    def test_clean_f32_and_bf16(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax.numpy as jnp
            import numpy as np

            def init(n):
                a = jnp.zeros(n, dtype=jnp.float32)
                b = jnp.ones(n, dtype=jnp.bfloat16)
                c = np.zeros(n, dtype=np.float64)  # host-side f64 is allowed
                return a, b, c
            """,
            "float64-promotion",
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# config-key-drift
# ---------------------------------------------------------------------------

_CONSTANTS_SRC = """
ZERO_OPTIMIZATION = "zero_optimization"
ZERO_STAGE = "stage"
ZERO_STAGE_DEFAULT = 0
FP16_ENABLED = "enabled"
BF16_ENABLED = "enabled"
"""


class TestConfigDrift:
    def _project(self, tmp_path, config_src):
        (tmp_path / "config").mkdir()
        (tmp_path / "config" / "constants.py").write_text(textwrap.dedent(_CONSTANTS_SRC))
        (tmp_path / "config" / "config.py").write_text(textwrap.dedent(config_src))
        return lint_paths([str(tmp_path)], select=["config-key-drift"], use_baseline=False)

    def test_missing_constant_is_tier_a(self, tmp_path):
        res = self._project(
            tmp_path,
            """
            from config import constants as C

            def parse(d):
                return d.get(C.ZERO_OPTIMIZATION, C.MISSING_DEFAULT)
            """,
        )
        assert [f.severity for f in res.findings] == [Severity.A]
        assert "MISSING_DEFAULT" in res.findings[0].message

    def test_literal_duplicating_unique_constant_is_tier_b(self, tmp_path):
        res = self._project(
            tmp_path,
            """
            from config import constants as C

            def parse(d):
                stage = d.get("stage", 0)          # drift: C.ZERO_STAGE exists
                on = d.get("enabled", False)       # ambiguous value: not drift
                return stage, on
            """,
        )
        assert [f.severity for f in res.findings] == [Severity.B]
        assert "ZERO_STAGE" in res.findings[0].message

    def test_clean_accessors(self, tmp_path):
        res = self._project(
            tmp_path,
            """
            from config import constants as C

            def parse(d):
                return d.get(C.ZERO_STAGE, C.ZERO_STAGE_DEFAULT)
            """,
        )
        assert res.findings == []

    def test_no_findings_without_both_files(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            from config import constants as C
            X = C.ANYTHING_AT_ALL
            """,
            "config-key-drift",
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# bare-jit / jit-in-loop
# ---------------------------------------------------------------------------


class TestJitHygiene:
    def test_bare_jit_flagged(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def compile_step(fn):
                return jax.jit(fn, donate_argnums=(0,))
            """,
            "bare-jit",
        )
        assert rule_ids(res) == ["bare-jit"]

    def test_scoped_or_sharded_jit_clean(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax
            from deepspeed_tpu.parallel.sequence import scoped_to

            def compile_step(self, fn, mesh, sh):
                a = jax.jit(scoped_to(mesh, fn))
                b = jax.jit(self._scoped(fn), donate_argnums=(0,))
                c = jax.jit(fn, out_shardings=sh)
                return a, b, c
            """,
            "bare-jit",
        )
        assert res.findings == []

    def test_jit_in_loop_flagged(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def sweep(fns, x):
                outs = []
                for fn in fns:
                    outs.append(jax.jit(fn)(x))
                return outs
            """,
            "jit-in-loop",
        )
        assert rule_ids(res) == ["jit-in-loop"]

    def test_jit_outside_loop_clean(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def sweep(fn, xs):
                step = jax.jit(fn)
                return [step(x) for x in xs]

            def cached(self, fn, xs):
                for x in xs:
                    if "step" not in self._compiled:
                        # defs inside loops are not themselves loop work
                        def build():
                            return jax.jit(fn)
                return xs
            """,
            "jit-in-loop",
        )
        # the comprehension is not a For statement, and the nested def
        # resets the loop context
        assert res.findings == []


# ---------------------------------------------------------------------------
# missing-sharding-constraint
# ---------------------------------------------------------------------------


class TestSharding:
    def test_unpinned_collective_in_comm(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def all_reduce(x, axis):
                return jax.lax.psum(x, axis)
            """,
            "missing-sharding-constraint",
            name="comm/reduce.py",
        )
        assert rule_ids(res) == ["missing-sharding-constraint"]
        assert res.findings[0].severity == Severity.C

    def test_clean_when_module_pins_layout(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax
            from jax.sharding import NamedSharding, PartitionSpec

            def all_reduce(x, axis, mesh):
                out = jax.lax.psum(x, axis)
                return jax.lax.with_sharding_constraint(
                    out, NamedSharding(mesh, PartitionSpec()))
            """,
            "missing-sharding-constraint",
            name="comm/reduce.py",
        )
        assert res.findings == []

    def test_not_applied_outside_comm_and_zero(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def all_reduce(x, axis):
                return jax.lax.psum(x, axis)
            """,
            "missing-sharding-constraint",
            name="models/layer.py",
        )
        assert res.findings == []

    def test_rule_engine_constructor_counts_as_marker(self, tmp_path):
        # a layout resolved through the partition-rule engine is pinned:
        # compressed.py-style exchanges routed via dp_rows_spec are clean
        res = lint_src(
            tmp_path,
            """
            import jax
            from deepspeed_tpu.sharding.layout import dp_rows_spec

            def exchange(x, axis):
                rows = dp_rows_spec(axis)
                return jax.lax.psum(x, axis), rows
            """,
            "missing-sharding-constraint",
            name="comm/exchange.py",
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# hand-built-partition-spec (tier B, PR 8 partition-rule engine)
# ---------------------------------------------------------------------------


class TestHandBuiltSpec:
    def test_flags_axis_literal_specs(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            from jax.sharding import PartitionSpec as P
            from jax.sharding import PartitionSpec

            BATCH = P(("data", "fsdp"))
            STACKED = PartitionSpec("pipe", None, "model")

            def batch_spec(ndim):
                return P("data", *([None] * (ndim - 1)))
            """,
            "hand-built-partition-spec",
            name="runtime/custom_engine.py",
        )
        assert rule_ids(res) == ["hand-built-partition-spec"] * 3
        assert all(f.severity == Severity.B for f in res.findings)
        assert "partition-rule engine" in res.findings[0].message

    def test_sharding_package_and_plumbing_are_clean(self, tmp_path):
        # the rule engine itself is the sanctioned home; replicated specs
        # and variable-axis plumbing (spec manipulation code) don't match
        res = lint_src(
            tmp_path,
            """
            from jax.sharding import PartitionSpec as P

            REPL = P()
            PADDED = P(None, None)

            def rows(axis_name):
                return P(axis_name)

            def shift(base):
                return P(None, *tuple(base))
            """,
            "hand-built-partition-spec",
            name="runtime/plumbing.py",
        )
        assert res.findings == []
        res2 = lint_src(
            tmp_path,
            """
            from jax.sharding import PartitionSpec as P

            def vocab_embedding():
                return P("model", None)
            """,
            "hand-built-partition-spec",
            name="deepspeed_tpu/sharding/layout2.py",
        )
        assert res2.findings == []

    def test_engine_zoo_has_no_hand_built_specs(self):
        # the acceptance seam: every engine resolves through sharding/;
        # zero CURRENT findings and zero GRANDFATHERED entries repo-wide
        res = lint_paths(
            [os.path.join(REPO_ROOT, "deepspeed_tpu")],
            select=["hand-built-partition-spec"],
            use_baseline=False,
        )
        assert res.findings == [], [
            f"{f.path}:{f.line}" for f in res.findings
        ]

    def test_baseline_shrank_not_grew(self):
        # burn-down ratchet: rule-engine adoption retired the
        # missing-sharding-constraint entries (21 -> 18), the bare-jit
        # sweep over model init / profiler / eigenvalue retired four
        # more (18 -> 14), and the mesh-scoping sweep over the offload
        # drain / param-offload programs / int8 pack retired every
        # bare-jit entry (14 -> 6) — the checked-in baseline only goes
        # down
        with open(os.path.join(REPO_ROOT, ".ds_lint_baseline.json")) as f:
            entries = json.load(f)["findings"]
        assert len(entries) <= 6
        rules_present = {e["rule"] for e in entries}
        assert "missing-sharding-constraint" not in rules_present
        assert "hand-built-partition-spec" not in rules_present
        assert "bare-jit" not in rules_present
        # the burned-down files carry no grandfathered entries at all
        burned = {"models/bert.py", "models/gpt2.py",
                  "profiling/flops_profiler.py", "runtime/eigenvalue.py",
                  "runtime/engine.py", "runtime/weight_quantizer.py"}
        stale = [e for e in entries
                 if any(e["path"].endswith(b) for b in burned)]
        assert stale == [], stale

    def test_baseline_has_no_stale_entries(self):
        # every grandfathered fingerprint must still match a live
        # finding — dead entries mask regressions at the same site
        # (shares the one full self-run with TestSelfRun: ~6s each)
        res, _ = _repo_self_run()
        with open(os.path.join(REPO_ROOT, ".ds_lint_baseline.json")) as f:
            entries = json.load(f)["findings"]
        live = {f.fingerprint for f in res.baselined} | {
            f.fingerprint for f in res.findings
        }
        stale = [e for e in entries if e["fingerprint"] not in live]
        assert stale == [], stale


# ---------------------------------------------------------------------------
# prng-key-reuse
# ---------------------------------------------------------------------------


class TestPrngReuse:
    def test_reused_key_flagged(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def init(n):
                key = jax.random.PRNGKey(0)
                w = jax.random.normal(key, (n, n))
                b = jax.random.uniform(key, (n,))
                return w, b
            """,
            "prng-key-reuse",
        )
        assert rule_ids(res) == ["prng-key-reuse"]
        assert "split" in res.findings[0].message

    def test_split_keys_clean(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            def init(n):
                key = jax.random.PRNGKey(0)
                kw, kb = jax.random.split(key)
                w = jax.random.normal(kw, (n, n))
                b = jax.random.uniform(kb, (n,))
                return w, b
            """,
            "prng-key-reuse",
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# non-atomic-checkpoint-write (tier B, PR 2 resilience subsystem)
# ---------------------------------------------------------------------------


class TestAtomicCheckpointWrite:
    def test_flags_bare_meta_and_latest_writes(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import json
            import os

            LATEST_FILE = "latest"

            def save(path, save_dir, meta, tag):
                with open(os.path.join(path, "meta.json"), "w") as f:
                    json.dump(meta, f)
                with open(os.path.join(save_dir, LATEST_FILE), mode="w") as f:
                    f.write(tag)
            """,
            "non-atomic-checkpoint-write",
        )
        assert rule_ids(res) == ["non-atomic-checkpoint-write"] * 2
        assert all(f.severity == Severity.B for f in res.findings)
        assert "atomic_write_text" in res.findings[0].message

    def test_clean_reads_other_files_and_helper(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import os

            from deepspeed_tpu.resilience.atomic import atomic_write_text

            def save(path, save_dir, tag, log_lines):
                # read mode is fine
                with open(os.path.join(path, "meta.json")) as f:
                    meta = f.read()
                # non-metadata writes are fine
                with open(os.path.join(path, "train.log"), "w") as f:
                    f.writelines(log_lines)
                # the sanctioned path
                atomic_write_text(os.path.join(save_dir, "latest"), tag)
                return meta
            """,
            "non-atomic-checkpoint-write",
        )
        assert res.findings == []

    def test_dynamic_mode_not_flagged(self, tmp_path):
        # a non-literal mode can't be proven to write; stay quiet
        res = lint_src(
            tmp_path,
            """
            def touch(path, mode):
                return open(path + "/meta.json", mode)
            """,
            "non-atomic-checkpoint-write",
        )
        assert res.findings == []


# ---------------------------------------------------------------------------
# unguarded-collective-barrier (tier B, PR 5 supervision subsystem)
# ---------------------------------------------------------------------------


class TestBarrierGuard:
    def test_flags_bare_blocking_syncs(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import numpy as np
            from jax.experimental import multihost_utils

            def barrier(tag):
                multihost_utils.sync_global_devices(f"ckpt_{tag}")

            def join(x):
                return np.asarray(multihost_utils.process_allgather(x))
            """,
            "unguarded-collective-barrier",
        )
        assert rule_ids(res) == ["unguarded-collective-barrier"] * 2
        assert all(f.severity == Severity.B for f in res.findings)
        assert "armed" in res.findings[0].message

    def test_clean_armed_region_and_helper(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            from contextlib import nullcontext

            import numpy as np
            from jax.experimental import multihost_utils

            from deepspeed_tpu.resilience.supervision import supervised_sync

            def barrier(tag, sup):
                with sup.armed(f"barrier:{tag}"):
                    multihost_utils.sync_global_devices(tag)

            def conditional(tag, sup):
                # the engine's `armed-if-supervised` conditional form
                with sup.armed(tag) if sup is not None else nullcontext():
                    return np.asarray(multihost_utils.process_allgather(tag))

            def supervised_join(x):
                # wrapper modules: supervised_* functions arm themselves
                return multihost_utils.process_allgather(x)

            def sanctioned(tag, sup):
                supervised_sync(tag, supervisor=sup)
            """,
            "unguarded-collective-barrier",
        )
        assert res.findings == []

    def test_guard_outside_def_does_not_cover_the_def(self, tmp_path):
        # arming at import time is not arming at call time
        res = lint_src(
            tmp_path,
            """
            from jax.experimental import multihost_utils

            with SUP.armed("module-setup"):
                def later():
                    multihost_utils.sync_global_devices("x")
            """,
            "unguarded-collective-barrier",
        )
        assert rule_ids(res) == ["unguarded-collective-barrier"]


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


class TestUnfencedTiming:
    def test_flags_delta_around_jit_bound_callable(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import time
            import jax

            def step(x):
                return x * 2

            f = jax.jit(step)

            def bench(x):
                t0 = time.perf_counter()
                y = f(x)
                dt = time.perf_counter() - t0
                return dt
            """,
            "unfenced-timing",
        )
        assert rule_ids(res) == ["unfenced-timing"]
        assert res.findings[0].severity == Severity.C
        assert "block_until_ready" in res.findings[0].message

    def test_flags_engine_step_api_and_direct_jit_call(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import time
            import jax

            class Driver:
                def run(self, eng, b, g, x):
                    t0 = time.time()
                    eng.train_batch(b)
                    dt1 = time.time() - t0
                    t1 = time.perf_counter()
                    jax.jit(g)(x)
                    dt2 = time.perf_counter() - t1
                    return dt1, dt2
            """,
            "unfenced-timing",
        )
        assert rule_ids(res) == ["unfenced-timing", "unfenced-timing"]

    def test_clean_when_fenced(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import time
            import jax

            def step(x):
                return x * 2

            f = jax.jit(step)

            def bench_block(x):
                t0 = time.perf_counter()
                y = f(x)
                jax.block_until_ready(y)
                return time.perf_counter() - t0

            def bench_float(eng, b):
                t0 = time.time()
                loss = float(eng.train_batch(b))
                return time.time() - t0
            """,
            "unfenced-timing",
        )
        assert rule_ids(res) == []

    def test_clean_when_no_jitted_call_in_window(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import time

            def bench(load):
                t0 = time.time()
                data = load()
                return time.time() - t0
            """,
            "unfenced-timing",
        )
        assert rule_ids(res) == []

    def test_traced_functions_are_out_of_scope(self, tmp_path):
        # timing INSIDE a jit is host-sync-in-jit territory, not this rule
        res = lint_src(
            tmp_path,
            """
            import time
            import jax

            @jax.jit
            def step(x):
                t0 = time.perf_counter()
                y = x * 2
                dt = time.perf_counter() - t0
                return y
            """,
            "unfenced-timing",
        )
        assert rule_ids(res) == []


class TestSuppression:
    SRC = """
    import jax
    import numpy as np

    @jax.jit
    def step(x):
        a = float(x){inline}
        return a
    """

    def test_same_line_disable(self, tmp_path):
        src = self.SRC.format(inline="  # ds-lint: disable=host-sync-in-jit")
        res = lint_src(tmp_path, src, "host-sync-in-jit")
        assert res.findings == [] and res.suppressed == 1

    def test_standalone_comment_suppresses_next_line(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            @jax.jit
            def step(x):
                # ds-lint: disable=host-sync-in-jit
                a = float(x)
                return a
            """,
            "host-sync-in-jit",
        )
        assert res.findings == [] and res.suppressed == 1

    def test_standalone_pragma_skips_intervening_comments(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax

            @jax.jit
            def step(x):
                # ds-lint: disable=host-sync-in-jit
                # host int math on a static shape, not a sync
                a = float(x)
                return a
            """,
            "host-sync-in-jit",
        )
        assert res.findings == [] and res.suppressed == 1

    def test_disable_file(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            # ds-lint: disable-file=host-sync-in-jit
            import jax

            @jax.jit
            def step(x):
                return float(x) + int(x)
            """,
            "host-sync-in-jit",
        )
        assert res.findings == [] and res.suppressed == 2

    def test_disable_all(self, tmp_path):
        src = self.SRC.format(inline="  # ds-lint: disable=all")
        res = lint_src(tmp_path, src, "host-sync-in-jit")
        assert res.findings == [] and res.suppressed == 1

    def test_other_rule_not_suppressed(self, tmp_path):
        src = self.SRC.format(inline="  # ds-lint: disable=print-under-trace")
        res = lint_src(tmp_path, src, "host-sync-in-jit")
        assert rule_ids(res) == ["host-sync-in-jit"]


# ---------------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------------

_VIOLATION = """
import jax

@jax.jit
def step(x):
    return float(x)
"""


class TestBaseline:
    def test_roundtrip_grandfathers_existing(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent(_VIOLATION))
        bl = tmp_path / ".ds_lint_baseline.json"

        first = lint_paths([str(mod)], baseline_path=str(bl))
        assert len(first.findings) == 1
        baseline_mod.save(str(bl), first.all_current)

        second = lint_paths([str(mod)], baseline_path=str(bl))
        assert second.findings == [] and len(second.baselined) == 1

    def test_new_finding_not_grandfathered(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent(_VIOLATION))
        bl = tmp_path / ".ds_lint_baseline.json"
        baseline_mod.save(str(bl), lint_paths([str(mod)], baseline_path=str(bl)).all_current)

        mod.write_text(textwrap.dedent(_VIOLATION) + "\n\n@jax.jit\ndef step2(x):\n    return int(x)\n")
        res = lint_paths([str(mod)], baseline_path=str(bl))
        assert len(res.findings) == 1 and res.findings[0].line > 5
        assert len(res.baselined) == 1

    def test_fingerprint_survives_line_drift(self, tmp_path):
        mod = tmp_path / "mod.py"
        mod.write_text(textwrap.dedent(_VIOLATION))
        bl = tmp_path / ".ds_lint_baseline.json"
        baseline_mod.save(str(bl), lint_paths([str(mod)], baseline_path=str(bl)).all_current)

        # prepend unrelated code: line numbers shift, fingerprints don't
        mod.write_text("X = 1\nY = 2\n" + textwrap.dedent(_VIOLATION))
        res = lint_paths([str(mod)], baseline_path=str(bl))
        assert res.findings == [] and len(res.baselined) == 1

    def test_discovery_walks_up(self, tmp_path, monkeypatch):
        pkg = tmp_path / "pkg" / "sub"
        pkg.mkdir(parents=True)
        mod = pkg / "mod.py"
        mod.write_text(textwrap.dedent(_VIOLATION))
        bl = tmp_path / ".ds_lint_baseline.json"
        res0 = lint_paths([str(mod)], baseline_path=str(bl))
        baseline_mod.save(str(bl), res0.all_current)

        monkeypatch.chdir(tmp_path / "pkg")
        res = lint_paths([str(mod)])  # no explicit baseline: discovered
        assert res.baseline_path == str(bl)
        assert res.findings == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


class TestCli:
    def test_exit_zero_on_clean_tree(self, tmp_path):
        (tmp_path / "ok.py").write_text("import jax.numpy as jnp\n\n\ndef f(x):\n    return jnp.sum(x)\n")
        assert cli_main([str(tmp_path), "--no-baseline"]) == 0

    def test_exit_one_on_tier_a(self, tmp_path, capsys):
        mod = tmp_path / "bad.py"
        mod.write_text(textwrap.dedent(_VIOLATION))
        assert cli_main([str(mod), "--no-baseline"]) == 1
        out = capsys.readouterr().out
        assert "host-sync-in-jit" in out and "[A]" in out

    def test_tier_b_only_fails_with_fail_on_b(self, tmp_path):
        mod = tmp_path / "warn.py"
        mod.write_text("import jax\n\n\ndef f(fn):\n    return jax.jit(fn)\n")
        assert cli_main([str(mod), "--no-baseline"]) == 0
        assert cli_main([str(mod), "--no-baseline", "--fail-on", "B"]) == 1

    def test_select_and_disable(self, tmp_path):
        mod = tmp_path / "bad.py"
        mod.write_text(textwrap.dedent(_VIOLATION))
        assert cli_main([str(mod), "--no-baseline", "--select", "prng-key-reuse"]) == 0
        assert cli_main([str(mod), "--no-baseline", "--disable", "host-sync-in-jit"]) == 0
        assert cli_main([str(mod), "--no-baseline", "--select", "no-such-rule"]) == 2

    def test_write_baseline_then_clean(self, tmp_path, monkeypatch):
        mod = tmp_path / "bad.py"
        mod.write_text(textwrap.dedent(_VIOLATION))
        bl = tmp_path / ".ds_lint_baseline.json"
        assert cli_main([str(mod), "--baseline", str(bl), "--write-baseline"]) == 0
        data = json.loads(bl.read_text())
        assert data["version"] == 1 and len(data["findings"]) == 1
        assert cli_main([str(mod), "--baseline", str(bl)]) == 0

    def test_first_time_write_baseline_from_cwd(self, tmp_path, monkeypatch):
        # fingerprint roots must match between the --write-baseline run
        # (no baseline exists yet, file lands in cwd) and the next run
        # (which discovers that file): pkg-relative vs cwd-relative paths
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "bad.py").write_text(textwrap.dedent(_VIOLATION))
        monkeypatch.chdir(tmp_path)
        assert cli_main(["pkg", "--write-baseline"]) == 0
        assert (tmp_path / baseline_mod.BASELINE_NAME).is_file()
        assert cli_main(["pkg"]) == 0  # everything just written is grandfathered

    def test_json_format(self, tmp_path, capsys):
        mod = tmp_path / "bad.py"
        mod.write_text(textwrap.dedent(_VIOLATION))
        assert cli_main([str(mod), "--no-baseline", "--format", "json"]) == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["findings"][0]["rule"] == "host-sync-in-jit"
        assert payload["findings"][0]["severity"] == "A"

    def test_list_rules(self, capsys):
        assert cli_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        assert "host-sync-in-jit" in out and "config-key-drift" in out

    def test_no_paths_is_usage_error(self):
        assert cli_main([]) == 2

    def test_syntax_error_file_fails(self, tmp_path, capsys):
        (tmp_path / "broken.py").write_text("def f(:\n")
        assert cli_main([str(tmp_path), "--no-baseline"]) == 1
        assert "parse-error" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# raw-collective-outside-comm-layer (tier B, PR 6 comm subsystem)
# ---------------------------------------------------------------------------


class TestRawCollective:
    def test_flags_raw_lax_collectives(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax
            from jax import lax

            def exchange(g, dx):
                g = jax.lax.psum(g, "data")
                part = lax.psum_scatter(g, "fsdp", scatter_dimension=0, tiled=True)
                nxt = lax.ppermute(dx, "pipe", [(0, 1), (1, 0)])
                return part, nxt
            """,
            "raw-collective-outside-comm-layer",
        )
        assert rule_ids(res) == ["raw-collective-outside-comm-layer"] * 3
        assert all(f.severity == Severity.B for f in res.findings)
        assert "comm" in res.findings[0].message

    def test_comm_package_and_wrappers_are_clean(self, tmp_path):
        # the comm package itself is the sanctioned home; call sites
        # routed through comm.collectives don't match the rule
        res = lint_src(
            tmp_path,
            """
            import jax

            def body(x):
                return jax.lax.psum(x, "data")
            """,
            "raw-collective-outside-comm-layer",
            name="deepspeed_tpu/comm/mymod.py",
        )
        assert rule_ids(res) == []
        res2 = lint_src(
            tmp_path,
            """
            from deepspeed_tpu.comm import collectives

            def exchange(g, dx, S):
                g = collectives.all_reduce(g, "data")
                return collectives.p2p_shift(dx, "pipe", S, 1)
            """,
            "raw-collective-outside-comm-layer",
        )
        assert rule_ids(res2) == []


# ---------------------------------------------------------------------------
# raw-pallas-call-outside-kernels (tier B, PR 12 kernel seam)
# ---------------------------------------------------------------------------


class TestPallasSeam:
    def test_flags_raw_pallas_call_outside_seam(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def double(x):
                def kern(x_ref, o_ref):
                    o_ref[:] = x_ref[:] * 2.0

                return pl.pallas_call(
                    kern, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype)
                )(x)
            """,
            "raw-pallas-call-outside-kernels",
            name="deepspeed_tpu/runtime/mymod.py",
        )
        assert rule_ids(res) == ["raw-pallas-call-outside-kernels"]
        assert all(f.severity == Severity.B for f in res.findings)
        assert "ops/kernels" in res.findings[0].message

    def test_bare_import_spelling_also_flags(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            from jax.experimental.pallas import pallas_call

            def f(kern, x, shape):
                return pallas_call(kern, out_shape=shape)(x)
            """,
            "raw-pallas-call-outside-kernels",
            name="deepspeed_tpu/serving/mymod.py",
        )
        assert rule_ids(res) == ["raw-pallas-call-outside-kernels"]

    def test_kernel_seam_packages_are_clean(self, tmp_path):
        src = """
            from jax.experimental import pallas as pl

            def launch(kern, x, shape):
                return pl.pallas_call(kern, out_shape=shape)(x)
            """
        for home in (
            "deepspeed_tpu/ops/kernels/mykernel.py",
            "deepspeed_tpu/ops/attention/mykernel.py",
        ):
            res = lint_src(tmp_path, src, "raw-pallas-call-outside-kernels", name=home)
            assert rule_ids(res) == [], home

    def test_non_pallas_calls_are_clean(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            import jax.numpy as jnp

            def f(x):
                return jnp.sum(x)
            """,
            "raw-pallas-call-outside-kernels",
            name="deepspeed_tpu/runtime/mymod.py",
        )
        assert rule_ids(res) == []


# ---------------------------------------------------------------------------
# raw-metric-emit (tier C, PR 9 telemetry plane)
# ---------------------------------------------------------------------------


class TestRawMetricEmit:
    def test_flags_direct_emits_and_handbuilt_writer(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            from torch.utils.tensorboard import SummaryWriter

            def report(monitor, step, loss):
                writer = SummaryWriter(log_dir="runs")
                writer.add_scalar("loss", loss, step)
                monitor.write_events([("Train/Samples/lr", 0.1)], step)
            """,
            "raw-metric-emit",
        )
        assert rule_ids(res) == ["raw-metric-emit"] * 3
        assert all(f.severity == Severity.C for f in res.findings)
        assert "registry" in res.findings[1].message

    def test_telemetry_package_and_monitor_are_exempt(self, tmp_path):
        src = """
            def export(monitor, snapshot, step):
                for m in snapshot["metrics"]:
                    monitor.add_scalar(m["name"], m["value"], step)
            """
        res = lint_src(tmp_path, src, "raw-metric-emit",
                       name="deepspeed_tpu/telemetry/exporters.py")
        assert rule_ids(res) == []
        res2 = lint_src(tmp_path, src, "raw-metric-emit",
                        name="deepspeed_tpu/utils/monitor.py")
        assert rule_ids(res2) == []

    def test_registry_publishes_are_clean(self, tmp_path):
        res = lint_src(
            tmp_path,
            """
            from deepspeed_tpu.telemetry import get_registry

            def report(tm, loss, step):
                tm.gauge("train/loss").set(loss)
                get_registry().counter("steps").inc()
                tm.publish_train_progress(step=step, samples=1, loss=loss,
                                          lr=0.1, loss_scale=1.0)
            """,
            "raw-metric-emit",
        )
        assert rule_ids(res) == []


# ---------------------------------------------------------------------------
# self-run: the repo gates on itself
# ---------------------------------------------------------------------------


class TestSelfRun:
    def test_package_is_clean_with_baseline(self):
        baseline = os.path.join(REPO_ROOT, ".ds_lint_baseline.json")
        assert os.path.isfile(baseline), "checked-in baseline missing"
        res, elapsed = _repo_self_run()
        new = [f.format() for f in res.findings + res.parse_errors]
        assert new == [], "new ds_lint findings:\n" + "\n".join(new)
        assert elapsed < 15.0, f"ds_lint self-run took {elapsed:.1f}s of CPU time (budget 15s)"

    def test_seeded_violation_is_caught(self, tmp_path):
        # the acceptance check: introducing a violation next to the real
        # package must flip the gate even with the baseline applied
        baseline = os.path.join(REPO_ROOT, ".ds_lint_baseline.json")
        bad = tmp_path / "seeded.py"
        bad.write_text(textwrap.dedent(_VIOLATION))
        res = lint_paths(
            [os.path.join(REPO_ROOT, "deepspeed_tpu"), str(bad)],
            baseline_path=baseline,
        )
        assert [f.rule for f in res.failing()] == ["host-sync-in-jit"]
