"""``run.py`` exits non-zero and prints no result line when JAX finds
no TPU, and in a directory that holds only the benchmark's own files."""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ARGS = ["--workload", "train-large-seq1024", "--seed", "4000000011", "--seconds", "1", "--trace", "0"]


def _run(cwd, env_extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "17", **env_extra}
    return subprocess.run([sys.executable, "benchmark/run.py", *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result_line(tmp_path):
    p = _run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_directory_with_only_the_benchmarks_files_exits_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
