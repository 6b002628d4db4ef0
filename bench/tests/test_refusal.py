"""Off a TPU the benchmark stops with no result line."""
import os
import subprocess
import sys

from bench.harness import spec


def _run(args, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_cpu_run_exits_nonzero_without_result():
    p = _run(["--workload", "yi6b-chat-short", "--seed", "2147483701",
              "--seconds", "2", "--trace", "0"], spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory with only BENCHMARK.json and bench/ holds no program."""
    import shutil
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(["--workload", "yi6b-chat-short", "--seed", "1",
              "--seconds", "2", "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
