"""bench/run.py refuses a machine without a TPU, and a checkout that
holds only the benchmark: non-zero exit, nothing on standard output."""
import os
import shutil
import subprocess
import sys

import bench_tiny

CMD = [sys.executable, "bench/run.py", "--workload", "phi3.1chip.int8",
       "--seed", str(2 ** 31 + 9), "--seconds", "1", "--trace", "0"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env


def test_exits_nonzero_without_a_tpu():
    p = subprocess.run(CMD, cwd=bench_tiny.ROOT, env=_env(),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "not a TPU" in p.stderr


def test_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(bench_tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(bench_tiny.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(CMD, cwd=tmp_path, env=_env(), capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
