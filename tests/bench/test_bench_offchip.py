"""The harness measures only the chip: elsewhere it exits non-zero and
prints no result."""
import os
import shutil
import subprocess
import sys

import _tiny_cells


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-granite-l4-2k",
         "--seed", str(2**31 + 5), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_exits_non_zero_without_a_tpu():
    p = _run(_tiny_cells.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(os.path.join(_tiny_cells.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(_tiny_cells.ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
