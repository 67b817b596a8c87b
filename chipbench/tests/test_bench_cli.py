"""The command refuses to run without a TPU, and in a directory that holds
only ``BENCHMARK.json`` and the benchmark's own files: non-zero exit, no
result line."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cells


def _run(root, *extra_env):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **dict(extra_env)}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "plfua_n100k.stream",
         "--seed", "4294967301", "--seconds", "1", "--trace", "0"],
        cwd=root, capture_output=True, text=True, timeout=300, env=env,
    )


def _no_result_line(stdout):
    for line in stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_command_exits_non_zero_off_tpu():
    proc = _run(cells.ROOT)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    _no_result_line(proc.stdout)


def test_command_exits_non_zero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(cells.ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    _no_result_line(proc.stdout)
