"""``chip_smoke.py`` rehearsed on the CPU: its phases at tiny sizes (the
kernel in interpret mode, which ``ops.cache_sim`` picks off-TPU) must pass
their own exactness checks, the script itself must refuse to run without a
TPU, and the compile-cache rule it shares with ``benchmarks.run`` holds."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO_ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_flat_stream_phase_is_exact(smoke):
    row = smoke.phase_flat_stream(n_objects=512, capacity=64, chunk_len=128, n_chunks=4)
    assert row["exact"] and row["hits"] == row["ref_hits"] > 0


def test_tree_stream_phase_is_exact(smoke):
    row = smoke.phase_tree_stream(n_objects=512, capacities=(8, 32, 64),
                                  chunk_len=128, n_chunks=2)
    assert row["exact"] and sum(row["tier_hits"]) > 0


@pytest.mark.parametrize("kind", ["lru", "plfua_dyn", "arc"])
def test_kernel_phase_is_exact(smoke, kind):
    # sketch periods cut with the trace so plfua_dyn refreshes mid-trace
    knobs = {**smoke.KERNEL_KNOBS, "plfua_dyn": {"refresh": 100}}
    out = smoke.phase_kernel(n_objects=512, capacity=16, n_samples=2,
                             trace_len=300, kinds=(kind,), knobs=knobs)
    assert out["exact"] and len(out["rows"]) == 1


def test_multichip_phase_runs_on_a_one_device_mesh(smoke):
    row = smoke.phase_multichip(smoke.fleet.fleet_mesh(jax.devices()[:1]),
                                n_objects=512, capacities=(8, 32, 64),
                                trace_len=256, n_samples=2)
    assert row["exact"] and row["mesh_devices"] == 1


def test_script_refuses_to_run_without_a_tpu():
    proc = subprocess.run(
        [sys.executable, str(REPO_ROOT / "chip_smoke.py")], capture_output=True,
        text=True, timeout=300, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_repo(monkeypatch, cache_dir_config):
    from repro import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_leaves_the_environment_to_jax(monkeypatch, cache_dir_config):
    from repro import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before
