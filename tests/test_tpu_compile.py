"""Compile the chip's programs for a described TPU v5e, with no chip attached.

The TPU compiler is installed with jax; it compiles for a topology that is
described rather than attached, and refuses what the chip would refuse: a
block shape off the (8, 128) tiling, an op Mosaic cannot lower, more VMEM or
SMEM than the chip has. Nothing runs here, so these tests say nothing about
results or times (the interpret-mode sweeps and ``chip_smoke.py`` do that).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, so a module-level call would make
pytest-xdist workers collect different tests. All compiles stay in this file
so one worker holds the library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import fleet
from repro.core import registry, sketch
from repro.fleet import stream
from repro.kernels.cache_sim.cache_sim import KERNEL_KINDS, cache_sim_pallas

# the paper's largest grid case (core/zipf.py: N = 100,000 at rate 0.02,
# 12 samples); the trace is cut to 20,000 requests like chip_smoke phase (c)
N, CAP, S, T = 100_000, 2_000, 12, 20_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one: keep the cache off in this file."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype, sharding=sharding)


def _compile_kernel(one_chip, kind, *, sized=False, grouped=False, **kw):
    if kind == "wlfu":
        kw.setdefault("window", sketch.default_window(CAP))

    def run(traces, *extra):
        extra = list(extra)
        sizes = extra.pop(0) if sized else None
        groups = extra.pop(0) if grouped else None
        return cache_sim_pallas(
            traces, kind=kind, n_objects=N, capacity=CAP, sizes=sizes,
            groups=groups, interpret=False, **kw,
        )

    args = [jax.ShapeDtypeStruct((S, T), jnp.int32, sharding=one_chip)]
    args += [jax.ShapeDtypeStruct((N,), jnp.int32, sharding=one_chip)] * (
        int(sized) + int(grouped)
    )
    compiled = jax.jit(run).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("kind", KERNEL_KINDS)
def test_kernel_compiles_natively(one_chip, kind):
    _compile_kernel(one_chip, kind)


def test_kernel_with_telemetry_compiles(one_chip):
    _compile_kernel(one_chip, "plfua_dyn", telemetry_window=1_000)


def test_kernel_with_grouped_telemetry_compiles(one_chip):
    _compile_kernel(one_chip, "tinylfu", grouped=True, telemetry_window=1_000, n_groups=4)


def test_kernel_byte_mode_gdsf_compiles(one_chip):
    _compile_kernel(
        one_chip, "gdsf", sized=True, capacity_bytes=CAP * 64,
        max_victims=registry.DEFAULT_MAX_VICTIMS,
    )


def test_three_tier_stream_chunk_compiles(one_chip):
    """The general (level-major) stream engine's chunk at N = 2^17."""
    topo = fleet.tree(
        n_objects=2**17, widths=(8, 2, 1), kinds=("lru", "plfu", "plfu"),
        capacities=(4096, 16384, 65536), router="hash",
    )
    fs = stream.FleetStream(stream.StreamConfig(topo=topo, chunk_len=2048))
    carry = jax.tree.map(lambda a: _shape(a, one_chip), fs._carry)
    chunk = jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one_chip)
    fs._push_fn.lower(carry, chunk, chunk).compile()
