"""Stationary Zipf: every request an independent draw from the Zipf law over
popularity ranks (id 0 the hottest). The benchmark's own copy of
``workloads.device._stationary``, so later changes to the program cannot
move the yardstick."""
import jax
import jax.numpy as jnp


def generate(key, cdf, n_objects: int, length: int, params: dict):
    return ranks(cdf, jax.random.uniform(key, (length,)), n_objects)


def ranks(cdf, u, n_objects: int):
    """Inverse-CDF draw: the first rank whose CDF exceeds ``u``. Where the
    draws outnumber the catalogue, one sort of both beats a binary search per
    draw (20 ms against 137 ms for 12 x 100,000 draws over 100,000 ranks on
    a TPU v5e); both give the same ranks."""
    method = "sort" if u.size >= cdf.size else "scan"
    idx = jnp.searchsorted(cdf, u, side="right", method=method)
    return jnp.minimum(idx, n_objects - 1).astype(jnp.int32)
