"""The one traffic generator: reads a mix's parameters and the
configuration's catalogue and skew, and makes request ids on the device from
``--seed``. The program under test receives only the int32 id arrays.

Every block of ids is a pure function of ``(seed, block index)``: the seed
becomes a threefry key (all 64 bits of it), a stream's chunk ``c`` is drawn
from ``fold_in(key, c)`` and sample ``s`` of replication ``r`` from
``fold_in(fold_in(key, r), s)``. The seed and the index are traced, so one
compiled program serves every seed and every block."""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np


def key_data(seed: int) -> np.ndarray:
    """The raw threefry key of ``seed`` (the same as ``PRNGKey(seed)`` for
    seeds below 2**64), as a (2,) uint32 array."""
    s = int(seed) & 0xFFFFFFFFFFFFFFFF
    return np.array([s >> 32, s & 0xFFFFFFFF], np.uint32)


def zipf_cdf(n_objects: int, alpha: float) -> np.ndarray:
    """Zipf(alpha) CDF over ranks 1..n, summed in float64, stored float32."""
    w = np.arange(1, n_objects + 1, dtype=np.float64) ** (-float(alpha))
    return np.cumsum(w / w.sum()).astype(np.float32)


class Traffic:
    """The mix ``traffic`` over the catalogue of ``config``, seeded.

    ``block(i)`` dispatches block ``i`` (a stream chunk of ``chunk_len`` ids,
    or one replication of ``n_samples`` x ``sample_len`` ids) and returns the
    device array without waiting for it."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        n = int(config["n_objects"])
        gen = importlib.import_module(f"chipbench.scenarios.{traffic['scenario']}").generate
        params = dict(traffic.get("params", {}))
        self._key = jnp.asarray(key_data(seed))
        self._cdf = jnp.asarray(zipf_cdf(n, config["alpha"]))
        if "n_samples" in traffic:
            S, T = int(traffic["n_samples"]), int(traffic["sample_len"])

            def chipbench_traffic(key, i, cdf):
                rk = jax.random.fold_in(key, i)
                keys = jax.vmap(lambda s: jax.random.fold_in(rk, s))(
                    jnp.arange(S, dtype=jnp.uint32)
                )
                return jax.vmap(lambda k: gen(k, cdf, n, T, params))(keys)

            self.shape = (S, T)
        else:
            G = int(traffic["chunk_len"])

            def chipbench_traffic(key, i, cdf):
                return gen(jax.random.fold_in(key, i), cdf, n, G, params)

            self.shape = (G,)
        self._fn = jax.jit(chipbench_traffic)

    def block(self, i: int):
        return self._fn(self._key, jnp.uint32(i), self._cdf)


#: name of the traffic program's XLA module (``jit_`` + the function name):
#: the per-layer readers leave its device time out of the engine's
MODULE = "jit_chipbench_traffic"
