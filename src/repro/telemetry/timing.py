"""Measured host-side timing with the compile/execute split (PR 6).

The paper's headline metric is *total CPU time per algorithm*; on the jitted
tiers a credible measurement needs three disciplines the ad-hoc bench loops
kept getting wrong:

* **compile vs execute** — the first call of a jitted function traces and
  compiles; folding that into a steps/sec number is a category error. The
  harness isolates it via AOT ``fn.lower(...).compile()`` and times the
  compiled executable only.
* **warmup** — even the compiled executable's first call can pay transfer /
  commit costs, so at least one untimed call always precedes the clock.
* **block_until_ready** — JAX dispatch is asynchronous; every timed call is
  wrapped in ``jax.block_until_ready`` so device work cannot leak past the
  timer.

``Timing.j_per_step`` converts the measured wall interval into management
energy per request through the same CPU-core power model the analytic tables
use (:func:`repro.core.energy.mgmt_energy_j`), giving the ROADMAP's
"measured numbers supersede the roofline" hook a single code path.
"""
from __future__ import annotations

import dataclasses
import time

import jax

from repro.core import energy


@dataclasses.dataclass(frozen=True)
class Timing:
    """One measured run: ``execute_s`` is best-of-``repeats`` wall seconds
    per call (min, the standard noise-floor estimator); ``steps`` is the
    simulated-request count the caller attributes to one call."""

    steps: int
    repeats: int
    compile_s: float
    execute_s: float
    mean_execute_s: float

    @property
    def steps_per_s(self) -> float:
        return self.steps / self.execute_s if self.execute_s > 0 else float("inf")

    @property
    def us_per_step(self) -> float:
        return self.execute_s / self.steps * 1e6

    @property
    def j_per_step(self) -> float:
        """Measured management energy per simulated request (paper cost model)."""
        return energy.mgmt_energy_j(self.execute_s) / self.steps

    def derived(self, **extra) -> str:
        """The benchmark-row `key=value` summary (see benchmarks/run.py)."""
        parts = [
            f"steps_per_s={self.steps_per_s:.4g}",
            f"compile_s={self.compile_s:.3f}",
            f"execute_s={self.execute_s:.4f}",
            f"j_per_step={self.j_per_step:.3e}",
        ]
        parts.extend(f"{k}={v}" for k, v in extra.items())
        return " ".join(parts)


def j_per_step(cpu_seconds: float, steps: int) -> float:
    """Management J per request from a measured CPU interval — the measured
    counterpart of the analytic per-op energy tables."""
    return energy.mgmt_energy_j(cpu_seconds) / steps


def measure(
    fn, *args, steps: int, static=(), repeats: int = 3, warmup: int = 1,
    make_args=None, **kwargs
) -> Timing:
    """Measure ``fn(*args, **kwargs)`` with compile/execute separation.

    For a jitted ``fn`` the AOT path (``lower(...).compile()``) isolates
    ``compile_s``, and the timed calls go through the compiled executable —
    which no longer takes the static arguments, so ``static`` lists their
    positional indices (keyword arguments are assumed static and baked in).
    Plain callables are timed the same way with ``compile_s = 0``.

    ``make_args``: required when ``fn`` donates input buffers (e.g. the
    streaming engines' carry state). Reusing one argument tuple across the
    warmup + every timed repeat would hand the executable buffers a previous
    call already consumed — an error on backends that reclaim them, silently
    stale state elsewhere. The thunk returns a fresh ``args`` tuple (full
    positional list; the ``static`` filter is applied to it too) and runs
    *before* the clock each repeat, with its outputs blocked on, so argument
    materialization never leaks into the timing. ``args`` then only shapes
    the trace/compile; the measured calls consume the thunk's buffers.
    """
    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    skip = set(static)
    filt = lambda a: tuple(x for i, x in enumerate(a) if i not in skip)
    if getattr(fn, "lower", None) is not None:
        t0 = time.perf_counter()
        compiled = fn.lower(*args, **kwargs).compile()
        compile_s = time.perf_counter() - t0
        if make_args is None:
            dyn = filt(args)
            prep = lambda: dyn
        else:
            prep = lambda: jax.block_until_ready(filt(make_args()))
        call = lambda a: compiled(*a)
    else:
        compile_s = 0.0
        if make_args is None:
            prep = lambda: args
        else:
            prep = lambda: jax.block_until_ready(make_args())
        call = lambda a: fn(*a, **kwargs)
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(call(prep()))
    times = []
    for _ in range(max(repeats, 1)):
        a = prep()
        t0 = time.perf_counter()
        jax.block_until_ready(call(a))
        times.append(time.perf_counter() - t0)
    return Timing(
        steps=int(steps),
        repeats=len(times),
        compile_s=compile_s,
        execute_s=min(times),
        mean_execute_s=sum(times) / len(times),
    )
