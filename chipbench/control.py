"""Controls of the check that decides ``correct``: run a cell with its timed
path broken underneath and read the numbers the check compares. The
benchmark's own runs never do this.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --faults none,flip,stale,half --seconds 3

Faults, each patched into the program from outside for the run:

* ``flip``: one answer altered where it is produced (the first decision of
  every chunk, or of every sample of a replication, inverted). The
  configurations state that every decision equals the reference, so this
  breaks their guarantee by the least possible amount: the control.
* ``stale``: a step that returns its state unchanged (every push restores
  the carry it was given; a replication never fills its caches).
* ``half``: half of the batch left out (the second half of every chunk, or
  of every replication's samples, replaced by the first half's).
* ``none``: the program as it is, for the lower reading.

All runs of one call share a process, so the programs compile once. Each run
prints ``control <cell> fault=<f> seed=<n> correct=<bool> <check>=<value> ...``.
"""
from __future__ import annotations

import argparse
import contextlib
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (_ROOT / "src", _ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

FAULTS = ("none", "flip", "stale", "half")


@contextlib.contextmanager
def fault(name: str):
    """Patch ``FleetStream.push`` and ``jax_cache.simulate_batch`` with the
    fault ``name`` for the duration of the block."""
    import jax
    import jax.numpy as jnp

    from repro import fleet
    from repro.core import jax_cache

    push, batch = fleet.FleetStream.push, jax_cache.simulate_batch
    if name == "flip":
        def faulty_push(self, trace, assignment=None):
            out = push(self, trace, assignment)
            nh = out["node_hit"]
            return {**out, "node_hit": (nh[0].at[0, 0].set(~nh[0][0, 0]),) + tuple(nh[1:])}

        def faulty_batch(spec, traces, *a, **k):
            h = batch(spec, traces, *a, **k)
            return h.at[:, 0].set(~h[:, 0])
    elif name == "stale":
        def faulty_push(self, trace, assignment=None):
            saved = jax.tree.map(jnp.copy, self._carry)
            out = push(self, trace, assignment)
            self._carry = saved
            return out

        def faulty_batch(spec, traces, *a, **k):
            return jnp.zeros(traces.shape, bool)
    elif name == "half":
        def faulty_push(self, trace, assignment=None):
            h = trace.shape[0] // 2
            return push(self, jnp.concatenate([trace[:h], trace[:h]]), None)

        def faulty_batch(spec, traces, *a, **k):
            S = traces.shape[0]
            h = batch(spec, traces[: max(1, S // 2)], *a, **k)
            return jnp.tile(h, (-(-S // h.shape[0]), 1))[:S]
    elif name == "none":
        faulty_push, faulty_batch = push, batch
    else:
        raise ValueError(f"unknown fault {name!r}; expected one of {FAULTS}")
    fleet.FleetStream.push, jax_cache.simulate_batch = faulty_push, faulty_batch
    try:
        yield
    finally:
        fleet.FleetStream.push, jax_cache.simulate_batch = push, batch


def run(cell, seed: int, name: str, seconds: float) -> dict:
    """One run of ``cell`` under fault ``name``: its result line."""
    from chipbench import harness

    with fault(name):
        return harness.run_cell(cell, seed, seconds, False, time.perf_counter())


def main(argv=None) -> int:
    from chipbench import cells

    ap = argparse.ArgumentParser(description="Read the check's numbers under faults.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    for name in args.faults.split(","):
        for seed in (int(s) for s in args.seeds.split(",")):
            line = run(cell, seed, name, args.seconds)
            checks = " ".join(f"{k}={v['value']}" for k, v in line["checks"].items())
            print(f"control {cell.name} fault={name} seed={seed} "
                  f"correct={line['correct']} {checks}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
