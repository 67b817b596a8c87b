"""Device microseconds of the policy engine per 1,000 simulated requests:
every program of the traced window except the benchmark's traffic program
(``FleetStream`` chunk programs and routing, ``jax_cache.simulate_batch``)."""
from chipbench.generator import MODULE


def read(run):
    t = run.trace
    if t is None or not run.window["requests"]:
        return None
    engine_s = sum(s for name, s in t.module_s.items() if name != MODULE)
    return engine_s * 1e6 / (run.window["requests"] / 1000) if engine_s > 0 else None
