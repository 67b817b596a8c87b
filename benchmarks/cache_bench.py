"""Policy micro-benchmarks across the three implementation tiers:
Python reference (the paper's timed implementation), vectorised JAX scan, and
the Pallas kernel. ``ops.cache_sim`` compiles the kernel natively on a TPU and
runs the Pallas interpreter elsewhere; every kernel row names the backend it
ran on, and an interpreter timing says nothing about the chip."""
from __future__ import annotations

import numpy as np

from repro import telemetry
from repro.core import jax_cache, policies, registry, simulate, zipf


def python_reference(full: bool = False):
    n, cap = (10_000, 900) if full else (2_000, 180)
    tlen = zipf.PAPER_TRACE_LEN if full else 20_000
    trace = zipf.sample_trace(n, tlen, seed=0)
    rows = []
    for name in policies.POLICY_NAMES:
        pol = policies.make_policy(name, cap, n_objects=n)
        r = simulate.run_trace(pol, trace)
        rows.append(
            (f"cache_py/{name}", r.cpu_time_s / tlen * 1e6, f"CHR={r.chr:.4f} meta={r.metadata_entries}")
        )
    return rows


def jax_batched(full: bool = False):
    n, cap = (10_000, 900) if full else (2_000, 180)
    tlen = 20_000 if not full else 50_000
    samples = 4
    traces = zipf.sample_traces(n, n_samples=samples, trace_len=tlen, seed=1)
    rows = []
    from benchmarks.cdn_bench import policy_window

    for kind in registry.names(jax=True):
        spec = jax_cache.PolicySpec(
            kind=kind, n_objects=n, capacity=cap, window=policy_window(kind)
        )
        tr = telemetry.measure(
            jax_cache.simulate_batch, spec, traces, static=(0,), steps=tlen * samples
        )
        hits = jax_cache.simulate_batch(spec, traces)
        chr_ = float(np.asarray(hits).mean())
        rows.append(
            (
                f"cache_jax/{kind}",
                tr.us_per_step,
                tr.derived(CHR=f"{chr_:.4f}", samples=samples),
            )
        )
    return rows


def _kernel_kwargs(kind: str, cap: int) -> dict:
    """The sweep's non-default knobs: a wlfu window sized like the cdn bench,
    and small sketch params so aging/refresh actually fire mid-trace."""
    from benchmarks.cdn_bench import policy_window

    kw = {"window": policy_window(kind)}
    if kind == "tinylfu":
        kw["window"] = 10 * cap
    if kind == "plfua_dyn":
        kw["refresh"] = 10 * cap
    return kw


def _kernel_mode() -> str:
    """How ``ops.cache_sim`` runs the kernel on this backend."""
    import jax

    backend = jax.default_backend()
    return f"{backend}/{'native' if backend == 'tpu' else 'interpret'}"


def pallas_kernel(full: bool = False):
    from repro.kernels.cache_sim.ops import cache_sim

    n, cap, tlen = 512, 64, 2_000  # interpret mode is python-speed: keep small
    traces = zipf.sample_traces(n, n_samples=2, trace_len=tlen, seed=2)
    rows = []
    for kind in registry.names(pallas=True):
        kw = _kernel_kwargs(kind, cap)
        # the old loop timed the *first* call — compile folded into steps/sec;
        # measure() isolates compile_s and times only warmed, blocked calls
        tr = telemetry.measure(
            cache_sim, traces, kind=kind, n_objects=n, capacity=cap,
            steps=tlen * 2, repeats=1, **kw,
        )
        hits, _, _ = cache_sim(traces, kind=kind, n_objects=n, capacity=cap, **kw)
        rows.append(
            (
                f"cache_pallas/{kind}",
                tr.us_per_step,
                tr.derived(
                    CHR=f"{float(np.asarray(hits).sum()) / (tlen * 2):.4f}",
                    kernel_mode=_kernel_mode(),
                ),
            )
        )
    return rows


def kernel_vs_jax(full: bool = False):
    """Kernel-vs-jax steps-per-sec, one row per sketch-admission kind (wlfu
    rides along as the windowed non-sketch control). Both tiers run the same
    traces and must agree on hits. On a TPU the kernel is compiled natively;
    elsewhere it runs in the Pallas interpreter, so there only the jax column
    is a throughput of the backend. The row names which of the two it was."""
    from repro.kernels.cache_sim.ops import cache_sim

    n, cap = (2_000, 180) if full else (512, 64)
    tlen = 8_000 if full else 2_000
    samples = 2
    traces = zipf.sample_traces(n, n_samples=samples, trace_len=tlen, seed=3)
    steps = tlen * samples
    rows = []
    for kind in registry.names(sketch=True) + ("wlfu",):
        kw = _kernel_kwargs(kind, cap)
        spec = jax_cache.PolicySpec(kind=kind, n_objects=n, capacity=cap, **kw)

        tr_j = telemetry.measure(
            jax_cache.simulate_batch, spec, traces, static=(0,), steps=steps
        )
        args = dict(kind=kind, n_objects=n, capacity=cap, **kw)
        tr_k = telemetry.measure(cache_sim, traces, steps=steps, repeats=1, **args)

        hits_j = jax_cache.simulate_batch(spec, traces)
        hits_k, _, _ = cache_sim(traces, **args)
        assert int(np.asarray(hits_k).sum()) == int(
            np.asarray(hits_j).sum()
        ), f"kernel/jax hit divergence for {kind}"
        rows.append(
            (
                f"kernel_vs_jax/{kind}",
                tr_k.us_per_step,
                f"kernel={tr_k.steps_per_s:,.0f} steps/s jax={tr_j.steps_per_s:,.0f} steps/s "
                f"ratio={tr_k.steps_per_s / tr_j.steps_per_s:.3f} "
                f"kernel_compile_s={tr_k.compile_s:.3f} jax_compile_s={tr_j.compile_s:.3f} "
                f"kernel_mode={_kernel_mode()}",
            )
        )
    return rows


def _scan_of(spec: jax_cache.PolicySpec, batch: int):
    """A scan of ``jax_cache.step`` from the empty cache over a ``(steps,)``
    trace, vmapped over ``(batch, steps)`` traces where ``batch > 1``."""
    import jax

    def scan(tr):
        return jax.lax.scan(
            lambda s, x: jax_cache.step(spec, s, x), jax_cache.init_state(spec), tr
        )[1]

    return scan if batch == 1 else jax.vmap(scan)


def step_lanes(full: bool = False):
    """The crossover ``jax_cache._ONE_HOT_LANES`` is set from: us a serial
    step of a plfua scan (the paper's C 2,000 and hot set 4,000, or 2% and
    4% of the row reduced) with one-hot and with index lane access, by row
    length, unbatched and vmapped. Both forms must give the same hits."""
    import jax

    lanes = (4_096, 8_192, 16_384, 32_768, 65_536, 100_000) if full else (256, 1_024)
    batches = (1, 8, 12) if full else (1, 4)
    steps = 2_048 if full else 128
    rows = []
    for n in lanes:
        cap = 2_000 if full else n // 50
        spec = jax_cache.PolicySpec("plfua", n_objects=n, capacity=cap, hot_size=2 * cap)
        for batch in batches:
            traces = zipf.sample_traces(n, n_samples=batch, trace_len=steps, seed=n + batch)
            traces = traces[0] if batch == 1 else traces
            us, hits = {}, {}
            for form, limit in (("onehot", n), ("index", n - 1)):
                saved, jax_cache._ONE_HOT_LANES = jax_cache._ONE_HOT_LANES, limit
                try:
                    fn = jax.jit(_scan_of(spec, batch))
                    tr = telemetry.measure(fn, traces, steps=steps, repeats=5)
                    hits[form] = np.asarray(fn(traces))
                finally:
                    jax_cache._ONE_HOT_LANES = saved
                us[form] = tr.us_per_step
            same = bool((hits["onehot"] == hits["index"]).all())
            assert same, f"one-hot and index forms differ at {n} lanes, batch {batch}"
            for form in us:
                rows.append((
                    f"step_lanes/{form}/n{n}/b{batch}", us[form],
                    f"index_over_onehot={us['index'] / us['onehot']:.3f} "
                    f"chr={hits[form].mean():.4f} backend={jax.default_backend()}",
                ))
    return rows


ALL = {
    "cache_py": python_reference,
    "cache_jax": jax_batched,
    "cache_pallas": pallas_kernel,
    "kernel_vs_jax": kernel_vs_jax,
    "step_lanes": step_lanes,
}
