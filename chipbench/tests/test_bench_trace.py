"""The trace reduction on a synthetic trace (busy union, attribution by
module, idle gaps by host span), the per-layer readers on it, and the loader
on a real profile recorded here on the CPU (host spans, no chip plane)."""
import types

import pytest

from chipbench import cells, trace

MS = 1_000_000  # ns


def synthetic() -> trace.Trace:
    # window 0..100 ms; traffic 0-2, engine 2-40 and 45-90 (overlapping op
    # pieces), a 5 ms gap while the host pushed, a 10 ms gap while it blocked
    modules = [[("jit_chipbench_traffic", 0, 2 * MS), ("jit_chunk_fn", 2 * MS, 40 * MS),
                ("jit_chunk_fn", 45 * MS, 90 * MS), ("jit_chunk_fn", 80 * MS, 90 * MS)]]
    ops = [[("%while.1", 2 * MS, 40 * MS), ("%fusion.2", 3 * MS, 4 * MS),
            ("%while.1", 45 * MS, 90 * MS), ("%gen", 0, 2 * MS)]]
    host = [("chipbench:window", 0, 100 * MS), ("chipbench:push", 39 * MS, 46 * MS),
            ("chipbench:block", 88 * MS, 100 * MS)]
    return trace.Trace(modules, ops, host)


def test_busy_union_modules_and_gaps():
    red = trace.reduce(synthetic())
    assert red.n_chips == 1
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s == pytest.approx(0.085)  # 2 + 38 + 45 ms; the overlap counts once
    assert red.module_s == pytest.approx({"jit_chipbench_traffic": 0.002, "jit_chunk_fn": 0.083})
    assert red.gaps == [("push", pytest.approx(0.005)), ("block", pytest.approx(0.010))]
    assert red.op_s["jit_chunk_fn/%while.1"] == pytest.approx(0.083)
    assert red.op_s["jit_chipbench_traffic/%gen"] == pytest.approx(0.002)
    br = trace.breakdown(red, top=2)
    assert [n for n, _ in br["device_ops"]] == ["jit_chunk_fn/%while.1", "jit_chipbench_traffic/%gen"]
    assert br["idle_gaps"][0] == ["block", pytest.approx(0.010)]


def test_window_clips_events_and_names_strip_fingerprints():
    t = synthetic()
    t.host[0] = ("chipbench:window", 10 * MS, 50 * MS)
    t.modules[0].append(("jit_x(123456)", 41 * MS, 42 * MS))
    red = trace.reduce(t)
    assert red.busy_s == pytest.approx(0.036)  # 10-40, 41-42, 45-50
    assert red.module_s["jit_x"] == pytest.approx(0.001)
    assert trace.module_name("jit_chunk_fn(11538878696163622094)") == "jit_chunk_fn"
    assert trace.op_name("%fusion.20 = (pred[1]) fusion(...)") == "%fusion.20"


def test_a_trace_without_its_window_span_is_refused():
    t = synthetic()
    t.host = t.host[1:]
    with pytest.raises(ValueError):
        trace.reduce(t)


def _read(metric, **run):
    return cells.load_module("metrics", metric).read(types.SimpleNamespace(**run))


def test_readers_on_the_synthetic_window():
    red = trace.reduce(synthetic())
    window = {"requests": 4096, "wall_s": 0.1, "chunks": 2, "lags_s": [0.04] * 19 + [0.08],
              "push_s": [0.001, 0.003]}
    run = dict(setup_s=12.5, window=window, trace=red)
    assert _read("device_idle_pct", **run) == pytest.approx(15.0)
    assert _read("engine_us_per_kreq", **run) == pytest.approx(0.083e6 / 4.096)
    assert _read("host_push_us_per_chunk", **run) == pytest.approx(2000.0)
    assert _read("sim_req_per_s", **run) == pytest.approx(40960.0)
    assert _read("chunk_lag_p95_ms", **run) == pytest.approx(42.0)
    assert _read("setup_s", **run) == 12.5
    # the stream cells' metrics of the same name read the same
    for metric in ("sim_req_per_s", "device_idle_pct", "engine_us_per_kreq"):
        assert _read(f"{metric}.stream", **run) == _read(metric, **run)
    # nothing to read: the reader returns nothing, never 0
    bare = dict(setup_s=1.0, window={"requests": 10, "wall_s": 1.0, "chunks": 1}, trace=None)
    for metric in ("device_idle_pct", "engine_us_per_kreq", "host_push_us_per_chunk",
                   "chunk_lag_p95_ms", "device_idle_pct.stream", "engine_us_per_kreq.stream"):
        assert _read(metric, **bare) is None


def test_loader_reads_host_spans_of_a_cpu_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("chipbench:window"):
            with jax.profiler.TraceAnnotation("chipbench:push"):
                jax.block_until_ready(jnp.arange(8) * 2)
    t = trace.load(tmp_path)
    names = [n for n, _, _ in t.host]
    assert "chipbench:window" in names and "chipbench:push" in names
    red = trace.reduce(t)
    assert red.n_chips == 0 and red.busy_s == 0 and red.window_s > 0
    # a profile that misses the chip reads all idle, not nothing
    assert _read("device_idle_pct", setup_s=1.0, window={}, trace=red) == 100.0


def test_own_parser_agrees_with_jax_profile_data(tmp_path):
    import jax
    import jax.numpy as jnp

    from chipbench import xplane

    with jax.profiler.trace(str(tmp_path)):
        for i in range(3):
            with jax.profiler.TraceAnnotation(f"chipbench:step{i}"):
                jax.block_until_ready(jnp.arange(8) * i)
    path = next(tmp_path.rglob("*.xplane.pb"))
    theirs = sorted((e.start_ns, e.duration_ns, e.name)
                    for p in jax.profiler.ProfileData.from_file(str(path)).planes
                    for ln in p.lines for e in ln.events if e.name.startswith("chipbench:"))
    space = xplane.parse(path.read_bytes())
    origin = xplane.origin_ns(space)
    ours = sorted((s, e - s, n) for p in space.planes for ln in p.lines
                  for n, s, e in xplane.events(p, ln, origin) if n.startswith("chipbench:"))
    assert [(d, n) for _, d, n in ours] == [(d, n) for _, d, n in theirs] and len(ours) == 3
    shift = {round(a[0] - b[0]) for a, b in zip(theirs, ours)}
    assert len(shift) == 1  # one clock, another origin
