"""The stage reduction (``chipbench/stages.py``) on a synthetic trace and on
a profile file written here, the existing reduction's readings left as they
were by the program's own spans and scopes, and the spans of
``FleetStream.push`` in a real CPU profile."""
import json
import types

import pytest

from chipbench import cells, stages, trace

MS = 1_000_000  # ns
STEP = "jit(fast_chunk)/repro.step/while"
VICTIM = "jit(fast_chunk)/repro.step/while/body/closed_call/repro.victim/reduce"


def synthetic() -> stages.Profile:
    # window 0..100 ms; two chunk programs, 2-40 and 45-90, each a selection,
    # the scan (a loop whose body ops overlap it) and a tail stage; the host
    # pushes in 39-46 (the program's push and dispatch inside) and blocks 88-100
    modules = [[("jit_fast_chunk(42)", 2 * MS, 40 * MS), ("jit_fast_chunk(42)", 45 * MS, 90 * MS)]]
    ops = [[("jit(fast_chunk)/repro.select/sort", 2 * MS, 3 * MS), (STEP, 3 * MS, 38 * MS),
            (VICTIM, 5 * MS, 6 * MS), (VICTIM, 10 * MS, 12 * MS),
            ("jit(fast_chunk)/repro.scatter/scatter", 38 * MS, 40 * MS),
            ("jit(fast_chunk)/repro.select/sort", 45 * MS, 46 * MS), (STEP, 46 * MS, 88 * MS),
            (VICTIM, 50 * MS, 51 * MS), ("jit(fast_chunk)/repro.roster/sort", 88 * MS, 90 * MS),
            ("jit(fast_chunk)/add", 89 * MS, 90 * MS)]]
    host = [("chipbench:window", 0, 100 * MS), ("chipbench:push", 39 * MS, 46 * MS),
            ("repro:push", 39 * MS, 45.5 * MS), ("repro:dispatch", 44 * MS, 45 * MS),
            ("chipbench:block", 88 * MS, 100 * MS), ("repro:push", 101 * MS, 102 * MS)]
    return stages.Profile(modules, ops, host)


def test_scopes_nest_and_loops_count_once():
    st = stages.reduce(synthetic())
    assert st.window_s == pytest.approx(0.1)
    assert st.scope_s == pytest.approx({"step": 0.077, "victim": 0.004, "select": 0.002,
                                        "scatter": 0.002, "roster": 0.002})
    assert st.scope_ops == {"step": 5, "victim": 3, "select": 2, "scatter": 1, "roster": 1}
    assert st.body_s == pytest.approx(st.scope_s)  # every loop here is named
    assert st.scope_s["victim"] < st.scope_s["step"]  # a subset of the scan
    table = st.table(requests=4096)
    assert list(table)[0] == "step"
    assert table["victim"]["ops_per_kreq"] == pytest.approx(3 * 1000 / 4096)
    assert stages.stages_of("jit(f)/vmap(repro.step)/while/body/repro.victim/x") == {"step", "victim"}


def test_a_loop_without_a_name_takes_its_body_scope():
    # as on the TPU: the loop carries no framework name, its condition op
    # names the scope around the scan, its body ops name the scan's scope
    body = "jit(fast_chunk)/repro.lanes/jit(searchsorted)/while/body/gather"
    ops = [("", 3 * MS, 38 * MS), (VICTIM, 5 * MS, 6 * MS), ("jit(fast_chunk)/lt", 7 * MS, 8 * MS),
           (VICTIM, 10 * MS, 12 * MS), ("", 39 * MS, 40 * MS),
           ("", 50 * MS, 60 * MS), (body, 51 * MS, 52 * MS)]
    prof = stages.Profile([[("jit_fast_chunk(42)", 2 * MS, 61 * MS)]], [ops],
                          [("chipbench:window", 0, 100 * MS)])
    st = stages.reduce(prof)
    assert st.scope_s == pytest.approx({"step": 0.035, "victim": 0.003, "lanes": 0.010})
    assert st.body_s == pytest.approx({"step": 0.003, "victim": 0.003, "lanes": 0.001})
    assert st.scope_ops == {"step": 3, "victim": 2, "lanes": 2}


def test_program_spans_and_the_gaps_they_overlap():
    st = stages.reduce(synthetic())
    # only the spans inside the window
    assert st.span_s == {"repro:push": [pytest.approx(0.0065)],
                         "repro:dispatch": [pytest.approx(0.001)]}
    # the gaps as trace.reduce names them, with the innermost program span
    assert st.gaps == [("host", None, pytest.approx(0.002)),
                       ("push", "repro:dispatch", pytest.approx(0.005)),
                       ("block", None, pytest.approx(0.010))]
    red = trace.reduce(trace.Trace(synthetic().modules, [[]],
                                   [h for h in synthetic().host if h[0].startswith("chipbench:")]))
    assert [(a, s) for a, _, s in st.gaps] == red.gaps


def _xspace(prof: stages.Profile, intern_paths: bool):
    """A serialized ``XSpace`` holding ``prof``: one chip plane whose
    operations carry their path in the ``tf_op`` stat (a string, or a
    reference to an interned one), and one host plane."""
    classes = stages._classes()
    space = classes["XSpace"]()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=1, value=dict(id=1, name="tf_op"))
    dev.stat_metadata.add(key=2, value=dict(id=2, name="hlo_category"))
    ids: dict = {}

    def meta(plane, name, path=None):
        if (plane.name, name) not in ids:
            k = len(ids) + 10
            ids[(plane.name, name)] = k
            entry = plane.event_metadata.add(key=k, value=dict(id=k, name=name))
            if path is not None:
                entry.value.stats.add(metadata_id=2, str_value="loop fusion")
                if intern_paths:
                    plane.stat_metadata.add(key=k + 1000, value=dict(id=k + 1000, name=path))
                    entry.value.stats.add(metadata_id=1, ref_value=k + 1000)
                else:
                    entry.value.stats.add(metadata_id=1, str_value=path)
        return ids[(plane.name, name)]

    def line(plane, name, events, op=False):
        ln = plane.lines.add(name=name, timestamp_ns=0)
        # an operation's event is named by its HLO instruction
        hlo = {n: f"%op.{i}" for i, n in enumerate(dict.fromkeys(n for n, _, _ in events))}
        for n, s, e in events:
            mid = meta(plane, hlo[n], n) if op else meta(plane, n)
            ln.events.add(metadata_id=mid, offset_ps=int(s * 1000), duration_ps=int((e - s) * 1000))

    line(dev, "XLA Modules", prof.modules[0])
    line(dev, "XLA Ops", prof.ops[0], op=True)
    line(space.planes.add(name="/host:CPU"), "python", prof.host)
    return space.SerializeToString()


@pytest.mark.parametrize("intern_paths", [False, True])
def test_loader_reads_operation_paths_from_a_profile_file(tmp_path, intern_paths):
    (tmp_path / "x.xplane.pb").write_bytes(_xspace(synthetic(), intern_paths))
    loaded = stages.load(tmp_path)
    assert {p for p, _, _ in loaded.ops[0]} == {p for p, _, _ in synthetic().ops[0]}
    assert {n for n, _, _ in loaded.host} == {n for n, _, _ in synthetic().host}
    assert stages.reduce(loaded).scope_s == pytest.approx(stages.reduce(synthetic()).scope_s)


def _read(metric, red):
    run = types.SimpleNamespace(setup_s=1.0, trace=red,
                                window={"requests": 4096, "wall_s": 0.1, "chunks": 2})
    return cells.load_module("metrics", metric).read(run)


def test_program_names_leave_the_benchmark_readings_as_they_were(tmp_path):
    plain = synthetic()
    plain.host = [h for h in plain.host if not h[0].startswith(stages.PROGRAM_PREFIX)]
    plain.ops = [[(f"%op{i}", s, e) for i, (_, s, e) in enumerate(plain.ops[0])]]
    for name, prof in (("named", synthetic()), ("plain", plain)):
        (tmp_path / name).mkdir()
        (tmp_path / name / "x.xplane.pb").write_bytes(_xspace(prof, False))
    named, bare = (trace.reduce(trace.load(tmp_path / n)) for n in ("named", "plain"))
    assert (named.busy_s, named.module_s, named.gaps) == (bare.busy_s, bare.module_s, bare.gaps)
    for metric in ("device_idle_pct", "engine_us_per_kreq", "device_idle_pct.stream",
                   "engine_us_per_kreq.stream"):
        assert _read(metric, named) == _read(metric, bare)


def test_cli_prints_the_stage_table(tmp_path, capsys):
    (tmp_path / "x.xplane.pb").write_bytes(_xspace(synthetic(), False))
    assert stages.main([str(tmp_path), "--requests", "4096"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["stages"]["step"]["s"] == pytest.approx(0.077)
    assert out["spans"]["repro:push"]["count"] == 1


def test_push_spans_nest_in_a_cpu_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro import fleet

    topo = fleet.tree(n_objects=400, widths=(2, 1), kinds=("lru", "plfu"),
                      capacities=(40, 80), router="hash")
    fs = fleet.FleetStream(fleet.StreamConfig(topo=topo, chunk_len=16))
    chunk = jnp.arange(16, dtype=jnp.int32)
    jax.block_until_ready(fs.push(chunk))  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("chipbench:window"):
            for _ in range(3):
                fs.push(chunk)
            fs.block()
            fs.stats()
    spans = [h for h in stages.load(tmp_path).host if h[0].startswith(stages.PROGRAM_PREFIX)]
    names = [n for n, _, _ in spans]
    for name in ("repro:push", "repro:route", "repro:dispatch"):
        assert names.count(name) == 3, names
    assert names.count("repro:sync") == 1
    assert "repro:stitch" not in names  # no telemetry, nothing kept
    pushes = [(s, e) for n, s, e in spans if n == "repro:push"]
    for n, s, e in spans:
        if n in ("repro:route", "repro:dispatch"):
            assert any(ps <= s and e <= pe for ps, pe in pushes), n
    assert len(stages.reduce(stages.load(tmp_path)).span_s["repro:push"]) == 3
