"""The ARC stream cell (``arc_n100k.stream``) at a tiny size on the CPU: the
plain ARC reference agrees with the program's own oracle, the run is exact
and not correct under a broken timed path, and the directory stream driver
hands on the fast path's lane counters, ``None`` where a program lacks one."""
import contextlib
import types

import pytest

from chipbench import cells, control
from chipbench.reference import arc
from chipbench.tests import tiny

CELL = "arc_n100k.stream"


def test_config_is_the_sources_own():
    cfg = cells.resolve(CELL).config
    assert cfg["kinds"] == ["arc"] and cfg["widths"] == [1]
    assert (cfg["n_objects"], cfg["alpha"], cfg["capacities"]) == (100_000, 1.1, [2000])
    assert cfg["reduced"] == [] and "p0" in cfg["assumed"]
    entry = next(c for c in cells.load_benchmark()["configs"] if c["name"] == "arc_n100k")
    assert entry["reduced"] == []


@pytest.mark.parametrize("cap", [1, 2, 7, 40])
@pytest.mark.parametrize("scenario", ["stationary", "churn", "scan"])
def test_reference_agrees_with_the_programs_oracle(scenario, cap):
    """Hit for hit and in occupancy after every request, and in the directory
    at the end, against ``repro.core.policies.ARCCache``."""
    from repro import workloads
    from repro.core import policies

    n = 400
    trace = workloads.make_traces(scenario, n, 1, 4_000, seed=11 + cap)[0].tolist()
    want = policies.make_policy("arc", cap, n_objects=n)
    got = arc.Policy(cap)
    for t, x in enumerate(trace):
        assert got.request(x) == want.request(x), (scenario, cap, t)
        assert len(got) == len(want._t1) + len(want._t2), (scenario, cap, t)
    for mine, theirs in zip((got.t1, got.t2, got.b1, got.b2),
                            (want._t1, want._t2, want._b1, want._b2)):
        assert list(mine) == list(theirs)
    assert got.p == want.p
    assert len(got) + len(got.b1) + len(got.b2) <= 2 * cap


def test_tiny_run_reaches_a_full_directory_and_flip_is_caught():
    line = tiny.run(CELL)
    assert line["correct"] and line["failed"] == 0, line["checks"]
    assert all(c["value"] == 0 == c["limit"] for c in line["checks"].values())
    with control.fault("flip"):
        line = tiny.run(CELL)
    assert not line["correct"] and line["checks"]["decisions_differ"]["value"] >= 1


def _driver():
    cell = tiny.tiny(cells.resolve(CELL))
    return cells.load_module("drivers", "directory_stream").Driver(
        cell, tiny.SEED, lambda name: contextlib.nullcontext()
    )


def test_driver_hands_on_the_lane_counters():
    driver = _driver()
    warm = driver.setup()
    cap = driver.capacity[0]
    assert warm["edges_full"] and warm["directory"] == 2 * cap
    window = driver.window(0.2)
    driver.release()
    lanes_per_chunk = 2 * cap + driver.G  # the fast path's directory lanes
    assert window["lanes"] == window["chunks"] * lanes_per_chunk
    assert 0 < window["ghost_lanes"] < window["lanes_valid"] <= window["lanes"]
    pct = cells.load_module("metrics", "ghost_lane_pct").read(types.SimpleNamespace(window=window))
    assert pct == pytest.approx(100.0 * window["ghost_lanes"] / window["lanes"])
    # at steady state the directory holds c ghosts, every one a lane
    assert pct == pytest.approx(100.0 * cap / lanes_per_chunk)
    fill = cells.load_module("metrics", "lane_fill_pct").read(types.SimpleNamespace(window=window))
    assert 0 < fill <= 100


def test_driver_hands_on_none_for_a_missing_counter(monkeypatch):
    """A program whose ``StreamStats`` has no ``ghost_lanes`` (the level-major
    engine's parent, say) leaves it ``None``, and the metric out."""
    driver = _driver()
    stats = type(driver.stream).stats

    def without_ghosts(self, elapsed_s=None):
        st = stats(self, elapsed_s)
        return types.SimpleNamespace(**{k: v for k, v in vars(st).items() if k != "ghost_lanes"})

    monkeypatch.setattr(type(driver.stream), "stats", without_ghosts)
    driver.setup()
    window = driver.window(0.1)
    driver.release()
    assert window["ghost_lanes"] is None and window["lanes"] > 0
    read = cells.load_module("metrics", "ghost_lane_pct").read
    assert read(types.SimpleNamespace(window=window)) is None
    assert read(types.SimpleNamespace(window={})) is None
