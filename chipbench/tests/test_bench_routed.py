"""The routed stream cell (``edge8_origin4_n100k.stream``) at a tiny size on
the CPU: exact against its plain reference, not correct under a broken timed
path, the reference routing as the program does, and ``lane_fill_pct``
reading the driver's lane counters."""
import contextlib
import types

import numpy as np
import pytest

from chipbench import cells, control
from chipbench.reference import routed_fleet
from chipbench.tests import tiny

CELL = "edge8_origin4_n100k.stream"


def test_config_states_its_edge_count():
    cfg = cells.resolve(CELL).config
    assert (cfg["n_edges"], cfg["n_origin_nodes"]) == (8, 4)
    assert "widths" not in cfg  # the routed driver builds them from the two counts
    assert set(cfg["reduced"]) == {"n_objects", "n_edges"}
    assert cfg["published"]["edge_caches"].startswith("nine")
    assert cfg["routers"] == ["sticky", "hash"] and cfg["session_len"] == 64


def test_tiny_routed_cell_is_exact():
    line = tiny.run(CELL)
    assert line["correct"] and line["failed"] == 0, line["checks"]
    assert set(line["checks"]) == {"decisions_differ", "node_requests_off", "node_hits_off",
                                   "occupancy_off", "origin_off"}
    assert all(c["value"] == 0 == c["limit"] for c in line["checks"].values())


@pytest.mark.parametrize("fault", ["flip", "stale", "half"])
def test_a_broken_routed_stream_is_not_correct(fault):
    with control.fault(fault):
        line = tiny.run(CELL)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


_TREES = {
    "sticky_hash": ([8, 4], ["sticky", "hash"]),
    "round_robin_sticky_tree": ([4, 2, 1], ["round_robin", "sticky", "tree"]),
    "hash_round_robin_hash": ([3, 2, 2], ["hash", "round_robin", "hash"]),
}


@pytest.mark.parametrize("name", sorted(_TREES))
def test_reference_routes_as_the_program_does(name):
    """The plain replay equals the program's own oracle
    (``fleet.simulate_fleet_reference``) fed the device router's edge
    assignment: per level the node and the hit of every request."""
    import jax.numpy as jnp

    from repro import fleet, workloads
    from repro.cdn import router
    from repro.fleet.reference import simulate_fleet_reference

    widths, routers = _TREES[name]
    n, L = 500, len(widths)
    cfg = {"n_objects": n, "widths": widths, "kinds": ["plfua"] + ["plfu"] * (L - 1),
           "capacities": [6] + [15] * (L - 1), "hot_size": [12] + [0] * (L - 1),
           "routers": routers, "session_len": 24}
    topo = fleet.tree(n_objects=n, widths=tuple(widths), kinds=tuple(cfg["kinds"]),
                      capacities=tuple(cfg["capacities"]), hot_size=tuple(cfg["hot_size"]),
                      routers=tuple(routers), session_len=24)
    trace = workloads.make_traces("stationary", n, 1, 1_000, seed=5)[0]
    edge = np.asarray(router.route_device(jnp.asarray(trace), widths[0], routers[0],
                                          session_len=24))
    want = simulate_fleet_reference(topo, trace, edge)
    assigns = fleet.level_assignments(topo, trace, edge)
    rep = routed_fleet.replay(cfg, trace)
    for l in range(L):
        np.testing.assert_array_equal(rep.node[l], assigns[l], err_msg=f"level {l} node")
        np.testing.assert_array_equal(rep.served == l, want.level_hit[l], err_msg=f"level {l} hit")


def test_lane_fill_pct_reads_the_drivers_counters():
    cell = tiny.tiny(cells.resolve(CELL))
    driver = cells.load_module("drivers", "routed_stream").Driver(
        cell, tiny.SEED, lambda name: contextlib.nullcontext()
    )
    driver.setup()
    window = driver.window(0.2)
    driver.release()
    G, chunks = driver.G, window["chunks"]
    assert window["lanes"] == chunks * G * 12
    # each of the window's requests is active at its edge, and at one origin
    # node where the edge missed
    ref = routed_fleet.replay(driver.config, driver.ids)
    served = ref.served[driver.window_from * G:]
    assert len(served) == chunks * G
    assert window["lanes_valid"] == len(served) + int((served >= 1).sum())
    read = cells.load_module("metrics", "lane_fill_pct").read
    fill = read(types.SimpleNamespace(window=window))
    assert fill == pytest.approx(100.0 * window["lanes_valid"] / window["lanes"])
    assert 100.0 / 12 <= fill <= 200.0 / 12
    # a program without the counters leaves the metric out
    assert read(types.SimpleNamespace(window={"lanes": None, "lanes_valid": None})) is None
    assert read(types.SimpleNamespace(window={})) is None
