"""Each driver at a tiny size on the CPU: the run is exact against the plain
reference, and it comes out not correct when the timed path underneath is
broken (one answer altered, state left unchanged, half the batch left out)."""
import numpy as np
import pytest

from chipbench import cells, control
from chipbench.reference import fleet as fleet_ref
from chipbench.tests import tiny

CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]
#: one cell of each engine the benchmark drives (the tree: the level-major one)
ENGINES = ["plfua_n100k.stream", "tree3.stationary", "plfua_n100k.replications"]


def _cell(name):
    return tiny.tree_cell() if name == "tree3.stationary" else cells.resolve(name)


@pytest.mark.parametrize("name", CELLS + ["tree3.stationary"])
def test_tiny_run_matches_the_reference(name):
    cell = _cell(name)
    line = tiny.run(cell)
    assert line["correct"] and line["failed"] == 0, line["checks"]
    assert line["attempted"] > 0 and list(line)[-1] == "checks"
    assert all(c["value"] == 0 == c["limit"] for c in line["checks"].values())
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("fault", ["flip", "stale", "half"])
@pytest.mark.parametrize("name", ENGINES)
def test_a_broken_timed_path_is_not_correct(name, fault):
    with control.fault(fault):
        line = tiny.run(_cell(name))
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_a_single_flipped_decision_fails_the_comparison():
    with control.fault("flip"):
        line = tiny.run("plfua_n100k.stream")
    # one decision a chunk is inverted, and each is caught
    chunks = line["attempted"] // 256
    assert line["checks"]["decisions_differ"]["value"] >= chunks >= 1


def test_reference_tree_routes_by_hash_and_parent_map():
    cfg = {"n_objects": 1000, "widths": [8, 2, 1], "kinds": ["lru", "plfu", "plfu"],
           "capacities": [1, 8, 16], "hot_size": [0, 0, 0], "router": "hash"}
    ids = np.arange(64)
    edge, mid, root = fleet_ref.assignments(cfg, ids)
    assert set(edge.tolist()) == set(range(8))
    assert (mid == edge // 4).all() and (root == 0).all()
    a, b = ids[edge == edge[0]][:2]
    rep = fleet_ref.replay(cfg, np.array([a, b, a, a]))
    # b evicts a from their one-slot edge; a then hits its parent, then the edge
    assert rep.served.tolist() == [3, 3, 1, 0] and rep.origin == 2
    assert rep.counters(0)["requests"][edge[0]] == 4 and rep.counters(1)["hits"] == [1, 0]
