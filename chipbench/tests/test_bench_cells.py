"""``BENCHMARK.json`` resolves by name: every cell finds its configuration,
traffic mix, driver, scenario, reference kinds and metric readers in files
of their own, and a cell added as new files needs no edit elsewhere."""
import json
import shutil

import pytest

from chipbench import cells
from chipbench.tests import tiny

BENCH = cells.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_every_file_by_name(name):
    cell = cells.resolve(name)
    assert cell.config["name"] == next(w["config"] for w in BENCH["workloads"] if w["name"] == name)
    assert cells.load_module("drivers", cell.traffic["driver"]).Driver
    assert (cells.ROOT / "chipbench" / "scenarios" / f"{cell.traffic['scenario']}.py").is_file()
    for kind in cell.config["kinds"]:
        assert (cells.ROOT / "chipbench" / "reference" / f"{kind}.py").is_file()
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cells.load_module("metrics", m["name"]).read)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2 and cell.per_layer


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in BENCH[k]]
    assert len(names) == len(set(names))
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"]


def test_a_cell_added_as_new_files_is_picked_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(cells.ROOT / "chipbench", root / "chipbench")
    bench = json.loads(json.dumps(BENCH))
    cfg = dict(tiny.TREE_CONFIG, name="tree2_n50k", n_objects=50_000, widths=[4, 1],
               kinds=["plfu", "lru"], capacities=[1000, 5000], hot_size=[0, 0])
    (root / "chipbench/configs/tree2_n50k.json").write_text(json.dumps(cfg))
    mix = {"driver": "stream", "scenario": "stationary", "chunk_len": 512}
    (root / "chipbench/traffic/stationary_fast.json").write_text(json.dumps(mix))
    bench["configs"].append({"name": "tree2_n50k", "source": "https://arxiv.org/abs/2503.02504",
                             "file": "chipbench/configs/tree2_n50k.json", "reduced": [],
                             "why": "a new tree"})
    bench["workloads"].append({"name": "tree2_n50k.stationary_fast", "config": "tree2_n50k",
                               "traffic": "stationary_fast", "chips": 1, "why": "a new cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = cells.resolve("tree2_n50k.stationary_fast", root=root)
    assert cell.config["widths"] == [4, 1] and cell.traffic["chunk_len"] == 512
    assert cells.load_module("drivers", cell.traffic["driver"], root).Driver
    # and it runs, exact against the reference, with no other file touched
    small = tiny.tiny(cell)
    line = tiny.harness.run_cell(small, 5, 0.2, False, tiny.time.perf_counter())
    assert line["correct"], line["checks"]


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        cells.resolve("no_such.cell")
