"""Cells of ``BENCHMARK.json`` cut to a size the CPU runs in a second, with
the sizes steered here (never through an option of the benchmark)."""
import dataclasses
import time

from chipbench import cells, harness

SEED = 2**40 + 12345  # past 32 bits, as the driver's seeds are

#: a 3-tier tree (no cell of the benchmark yet), so that the stream driver's
#: level-major engine and the reference's tree replay stay exercised
TREE_CONFIG = {"name": "tree3", "n_objects": 100000, "alpha": 1.1, "widths": [8, 2, 1],
               "kinds": ["lru", "plfu", "plfu"], "capacities": [2000, 5493, 25000],
               "hot_size": [0, 0, 0], "router": "hash"}


def tree_cell() -> cells.Cell:
    """The stream cell with its flat cache swapped for ``TREE_CONFIG``."""
    return dataclasses.replace(cells.resolve("plfua_n100k.stream"), name="tree3.stationary",
                               config=dict(TREE_CONFIG))


def tiny(cell: cells.Cell) -> cells.Cell:
    cfg = dict(cell.config)
    cfg["n_objects"] = 3000
    cfg["capacities"] = [max(4, c // 100) for c in cfg["capacities"]]
    cfg["hot_size"] = [2 * c if h else 0 for c, h in zip(cfg["capacities"], cfg["hot_size"])]
    traffic = dict(cell.traffic)
    if "chunk_len" in traffic:
        traffic.update(chunk_len=256)
    if "sample_len" in traffic:
        traffic.update(n_samples=3, sample_len=2000)
    return dataclasses.replace(cell, config=cfg, traffic=traffic)


def run(cell, seconds: float = 0.3, trace: bool = False, seed: int = SEED) -> dict:
    """One tiny run of ``cell``, a name in ``BENCHMARK.json`` or a ``Cell``."""
    cell = cells.resolve(cell) if isinstance(cell, str) else cell
    stream = cells.load_module("drivers", "stream")
    cap, stream.WARM_MAX_CHUNKS = stream.WARM_MAX_CHUNKS, 8  # a fault may keep edges empty
    try:
        return harness.run_cell(tiny(cell), seed, seconds, trace, time.perf_counter())
    finally:
        stream.WARM_MAX_CHUNKS = cap
