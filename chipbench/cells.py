"""Resolve a cell of ``BENCHMARK.json`` into its configuration, traffic mix
and metrics, and load the named pieces (driver, scenario, reference kind,
metric reader) from their own files. Nothing here knows any cell by name."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    """One workload entry with everything it refers to loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple  # the end-to-end metric entries this cell reports
    per_layer: tuple  # the per-layer metric entries this cell reports
    root: pathlib.Path = ROOT


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration and traffic files read."""
    bench = load_benchmark(root)
    entry = _by_name(bench["workloads"], name, "workload")
    cfg_entry = _by_name(bench["configs"], entry["config"], "configuration")
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(root / "chipbench" / "traffic" / f"{entry['traffic']}.json") as f:
        traffic = json.load(f)
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        root=root,
    )


def load_module(kind: str, name: str, root: pathlib.Path = ROOT):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots, so
    the file is loaded by path rather than imported by dotted name)."""
    path = root / "chipbench" / kind / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no {kind} file {path.relative_to(root)}")
    mod_name = "chipbench_file_" + re.sub(r"\W", "_", str(path))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod
