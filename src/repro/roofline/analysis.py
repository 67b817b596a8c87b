"""Roofline terms from a compiled (SPMD-partitioned) module.

The dry-run compiles each (arch x shape x mesh) cell against 512 host devices;
``compiled.as_text()`` is then the *per-device* HLO program, so every operand
shape is already per-device and collective bytes can be summed directly with
ring-model factors. ``compiled.cost_analysis()`` provides per-device FLOPs and
bytes-accessed.

Terms (v5e):
    compute    = flops_per_dev / 197e12
    memory     = bytes_per_dev / 819e9
    collective = sum(ring_bytes(op) for op in HLO) / 50e9   (per-link, 1 link)
Cross-pod (DCN) collectives are reported separately with a 25 GB/s/host
assumption (pod axis appears only in the multi-pod mesh).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s
ICI_BW = 50e9  # bytes/s per link (~one link assumed: conservative)
DCN_BW = 25e9  # bytes/s per host across pods (assumption, documented)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[")
_LIST_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(shape_str: str) -> int:
    """Sum byte sizes of all array shapes in an HLO result-type string."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        return int(m.group(2))  # [num_groups, group_size]<=[...]
    m = _LIST_GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return 2  # unknown format: conservative non-trivial group


@dataclasses.dataclass
class CollectiveStats:
    counts: dict
    raw_bytes: dict  # per-device operand/result bytes by op kind
    ring_bytes: float  # ring-model bytes actually serialised on the wire

    def total_raw(self) -> float:
        return float(sum(self.raw_bytes.values()))


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: dict = defaultdict(int)
    raw: dict = defaultdict(float)
    ring = 0.0
    for line in hlo_text.splitlines():
        ls = line.strip()
        # match ' = <shape> <op>(' to catch result-typed collective ops
        m = re.search(r"=\s+((?:\([^)]*\)|\S+))\s+(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(", ls)
        if not m:
            continue
        if "-done(" in ls:
            continue  # paired with -start; count once
        shape_str, op = m.group(1), m.group(2)
        nbytes = _shape_bytes(shape_str)
        g = _group_size(ls)
        if g <= 1:
            continue
        counts[op] += 1
        raw[op] += nbytes
        if op == "all-reduce":
            ring += 2.0 * nbytes * (g - 1) / g
        elif op in ("all-gather", "reduce-scatter", "all-to-all"):
            ring += nbytes * (g - 1) / g
        else:  # collective-permute: single hop
            ring += nbytes
    return CollectiveStats(dict(counts), dict(raw), ring)


@dataclasses.dataclass
class Roofline:
    flops_per_dev: float
    bytes_per_dev: float
    collective_ring_bytes: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    collectives: CollectiveStats
    model_flops_global: float = 0.0
    n_devices: int = 1

    @property
    def step_s(self) -> float:
        """Roofline step time: terms overlap at best, so lower bound = max."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_ratio(self) -> float:
        hlo_global = self.flops_per_dev * self.n_devices
        return self.model_flops_global / hlo_global if hlo_global else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of peak the dominant-term step time achieves on useful
        model FLOPs: (model_flops/chips/step_s) / peak."""
        if self.step_s == 0:
            return 0.0
        return (self.model_flops_global / self.n_devices / self.step_s) / PEAK_FLOPS

    def row(self) -> dict:
        return {
            "flops_per_dev": self.flops_per_dev,
            "bytes_per_dev": self.bytes_per_dev,
            "coll_ring_bytes": self.collective_ring_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "step_s": self.step_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops_global,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
            "collective_counts": self.collectives.counts,
            "collective_raw_bytes": self.collectives.raw_bytes,
        }


def cost_dict(cost) -> dict:
    """``compiled.cost_analysis()`` as a dict (it may be None)."""
    return cost or {}


def analyze(
    hlo_text: str,
    cost: dict,
    *,
    n_devices: int,
    model_flops_global: float = 0.0,
) -> Roofline:
    """Derive the three terms from the per-device HLO.

    XLA:CPU's cost_analysis counts while bodies once (tests/test_roofline.py
    calibrates this), so the primary source is the structural model in
    roofline/hlo_model.py, which multiplies loop bodies by their trip counts.
    The raw cost_analysis numbers are kept as a cross-check lower bound.
    """
    from repro.roofline import hlo_model

    mc = hlo_model.module_cost(hlo_text)
    cost = cost_dict(cost)
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    flops = max(mc.flops, xla_flops)
    nbytes = max(mc.traffic_bytes, xla_bytes)
    coll = CollectiveStats(
        {k: int(v) for k, v in mc.coll_counts.items()}, dict(mc.coll_raw), mc.coll_ring_bytes
    )
    compute_s = flops / PEAK_FLOPS
    memory_s = nbytes / HBM_BW
    collective_s = coll.ring_bytes / ICI_BW
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return Roofline(
        flops_per_dev=flops,
        bytes_per_dev=nbytes,
        collective_ring_bytes=coll.ring_bytes,
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=collective_s,
        dominant=dominant,
        collectives=coll,
        model_flops_global=model_flops_global,
        n_devices=n_devices,
    )


def model_flops(n_params: float, n_active: float, tokens: float, kind: str) -> float:
    """6ND train (fwd+bwd), 2ND prefill/decode; MoE uses active params."""
    n = n_active
    return (6.0 if kind == "train" else 2.0) * n * tokens
