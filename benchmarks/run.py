"""Benchmark entry point: one function per paper table/figure + the roofline,
serving-energy and fleet tables. Prints ``name,us_per_call,derived`` CSV.

    PYTHONPATH=src python -m benchmarks.run              # reduced scale
    PYTHONPATH=src python -m benchmarks.run --full       # the paper's grid
    PYTHONPATH=src python -m benchmarks.run --only fig4
    PYTHONPATH=src python -m benchmarks.run --only fleet_policies,fleet_scale \
        --record BENCH_PR3.json                          # perf trajectory
    PYTHONPATH=src python -m benchmarks.run --compare BENCH_PR5.json --strict

``--record`` additionally writes every produced row (plus the run
configuration) to a JSON file — the regression trail benchmark PRs check in.
``--compare BASELINE.json`` diffs the produced rows against a recorded
baseline (benchmarks.compare: CHR drops and throughput cliffs); report-only
unless ``--strict``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--full", action="store_true", help="paper-scale grids (slow)")
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated group list (fig2..fig11, metadata, cache_py, "
        "cache_jax, cache_pallas, kernel_vs_jax, step_lanes, cdn, cdn_router, "
        "cdn_topo, fleet_policies, fleet_depth, fleet_placement, fleet_scale, "
        "cache_sizes, fleet_bytes, cache_scan, fleet_scan, fleet_stream, "
        "serving_energy, roofline, cache_roofline, telemetry_timing, "
        "telemetry_overhead, telemetry_tenants) — see docs/benchmarks.md",
    )
    ap.add_argument(
        "--record",
        default=None,
        metavar="PATH",
        help="also write the rows as JSON (perf-regression trail)",
    )
    ap.add_argument(
        "--compare",
        default=None,
        metavar="BASELINE",
        help="diff produced rows against a recorded baseline JSON "
        "(report-only unless --strict)",
    )
    ap.add_argument(
        "--strict",
        action="store_true",
        help="with --compare: exit non-zero on regression",
    )
    ap.add_argument("--chr-tol", type=float, default=None,
                    help="override compare's absolute CHR-drop tolerance")
    ap.add_argument("--perf-tol", type=float, default=None,
                    help="override compare's relative throughput tolerance")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    baseline = None
    if args.compare is not None:
        # load before running: --record may legitimately overwrite the file
        # being compared against (refreshing the trail in one invocation)
        with open(args.compare) as fh:
            baseline = json.load(fh)

    from benchmarks import (
        bytes_bench,
        cache_bench,
        cdn_bench,
        fleet_bench,
        paper_figs,
        roofline_bench,
        scan_bench,
        serving_energy,
        stream_bench,
        telemetry_bench,
    )

    groups: dict = {}
    groups.update(paper_figs.ALL)
    groups.update(cache_bench.ALL)
    groups.update(cdn_bench.ALL)
    groups.update(fleet_bench.ALL)
    groups.update(bytes_bench.ALL)
    groups.update(scan_bench.ALL)
    groups.update(stream_bench.ALL)
    groups.update(serving_energy.ALL)
    groups.update(roofline_bench.ALL)
    groups.update(telemetry_bench.ALL)

    if args.only is None:
        selected = groups
    else:
        names = [g.strip() for g in args.only.split(",") if g.strip()]
        unknown = [g for g in names if g not in groups]
        if unknown:
            sys.exit(
                f"unknown group(s) {unknown}; choose from: {', '.join(groups)}"
            )
        selected = {g: groups[g] for g in names}
    recorded: list[dict] = []
    failed: list[str] = []
    print("name,us_per_call,derived")
    for gname, fn in selected.items():
        t0 = time.time()
        try:
            rows = fn(full=args.full)
        except Exception as e:  # pragma: no cover
            # keep the failure visible everywhere the results go: CSV row,
            # recorded JSON, and (below) a non-zero exit for CI
            derived = f"{type(e).__name__}: {e}"
            print(f"{gname}/ERROR,0,{derived}")
            recorded.append(
                {"group": gname, "name": f"{gname}/ERROR", "us_per_call": 0.0,
                 "derived": derived}
            )
            failed.append(gname)
            continue
        for name, us, derived in rows:
            print(f'{name},{us:.3f},"{derived}"')
            recorded.append(
                {"group": gname, "name": name, "us_per_call": us, "derived": derived}
            )
            if name.endswith("/ERROR"):  # per-row failures (e.g. a scaling
                failed.append(name)  # subprocess) must fail the run too
        print(f"# {gname}: {time.time() - t0:.1f}s", file=sys.stderr)

    if args.record is not None:
        payload = {
            "config": {"full": args.full, "groups": sorted(selected)},
            "rows": recorded,
        }
        with open(args.record, "w") as f:
            json.dump(payload, f, indent=1)
            f.write("\n")
        print(f"# recorded {len(recorded)} rows -> {args.record}", file=sys.stderr)
    if baseline is not None:
        from benchmarks import compare as bench_compare

        tols = {}
        if args.chr_tol is not None:
            tols["chr_tol"] = args.chr_tol
        if args.perf_tol is not None:
            tols["perf_tol"] = args.perf_tol
        regs, notes = bench_compare.compare(
            baseline, {"rows": recorded}, **tols
        )
        code = bench_compare.report(regs, notes, strict=args.strict)
        if code:
            failed.append(f"compare vs {args.compare}")
    if failed:
        sys.exit(f"benchmark group(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
