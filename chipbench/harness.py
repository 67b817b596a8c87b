"""One run of one cell: set up, measure for ``--seconds``, check against the
plain reference, print the result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` a profiled window of at most ``TRACE_SECONDS`` gives the
per-layer metrics, ``busy_s``/``window_s`` and a breakdown. Every number the
check compares is printed beside its limit, as the last lines of standard
error and under ``checks``, the last key of the line. The run refuses (exit
2, no line) where JAX finds no TPU or fewer chips than the cell asks for."""
from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import sys
import tempfile
import time
import types

from chipbench import cells

#: longest profiled window: the TPU profiler records every operation, loop
#: bodies included (1 to 2 million events a second in these cells), and
#: takes some 33 us an event to stop (20 s for 4 chunks of the tree, TPU v5e)
TRACE_SECONDS = 0.5


class CompileCounter:
    """XLA compilations and persistent-cache loads, from JAX's monitoring
    events, counted from ``mark()`` on."""

    def __init__(self):
        import jax

        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        self.compiles = self.cache_hits = 0

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed directory of the
    checkout (``.jax_cache``, the program's own default), whatever the
    environment says, holding every program however fast it compiled and
    evicting none: only a checkout's first run of a cell compiles, and two
    checkouts share nothing."""
    import jax

    path = str(cells.ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def _span_factory(enabled: bool):
    import jax

    def span(name):
        if not enabled:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(f"chipbench:{name}")

    return span


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool, t_start: float) -> dict:
    """Drive the cell once and return the result line (no device check)."""
    cache = enable_compile_cache()
    counter = CompileCounter()
    try:
        return _run(cell, seed, seconds, trace, t_start, cache, counter)
    finally:
        counter.close()


def _run(cell, seed, seconds, trace, t_start, cache, counter) -> dict:
    import jax

    from chipbench import trace as trace_mod

    span = _span_factory(trace)
    driver = cells.load_module("drivers", cell.traffic["driver"], cell.root).Driver(
        cell, seed, span
    )
    warm = driver.setup()
    setup_s = time.perf_counter() - t_start
    print(f"setup_s={setup_s} compiles={counter.compiles} "
          f"cache_hits={counter.cache_hits} compile_cache={cache} "
          + " ".join(f"{k}={v}" for k, v in warm.items()), flush=True)
    counter.mark()
    reduced = None
    if trace:
        prof_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
        try:
            with span("window"):
                window = driver.window(min(seconds, TRACE_SECONDS))
        finally:
            jax.profiler.stop_trace()
    else:
        window = driver.window(seconds)
    lags = sorted(window.get("lags_s", [0.0]))
    print(f"window_s={window['wall_s']} blocks={window['chunks']} "
          f"requests={window['requests']} window_compiles={counter.compiles} "
          f"window_cache_hits={counter.cache_hits} lag_max_s={lags[-1]} "
          f"slow_blocks={sum(x > 1.5 * lags[len(lags) // 2] for x in lags)}", flush=True)
    device = jax.devices()[0]
    stats = device.memory_stats() or {}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()),
           "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    served = driver.release()
    print(" ".join(f"{k}={v}" for k, v in served.items()), flush=True)
    if trace:
        try:
            reduced = trace_mod.reduce(trace_mod.load(prof_dir))
        finally:
            shutil.rmtree(prof_dir, ignore_errors=True)
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
    verdict = driver.check()
    checks = verdict["checks"]
    correct = (window["requests"] > 0 and verdict["checked"] > 0
               and all(v <= lim for v, lim in checks.values()))
    run = types.SimpleNamespace(setup_s=setup_s, window=window, trace=reduced)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cells.load_module("metrics", m["name"], cell.root).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": window["requests"],
            "failed": verdict["failed"], "metrics": metrics, "device": dev}
    if reduced is not None:
        line["breakdown"] = trace_mod.breakdown(reduced)
    line["checks"] = {k: {"value": v, "limit": lim} for k, (v, lim) in checks.items()}
    return line


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"chipbench: {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform!r} device(s): nothing was run",
              file=sys.stderr)
        return 2
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start)
    sys.stdout.flush()
    for name, c in line["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
