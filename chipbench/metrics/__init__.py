"""Metric readers, one file each, named as in ``BENCHMARK.json``:
``read(run) -> float | None``; ``None`` leaves the metric out of the line."""
