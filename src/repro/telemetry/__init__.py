"""repro.telemetry — in-scan windowed metrics + measured CPU-time timing.

Five pieces (see docs/observability.md):

* :mod:`repro.telemetry.spec` — :class:`TelemetrySpec` and the xp-generic
  window bucketing shared by the jitted scans, the Pallas kernel, and the
  host-side oracle — including the PR 8 group axis (``n_groups``) that
  segments every metric by an id→group catalogue (tenant attribution).
* :mod:`repro.telemetry.timing` — warmup + ``block_until_ready`` measurement
  harness with the AOT compile/execute split and measured J/op.
* :mod:`repro.telemetry.latency` — per-tier service-time model resolving
  grouped fleet series into per-tenant serving-level histograms and
  discrete p50/p99 request latency.
* :mod:`repro.telemetry.dashboard` — self-contained static HTML operator
  dashboard (inline-SVG sparklines, no external assets) rendered from the
  same per-window rows the JSONL exporters serialise.
* :mod:`repro.telemetry.export` — JSONL/CSV per-window row exporters.

The host-side oracle lives in :mod:`repro.telemetry.oracle` (imported
explicitly by the tests; it pulls the reference-policy stack in).
"""
from repro.telemetry.spec import (
    METRIC_INDEX,
    METRICS,
    N_METRICS,
    TelemetrySpec,
    bucket_end,
    bucket_sum,
    chunk_window_matrix,
    group_onehot,
    grouped_series_from_run,
    n_windows,
    series_from_run,
    window_sizes,
    windowed_pressure,
)
from repro.telemetry.latency import LatencyModel, percentile_us
from repro.telemetry.timing import Timing, j_per_step, measure

__all__ = [
    "METRIC_INDEX",
    "METRICS",
    "N_METRICS",
    "LatencyModel",
    "TelemetrySpec",
    "Timing",
    "bucket_end",
    "bucket_sum",
    "chunk_window_matrix",
    "group_onehot",
    "grouped_series_from_run",
    "j_per_step",
    "measure",
    "n_windows",
    "percentile_us",
    "series_from_run",
    "window_sizes",
    "windowed_pressure",
]
