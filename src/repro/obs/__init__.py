"""repro.obs — the telemetry plane.

Four layers (see ISSUE/README "Observability"):

* **device counters + flight recorder** (:mod:`repro.obs.counters`):
  layout of the int32 counter block the router scan carry accumulates
  on-device — including the per-FRAME attribution columns (queue wait /
  stall / per-axis transit / defections) that ride with every frame and
  reconstruct its arrive step exactly — plus the host folds that turn
  per-rank deltas into the observed per-(link, direction) load matrix,
  the runtime counterpart of the static
  ``repro.analysis.comm.demand_link_loads`` matrix;
* **metrics registry** (:mod:`repro.obs.metrics`): labeled Counter /
  Gauge / log2-bucket Histogram (with interpolated ``quantile``) /
  Series with one ``snapshot()``, and the shared arrive-window
  statistics both the fabric and the stream reader report through;
* **causal spans + SLOs** (:mod:`repro.obs.spans`,
  :mod:`repro.obs.slo`): request ids minted at ingress flow through
  mailbox / batcher / stream lanes / serve as one connected Perfetto
  arc, and declared latency/throughput targets evaluate against
  snapshots with burn-rate output;
* **spans and export** (:mod:`repro.obs.trace`, :mod:`repro.obs.report`):
  :func:`~repro.obs.trace.span`, the serve path's host spans, written into
  the ``jax.profiler`` trace on the device's clock whenever one is being
  captured (and into a recorder's timeline when one is passed);
  Chrome-trace JSON timelines on the recorder's own ``perf_counter``
  clock, text/JSON metric reports, snapshot diffs, attribution tables,
  plus ``python -m repro.obs`` to summarize, ``--validate``, ``diff``,
  ``attribution``, ``slo``, or ``history``.
"""
from .counters import (
    ATT_FIELDS,
    CTR_FIELDS,
    CTR_GLOBALS,
    FrameAttribution,
    att_transit_index,
    counters_to_dict,
    ctr_index,
    global_index,
    load_drift,
    n_att,
    n_counters,
    observed_link_loads,
    static_load_frames,
)
from .metrics import (
    SNAPSHOT_SCHEMA,
    ClassWindows,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Series,
    format_key,
    quantile_from_buckets,
    validate_snapshot,
    window_stats,
)
from .report import (
    attribution_rows,
    diff_snapshots,
    environment_meta,
    render_attribution,
    render_diff,
    render_json,
    render_text,
)
from .slo import SLOReport, SLOResult, evaluate_slo, parse_slo
from .spans import RequestSpan, SpanEvent, SpanTracker, tick_breakdown
from .trace import SPAN_NAMES, TraceRecorder, span, validate_trace

__all__ = [
    "ATT_FIELDS",
    "CTR_FIELDS",
    "CTR_GLOBALS",
    "ClassWindows",
    "Counter",
    "FrameAttribution",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "RequestSpan",
    "SLOReport",
    "SLOResult",
    "SNAPSHOT_SCHEMA",
    "SPAN_NAMES",
    "Series",
    "SpanEvent",
    "SpanTracker",
    "TraceRecorder",
    "att_transit_index",
    "attribution_rows",
    "counters_to_dict",
    "ctr_index",
    "diff_snapshots",
    "environment_meta",
    "evaluate_slo",
    "format_key",
    "global_index",
    "load_drift",
    "n_att",
    "n_counters",
    "observed_link_loads",
    "parse_slo",
    "quantile_from_buckets",
    "render_attribution",
    "render_diff",
    "render_json",
    "render_text",
    "span",
    "static_load_frames",
    "tick_breakdown",
    "validate_snapshot",
    "validate_trace",
    "window_stats",
]
