"""repro.telemetry — a jit-safe flight recorder for every engine.

Three layers:

* **Recording** (device side, jit-safe): a static :class:`TelemetryConfig`
  level gates everything — ``OFF`` (default) keeps each engine's jaxpr
  byte-identical to the pre-telemetry build; ``SUMMARY`` adds per-slot
  metric streams as extra stacked scan outputs; ``TRACE`` adds the
  fixed-capacity, mask-compacted :class:`EventRing` written inside
  ``lax.scan`` / ``lax.cond`` bodies (recovery epochs, placement-epoch
  churn, dead-site ingest redirects). Engines return
  ``(outputs, TelemetryFrame)`` when a level is enabled.
* **Decoding** (host side): :func:`collect_records` turns outputs + frame
  into a flat JSON-ready record stream — in-scan events, derived events
  (GMSA manager-switch edges), per-slot metrics, the embedded summary.
* **Export**: :func:`write_jsonl` / :func:`read_jsonl`,
  :func:`render_timeline`, and :func:`cross_check`, with the CLI
  ``python -m repro.telemetry.report run.jsonl --check``.
* **Distributions & spans** (PR 8): :class:`HistogramSpec` enables
  jit-safe log-bucket histograms riding the scan bodies (request sojourn,
  queue delay, site cost) decoded to p50/p95/p99 with error bounds
  (:mod:`repro.telemetry.metrics`); :mod:`repro.telemetry.spans` folds
  record streams into lifecycle spans exported as Chrome trace-event
  JSON; :mod:`repro.telemetry.slo` evaluates percentile SLOs with
  multi-window burn-rate alerts.
* **Layer names** (:mod:`repro.telemetry.scopes`): the ``jax.named_scope``
  names the engines open around each layer and the kernels' names, so a
  device profile attributes its ops to the layers; they change only the
  compiled program's metadata.
"""

from repro.telemetry.config import (
    OFF,
    SUMMARY,
    TRACE,
    Level,
    TelemetryConfig,
    enabled,
    histograms,
    tracing,
)
from repro.telemetry.metrics import (
    HistogramSpec,
    fifo_sojourn_replay,
    hist_add,
    hist_init,
    hist_quantiles,
    hist_series,
    percentile_table,
    sojourn_init,
    sojourn_step,
    weighted_percentile,
)
from repro.telemetry.slo import SloSpec, burn_events, evaluate_slo
from repro.telemetry.spans import (
    controller_spans,
    request_spans,
    spans_from_records,
    straggler_spans,
    to_chrome_trace,
    write_chrome_trace,
)
from repro.telemetry.ring import (
    EV_EPOCH,
    EV_HEDGE,
    EV_INGEST_REDIRECT,
    EV_LINK_DOWN,
    EV_RECOVERY,
    EV_REPAIR,
    EV_SWITCH,
    EventRing,
    TelemetryFrame,
    empty_frame,
    ring_events,
    ring_init,
    ring_push,
)
from repro.telemetry.collect import (
    collect_records,
    engine_kind,
    fleet_records,
    hedge_events,
    link_down_events,
    switch_events,
    time_to_slo,
)
from repro.telemetry.export import (
    cross_check,
    read_jsonl,
    render_timeline,
    sparkline,
    write_jsonl,
)

__all__ = [
    "Level", "TelemetryConfig", "OFF", "SUMMARY", "TRACE",
    "enabled", "tracing", "histograms",
    "EventRing", "TelemetryFrame", "empty_frame",
    "ring_init", "ring_push", "ring_events",
    "EV_RECOVERY", "EV_EPOCH", "EV_SWITCH", "EV_INGEST_REDIRECT",
    "EV_REPAIR", "EV_HEDGE", "EV_LINK_DOWN",
    "collect_records", "engine_kind", "fleet_records", "switch_events",
    "hedge_events", "link_down_events", "time_to_slo",
    "write_jsonl", "read_jsonl", "render_timeline", "sparkline",
    "cross_check",
    "HistogramSpec", "hist_init", "hist_add", "hist_series",
    "hist_quantiles", "percentile_table", "sojourn_init", "sojourn_step",
    "fifo_sojourn_replay", "weighted_percentile",
    "SloSpec", "burn_events", "evaluate_slo",
    "request_spans", "controller_spans", "spans_from_records",
    "straggler_spans", "to_chrome_trace", "write_chrome_trace",
]
