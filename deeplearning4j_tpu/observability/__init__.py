"""Unified observability: metrics registry + trace spans + exposition.

Every layer of the system previously self-reported in a different
dialect — `InferenceEngine.health()`'s ad-hoc dict, train listeners
printing to the log, `ui/stats.py` and `scaleout/stats.py` keeping
private timing state — and nothing was scrapeable. This package is the
one substrate they all publish into:

- `metrics` — thread-safe `MetricsRegistry` of labeled
  `Counter`/`Gauge`/`Histogram` (fixed buckets, per-cell locks,
  monotonic `perf_counter` timers); a process default registry plus
  injectable instances; `NULL_REGISTRY` to disable by injection.
- `tracing` — nestable `span(name, **args)` context managers that
  append a record (id, parent, name, start, end, tick, rid, args) to
  a bounded process-wide ring (`default_spans()`; `NULL_SPANS`
  disables by injection), hold a `jax.profiler.TraceAnnotation` so
  spans land in XLA profiles, and `snapshot()` with a clock anchor
  that puts them on the profiler's clock.
- `export` — Prometheus text exposition + JSON snapshot, served by the
  stdlib `MetricsServer` (`/metrics`, `/healthz`, `/readyz` with
  pluggable health callables, plus `/debugz`, `/slo`,
  `/timeline.json` when the serving introspection callables are
  wired) and mountable on the training dashboard
  (`ui.server.UIServer.attach_metrics`).
- `events` — the per-request flight recorder (ISSUE-6): a bounded
  thread-safe ring of typed lifecycle events plus `RequestTrace`
  (exposed as `RequestHandle.trace`); `NULL_RECORDER` disables by
  injection.
- `slo` — `SLOTracker`: TTFT / TPOT / e2e / queue-age histograms and
  goodput derived from the traces, with a windowed `report()`.
- `timeline` — Chrome/Perfetto `trace_event` JSON export of the
  recorder: one lane per serving slot plus a queue lane.
- `stitch` — distributed-trace stitching (ISSUE-13): merge a fleet
  router's trace with the per-hop replica traces (clock-offset
  aligned) into one `StitchedTrace` of events + queue/prefill/
  decode/handoff spans, plus the fleet-wide Perfetto export with one
  process lane group per replica per tier.
- `federation` — metrics federation (ISSUE-13): merge per-replica
  registry snapshots into ONE fleet scrape (counters summed and
  histograms bucket-merged under `tier=`, gauges kept per-replica
  under `tier=`/`replica=`), with a series-cardinality guard.
- `profiling` — continuous profiling & cost attribution (ISSUE-15):
  `EngineProfiler` (per-program XLA cost table, per-tick device-time
  attribution, live `serving_mfu`, roofline classification),
  `TenantMeter` (per-tenant analytic FLOP/byte metering with a
  top-N + "other" cardinality bound), and `ProfileCapture`
  (single-flight `/profilez?seconds=N` jax.profiler capture).

Publishers: `serving.InferenceEngine` (queue/batch/shed/quarantine/
retry/breaker/decode-latency; `health()` is registry-backed),
`train.listeners.{PerformanceListener,ScoreIterationListener}`,
`scaleout.stats.SparkTrainingStats` + `scaleout.parallel_trainer`
spans, and `datasets.iterators.AsyncDataSetIterator` prefetch gauges.
Lifecycle, naming conventions and a scrape walkthrough:
docs/observability.md.
"""
from deeplearning4j_tpu.observability.metrics import (  # noqa: F401
    DECODE_LATENCY_BUCKETS, DEFAULT_BUCKETS, Counter, Gauge, Histogram,
    MetricsRegistry, NULL_REGISTRY, NullRegistry, default_registry)
from deeplearning4j_tpu.observability.tracing import (  # noqa: F401
    NULL_SPANS, Span, SpanRing, annotate, current_span, default_spans,
    mark, span, traced)
from deeplearning4j_tpu.observability.export import (  # noqa: F401
    CONTENT_TYPE_LATEST, MetricsServer, json_snapshot, probe_response,
    prometheus_text, snapshot_prometheus_text)
from deeplearning4j_tpu.observability.events import (  # noqa: F401
    EVENT_KINDS, Event, FlightRecorder, NULL_RECORDER, NULL_TRACE,
    NullRecorder, RequestTrace, TERMINAL_KINDS)
from deeplearning4j_tpu.observability.slo import (  # noqa: F401
    NULL_SLO, SLOTracker, TPOT_BUCKETS)
from deeplearning4j_tpu.observability.timeline import (  # noqa: F401
    timeline_json, trace_events)
from deeplearning4j_tpu.observability.stitch import (  # noqa: F401
    SPAN_NAMES, StitchedTrace, fleet_timeline_json, router_lane_events,
    stitch)
from deeplearning4j_tpu.observability.federation import (  # noqa: F401
    DEFAULT_SERIES_BUDGET, check_cardinality, merge_snapshots,
    series_cardinality)
from deeplearning4j_tpu.observability.profiling import (  # noqa: F401
    DEFAULT_TENANT, EngineProfiler, NULL_PROFILER, NullProfiler,
    OTHER_TENANT, ProfileCapture, TenantMeter, cost_from_compiled,
    roofline)
