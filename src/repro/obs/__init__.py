"""repro.obs — metrics + tracing across serve/store/train.

The observability layer SHARK's operational claims (30% QPS, tail
latency under re-tiering) are measured against: a zero-dependency
in-process metrics registry plus span tracing, instrumented through
every hot path and exported as statsd lines or ``metrics_snapshot/v1``
JSONL (``launch/serve.py --metrics-out`` / ``launch/pipeline.py
--metrics-out``).

  registry   counters / gauges / streaming histograms (fixed
             log-spaced buckets, p50/p95/p99/max, exact cross-shard
             merge) behind a disabled-by-default switch
  trace      ``span("stage")`` nestable timed stages and
             ``timeblock``, the one wall-clock idiom shared by the
             serve, train and bench loops (``tb.sync(x)`` =
             ``jax.block_until_ready`` inside the clock); both write
             profiler annotations while a trace runs and land in the
             span log (``span_log()``, ``time.perf_counter_ns``) with
             every compile and compile-cache load
  export     ``metrics_snapshot/v1`` snapshots, statsd line protocol,
             and the periodic JSONL sink driven by ``tick()``
             (``close_sink()`` on loop exit lands the final partial
             window)
  fleet      cross-replica aggregation: ``FleetAggregator`` re-merges
             per-replica registries / snapshot streams bucket-exactly
             (fleet percentiles are union-stream percentiles, never
             mean-of-p99s); ``obs.bind(reg)`` scopes the module-level
             calls to one replica's namespaced registry

Metric catalog + span taxonomy: docs/observability.md.
"""

from repro.obs.export import (  # noqa: F401
    JsonlSink,
    close_sink,
    flush,
    registry_from_snapshot,
    set_sink,
    snapshot,
    statsd_lines,
    tick,
)
from repro.obs.fleet import (  # noqa: F401
    FleetAggregator,
    last_snapshot,
    merge_snapshots,
)
from repro.obs.registry import (  # noqa: F401
    Histogram,
    Registry,
    bind,
    disable,
    enable,
    enabled,
    ensure_histograms,
    gauge,
    get_registry,
    inc,
    observe,
)
from repro.obs.trace import (  # noqa: F401
    Span,
    SpanLog,
    Timeblock,
    current_path,
    span,
    span_log,
    timeblock,
)
