"""repro.obs — unified observability: metric registry, tracker, sinks.

See `repro.obs.tracker` for the architecture. The short version:

    tracker = Tracker([JsonlSink("run.jsonl"), TraceEventSink("trace.json")])
    Engine(...).fit(state, batches, tracker=tracker)

and every layer below — executors, ascent lanes, the remote client, the
ascent pool's workers, the elastic resize path — reports spans and metrics
through `current_tracker()` for the duration of the fit. Every span, with
or without a tracker installed, is also a `jax.profiler` annotation named
`repro.<name>`, so a profiler trace shows it on the device's clock.
"""
from repro.obs.registry import (ENGINE_METRIC_KEYS,
                                ENGINE_OPTIONAL_METRIC_KEYS, METRIC_KEYS,
                                REGISTRY, TRACE_COUNTER_KEYS, MetricKey,
                                UnknownMetricError, metric_key,
                                registry_table, scalar_metrics,
                                validate_keys)
from repro.obs.tracker import (Event, JsonlSink, MemorySink, Sink, Span,
                               Tracker, current_tracker, jsonl_record,
                               set_global_tracker, trace_now, use_tracker)
from repro.obs.trace import TraceEventSink

__all__ = [
    "ENGINE_METRIC_KEYS", "ENGINE_OPTIONAL_METRIC_KEYS", "METRIC_KEYS",
    "REGISTRY", "TRACE_COUNTER_KEYS", "MetricKey", "UnknownMetricError",
    "metric_key", "registry_table", "scalar_metrics", "validate_keys",
    "Event", "JsonlSink", "MemorySink", "Sink", "Span", "Tracker",
    "current_tracker", "jsonl_record", "set_global_tracker", "trace_now",
    "use_tracker", "TraceEventSink",
]
