"""Observability spine: metrics registry, sinks, spans and the stats schema.

``repro.obs`` is the single write path for serving witnesses.  The
runtime and scheduler mutate registry handles (``metrics``); attachable
sinks (``sinks``) fan emissions out to logs / JSONL / Prometheus text;
``spans`` puts the runtime's ``pool.*`` host spans into a profiler trace;
``programs`` keeps the newest runtime's executors for reading one by name;
``schema`` declares every exported stats key with its description and is
the one source of truth for docs, registry metric HELP text, and the
golden-key tests.
"""
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    timer,
)
from repro.obs.sinks import (  # noqa: F401
    CompositeSink,
    JsonlSink,
    LogSink,
    PromSink,
    read_jsonl,
)
from repro.obs.spans import span  # noqa: F401
from repro.obs.programs import ExecutorNotes, latest_hlo_texts  # noqa: F401
from repro.obs import schema  # noqa: F401
from repro.obs.d2h import leaves_nbytes  # noqa: F401

__all__ = [
    "leaves_nbytes",
    "ExecutorNotes",
    "latest_hlo_texts",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "timer",
    "CompositeSink",
    "JsonlSink",
    "LogSink",
    "PromSink",
    "read_jsonl",
    "schema",
    "span",
]
