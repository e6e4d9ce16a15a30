"""repro.obs — privacy-safe observability for the serving engine.

`Tracer` records bounded per-request stage spans (see `repro.obs.trace`
for the redact-by-construction schema), `StageHistogram` keeps fixed-
bucket per-stage latency profiles, and `repro.obs.export` writes
Perfetto-loadable Chrome-trace timelines.  Each `Tracer.span` also opens
a ``repro/<name>`` annotation on a running `jax.profiler` session.
Tracing is off by default; `NULL_TRACER` is the shared no-op sink.
"""

from repro.obs.histogram import StageHistogram, summarize
from repro.obs.trace import (ALLOWED_ATTR_KEYS, ANNOTATION_PREFIX,
                             NULL_TRACER, NullTracer, Span, Tracer,
                             validate_attrs)
from repro.obs.export import (chrome_trace_events, load_chrome_trace,
                              write_chrome_trace)

__all__ = [
    "ALLOWED_ATTR_KEYS", "ANNOTATION_PREFIX", "NULL_TRACER", "NullTracer",
    "Span", "Tracer", "StageHistogram", "summarize", "validate_attrs",
    "chrome_trace_events", "load_chrome_trace", "write_chrome_trace",
]
