"""Host-side telemetry: metrics registry + structured tracing (DESIGN.md §6).

Everything in this package runs on the host: no instrument touches the
device or a traced program, and a profiler annotation
(``jax.profiler.TraceAnnotation``, the one JAX import) is opened only
while tracing is on.  The hard invariant (pinned by
``tests/test_obs_invariants.py``): enabling telemetry changes zero
search bits and adds zero new jit traces post-warmup; disabling it
reduces every instrument to an attribute check.
"""

from . import metrics, trace
from .metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    render,
    snapshot,
    validate_exposition,
)
from .trace import QueryCard, Tracer, get_tracer, span

__all__ = [
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "QueryCard",
    "Tracer",
    "counter",
    "gauge",
    "get_tracer",
    "histogram",
    "metrics",
    "render",
    "snapshot",
    "span",
    "trace",
    "validate_exposition",
]
