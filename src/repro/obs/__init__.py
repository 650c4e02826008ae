"""repro.obs — spans, metrics, and the names on the device program.

Three pieces:

  * :mod:`repro.obs.tracer` — thread-safe span tracer with Chrome-trace
    JSON export (``chrome://tracing`` / Perfetto) and an in-process ring
    buffer; a no-op tracer is the process default so instrumentation is
    zero-cost until :func:`enable` / :func:`tracing` installs a real one.
    Under :func:`profiler_sink` spans also land in a ``jax.profiler``
    trace, on the device's clock.
  * :mod:`repro.obs.scopes` — the ``jax.named_scope`` names every device
    op of a transform carries (``croft.stage.<name>/k<i>`` and one role:
    ``croft.dft``, ``croft.relayout``, ``croft.transpose``,
    ``croft.scale``), which split a device trace by stage and role.
  * :mod:`repro.obs.metrics` — named counters, gauges, and log-bucketed
    histograms with quantile estimation; JSON snapshots and Prometheus
    text exposition.
"""

from repro.obs.tracer import (  # noqa: F401
    CATEGORIES,
    NOOP,
    NoopTracer,
    Tracer,
    current_tags,
    disable,
    enable,
    get_tracer,
    profiler_sink,
    set_tracer,
    tag_scope,
    tracing,
)
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)

__all__ = [
    "CATEGORIES", "NOOP", "NoopTracer", "Tracer", "current_tags",
    "disable", "enable", "get_tracer", "profiler_sink", "set_tracer",
    "tag_scope", "tracing", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "get_registry", "set_registry",
]
