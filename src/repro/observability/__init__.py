"""Runtime observability: metrics, span tracing, profiler annotation.

Off by default and zero-cost when off; see ARCHITECTURE.md
("Observability") for the tier-by-tier instrumentation map.

    from repro import observability as obs

    obs.enable()                          # or REPRO_OBSERVABILITY=1
    with obs.span("my.workload"):
        q, r = solver.solve(a)
    obs.export_chrome_trace("trace.json")
    print(obs.metrics.to_prometheus())

Spans time host work and never block on the device.  A
``jax.profiler`` capture turns them on for its duration and records
each into the profile's host plane, on the device trace's clock.

Render a capture:  ``python -m repro.observability.report --help``
"""

from . import instrument, metrics, profiler, trace
from .instrument import (annotations_enabled, disable, enable, enabled_scope,
                         tracing_enabled)
from .metrics import REGISTRY, counter, gauge, histogram, snapshot
from .profiler import annotate, capture, kernel_label, megakernel_label
from .trace import chrome_trace, export_chrome_trace, span, spans, tree

__all__ = [
    "REGISTRY",
    "annotate",
    "annotations_enabled",
    "capture",
    "chrome_trace",
    "counter",
    "disable",
    "enable",
    "enabled_scope",
    "export_chrome_trace",
    "gauge",
    "histogram",
    "instrument",
    "kernel_label",
    "megakernel_label",
    "metrics",
    "profiler",
    "snapshot",
    "span",
    "spans",
    "trace",
    "tracing_enabled",
    "tree",
]
