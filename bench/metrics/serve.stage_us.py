"""Staging host time per request, serving cells: the summed
``serving.stage`` program spans (zero-fill, copy and ``device_put`` of
each dispatch chunk) of the traced window over the requests answered in
it, in microseconds.  None where the program records no such span."""

from repro.observability import trace


def read(ctx):
    us = [s.duration_us for s in trace.spans() if s.name == "serving.stage"]
    n = ctx.counters.get("requests", 0)
    return sum(us) / n if us and n else None
