"""Planner host time per ``qr()`` call, dense cells: the summed
``qr.plan`` program spans of the traced window over the number of
``qr.call`` spans, in microseconds.  None where the program records no
such span."""

from repro.observability import trace


def read(ctx):
    spans = trace.spans()
    calls = sum(1 for s in spans if s.name == "qr.call")
    plan = [s.duration_us for s in spans if s.name == "qr.plan"]
    return sum(plan) / calls if calls and plan else None
