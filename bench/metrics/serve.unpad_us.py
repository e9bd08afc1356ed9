"""Unpadding host time per request, serving cells: the summed
``serving.unpad`` program spans (the per-request slices of each chunk's
padded Q and R) of the traced window over the requests answered in it,
in microseconds.  None where the program records no such span."""

from repro.observability import trace


def read(ctx):
    us = [s.duration_us for s in trace.spans() if s.name == "serving.unpad"]
    n = ctx.counters.get("requests", 0)
    return sum(us) / n if us and n else None
