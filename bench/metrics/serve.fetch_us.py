"""Host fetch time per request, serving cells: the summed
``serving.fetch`` program spans (each a chunk's padded Q and R stacks
materialized on the host, once per chunk, inside ``serving.unpad``) of
the traced window over the requests answered in it, in microseconds.
None where the program records no such span."""

from repro.observability import trace


def read(ctx):
    us = [s.duration_us for s in trace.spans() if s.name == "serving.fetch"]
    n = ctx.counters.get("requests", 0)
    return sum(us) / n if us and n else None
