"""Host time of ``QRService.submit_many`` per request, serving cells: the
summed ``serving.submit`` program spans of the traced window over the
requests answered in it, in microseconds.  The span holds admission,
bucketing, planning, staging, dispatch and unpadding; the client's
fetch of Q and R lies outside it.  None where the program records no
such span."""

from repro.observability import trace


def read(ctx):
    us = [s.duration_us for s in trace.spans() if s.name == "serving.submit"]
    n = ctx.counters.get("requests", 0)
    return sum(us) / n if us and n else None
