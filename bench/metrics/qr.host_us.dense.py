"""Host time of one ``qr()`` call, dense cells: the summed ``qr.call``
program spans of the traced window over their number, in microseconds.
The span covers planning and dispatch; the caller's wait for the
device (``block_until_ready``) lies outside it.  None where the program
records no such span."""

from repro.observability import trace


def read(ctx):
    calls = [s.duration_us for s in trace.spans() if s.name == "qr.call"]
    return sum(calls) / len(calls) if calls else None
