"""Matrices served over batch slots dispatched in the window, from the
deltas of the counters behind ``QRService.stats()["bucket_fill_ratio"]``."""


def read(ctx):
    c = ctx.counters
    slots = c["served"] + c["padded_slots"]
    return 100.0 * c["served"] / slots if slots else None
