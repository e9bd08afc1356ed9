"""The factorization's share of its roofline, dense cells.

The least time the chip could take for the fixed work of the calls
completed in the traced window (``bench/work.py``: 2 qr_flops(m, n) and
the compulsory bytes, under the published peaks of ``bench/peaks.py``),
over the device's busy time in that window.  The peak is bfloat16's
while the factorization computes float32 products at ``highest``, so the
share reads low by design."""

import work


def read(ctx):
    flops, nbytes = ctx.work
    if not flops or ctx.trace["busy_s"] <= 0:
        return None
    least, _bound = work.least_time(flops, nbytes, ctx.peaks)
    return 100.0 * least / ctx.trace["busy_s"]
