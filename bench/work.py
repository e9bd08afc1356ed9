"""The fixed work of one QR, set by its shape alone.

Whatever route or kernel factors the matrix, a reduced QR of an m x n
float32 matrix is counted as

  flops = 2 * qr_flops(m, n): LAPACK's GEQRF count plus ORGQR's for the
          thin Q, each ``2 k^2 (max(m, n) - k/3)`` with k = min(m, n)
          (LAPACK Working Note 41's counts, the convention of
          ``repro.launch.roofline.qr_flops``);
  bytes = 4 * (m n + m k + k n): A read once, Q (m x k) and R (k x n)
          written once.  For m = n this is 4 (2 m n + n^2).

A kernel that replaces another cannot change these counts.
"""

from __future__ import annotations

from typing import Iterable, Tuple


def qr_flops(m: int, n: int) -> float:
    k = min(m, n)
    return 2.0 * k * k * (max(m, n) - k / 3.0)


def qr_work(m: int, n: int) -> Tuple[float, float]:
    """(flops, bytes) of one reduced float32 QR with Q formed."""
    k = min(m, n)
    return 2.0 * qr_flops(m, n), 4.0 * (m * n + m * k + k * n)


def total_work(shapes: Iterable[Tuple[int, int]]) -> Tuple[float, float]:
    flops = nbytes = 0.0
    for m, n in shapes:
        f, b = qr_work(m, n)
        flops += f
        nbytes += b
    return flops, nbytes


def least_time(flops: float, nbytes: float, pk) -> Tuple[float, str]:
    """Least seconds the chip could take for this work under the peaks
    ``pk``, and which bound sets it ("compute" or "memory")."""
    t_c, t_m = flops / pk.flops, nbytes / pk.hbm_bw
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
