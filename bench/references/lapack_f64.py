"""Plain reference of a dense QR: float64 LAPACK on the host.

``reference_r`` is the R factor of GEQRF in float64 (``numpy.linalg.qr``,
mode "r").  It imports nothing of the program and takes nothing that the
program made.
"""

from __future__ import annotations

import numpy as np


def reference_r(a) -> np.ndarray:
    return np.linalg.qr(np.asarray(a, np.float64), mode="r")
