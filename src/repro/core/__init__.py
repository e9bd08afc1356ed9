"""repro.core — Householder/MHT QR factorization (the paper's contribution).

Layers:
    householder  classical HT (DGEQR2 semantics), Q application, and
                 blocked WY Q formation (DORGQR)
    mht          Modified Householder Transform (fused macro-op updates)
    blocked      WY-blocked QR (DGEQRF / DGEQRFHT / fori_loop variant)
    tsqr         communication-avoiding distributed QR over mesh axes
    tilegraph    tiled task-graph QR: GEQRT/TSQRT/LARFB/SSRFB tile DAG,
                 statically wavefront-scheduled (cross-panel parallelism)
    engine       wavefront macro-op engine: executes the levelized DAG
                 as one in-place Pallas dispatch per level
                 (dispatch_mode="wavefront"), as ONE persistent
                 task-table dispatch with double-buffered tile DMA
                 ("megakernel"), or as the bitwise-identical vmapped
                 jnp oracle (use_kernel=False)
    distgraph    multi-device sharded tiled QR: per-device row-block
                 wavefront domains (shard_map) + TSQR-style R merge tree
    dag          beta/theta parallelism quantification (paper fig 9),
                 extended to the tiled/sharded wavefront DAGs
    plan         QRConfig + method registry + plan() -> QRSolver
    api          qr() / orthogonalize() / lstsq() / qr_algorithm_eig()

Realization selection is centralized in :mod:`repro.core.plan`: each
algorithm module registers capability metadata (``register_method``) at
import, and ``plan(shape, dtype, QRConfig(...))`` resolves method / block
size / kernel policy / TSQR tree shape — including ``method="auto"``
shape-and-hardware heuristics — into a hashable :class:`QRSolver`.  The
functions in :mod:`repro.core.api` are thin wrappers over that planner.
"""

from repro.core.api import lstsq, orthogonalize, qr, qr_algorithm_eig
from repro.core.blocked import geqrf, geqrf_fori, larft
from repro.core.householder import apply_q, form_q, geqr2, house_vector, unpack_r, unpack_v
from repro.core.mht import geqr2_ht, mht_update
from repro.core.plan import (
    MethodSpec,
    QRConfig,
    QRSolver,
    available_methods,
    get_method,
    plan,
    register_method,
)
from repro.core.engine import schedule_stats
from repro.core.tilegraph import (
    sharded_wavefront_count,
    tiled_qr,
    wavefront_count,
    wavefronts,
)
from repro.core.distgraph import sharded_tiled_qr
from repro.core.tsqr import distributed_qr, tsqr_qr, tsqr_r, tsqr_tree_sharded

__all__ = [
    "qr", "orthogonalize", "lstsq", "qr_algorithm_eig",
    "QRConfig", "QRSolver", "MethodSpec", "plan",
    "register_method", "get_method", "available_methods",
    "geqr2", "geqr2_ht", "geqrf", "geqrf_fori", "larft",
    "house_vector", "apply_q", "form_q", "unpack_r", "unpack_v", "mht_update",
    "tsqr_r", "tsqr_qr", "tsqr_tree_sharded", "distributed_qr",
    "tiled_qr", "wavefronts", "wavefront_count", "schedule_stats",
    "sharded_tiled_qr", "sharded_wavefront_count",
]
