"""QR-Muon: momentum orthogonalization via MHT QR — the paper's technique
as a first-class training feature (DESIGN.md §3).

Muon (momentum + orthogonalized update) normally orthogonalizes with
Newton-Schulz.  Here the orthogonal factor comes from the *Modified
Householder Transform* blocked QR: ``O = Q(m) · diag(sign(diag R))`` —
an exactly-orthonormal factor with the same column space as the momentum,
computed by the paper's algorithm.  Methods:

    "qr"   MHT blocked QR (geqrf_fori: one fused O(1)-HLO program)
    "ns"   Newton-Schulz quintic (baseline for ablation)

Routing: matrix-shaped weights (not embeddings / heads / norms / biases)
get Muon; everything else gets AdamW.  Stacked leaves — (n_periods, ...)
layer stacks, (E, d, f) expert stacks, (H, dh, dh) xLSTM blocks — are
orthogonalized as batched 2-D problems via vmap over leading axes.

Distributed: pass ``orthogonalize_fn`` (e.g. built on
:func:`repro.core.tsqr.distributed_qr`) to orthogonalize FSDP-sharded
momentum with the butterfly-tree TSQR instead of gathering it.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.householder import form_q, unpack_r
from repro.core.plan import QRConfig, f32_products, plan as qr_plan
from repro.optim.newton_schulz import newton_schulz_orthogonalize

Array = jax.Array

__all__ = ["MuonState", "muon_init", "muon_update", "is_muon_param",
           "qr_orthogonalize_2d"]

_EXCLUDE_NAMES = ("embed", "lm_head", "table", "router", "shared_gate")


class _Out(NamedTuple):
    p: object
    mu: object
    nu: object


class _Pre(NamedTuple):
    """Pass-1 record of the two-pass batched-ortho update: AdamW leaves
    arrive finished (``p`` set, ``direction`` None); Muon leaves carry the
    momentum direction awaiting the shape-class-batched orthogonalization
    before pass 2 finishes ``p``."""
    p: object           # finished param (adam) or original param (muon)
    mu: object
    nu: object
    direction: object   # muon momentum direction, else None


class MuonState(NamedTuple):
    step: Array
    mu: object          # momentum (all leaves)
    nu: object          # adam second moment (None on muon leaves)


def _path_names(path) -> tuple:
    return tuple(str(getattr(k, "key", getattr(k, "name", k))) for k in path)


def is_muon_param(path, leaf) -> bool:
    names = _path_names(path)
    if any(n in _EXCLUDE_NAMES for n in names):
        return False
    if leaf.ndim < 2:
        return False
    d_out, d_in = leaf.shape[-2], leaf.shape[-1]
    return min(d_out, d_in) >= 8


def _pad_to(x: Array, mult: int) -> Array:
    k = min(x.shape)
    pad = (-k) % mult
    if pad == 0:
        return x
    # pad the short dimension with identity-ish columns (they factor to
    # exact reflectors and are sliced away after)
    if x.shape[0] <= x.shape[1]:
        return jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)], 0)
    return jnp.concatenate([x, jnp.zeros((x.shape[0], pad), x.dtype)], 1)


@f32_products
def qr_orthogonalize_2d(m_in: Array, *, block: int = 64,
                        q_method: str = "formq",
                        config: Optional[QRConfig] = None) -> Array:
    """Sign-fixed thin Q of a single (possibly wide) matrix via MHT QR.

    ``config`` (a :class:`repro.core.plan.QRConfig`) overrides
    ``block``/``q_method``; the factorization itself always routes through
    the planner's method registry (``geqrf_fori``: one fused O(1)-HLO
    program regardless of matrix size).

    ``q_method``:
      * "solve" (beyond-paper §Perf iteration Q1): Q = A R^{-1}
        by triangular solve — one dense op instead of the k-step
        reflector-application loop.  R comes from the stable MHT QR so
        this is NOT CholeskyQR (no Gram squaring); orthogonality matches
        form-Q to fp32 eps for optimizer-grade conditioning, and the
        diag-clamp handles rank deficiency.
      * "formq" (default — the paper-faithful baseline): accumulate
        reflectors; exact even for singular input.  Blocked WY
        (``householder.form_q``): one group of GEMMs per 128 reflectors.

    Accumulation runs in ``promote_types(param_dtype, float32)`` — bf16
    storage params factor in fp32 (and round back to bf16 on return),
    fp64 params keep fp64 precision — the factorization never silently
    downcasts the way the old hardcoded-fp32 plan did.
    """
    # Compute dtype: at least fp32 (bf16/f16 storage accumulates in
    # fp32), but NEVER below the param dtype (f64 stays f64).
    compute_dtype = jnp.promote_types(m_in.dtype, jnp.float32)
    if config is None:
        config = QRConfig(method="geqrf_fori", block=block, q_method=q_method,
                          precision=str(np.dtype(compute_dtype)),
                          sign_fix=True)
    q_method = config.q_method
    transpose = m_in.shape[0] < m_in.shape[1]
    a = m_in.T if transpose else m_in
    mrows, ncols = a.shape
    blk = min(config.block, ncols)
    acc = a.astype(compute_dtype)
    padded = _pad_to(acc, blk)
    # The optimizer needs the packed factored form — resolve "auto" to the
    # fused-program realization rather than letting the planner pick TSQR.
    method = "geqrf_fori" if config.method == "auto" else config.method
    solver = qr_plan(padded.shape, compute_dtype,
                     config.replace(block=blk, method=method))
    packed, taus = solver.factor(padded)
    r = unpack_r(packed)[:ncols, :ncols]
    if q_method == "solve":
        # Q = A R^{-1} with R^{-1} formed explicitly: the (n x n)
        # triangular solve runs against the identity (small, replicated)
        # and the application is a plain GEMM — shardable, unlike a
        # batched triangular solve over the full (m, n) operand (GSPMD
        # cannot shard the solve dimension and replicates ~GiB stacks).
        from jax.scipy.linalg import solve_triangular

        d = jnp.diagonal(r)
        dmax = jnp.maximum(jnp.max(jnp.abs(d)), 1e-30)
        clamp = jnp.where(jnp.abs(d) < 1e-7 * dmax,
                          jnp.where(d >= 0, 1e-7 * dmax, -1e-7 * dmax), d)
        r_safe = r + jnp.diag(clamp - d)
        r_inv = solve_triangular(r_safe, jnp.eye(ncols, dtype=compute_dtype),
                                 lower=False)
        q = acc @ r_inv
    else:
        q = form_q(packed, taus)[:mrows, :ncols]
    signs = jnp.where(jnp.diagonal(r) >= 0, 1.0, -1.0).astype(q.dtype)
    q = q * signs[None, :]
    return (q.T if transpose else q).astype(m_in.dtype)


def _orthogonalize_leaf(mu: Array, method: str,
                        orth_fn: Optional[Callable],
                        q_method: str = "formq",
                        shard_leaves: bool = False,
                        config: Optional[QRConfig] = None) -> Array:
    """Batched orthogonalization over any leading axes of a >=2-D leaf.

    ``shard_leaves`` (beyond-paper §Perf iteration Q2): constrain the
    vmapped (lead, m, n) stack to be layer-sharded over the data axis and
    each matrix replicated — the QR's sequential panel loops then run
    device-local (GSPMD otherwise threads tiny collectives through every
    panel iteration of the factorization loop), trading one gather of the
    momentum for collective-free factorization.  Falls back to no
    constraint when the lead dim does not divide."""
    lead = mu.shape[:-2]
    mats = mu.astype(jnp.float32)
    # NEVER reshape the leading axes together: merging an (n_periods, E)
    # pair whose E is model-sharded into one dim is unrepresentable in
    # GSPMD and forces full replication of the momentum stack (observed:
    # +100 GiB temp on the 16-expert cells).  Nested vmap keeps each axis
    # and its sharding intact.
    if shard_leaves and len(lead) >= 1:
        from repro.distributed.sharding import _policy
        from jax.sharding import PartitionSpec as P

        rules, _ = _policy()
        if rules is not None:
            spec = [None] * mats.ndim
            if mats.shape[0] % rules.data_size == 0:
                spec[0] = rules.data_spec()
            # model axis: prefer a second lead dim (expert stacks — each
            # expert's matrix stays whole and local); otherwise the QR's
            # column dim (min of the trailing dims; the orthogonalizer
            # transposes wide inputs) so the (m, n) planes never sit
            # unsharded.  Dynamic panel slices over a sharded column dim
            # are fine for 64-column slivers but replicate whole planes
            # when the lead dims are unsharded — hence the preference
            # order (measured: jamba 20 -> 50 GiB with col-sharding on
            # unsharded-lead expert stacks; qwen 16.2 -> 13.5 with
            # col-sharding on data-sharded 3-D stacks).
            if rules.tp_enabled:
                model_done = False
                for i in range(1, mats.ndim - 2):
                    if mats.shape[i] % rules.model_size == 0:
                        spec[i] = rules.model_axis
                        model_done = True
                        break
                if not model_done and spec[0] is not None:
                    a_dim, b_dim = mats.shape[-2], mats.shape[-1]
                    col = mats.ndim - 2 + (0 if a_dim <= b_dim else 1)
                    if mats.shape[col] % rules.model_size == 0:
                        spec[col] = rules.model_axis
            if any(s is not None for s in spec):
                mats = jax.lax.with_sharding_constraint(mats, P(*spec))
            else:
                # no clean sharding (e.g. 4-period stacks on a 16-way
                # axis): the batched triangular-solve/GEMM Q would
                # replicate whole (m, n) planes — use the incremental
                # reflector accumulation instead (one reused carry
                # buffer; measured jamba 41.5 -> baseline-class temp)
                q_method = "formq"
    if orth_fn is not None:
        f = orth_fn
    elif method == "qr":
        if config is not None:
            config = config.replace(q_method=q_method)
        f = functools.partial(qr_orthogonalize_2d, q_method=q_method,
                              config=config)
    elif method == "ns":
        f = newton_schulz_orthogonalize
    else:
        raise ValueError(f"unknown orthogonalization {method!r}")
    for _ in lead:
        f = jax.vmap(f)
    return f(mats)


def muon_init(params) -> MuonState:
    """Muon leaves carry a scalar placeholder ``nu`` (no second moment) so
    the state tree structure matches the params while costing no memory."""
    mu = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), params)
    nu = jax.tree_util.tree_map_with_path(
        lambda path, p: jnp.zeros((), jnp.float32) if is_muon_param(path, p)
        else jnp.zeros_like(p, jnp.float32), params)
    return MuonState(step=jnp.zeros((), jnp.int32), mu=mu, nu=nu)


def muon_update(
    grads, state: MuonState, params, *,
    lr: float | Array,
    momentum: float = 0.95,
    nesterov: bool = True,
    weight_decay: float = 0.0,
    method: str = "qr",
    adam_lr_ratio: float = 0.3,
    adam_b1: float = 0.9, adam_b2: float = 0.95, adam_eps: float = 1e-8,
    orthogonalize_fn: Optional[Callable] = None,
    qr_q_method: str = "formq",
    qr_shard_leaves: bool = False,
    qr_config: Optional[QRConfig] = None,
    batched_ortho: bool = False,
    ortho_policy=None,
):
    """One optimizer step.  ``lr`` is the Muon LR; AdamW params use
    ``lr * adam_lr_ratio`` (embeddings etc. want a smaller step).

    ``qr_config`` tunes the QR realization (method/block/kernel policy)
    of the orthogonalization; ``qr_q_method`` still wins for the Q
    materialization strategy (the sharding fallback logic may override it
    per leaf).

    ``batched_ortho=True`` routes the orthogonalizations through
    :func:`repro.optim.batched_ortho.batched_orthogonalize`: every Muon
    matrix of the step groups into shape classes and each class factors
    in ONE dispatch, dropping the per-step QR dispatch count from
    O(muon leaves) to O(shape classes).  Applies only to the plain QR
    path — a custom ``orthogonalize_fn`` or ``qr_shard_leaves`` (whose
    per-leaf sharding constraints a cross-leaf stack cannot express)
    keeps the leafwise route.  ``ortho_policy`` (a
    :class:`repro.serving.bucketing.BucketingPolicy`) overrides the
    shape-class edges."""
    step = state.step + 1
    t = step.astype(jnp.float32)
    bc1 = 1.0 - adam_b1 ** t
    bc2 = 1.0 - adam_b2 ** t

    use_batched = (batched_ortho and method == "qr"
                   and orthogonalize_fn is None and not qr_shard_leaves)

    def finish_muon(p, o):
        d_out, d_in = p.shape[-2], p.shape[-1]
        scale = jnp.sqrt(jnp.maximum(1.0, d_out / d_in))
        new_p = p - lr * (scale * o + weight_decay * p)
        return new_p.astype(p.dtype)

    if use_batched:
        from repro.optim.batched_ortho import batched_orthogonalize

        def pre(path, p, g, mu, nu):
            g = g.astype(jnp.float32)
            if is_muon_param(path, p):
                mu = momentum * mu + g
                direction = g + momentum * mu if nesterov else mu
                return _Pre(p, mu, nu, direction)
            mu2 = adam_b1 * mu + (1 - adam_b1) * g
            nu2 = adam_b2 * nu + (1 - adam_b2) * (g * g)
            upd_ = (mu2 / bc1) / (jnp.sqrt(nu2 / bc2) + adam_eps)
            new_p = p - (lr * adam_lr_ratio) * (upd_ + weight_decay * p)
            return _Pre(new_p.astype(p.dtype), mu2, nu2, None)

        is_pre = lambda x: isinstance(x, _Pre)  # noqa: E731
        pres = jax.tree_util.tree_map_with_path(
            lambda path, p, g, mu, nu: pre(path, p, g, mu, nu),
            params, grads, state.mu, state.nu)
        flat, treedef = jax.tree_util.tree_flatten(pres, is_leaf=is_pre)
        cfg = qr_config
        if cfg is not None:
            cfg = cfg.replace(q_method=qr_q_method)
        orth = iter(batched_orthogonalize(
            [f.direction for f in flat if f.direction is not None],
            policy=ortho_policy, config=cfg,
            fallback=functools.partial(qr_orthogonalize_2d,
                                       q_method=qr_q_method, config=cfg)))
        flat = [f if f.direction is None else
                f._replace(p=finish_muon(f.p, next(orth)), direction=None)
                for f in flat]
        out = jax.tree_util.tree_unflatten(treedef, flat)
        new_params = jax.tree.map(lambda o: o.p, out, is_leaf=is_pre)
        new_mu = jax.tree.map(lambda o: o.mu, out, is_leaf=is_pre)
        new_nu = jax.tree.map(lambda o: o.nu, out, is_leaf=is_pre)
        return new_params, MuonState(step=step, mu=new_mu, nu=new_nu)

    def upd(path, p, g, mu, nu):
        g = g.astype(jnp.float32)
        if is_muon_param(path, p):
            mu = momentum * mu + g
            direction = g + momentum * mu if nesterov else mu
            o = _orthogonalize_leaf(direction, method, orthogonalize_fn,
                                    q_method=qr_q_method,
                                    shard_leaves=qr_shard_leaves,
                                    config=qr_config)
            return finish_muon(p, o), mu, nu  # nu: scalar placeholder
        mu2 = adam_b1 * mu + (1 - adam_b1) * g
        nu2 = adam_b2 * nu + (1 - adam_b2) * (g * g)
        upd_ = (mu2 / bc1) / (jnp.sqrt(nu2 / bc2) + adam_eps)
        new_p = p - (lr * adam_lr_ratio) * (upd_ + weight_decay * p)
        return new_p.astype(p.dtype), mu2, nu2

    out = jax.tree_util.tree_map_with_path(
        lambda path, p, g, mu, nu: _Out(*upd(path, p, g, mu, nu)),
        params, grads, state.mu, state.nu)
    is_out = lambda x: isinstance(x, _Out)
    new_params = jax.tree.map(lambda o: o.p, out, is_leaf=is_out)
    new_mu = jax.tree.map(lambda o: o.mu, out, is_leaf=is_out)
    new_nu = jax.tree.map(lambda o: o.nu, out, is_leaf=is_out)
    return new_params, MuonState(step=step, mu=new_mu, nu=new_nu)
