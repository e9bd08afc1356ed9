"""QRPlan / solver-registry API tests.

Covers the planner redesign: registry round-trip, QRConfig hashability
under jit static args, the method="auto" routing table, batched solve vs
the jnp.linalg.qr oracle, the config-only API surface (the PR-1 legacy
string-kwarg shim is removed), and the mode="full" regression.
"""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import QRConfig, lstsq, orthogonalize, qr
from repro.core.plan import (
    MethodSpec,
    available_methods,
    get_method,
    plan,
    register_method,
    select_method,
    unregister_method,
)


def _rand(*shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# ------------------------------------------------------------------ registry

def test_registry_roundtrip():
    spec = MethodSpec(name="_dummy_qr", factor=lambda a, cfg: (a, a[0]),
                      description="test stub")
    register_method(spec)
    try:
        assert get_method("_dummy_qr") is spec
        assert "_dummy_qr" in available_methods()
    finally:
        unregister_method("_dummy_qr")
    assert "_dummy_qr" not in available_methods()


def test_unknown_method_errors():
    with pytest.raises(ValueError, match="unknown method"):
        get_method("nope")
    with pytest.raises(ValueError, match="unknown method"):
        plan((8, 8), jnp.float32, QRConfig(method="nope"))
    with pytest.raises(ValueError, match="unknown method"):
        qr(_rand(8, 8), config=QRConfig(method="nope"))


def test_builtins_registered():
    methods = available_methods()
    for name in ("geqr2", "geqr2_ht", "geqrf", "geqrf_ht", "geqrf_fori",
                 "tsqr", "tiled"):
        assert name in methods
    assert get_method("tsqr").min_aspect == 4.0
    assert not get_method("tsqr").supports_full_q
    assert get_method("geqrf_ht").kernel_backed
    assert get_method("tiled").kernel_backed


# ------------------------------------------------------------------ QRConfig

def test_qrconfig_hashable_and_value_semantics():
    a = QRConfig(method="geqrf_ht", block=16)
    b = QRConfig(method="geqrf_ht", block=16)
    assert a == b and hash(a) == hash(b)
    assert len({a, b}) == 1
    assert a.replace(block=32) != a


def test_qrconfig_validation():
    with pytest.raises(ValueError, match="mode"):
        QRConfig(mode="banana")
    with pytest.raises(ValueError, match="q_method"):
        QRConfig(q_method="banana")
    with pytest.raises(ValueError, match="block"):
        QRConfig(block=0)
    with pytest.raises(ValueError, match="dispatch_mode"):
        QRConfig(dispatch_mode="warpspeed")


def test_plan_resolves_engine_dispatch_mode():
    """Engine-backed methods resolve dispatch_mode=None on the kernel
    path (megakernel within budgets, honoring an explicit override) and
    leave it None on the jnp-oracle path."""
    shape = (96, 64)
    resolved = plan(shape, jnp.float32,
                    QRConfig(method="tiled", block=16, use_kernel=True))
    assert resolved.config.dispatch_mode == "megakernel"
    forced = plan(shape, jnp.float32,
                  QRConfig(method="tiled", block=16, use_kernel=True,
                           dispatch_mode="wavefront"))
    assert forced.config.dispatch_mode == "wavefront"
    oracle = plan(shape, jnp.float32,
                  QRConfig(method="tiled", block=16, use_kernel=False))
    assert oracle.config.dispatch_mode is None


def test_plan_dispatch_mode_accounts_for_dtype():
    """The auto rule resolves at the planned element width: a tile whose
    double-buffered megakernel set fits in fp32 but not fp64 must pin
    wavefront for fp64 input (else solve() would hit the runtime VMEM
    guard instead of falling back)."""
    from repro.kernels import macro_ops
    from repro.core.plan import kernel_vmem_budget

    nb = 288
    budget = kernel_vmem_budget("macro_ops")
    assert macro_ops.megakernel_vmem_bytes(nb, 4) <= budget \
        < macro_ops.megakernel_vmem_bytes(nb, 8)
    shape = (4 * nb, 2 * nb)
    cfg = QRConfig(method="tiled", block=nb, use_kernel=True)
    assert plan(shape, jnp.float32, cfg).config.dispatch_mode == "megakernel"
    assert plan(shape, jnp.float64, cfg).config.dispatch_mode == "wavefront"
    # the precision override wins over the input dtype
    assert plan(shape, jnp.float64,
                cfg.replace(precision="float32")
                ).config.dispatch_mode == "megakernel"


def test_kernel_fits_gate_prices_wavefront_floor():
    """The planner's fits-in-VMEM gate prices the kernel path at its
    wavefront floor: an fp64 shape whose wavefront set fits must keep
    use_kernel on TPU even though the megakernel set would not (auto
    then pins the wavefront lowering) — the megakernel is an opt-in
    upgrade, never a reason to lose the kernel path.  TPU tiles are
    lane-aligned (multiples of 128): at 384 the fp64 megakernel set
    (15 tiles) is over the 8 MiB budget and the wavefront set (7) is
    under it; at 256 the fp32 megakernel set fits."""
    nb = 384
    shape = (4 * nb, 2 * nb)
    s64 = plan(shape, jnp.float64, QRConfig(method="tiled", block=nb),
               backend="tpu")
    assert s64.config.use_kernel is True
    assert s64.config.block == nb
    assert s64.config.dispatch_mode == "wavefront"
    s32 = plan(shape, jnp.float32, QRConfig(method="tiled", block=256),
               backend="tpu")
    assert s32.config.use_kernel is True
    assert s32.config.dispatch_mode == "megakernel"


def test_qrconfig_as_jit_static_arg():
    @functools.partial(jax.jit, static_argnames=("cfg",))
    def f(a, cfg: QRConfig):
        return plan(a.shape, a.dtype, cfg).solve(a)

    a = _rand(24, 12, seed=1)
    q1, r1 = f(a, QRConfig(method="geqrf_ht", block=8))
    q2, r2 = f(a, QRConfig(method="geqrf_ht", block=8))  # cache hit
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    qn, rn = jnp.linalg.qr(a)
    s = jnp.sign(jnp.diagonal(r1)) * jnp.sign(jnp.diagonal(rn))
    np.testing.assert_allclose(np.asarray(q1 * s[None, :]), np.asarray(qn),
                               atol=3e-5)


# ------------------------------------------------------------- auto routing

def test_auto_picks_tsqr_for_tall_skinny():
    solver = plan((1024, 32), jnp.float32, QRConfig())
    assert solver.config.method == "tsqr"
    assert solver.config.nblocks == 8  # planner-chosen divisor of m
    assert 1024 % solver.config.nblocks == 0


def test_auto_picks_kernel_geqrf_ht_on_tpu_when_panel_fits():
    # aspect < 4 so TSQR is out; panel (256 x 32) easily fits VMEM
    solver = plan((256, 128), jnp.float32, QRConfig(), backend="tpu")
    assert solver.config.method == "geqrf_ht"
    assert solver.config.use_kernel is True


def test_auto_skips_kernel_when_panel_exceeds_vmem():
    # 2 * 40000 * 32 * 4 bytes > the 8 MiB budget
    solver = plan((40000, 16384), jnp.float32, QRConfig(), backend="tpu")
    assert solver.config.method == "geqrf_ht"
    assert solver.config.use_kernel is False


# The full auto routing table in one place: (shape, backend, ndevices)
# -> method.  ndevices=1 is the single-device column; the >1 columns
# exercise the device-count-aware sharded_tiled routing.
#
# These rows document the HEURISTIC rules, so they pin
# use_tuning_cache=False (_HEUR): with the committed measured cache
# active, swept shape classes (256^2..512^2 squares on CPU — including
# (255,255) and (511,500), which pad into those classes) route via the
# "tuned" rule instead.  tests/test_tuning.py covers that layer.
_HEUR = QRConfig(use_tuning_cache=False)

_ROUTING_TABLE = [
    ((1024, 32), "cpu", 1, "tsqr"),        # tall-skinny beats everything
    ((1024, 256), "cpu", 1, "tsqr"),       # exactly 4:1 is still TSQR
    ((512, 512), "cpu", 1, "tiled"),       # large near-square -> task graph
    ((512, 512), "tpu", 1, "tiled"),
    ((1023, 256), "cpu", 1, "geqrf_ht"),   # under the raised CPU floor
    ((1023, 512), "cpu", 1, "tiled"),      # at the CPU floor
    ((1023, 256), "tpu", 1, "tiled"),      # TPU keeps the 256 floor
    ((300, 280), "cpu", 1, "geqrf_ht"),    # LAPACK geqrf wins small squares
    ((300, 280), "tpu", 1, "tiled"),
    ((2048, 1024), "cpu", 1, "tiled"),     # at the tiled ceiling
    ((2049, 1024), "cpu", 1, "geqrf_ht"),  # past it: DAG would be too big
    ((40000, 16384), "tpu", 1, "geqrf_ht"),
    ((256, 128), "tpu", 1, "geqrf_ht"),    # min dim below the tiled floor
    ((256, 128), "cpu", 1, "geqrf_ht"),
    ((255, 255), "cpu", 1, "geqrf_ht"),    # one short of the (TPU) floor
    ((511, 500), "cpu", 1, "geqrf_ht"),    # one short of the CPU floor
    ((256, 256), "tpu", 1, "tiled"),       # TPU floor unchanged at 256
    ((256, 40000), "cpu", 1, "geqrf_ht"),  # wide but far from square
    ((24, 16), "cpu", 1, "geqr2_ht"),      # single panel
    # -- device-count-aware rows: past the tiled ceiling, near-square --
    ((512, 512), "cpu", 8, "tiled"),         # one device's budget: stay tiled
    ((2049, 1024), "cpu", 8, "sharded_tiled"),  # too big for one device
    ((4096, 4096), "cpu", 8, "sharded_tiled"),
    ((4096, 2048), "cpu", 2, "sharded_tiled"),  # within 2x the ceiling
    ((8192, 4096), "cpu", 2, "geqrf_ht"),    # past d * ceiling: blocked
    ((2049, 1024), "cpu", 1, "geqrf_ht"),    # no second device, no sharding
    ((1024, 2049), "cpu", 8, "geqrf_ht"),    # wide: row-sharding won't help
    ((40000, 16384), "cpu", 8, "geqrf_ht"),  # past the 8-device ceiling too
]


@pytest.mark.parametrize("shape,backend,ndevices,expected", _ROUTING_TABLE)
def test_auto_routing_table(shape, backend, ndevices, expected):
    assert select_method(shape, jnp.float32, _HEUR,
                         backend=backend, ndevices=ndevices) == expected


@pytest.mark.parametrize("shape,backend,ndevices,expected", _ROUTING_TABLE)
def test_auto_routing_table_explain(shape, backend, ndevices, expected):
    """Every routing-table decision is explainable: ``plan(explain=True)``
    attaches a PlanExplain whose selected decision names the winning rule
    with a non-empty machine-readable reason, and whose decision trail
    records why each earlier candidate was rejected."""
    solver = plan(shape, jnp.float32, _HEUR, backend=backend,
                  ndevices=ndevices, explain=True)
    ex = solver.explain
    assert ex is not None
    assert ex.method == expected == solver.config.method
    assert ex.shape == shape and ex.backend == backend
    assert ex.ndevices == ndevices
    sel = ex.selected
    assert sel is not None and sel.outcome == "selected" and sel.reason
    # Every decision in the trail is machine-readable: a stable rule
    # slug plus a human reason, never empty.
    for d in ex.decisions:
        assert d.rule and d.outcome in ("selected", "rejected",
                                        "fallback", "resolved")
        assert d.reason
    # The trail ends at the winner: no decisions after the selection.
    kinds = [d.outcome for d in ex.decisions]
    assert "selected" in kinds
    # fallback_reasons mirrors the fallback decisions exactly.
    assert ex.fallback_reasons == tuple(
        d.rule for d in ex.decisions if d.outcome == "fallback")


def test_plan_explain_default_off_and_identity_preserving():
    """explain=False (default) leaves solver.explain None, and the
    explain field never perturbs solver equality/hash (jit-static id)."""
    s0 = plan((512, 512), jnp.float32, QRConfig(), backend="cpu")
    s1 = plan((512, 512), jnp.float32, QRConfig(), backend="cpu",
              explain=True)
    assert s0.explain is None and s1.explain is not None
    assert s0 == s1 and hash(s0) == hash(s1)


def test_plan_explain_cpu_floor_fallback_reason():
    """The silent small-square degradation on CPU — near-square inside
    the tiled band but under the raised CPU floor — now carries a
    structured fallback reason."""
    solver = plan((300, 280), jnp.float32, QRConfig(), backend="cpu",
                  explain=True)
    assert solver.config.method == "geqrf_ht"
    assert "tiled_min_dim_cpu_floor" in solver.explain.fallback_reasons
    d = solver.explain.decision("tiled_min_dim_cpu_floor")
    assert d.outcome == "fallback" and "cpu" in d.reason.lower()


@pytest.mark.parametrize("shape,method", [((512, 512), "tiled"),
                                          ((4096, 4096), "sharded_tiled")])
def test_plan_tpu_kernel_tile_is_lane_aligned(shape, method):
    """The TPU compiler refuses the engine's tile DMAs below 128 lanes, so
    on TPU the kernel path never runs the ``block=32`` default: the tile
    is raised and the explain trail names the rule that raised it.  Off
    TPU (interpret mode has no such limit) the tile stays as asked."""
    kw = {"ndevices": 4} if method == "sharded_tiled" else {}
    solver = plan(shape, jnp.float32, QRConfig(), backend="tpu",
                  explain=True, **kw)
    assert solver.config.method == method and solver.config.use_kernel
    assert solver.config.block % 128 == 0
    d = solver.explain.decision("tpu_tile_lane_aligned")
    assert d.outcome == "resolved" and "32 -> 128" in d.reason
    cpu = plan(shape, jnp.float32, QRConfig(method=method, use_kernel=True),
               backend="cpu", explain=True, **kw)
    assert "tpu_tile_lane_aligned" not in [
        x.rule for x in cpu.explain.decisions]


def test_plan_explain_sharded_degraded_reason():
    """Past the tiled ceiling with only one device: the sharded route is
    rejected with a machine-readable reason, not silently skipped."""
    solver = plan((2049, 1024), jnp.float32, QRConfig(), backend="cpu",
                  ndevices=1, explain=True)
    assert solver.config.method == "geqrf_ht"
    rules = [d.rule for d in solver.explain.decisions]
    assert "sharded_past_ceiling" in rules


def test_auto_sharded_routing_respects_full_mode():
    """Full Q is not a sharded capability -> auto must not route there."""
    assert select_method((2049, 1024), jnp.float32, QRConfig(mode="full"),
                         backend="cpu", ndevices=8) != "sharded_tiled"


def test_auto_sharded_routing_respects_batched():
    """Batched stacks are not a sharded capability either — auto must
    keep them plannable (blocked path), not raise downstream."""
    assert select_method((4, 2049, 1024), jnp.float32, QRConfig(),
                         backend="cpu", ndevices=8) == "geqrf_ht"
    solver = plan((4, 2049, 1024), jnp.float32, QRConfig(), ndevices=8)
    assert solver.config.method == "geqrf_ht"


def test_auto_picks_tiled_for_large_near_square():
    # heuristic rule under test — pin the cache off (the measured CPU
    # cache routes 512^2 to geqrf_ht, which is the point of PR 8)
    solver = plan((512, 512), jnp.float32, _HEUR, backend="cpu")
    assert solver.config.method == "tiled"
    assert solver.config.use_kernel is False  # jnp path off-TPU
    solver_tpu = plan((512, 512), jnp.float32, _HEUR, backend="tpu")
    assert solver_tpu.config.method == "tiled"
    assert solver_tpu.config.use_kernel is True  # tile pair fits VMEM


def test_auto_small_problems_use_unblocked_mht():
    assert select_method((24, 16), jnp.float32, QRConfig()) == "geqr2_ht"


def test_auto_default_is_blocked_mht_on_cpu():
    solver = plan((256, 128), jnp.float32, QRConfig(), backend="cpu")
    assert solver.config.method == "geqrf_ht"
    assert solver.config.use_kernel is False


def test_auto_never_picks_tsqr_for_full_mode():
    solver = plan((1024, 32), jnp.float32, QRConfig(mode="full"))
    assert solver.config.method != "tsqr"
    q, r = solver.solve(_rand(1024, 32, seed=3))
    assert q.shape == (1024, 1024) and r.shape == (1024, 32)


def test_kernel_policy_single_vmem_budget():
    """Planner decisions and kernel runtime guards read one budget."""
    from repro.core.plan import DEFAULT_VMEM_BUDGET, kernel_vmem_budget
    from repro.kernels import ops, tile_ops

    assert kernel_vmem_budget() == DEFAULT_VMEM_BUDGET
    assert kernel_vmem_budget("mht_panel") == ops._POLICY.vmem_budget
    assert kernel_vmem_budget("tile_ops") == tile_ops._POLICY.vmem_budget
    assert ops._POLICY.vmem_budget == tile_ops._POLICY.vmem_budget
    # unknown policies fall back to the shared default
    assert kernel_vmem_budget("nope") == DEFAULT_VMEM_BUDGET


def test_capability_checks():
    with pytest.raises(ValueError, match="tall-skinny"):
        plan((64, 32), jnp.float32, QRConfig(method="tsqr"))
    with pytest.raises(ValueError, match="thin Q"):
        plan((256, 16), jnp.float32, QRConfig(method="tsqr", mode="full"))
    with pytest.raises(ValueError, match="kernel"):
        plan((64, 32), jnp.float32, QRConfig(method="geqr2", use_kernel=True))


def test_auto_tsqr_matches_oracle():
    a = _rand(1024, 32, seed=4)
    q, r = qr(a, config=QRConfig())
    rn = jnp.linalg.qr(a)[1]
    s = jnp.sign(jnp.diagonal(r)) * jnp.sign(jnp.diagonal(rn))
    np.testing.assert_allclose(np.asarray(r * s[:, None]), np.asarray(rn),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(q @ r), np.asarray(a), atol=1e-4)


# ------------------------------------------------------- batched + jit/vmap

def test_batched_qr_matches_oracle():
    a = _rand(3, 32, 16, seed=5)
    qb, rb = qr(a, config=QRConfig(method="geqrf_ht", block=8))
    assert qb.shape == (3, 32, 16) and rb.shape == (3, 16, 16)
    for i in range(3):
        qn, rn = jnp.linalg.qr(a[i])
        s = jnp.sign(jnp.diagonal(rb[i])) * jnp.sign(jnp.diagonal(rn))
        np.testing.assert_allclose(np.asarray(qb[i] * s[None, :]),
                                   np.asarray(qn), atol=3e-5)
        np.testing.assert_allclose(np.asarray(rb[i] * s[:, None]),
                                   np.asarray(rn), atol=3e-5)


def test_batched_solver_under_jit_and_vmap():
    a = _rand(4, 48, 12, seed=6)
    solver = plan(a.shape, a.dtype, QRConfig(method="geqrf_ht", block=4))
    out_solver = solver.solve(a)  # internal vmap rule
    f = jax.jit(jax.vmap(plan((48, 12), a.dtype,
                              QRConfig(method="geqrf_ht", block=4)).solve))
    out_jit = f(a)  # external jit+vmap over a 2-D solver
    np.testing.assert_allclose(np.asarray(out_solver[0]),
                               np.asarray(out_jit[0]), atol=1e-6)
    rec = jnp.einsum("bmk,bkn->bmn", out_jit[0], out_jit[1])
    np.testing.assert_allclose(np.asarray(rec), np.asarray(a), atol=1e-4)


def test_batched_auto_tsqr():
    a = _rand(2, 256, 16, seed=7)
    solver = plan(a.shape, a.dtype, QRConfig())
    assert solver.config.method == "tsqr"
    q, r = solver.solve(a)
    assert q.shape == (2, 256, 16) and r.shape == (2, 16, 16)
    rec = jnp.einsum("bmk,bkn->bmn", q, r)
    np.testing.assert_allclose(np.asarray(rec), np.asarray(a), atol=1e-4)


# ---------------------------------------------------- post-shim API surface

def test_legacy_string_kwargs_removed():
    """The PR-1 deprecation shim is gone: string kwargs are a TypeError,
    not a DeprecationWarning."""
    a = _rand(16, 8, seed=10)
    with pytest.raises(TypeError):
        qr(a, method="geqrf_ht")
    with pytest.raises(TypeError):
        qr(a, block=8)
    with pytest.raises(TypeError):
        orthogonalize(a, method="geqr2_ht")
    with pytest.raises(TypeError):
        lstsq(a, a[:, 0], method="geqrf")


def test_qr_default_config_is_auto_planner():
    """qr(a) with no config plans with QRConfig() — the auto route."""
    a = _rand(32, 12, seed=9)
    q1, r1 = qr(a)
    q2, r2 = plan(a.shape, a.dtype, QRConfig()).solve(a)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


# ------------------------------------------------ wrappers through planner

def test_orthogonalize_auto_routes_tall_skinny_through_tsqr():
    cfg = QRConfig()
    assert select_method((256, 16), jnp.float32,
                         cfg.replace(sign_fix=True)) == "tsqr"
    o = orthogonalize(_rand(256, 16, seed=12), config=cfg)
    np.testing.assert_allclose(np.asarray(o.T @ o), np.eye(16), atol=1e-4)
    # wide input factorizes the transpose — also tall-skinny, also TSQR
    ow = orthogonalize(_rand(16, 256, seed=13), config=cfg)
    assert ow.shape == (16, 256)
    np.testing.assert_allclose(np.asarray(ow @ ow.T), np.eye(16), atol=1e-4)


def test_lstsq_auto_routes_tall_skinny_through_tsqr():
    a = _rand(256, 8, seed=14)
    x_true = _rand(8, seed=15)
    b = a @ x_true
    x = lstsq(a, b, config=QRConfig())
    np.testing.assert_allclose(np.asarray(x), np.asarray(x_true), atol=1e-3)


# ------------------------------------------------- degenerate (zero-dim)

@pytest.mark.parametrize("shape", [(0, 5), (5, 0), (0, 0)])
def test_degenerate_routing_zero_dims(shape):
    """Zero-dim inputs route to the trivial method on every path — the
    PR-8 bugfix for the planner crashing where jnp.linalg.qr succeeds."""
    assert select_method(shape, jnp.float32, QRConfig()) == "degenerate"
    solver = plan(shape, jnp.float32, QRConfig(), explain=True)
    assert solver.config.method == "degenerate"
    sel = solver.explain.selected
    assert sel.rule == "degenerate_empty" and "zero-dim" in sel.reason


def test_degenerate_overrides_explicit_method():
    """An explicit method cannot factor an empty matrix — the override
    is applied and recorded in the decision reason, not raised."""
    solver = plan((0, 5), jnp.float32, QRConfig(method="tiled"),
                  explain=True)
    assert solver.config.method == "degenerate"
    assert "overrides config.method='tiled'" in solver.explain.selected.reason


def test_degenerate_method_rejects_nonempty():
    with pytest.raises(ValueError, match="zero-dim"):
        plan((8, 8), jnp.float32, QRConfig(method="degenerate"))


def test_degenerate_batched_solve():
    a = jnp.zeros((3, 0, 5), jnp.float32)
    q, r = plan(a.shape, a.dtype, QRConfig()).solve(a)
    assert q.shape == (3, 0, 0) and r.shape == (3, 0, 5)


# ------------------------------------------- explain-trail completeness

def test_route_trail_is_complete_prefix():
    """PR-8 bugfix: every core rule evaluated before the winner records
    a decision on EVERY path (sharded_past_ceiling used to vanish from
    the trail for near-square under-ceiling single-device shapes).  The
    recorded core-rule decisions must be exactly the contiguous run of
    ``plan._ROUTE_RULES`` from "tuned" through the selected rule."""
    from repro.core.plan import _ROUTE_RULES

    for shape, backend, ndevices, expected in _ROUTING_TABLE:
        solver = plan(shape, jnp.float32, _HEUR, backend=backend,
                      ndevices=ndevices, explain=True)
        core = [d for d in solver.explain.decisions if d.rule in _ROUTE_RULES]
        assert core[-1].outcome == "selected", (shape, backend, ndevices)
        assert all(d.outcome == "rejected" for d in core[:-1])
        got = tuple(d.rule for d in core)
        start = _ROUTE_RULES.index("tuned")
        stop = _ROUTE_RULES.index(core[-1].rule) + 1
        assert got == _ROUTE_RULES[start:stop], (shape, backend, ndevices)


def test_trail_records_sharded_rejection_under_the_ceiling():
    """The specific shape class the incomplete-trail bug dropped: the
    rejected branch used to be recorded only when ``near_square and
    max(m, n) > _TILED_MAX_DIM``, so any shape that fell through tiled
    *below* the ceiling lost its sharded decision entirely."""
    solver = plan((300, 280), jnp.float32, _HEUR, backend="cpu",
                  ndevices=1, explain=True)
    d = solver.explain.decision("sharded_past_ceiling")
    assert d is not None and d.outcome == "rejected"
    assert "not near-square" in d.reason


# ------------------------------------------- fallback-counter hygiene

def test_select_method_is_pure_query_no_counters():
    """PR-8 bugfix: ``select_method`` / ``_route`` are pure queries —
    only ``plan()`` emits planner.fallbacks, exactly once per plan, so
    ``plan(explain=True)`` cannot double-count against an earlier
    ``select_method`` probe of the same shape."""
    from repro.observability import metrics

    before = metrics.counter_value("planner.fallbacks",
                                   reason="tiled_min_dim_cpu_floor")
    select_method((300, 280), jnp.float32, QRConfig(), backend="cpu")
    select_method((300, 280), jnp.float32, QRConfig(), backend="cpu")
    assert metrics.counter_value(
        "planner.fallbacks", reason="tiled_min_dim_cpu_floor") == before
    plan((300, 280), jnp.float32, QRConfig(), backend="cpu", explain=True)
    assert metrics.counter_value(
        "planner.fallbacks", reason="tiled_min_dim_cpu_floor") == before + 1


def test_solver_q_method_solve_matches_formq():
    a = _rand(96, 24, seed=16)
    q1, _ = plan(a.shape, a.dtype,
                 QRConfig(method="geqrf_ht", q_method="formq")).solve(a)
    q2, _ = plan(a.shape, a.dtype,
                 QRConfig(method="geqrf_ht", q_method="solve")).solve(a)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), atol=1e-4)
