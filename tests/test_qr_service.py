"""QR serving layer: bucketing properties, service correctness, and
plan-cache behavior.

The load-bearing claims, each pinned by a test here or in the
conformance suite:

  * every request lands in exactly ONE bucket, and the per-dimension
    waste cap is honored whenever achievable at tile granularity
    (property tests over random request mixes);
  * serving answers equal the per-request path's answers — batched
    bitwise parity lives in test_conformance.py; here the end-to-end
    service (pad -> batch -> dispatch -> unpad) meets the numerical bar
    on heterogeneous mixes, both modes, both lowerings;
  * steady-state serving performs ZERO recompilations (the plan cache's
    compile counter is flat across repeated identical traffic);
  * the plan cache is a real LRU: hits refresh recency, evictions hit
    the least-recently-used plan, counters expose all of it.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis_compat import given, settings, st
from repro.serving import (
    BucketKey, BucketingPolicy, QRService, bucket_key, bucketize, pad_batch,
    pad_dim, pow2ish_edges)

# ------------------------------------------------------------- bucketing


@given(tile=st.sampled_from([8, 16, 32, 64]), d=st.integers(1, 5000),
       waste=st.floats(0.05, 0.5))
def test_pad_dim_properties(tile, d, waste):
    """Padded extent is a tile multiple >= max(d, tile); it is either a
    pow2-ish edge within the waste cap or the tile-granularity fallback;
    and the cap is honored whenever tile granularity can honor it."""
    e = pad_dim(d, tile=tile, max_waste=waste)
    assert e >= d and e >= tile and e % tile == 0
    tiled_up = -(-d // tile) * tile
    assert e == tiled_up or ((e - d) / e <= waste
                             and e in pow2ish_edges(tile, d))
    if (tiled_up - d) / tiled_up <= waste:
        assert (e - d) / e <= waste, \
            f"cap achievable at tile granularity but violated: {e} for {d}"


def test_pad_dim_monotone():
    """Bucket edges never cross: a larger matrix never gets a smaller
    bucket (required for the bucket count to stay logarithmic)."""
    for tile, waste in [(16, 0.25), (32, 0.25), (8, 0.1)]:
        pads = [pad_dim(d, tile=tile, max_waste=waste)
                for d in range(1, 700)]
        assert all(a <= b for a, b in zip(pads, pads[1:]))


def test_pow2ish_edges_ladder():
    assert pow2ish_edges(32, 200) == (32, 64, 96, 128, 192, 256)
    # consecutive ratio <= 1.5 from the third edge on
    edges = pow2ish_edges(16, 10000)
    ratios = [b / a for a, b in zip(edges[2:], edges[3:])]
    assert max(ratios) <= 1.5


def test_pad_batch_pow2_capped():
    assert [pad_batch(b, max_batch=8) for b in (1, 2, 3, 4, 5, 8, 9, 100)] \
        == [1, 2, 4, 4, 8, 8, 8, 8]
    with pytest.raises(ValueError):
        pad_batch(0, max_batch=8)


def test_policy_and_key_validation():
    with pytest.raises(ValueError):
        BucketingPolicy(tile=0)
    with pytest.raises(ValueError):
        BucketingPolicy(max_waste=1.0)
    with pytest.raises(ValueError):
        BucketKey(m=32, n=32, dtype="float32", mode="full")


@dataclasses.dataclass
class _Req:
    shape: tuple
    dtype: str
    mode: str


@given(seed=st.integers(0, 10_000), nreq=st.integers(1, 40))
def test_every_request_lands_in_exactly_one_bucket(seed, nreq):
    """bucketize partitions the request stream: every request appears
    exactly once, in the bucket bucket_key maps it to."""
    rng = np.random.default_rng(seed)
    policy = BucketingPolicy(tile=16, max_waste=0.3, max_batch=8)
    reqs = [_Req(shape=(int(rng.integers(1, 400)), int(rng.integers(1, 400))),
                 dtype=str(rng.choice(["float32", "float64"])),
                 mode=str(rng.choice(["reduced", "r"])))
            for _ in range(nreq)]
    buckets = bucketize(reqs, policy)
    seen = []
    for key, members in buckets.items():
        for r in members:
            assert bucket_key(*r.shape, r.dtype, r.mode, policy) == key
            assert key.m >= r.shape[0] and key.n >= r.shape[1]
            seen.append(id(r))
    assert sorted(seen) == sorted(id(r) for r in reqs)


# ------------------------------------------------------------ the service


def _check_qr(a, q, r, tol=2e-4):
    m, n = a.shape
    k = min(m, n)
    q, r = np.asarray(q), np.asarray(r)
    assert q.shape == (m, k) and r.shape == (k, n)
    assert np.abs(q @ r - a).max() <= tol
    assert np.abs(q.T @ q - np.eye(k, dtype=a.dtype)).max() <= tol
    assert np.abs(np.tril(r[:, :k], -1)).max() == 0.0


@pytest.fixture
def service():
    return QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                     use_kernel=False)


def test_heterogeneous_mix_reduced(service):
    """Square / tall / wide / off-tile requests through one flush; every
    answer is the unpadded factorization of ITS matrix."""
    rng = np.random.default_rng(0)
    shapes = [(48, 48), (96, 32), (20, 50), (37, 23), (48, 48), (45, 45)]
    arrs = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    results = service.submit_many(arrs)
    assert len(results) == len(arrs)
    for a, res in zip(arrs, results):
        _check_qr(a, res.q, res.r)
    stats = service.stats()
    assert stats["matrices_served"] == len(arrs)
    assert stats["requests"] == len(arrs)
    assert stats["dispatches"] >= 1


def test_r_mode(service):
    rng = np.random.default_rng(1)
    arrs = [rng.standard_normal((40, 24)).astype(np.float32)
            for _ in range(3)]
    results = service.submit_many(arrs, mode="r")
    for a, res in zip(arrs, results):
        assert res.q is None
        r = np.asarray(res.r)
        assert r.shape == (24, 24)
        assert np.abs(np.tril(r, -1)).max() == 0.0
        assert np.abs(r.T @ r - a.T @ a).max() <= 2e-3 * np.abs(a.T @ a).max()


def test_submit_flush_rids(service):
    """flush keys results by rid; interleaved modes coexist."""
    rng = np.random.default_rng(2)
    a, b = (rng.standard_normal((32, 32)).astype(np.float32)
            for _ in range(2))
    ra = service.submit(a)
    rb = service.submit(b, mode="r")
    out = service.flush()
    assert set(out) == {ra, rb}
    _check_qr(a, out[ra].q, out[ra].r)
    assert out[rb].q is None
    assert service.flush() == {}  # queue drained


def test_ragged_bucket_padding(service):
    """Different true shapes sharing one bucket: each slice's answer is
    its own unpadded factorization (zero padding is numerically free)."""
    rng = np.random.default_rng(3)
    shapes = [(64, 48), (60, 40), (57, 33)]  # all bucket to (64, 48)
    arrs = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    results = service.submit_many(arrs)
    for a, res in zip(arrs, results):
        _check_qr(a, res.q, res.r)
    stats = service.stats()
    assert stats["dispatches"] == 1, "one bucket must mean one dispatch"
    assert stats["padded_slots"] == 1  # batch 3 -> padded batch 4
    assert stats["bucket_fill_ratio"] == pytest.approx(3 / 4)


def test_max_batch_chunking(service):
    """A bucket larger than max_batch splits into full chunks."""
    rng = np.random.default_rng(4)
    arrs = [rng.standard_normal((32, 32)).astype(np.float32)
            for _ in range(6)]  # max_batch=4 -> chunks of 4 and 2
    results = service.submit_many(arrs)
    for a, res in zip(arrs, results):
        _check_qr(a, res.q, res.r)
    assert service.stats()["dispatches"] == 2
    assert service.stats()["padded_slots"] == 0  # 4 and 2 both pow2


def test_kernel_megakernel_serving_path():
    """The Pallas serving path (interpret on CPU): one bucket, batched
    megakernel dispatch, same numerical bar."""
    rng = np.random.default_rng(5)
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=True, dispatch_mode="megakernel")
    arrs = [rng.standard_normal((48, 32)).astype(np.float32)
            for _ in range(2)]
    for a, res in zip(arrs, svc.submit_many(arrs)):
        _check_qr(a, res.q, res.r)
    assert svc.stats()["dispatches"] == 1


def test_submit_validation(service):
    with pytest.raises(ValueError):
        service.submit(np.zeros((3, 3, 3), np.float32))
    with pytest.raises(ValueError):
        service.submit(np.zeros((3, 3), np.float32), mode="full")
    with pytest.raises(ValueError):
        QRService(cache_size=0)


# ------------------------------------------------------------- plan cache


def test_zero_recompiles_steady_state(service):
    """THE serving acceptance property: once the cache is warm, repeated
    traffic with the same shape mix compiles NOTHING new."""
    rng = np.random.default_rng(6)
    shapes = [(48, 48), (96, 32), (37, 23)]

    def mix():
        return [rng.standard_normal(s).astype(np.float32) for s in shapes]

    service.submit_many(mix())          # cold: compiles happen here
    warm = service.stats()["compiles"]
    assert warm > 0
    for _ in range(3):                  # steady state
        for a, res in zip(*(lambda m: (m, service.submit_many(m)))(mix())):
            _check_qr(a, res.q, res.r)
    stats = service.stats()
    assert stats["compiles"] == warm, \
        f"steady-state recompilation: {stats['compiles']} != {warm}"
    assert stats["cache_hits"] >= 3 * len(shapes)
    assert stats["cache_hit_rate"] > 0.5


def test_plan_cache_lru_eviction():
    """cache_size bounds resident plans; eviction is least-recently-USED
    (a hit refreshes recency), and the counters say so."""
    rng = np.random.default_rng(7)
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=False, cache_size=2)

    def go(shape):
        svc.submit_many([rng.standard_normal(shape).astype(np.float32)])

    go((32, 32))   # miss, compile  -> cache [A]
    go((64, 32))   # miss, compile  -> cache [A, B]
    go((32, 32))   # hit            -> cache [B, A] (A refreshed)
    go((96, 32))   # miss, compile  -> evicts B -> cache [A, C]
    s = svc.stats()
    assert (s["compiles"], s["cache_hits"], s["cache_evictions"]) == (3, 1, 1)
    assert s["plans_cached"] == 2
    go((32, 32))   # A survived the eviction (it was refreshed)
    assert svc.stats()["cache_hits"] == 2
    go((64, 32))   # B was the LRU victim -> miss, recompile
    s = svc.stats()
    assert s["compiles"] == 4 and s["cache_evictions"] == 2


def test_tuning_refresh_invalidates_plans():
    """A tuning-cache swap must invalidate every resident bucket plan:
    compiled plans bake in routing/dispatch decisions the old cache
    informed, so serving a stale plan under a new cache silently ignores
    the measurements.  The service fingerprints the active cache and
    drops its LRU on change, counting ``plan_invalidations``."""
    from repro.tuning.cache import TuningCache, active_cache, set_active_cache

    rng = np.random.default_rng(11)
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=False)

    def go(shape):
        svc.submit_many([rng.standard_normal(shape).astype(np.float32)])

    prev = active_cache()
    try:
        go((48, 48))
        s = svc.stats()
        assert s["plans_cached"] > 0 and s["plan_invalidations"] == 0
        compiles = s["compiles"]

        go((48, 48))    # same cache: steady state, no invalidation
        assert svc.stats()["compiles"] == compiles
        assert svc.stats()["plan_invalidations"] == 0

        set_active_cache(TuningCache(source="test:refresh"))
        go((48, 48))    # new fingerprint: plans dropped, recompile
        s = svc.stats()
        assert s["plan_invalidations"] == 1
        assert s["compiles"] == compiles + 1

        go((48, 48))    # new cache is now the steady state
        assert svc.stats()["plan_invalidations"] == 1
        assert svc.stats()["compiles"] == compiles + 1
    finally:
        set_active_cache(prev)


# ------------------------------------------------------------ host answers


@pytest.fixture
def no_faults():
    from repro.robustness import inject

    inject.reset()
    yield inject
    inject.reset()


def _rand(shape, seed):
    return np.random.default_rng(seed).standard_normal(shape).astype(
        np.float32)


def _slot_bytes(key):
    """Bytes of one batch slot of a chunk's padded output stacks."""
    r = key.m * key.n
    q = 0 if key.mode == "r" else key.m * min(key.m, key.n)
    return (q + r) * np.dtype(key.dtype).itemsize


@pytest.mark.parametrize("mode", ["reduced", "r"])
@pytest.mark.parametrize("path", ["kernel_chunk", "lapack_chunk",
                                  "recovered"])
def test_answers_are_host_arrays_on_every_path(path, mode, no_faults):
    """A batched chunk, a breaker-pinned LAPACK chunk and a per-request
    recovery all answer with host ``np.ndarray`` Q and R of the
    unpadded shapes; mode "r" answers with ``q is None``."""
    inject = no_faults
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=False, verify=True, breaker_threshold=1)
    arrs = [_rand((24, 12), s) for s in range(3)]
    if path == "lapack_chunk":          # trip the breaker, then serve
        with inject.active(inject.Fault(site="dispatch", match="32x16")):
            svc.submit_many([_rand((24, 12), 9)], mode=mode)
        assert svc.stats()["breaker_open"] == 1
    fault = (inject.Fault(site="output", match="32x16", slice_index=1)
             if path == "recovered" else None)
    if fault is None:
        results = svc.submit_many(arrs, mode=mode)
    else:
        with inject.active(fault):
            results = svc.submit_many(arrs, mode=mode)
    for a, res in zip(arrs, results):
        assert res.ok
        assert isinstance(res.r, np.ndarray) and res.r.shape == (12, 12)
        if mode == "r":
            assert res.q is None
        else:
            assert isinstance(res.q, np.ndarray) and res.q.shape == (24, 12)
            _check_qr(a, res.q, res.r)
    fetches = svc.stats()["host_fetches"]
    if path == "kernel_chunk":
        assert fetches == (1 if mode == "r" else 2)
        assert all(not res.r.flags.writeable for res in results)
    elif path == "lapack_chunk":
        assert fetches == 0
    else:
        assert svc.stats()["health_check_failures"] == 1


@pytest.mark.parametrize("mode", ["reduced", "r"])
@pytest.mark.parametrize("nreq", [1, 3, 4, 9, 16])
def test_one_host_fetch_per_chunk_output(nreq, mode):
    """A flush of ``nreq`` same-bucket requests fetches each chunk's
    output stacks once — 2 per chunk (1 for mode "r"), whatever the
    number of requests — and counts their padded bytes."""
    policy = BucketingPolicy(tile=16, max_batch=4)
    svc = QRService(policy=policy, use_kernel=False)
    results = svc.submit_many([_rand((24, 12), s) for s in range(nreq)],
                              mode=mode)
    assert all(res.ok for res in results)
    sizes = [min(4, nreq - i) for i in range(0, nreq, 4)]
    st = svc.stats()
    assert st["dispatches"] == len(sizes)
    assert st["host_fetches"] == len(sizes) * (1 if mode == "r" else 2)
    key = bucket_key(24, 12, np.float32, mode, policy)
    assert st["fetch_bytes"] == sum(
        pad_batch(b, max_batch=4) for b in sizes) * _slot_bytes(key)


@pytest.mark.parametrize("mode", ["reduced", "r"])
def test_answers_equal_padded_output_slices_bitwise(mode, monkeypatch):
    """Each answer is bit for bit its slice of the chunk's padded device
    outputs, sliced on the device as a per-request unpad would."""
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=4),
                    use_kernel=False)
    seen = []
    real = QRService._fetch

    def spy(self, key, plan, out, fid):
        seen.append(out)
        return real(self, key, plan, out, fid)

    monkeypatch.setattr(QRService, "_fetch", spy)
    shapes = [(24, 12), (20, 9), (30, 16), (17, 17), (40, 20)]
    arrs = [_rand(s, k) for k, s in enumerate(shapes)]
    results = svc.submit_many(arrs, mode=mode)
    assert len(seen) == svc.stats()["dispatches"]
    # Requests fill their bucket's chunk in submission order.
    by_key, slot = {}, []
    for a in arrs:
        key = bucket_key(*a.shape, a.dtype, mode, svc.policy)
        slot.append((key, by_key.setdefault(key, []).__len__()))
        by_key[key].append(a)
    order = list(by_key)
    for a, res, (key, s) in zip(arrs, results, slot):
        out = seen[order.index(key)]
        m, n = a.shape
        k = min(m, n)
        np.testing.assert_array_equal(
            res.r, np.asarray(out[-1][s, :k, :n]))
        if mode != "r":
            np.testing.assert_array_equal(
                res.q, np.asarray(out[0][s, :m, :k]))


@pytest.mark.parametrize("escalate", [True, False])
def test_fetch_error_escalates_or_restores(escalate, monkeypatch):
    """A device error surfacing at a chunk's fetch: with escalation on,
    every request of the chunk recovers per request (``fetch_failed``);
    with it off the error propagates and every request of the flush is
    back in the queue, including those of chunks fetched before it."""
    svc = QRService(policy=BucketingPolicy(tile=16, max_batch=2),
                    use_kernel=False, escalate=escalate)
    real = QRService._fetch
    calls = []

    def failing(self, key, plan, out, fid):
        calls.append(key)
        if len(calls) == 2:
            raise RuntimeError("device error at fetch")
        return real(self, key, plan, out, fid)

    monkeypatch.setattr(QRService, "_fetch", failing)
    arrs = [_rand((24, 12), s) for s in range(4)]      # two chunks
    if not escalate:
        rids = [svc.submit(a) for a in arrs]
        with pytest.raises(RuntimeError, match="device error at fetch"):
            svc.flush()
        assert sorted(r.rid for r in svc._pending) == rids
        monkeypatch.setattr(QRService, "_fetch", real)
        res = svc.flush()
        for rid, a in zip(rids, arrs):
            _check_qr(a, res[rid].q, res[rid].r)
        return
    results = svc.submit_many(arrs)
    for a, res in zip(arrs, results):
        assert res.ok and isinstance(res.q, np.ndarray)
        _check_qr(a, res.q, res.r)
    assert [e.rule for e in svc.escalations] == ["fetch_failed"]
    assert svc.stats()["escalations"] == 1
    assert svc.stats()["host_fetches"] == 2           # the first chunk's
