"""Service layer: continuous batching ≡ sequential `mac_solve`, prepared-
network cache safety (no in-flight eviction), and shape-bucket routing.

The load-bearing claim (ISSUE 3 acceptance): a `SolverService` fed requests
*over time* — staggered admission, mixed families, mixed shapes, searches
joining and leaving rounds mid-flight — returns solutions AND per-instance
search statistics bit-identical to running `mac_solve` on each CSP alone.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.engine as engine_mod
from repro.core import check_solution, mac_solve
from repro.core.csp import CSP
from repro.engines import get_engine
from repro.kernels import ops
from repro.problems import generate, generate_batch
from repro.service import (
    Bucket,
    FastForwardClock,
    PreparedNetworkCache,
    RequestStatus,
    SolverService,
    bucket_for,
    network_fingerprint,
    pad_csp,
    poisson_trace,
    replay,
)


def _assert_matches_sequential(req, csp, engine="einsum", **kw):
    ref_sol, ref_st = mac_solve(csp, engine=engine, **kw)
    assert req.status is RequestStatus.DONE
    assert req.solution == ref_sol
    assert req.stats.n_assignments == ref_st.n_assignments
    assert req.stats.n_backtracks == ref_st.n_backtracks
    assert req.stats.recurrences == ref_st.recurrences
    assert req.stats.revisions == ref_st.revisions


# --- continuous-batching parity (acceptance criterion) -----------------------


def test_staggered_admission_matches_sequential_mixed_families():
    """Requests arriving mid-flight across two buckets: results and stats are
    bit-identical to sequential mac_solve on every instance."""
    rb = generate_batch("model_rb", 6, n=10, hardness=1.0, seed=5)
    col = generate_batch("coloring_random", 4, n=12, edge_prob=0.3, k=3, seed=1)
    svc = SolverService(engine="einsum", initial_slots=2)

    reqs = [svc.submit(c) for c in rb[:3]]
    svc.step()
    svc.step()  # first wave is mid-search when the second wave arrives
    reqs += [svc.submit(c) for c in rb[3:] + col]
    svc.run_until_idle()

    outcomes = set()
    for req, csp in zip(reqs, rb + col):
        _assert_matches_sequential(req, csp)
        if req.solution is not None:
            assert check_solution(csp, req.solution)
        outcomes.add(req.solution is not None)
    assert outcomes == {True, False}  # the mix straddles SAT and UNSAT


def test_single_request_future_api():
    csp = generate("nqueens", n=8)
    svc = SolverService(engine="einsum")
    req = svc.submit(csp)
    assert not req.done()
    sol, stats = req.result()  # drives the event loop
    assert req.done() and req.status is RequestStatus.DONE
    _assert_matches_sequential(req, csp)
    assert req.latency_s is not None and req.latency_s >= 0
    assert sol is not None and check_solution(csp, sol)
    assert stats is req.stats


def test_sequential_engine_service_parity():
    """AC3 (supports_batch=False) rides the generic host-routing slot pool and
    still matches its own sequential mac_solve exactly."""
    csps = generate_batch("model_rb", 3, n=10, hardness=1.0, seed=5)
    svc = SolverService(engine="ac3")
    reqs = [svc.submit(c) for c in csps]
    svc.run_until_idle()
    for req, csp in zip(reqs, csps):
        _assert_matches_sequential(req, csp, engine="ac3")


def test_per_request_assignment_budget():
    csp = generate("pigeonhole", n=7)  # hard UNSAT: the budget must bite
    svc = SolverService(engine="einsum")
    req = svc.submit(csp, max_assignments=5)
    sol, stats = req.result()
    assert sol is None
    assert stats.exhausted  # budget-capped is inconclusive, NOT a proof of UNSAT
    ref_sol, ref_st = mac_solve(csp, engine="einsum", max_assignments=5)
    assert ref_sol is None and ref_st.exhausted
    assert stats.n_assignments == ref_st.n_assignments


def test_unsat_without_budget_is_not_exhausted():
    sol, stats = mac_solve(generate("pigeonhole", n=5), engine="einsum")
    assert sol is None and not stats.exhausted  # genuine UNSAT proof


def test_deadline_expires_only_the_late_request():
    clock = FastForwardClock()
    svc = SolverService(engine="einsum", clock=clock)
    hard = svc.submit(generate("pigeonhole", n=8), deadline_s=0.0)  # due instantly
    easy = svc.submit(generate("nqueens", n=8))
    svc.run_until_idle()
    assert hard.status is RequestStatus.TIMED_OUT and hard.solution is None
    assert easy.status is RequestStatus.DONE
    _assert_matches_sequential(easy, generate("nqueens", n=8))


def test_cancel_frees_cache_pin():
    svc = SolverService(engine="einsum")
    req = svc.submit(generate("pigeonhole", n=8))
    svc.step()  # admitted + pinned
    entry = svc.cache.lookup(req.bucket, req.fingerprint)
    assert entry is not None and entry.pins == 1
    assert svc.cancel(req) and req.status is RequestStatus.CANCELLED
    assert entry.pins == 0
    assert not svc.cancel(req)  # already terminal
    svc.run_until_idle()


def test_trace_replay_completes_and_measures():
    events = poisson_trace(["model_rb", "coloring_random"], rate=10.0,
                           duration=1.5, seed=0)
    assert events and all(e.t < 1.5 for e in events)
    clock = FastForwardClock()
    svc = SolverService(engine="einsum", clock=clock)
    requests = replay(svc, events, clock)
    assert len(requests) == len(events)
    assert all(r.status is RequestStatus.DONE for r in requests)
    snap = svc.snapshot()
    assert snap["completed"] == len(events)
    assert snap["throughput_rps"] > 0
    assert 0 <= snap["p50_ms"] <= snap["p95_ms"] <= snap["p99_ms"]
    assert snap["mean_rows_per_dispatch"] >= 1.0


# --- bitpacked slot fabric (ISSUE 4 acceptance) ------------------------------


@pytest.mark.pallas
@pytest.mark.parametrize("engine", ["pallas_packed", "pallas_dense"])
def test_pallas_service_parity_zero_host_routing(engine, monkeypatch):
    """SolverService on the Pallas engines: staggered admission, results and
    per-request stats bit-identical to sequential mac_solve, and ZERO
    `route_rows_on_host` calls — every round is the device-resident stacked
    slot-table dispatch (dispatch-counting test double)."""
    calls = []
    real = engine_mod.route_rows_on_host

    def counting(*args, **kw):
        calls.append(args)
        return real(*args, **kw)

    monkeypatch.setattr(engine_mod, "route_rows_on_host", counting)
    csps = generate_batch("model_rb", 3, n=10, hardness=1.0, seed=5)
    svc = SolverService(engine=engine, initial_slots=2)
    reqs = [svc.submit(c) for c in csps[:2]]
    svc.step()  # first wave mid-flight when the last request arrives
    reqs.append(svc.submit(csps[2]))
    svc.run_until_idle()
    for req, csp in zip(reqs, csps):
        _assert_matches_sequential(req, csp, engine=engine)
    assert calls == []  # device-resident slot table: zero host routing


def test_slot_table_advertisement_routes_pool_kind():
    """Engines advertise slot-table support; the pool kind follows the
    advertisement, never a backend-name check."""
    for name in ("einsum", "full", "pallas_dense", "pallas_packed"):
        eng = get_engine(name)
        assert eng.slot_table
        assert eng.open_slot_pool(8, 4, 2).stacked
    for name in ("ac3", "sharded"):
        eng = get_engine(name)
        assert not eng.slot_table
        assert not eng.open_slot_pool(8, 4, 2).stacked


@pytest.mark.parametrize(
    "engine",
    ["einsum", pytest.param("pallas_packed", marks=pytest.mark.pallas)],
)
def test_slot_pool_grow_preserves_resident_networks(engine):
    """`SlotPool.grow` keeps installed networks intact (results identical
    before/after), opens usable new slots, and refuses to shrink."""
    csps = generate_batch("model_rb", 3, n=10, hardness=0.9, seed=3)
    d = csps[0].dom.shape[1]
    eng = get_engine(engine)
    pool = eng.open_slot_pool(10, d, 2)
    pool.install(0, csps[0])
    pool.install(1, csps[1])
    doms = np.stack([np.asarray(c.dom) for c in csps[:2]])
    before = pool.enforce_rows(doms, slot_idx=np.array([0, 1]))
    bytes_before = pool.resident_nbytes

    pool.grow(4)
    assert pool.capacity == 4
    after = pool.enforce_rows(doms, slot_idx=np.array([0, 1]))
    np.testing.assert_array_equal(np.asarray(before.dom), np.asarray(after.dom))
    np.testing.assert_array_equal(
        np.asarray(before.n_recurrences), np.asarray(after.n_recurrences)
    )
    assert pool.resident_nbytes >= bytes_before  # tables grew, networks intact

    pool.install(3, csps[2])  # a newly grown slot is immediately usable
    got = pool.enforce_rows(np.asarray(csps[2].dom)[None], slot_idx=np.array([3]))
    ref = eng.prepare(csps[2]).enforce()
    assert bool(np.asarray(got.consistent)[0]) == bool(np.asarray(ref.consistent))
    if bool(np.asarray(ref.consistent)):
        np.testing.assert_array_equal(np.asarray(got.dom)[0], np.asarray(ref.dom))

    with pytest.raises(ValueError, match="cannot shrink"):
        pool.grow(2)
    with pytest.raises(ValueError, match="empty"):
        pool.enforce_rows(doms[:1], slot_idx=np.array([2]))


# --- prepared-network cache --------------------------------------------------


def test_packed_byte_accounting_admits_8x_more_networks():
    """The LRU budget counts the ENGINE's resident bytes: on the same budget,
    packed-word accounting holds 8 resident networks where the logical
    (unpacked bool) accounting holds exactly one."""
    n, d = 16, 32  # d = 32: one full u32 word per variable, no packing waste
    packed = get_engine("pallas_packed").network_nbytes(n, d)
    unpacked = get_engine("einsum").network_nbytes(n, d)
    budget = 8 * packed
    assert budget // unpacked == 1  # unpacked accounting: ONE network fits

    evicted = []
    cache = PreparedNetworkCache(budget, on_evict=evicted.append)
    for i in range(8):
        entry, hit = cache.acquire(Bucket(n, d), f"fp{i}", packed, lambda i=i: i)
        assert not hit
        cache.release(entry)
    assert len(cache) == 8 and cache.evictions == 0  # all 8 stay resident
    assert cache.bytes_in_use <= cache.byte_budget


def test_pinned_entries_survive_packed_accounting_pressure():
    """Eviction under the packed byte budget still never touches pinned
    entries: over-budget with everything pinned evicts nothing; releasing one
    pin makes exactly that entry evictable."""
    nbytes = get_engine("pallas_packed").network_nbytes(16, 32)
    evicted = []
    cache = PreparedNetworkCache(2 * nbytes, on_evict=evicted.append)
    e0, _ = cache.acquire(Bucket(16, 32), "fp0", nbytes, lambda: 0)
    e1, _ = cache.acquire(Bucket(16, 32), "fp1", nbytes, lambda: 1)
    # both pinned; a third admission runs over budget rather than evict
    e2, _ = cache.acquire(Bucket(16, 32), "fp2", nbytes, lambda: 2)
    assert cache.evictions == 0 and cache.bytes_in_use > cache.byte_budget
    cache.release(e0)  # fp0 unpinned -> the only legal victim
    e3, _ = cache.acquire(Bucket(16, 32), "fp3", nbytes, lambda: 3)
    assert [e.slot for e in evicted] == [0]
    assert cache.lookup(Bucket(16, 32), "fp0") is None
    assert all(
        cache.lookup(Bucket(16, 32), fp) is not None for fp in ("fp1", "fp2", "fp3")
    )
    for e in (e1, e2, e3):
        cache.release(e)


def test_cache_hit_shares_resident_slot():
    csp = generate("nqueens", n=8)  # deterministic: same network every time
    svc = SolverService(engine="einsum")
    r1 = svc.submit(csp)
    r2 = svc.submit(csp)
    svc.step()
    entry = svc.cache.lookup(r1.bucket, r1.fingerprint)
    assert entry is not None and entry.pins == 2  # both flights share one slot
    svc.run_until_idle()
    assert svc.cache.hits == 1 and svc.cache.misses == 1
    assert entry.pins == 0  # warm but unpinned after both retire
    _assert_matches_sequential(r1, csp)
    _assert_matches_sequential(r2, csp)


def test_cache_eviction_never_evicts_inflight_network():
    """Byte budget of ~2 networks under 4 concurrent distinct networks: the
    cache must run over budget rather than evict anything pinned."""
    # under-constrained (SAT side): no root wipeout, so all four searches
    # are still in flight after the first round
    csps = generate_batch("model_rb", 4, n=10, hardness=0.8, seed=5)
    bucket = bucket_for(10, csps[0].dom.shape[1])
    svc = SolverService(
        engine="einsum", cache_bytes=2 * bucket.network_nbytes + 1
    )
    reqs = [svc.submit(c) for c in csps]
    svc.step()  # all four admitted concurrently, all pinned
    entries = [svc.cache.lookup(r.bucket, r.fingerprint) for r in reqs]
    assert all(e is not None and e.pins == 1 for e in entries)
    assert svc.cache.evictions == 0  # over budget, but everything is in flight
    assert svc.cache.bytes_in_use > svc.cache.byte_budget
    svc.run_until_idle()
    for req, csp in zip(reqs, csps):
        _assert_matches_sequential(req, csp)

    # once unpinned, a new distinct admission DOES evict LRU entries
    more = generate_batch("model_rb", 2, n=10, hardness=0.8, seed=77)
    extra = [svc.submit(c) for c in more]
    svc.run_until_idle()
    assert svc.cache.evictions > 0
    assert svc.cache.lookup(reqs[0].bucket, reqs[0].fingerprint) is None  # LRU gone
    for req, csp in zip(extra, more):
        _assert_matches_sequential(req, csp)


def test_evicted_slot_is_reused():
    cache_calls = []
    cache = PreparedNetworkCache(100, on_evict=lambda e: cache_calls.append(e.slot))
    e1, hit = cache.acquire(Bucket(8, 4), "fp1", 60, lambda: 0)
    assert not hit and e1.pins == 1
    cache.release(e1)
    e2, hit = cache.acquire(Bucket(8, 4), "fp2", 60, lambda: 1)  # evicts fp1
    assert not hit and cache_calls == [0]
    assert cache.lookup(Bucket(8, 4), "fp1") is None
    e1b, hit = cache.acquire(Bucket(8, 4), "fp1", 60, lambda: 0)  # rebuilt
    assert not hit
    with pytest.raises(ValueError, match="without pin"):
        cache.release(e1)


def test_fingerprint_separates_network_from_domain():
    csp = generate("model_rb", n=10, seed=3)
    # different domain, same constraint network -> same fingerprint
    narrowed = csp._replace(dom=csp.dom.at[0, 1:].set(False))
    assert network_fingerprint(csp) == network_fingerprint(narrowed)
    other = generate("model_rb", n=10, seed=4)
    assert network_fingerprint(csp) != network_fingerprint(other)


# --- shape buckets -----------------------------------------------------------


def test_bucket_routing_round_trips_shapes():
    for n, d in [(3, 2), (8, 4), (9, 5), (16, 8), (17, 9), (100, 20)]:
        b = bucket_for(n, d)
        assert b.contains(n, d)
        assert b.n_p >= n and b.d_p >= d
        # idempotent: a bucket shape maps to itself
        assert bucket_for(b.n_p, b.d_p) == Bucket(b.n_p, b.d_p)
        # powers of two (with the floor), so bucket count stays O(log² shape)
        assert b.n_p & (b.n_p - 1) == 0 and b.d_p & (b.d_p - 1) == 0


def test_pad_csp_preserves_search_semantics():
    csp = generate("model_rb", n=10, hardness=1.0, seed=2)
    b = bucket_for(*csp.dom.shape)
    padded = pad_csp(csp, b)
    assert padded.dom.shape == (b.n_p, b.d_p)
    n, d = csp.dom.shape
    pd = np.asarray(padded.dom)
    assert not pd[:n, d:].any()  # padded values absent from real domains
    assert (pd[n:, 0] == True).all() and not pd[n:, 1:].any()  # noqa: E712
    assert not np.asarray(padded.mask)[n:, :].any()  # padded vars unconstrained
    with pytest.raises(ValueError, match="does not fit"):
        pad_csp(csp, Bucket(4, 4))


def _host_csp(csp):
    """The CSP as a client holds it: host (numpy) arrays."""
    return CSP(*(np.asarray(a) for a in csp))


def test_pad_csp_pads_host_arrays_on_the_host():
    """For numpy input `pad_csp` returns numpy arrays, bit-identical to the
    jax.numpy padding of the same CSP."""
    csp = generate("model_rb", n=10, hardness=1.0, seed=2)
    b = bucket_for(*csp.dom.shape)
    on_device = pad_csp(csp, b)
    on_host = pad_csp(_host_csp(csp), b)
    for got, want in zip(on_host, on_device):
        assert isinstance(got, np.ndarray)
        assert got.dtype == want.dtype and got.shape == want.shape
        np.testing.assert_array_equal(got, np.asarray(want))


@pytest.mark.parametrize(
    "engine, n, d",
    [
        pytest.param("pallas_packed", 50, 23, marks=pytest.mark.pallas, id="packed-W1"),
        pytest.param("pallas_packed", 40, 40, marks=pytest.mark.pallas, id="packed-W2"),
        pytest.param("pallas_dense", 12, 6, marks=pytest.mark.pallas, id="dense"),
        pytest.param("einsum", 10, 6, id="einsum"),
    ],
)
def test_slot_install_writes_the_encoded_network_and_nothing_else(engine, n, d):
    """One install writes `ops.encode_cons` of the padded network, and the
    mask cast to the table's dtype, into its slot, bit for bit; every other
    slot keeps its bytes."""
    b = bucket_for(n, d)
    csps = [
        _host_csp(generate("random_binary", n=n, d=d, density=0.3,
                           tightness=0.4, seed=s))
        for s in range(3)
    ]
    eng = get_engine(engine)
    pool = eng.open_slot_pool(b.n_p, b.d_p, 3)
    pool.install(0, pad_csp(csps[0], b))  # a bucket-shaped network fits too
    pool.install(2, csps[2])
    before = [np.asarray(t) for t in pool.tables]
    pool.install(1, csps[1])  # the request's own network, as admission hands it
    after = [np.asarray(t) for t in pool.tables]

    kind = getattr(eng, "kind", None)
    n_p, d_p = ops.kernel_dims(kind, b.n_p, b.d_p)[:2] if kind else (b.n_p, b.d_p)
    cons = jnp.pad(jnp.asarray(csps[1].cons),
                   ((0, n_p - n), (0, n_p - n), (0, d_p - d), (0, d_p - d)))
    mask = jnp.pad(jnp.asarray(csps[1].mask), ((0, n_p - n), (0, n_p - n)))
    want = (ops.encode_cons(kind, cons), mask.astype(jnp.uint8)) if kind else (cons, mask)
    if kind == "packed":
        assert after[0].shape[1] == -(-d_p // 32)  # W words per domain
    for got, old, row in zip(after, before, want):
        assert got.dtype == old.dtype
        np.testing.assert_array_equal(got[1], np.asarray(row))
        np.testing.assert_array_equal(got[[0, 2]], old[[0, 2]])


def test_cache_hit_uploads_no_network():
    """A miss uploads its network once, counted by `slots.install_h2d_bytes`;
    admitting a request whose network is resident adds nothing to it or to
    `slots.installed`, and moves no network bytes to the device (its
    admission passes the transfer guard: only the explicit root upload)."""
    import jax

    from repro import obs

    csp = _host_csp(generate("model_rb", n=10, hardness=1.0, seed=2))
    assert bucket_for(*csp.dom.shape) != Bucket(*csp.dom.shape)  # padded
    svc = SolverService(engine="einsum")
    uploaded = obs.REGISTRY.counter("slots.install_h2d_bytes")
    installed = obs.REGISTRY.counter("slots.installed")
    first = svc.submit(csp)
    svc.step()
    assert (obs.REGISTRY.counter("slots.install_h2d_bytes") - uploaded
            == csp.cons.nbytes + csp.mask.nbytes)
    assert obs.REGISTRY.counter("slots.installed") - installed == 1

    uploaded = obs.REGISTRY.counter("slots.install_h2d_bytes")
    second = svc.submit(csp)
    with jax.transfer_guard("disallow"):
        svc.step()
    assert svc.cache.hits == 1 and svc.cache.misses == 1
    assert obs.REGISTRY.counter("slots.install_h2d_bytes") == uploaded
    assert obs.REGISTRY.counter("slots.installed") - installed == 1
    svc.run_until_idle()
    _assert_matches_sequential(first, csp)
    _assert_matches_sequential(second, csp)


def test_requests_route_to_distinct_buckets():
    svc = SolverService(engine="einsum")
    small = svc.submit(generate("model_rb", n=8, seed=0))
    big = svc.submit(generate("random_binary", n=20, d=10, density=0.3,
                              tightness=0.3, seed=0))
    assert small.bucket != big.bucket
    svc.run_until_idle()
    snap = svc.snapshot()
    assert len(snap["buckets"]) == 2
    for info in snap["buckets"].values():
        assert info["resident_nbytes"] > 0  # slot tables are device-resident
    for req in (small, big):
        assert req.status is RequestStatus.DONE


def test_slot_pool_grows_beyond_initial_capacity():
    csps = generate_batch("model_rb", 5, n=10, hardness=0.8, seed=9)
    svc = SolverService(engine="einsum", initial_slots=1)
    reqs = [svc.submit(c) for c in csps]
    svc.run_until_idle()
    for req, csp in zip(reqs, csps):
        _assert_matches_sequential(req, csp)
    (bucket_info,) = svc.snapshot()["buckets"].values()
    assert bucket_info["capacity"] >= 5
