"""Engine-layer tests: registry, cross-backend parity, prepare-once contract.

Parity: every registered engine must produce the identical AC closure and
consistency verdict through the single Engine API — on a slice of the paper's
§5.2 grid, on n-queens, and on a wipeout instance — and ``enforce_batch`` must
equal looped ``enforce``.
"""

import dataclasses

import numpy as np
import pytest

from repro.core import CSPBenchSpec, PAPER_GRID, Engine, mac_solve, nqueens_csp, random_csp
from repro.core.engine import PreparedNetwork
from repro.engines import available_engines, get_engine
from repro.kernels import ops

ENGINES = available_engines()


def _closure(engine_name, csp, dom=None, changed0=None):
    prepared = get_engine(engine_name).prepare(csp)
    res = prepared.enforce(dom, changed0)
    return np.asarray(res.dom), bool(np.asarray(res.consistent))


# --- parity ---------------------------------------------------------------

# a small slice of the paper grid (full d=20 cells; n reduced only via the
# spec so the generator's structure is untouched)
GRID_SLICE = [
    PAPER_GRID[0],  # n=100, density=0.10
    dataclasses.replace(PAPER_GRID[14], n_vars=40),  # density=1.00 cell, shrunk
]


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("spec", GRID_SLICE, ids=lambda s: f"n{s.n_vars}p{s.density}")
def test_paper_grid_parity(engine, spec):
    csp = spec.build()
    ref_dom, ref_ok = _closure("einsum", csp)
    got_dom, got_ok = _closure(engine, csp)
    assert got_ok == ref_ok
    if ref_ok:
        np.testing.assert_array_equal(got_dom, ref_dom)


@pytest.mark.parametrize("engine", ENGINES)
def test_nqueens_parity(engine):
    csp = nqueens_csp(8)
    ref_dom, ref_ok = _closure("einsum", csp)
    got_dom, got_ok = _closure(engine, csp)
    assert got_ok == ref_ok
    if ref_ok:
        np.testing.assert_array_equal(got_dom, ref_dom)


@pytest.mark.parametrize("engine", ENGINES)
def test_wipeout_parity(engine):
    csp = random_csp(6, 4, density=1.0, tightness=0.4, seed=1)
    dom = np.asarray(csp.dom).copy()
    dom[2, :] = False  # empty domain → inconsistent
    _, ok = _closure(engine, csp, dom)
    assert ok is False


@pytest.mark.parametrize("engine", ENGINES)
def test_batch_matches_looped_enforce(engine):
    csp = nqueens_csp(8)
    eng = get_engine(engine)
    prepared = eng.prepare(csp)
    root = prepared.enforce()
    root_dom = np.asarray(root.dom)
    assert bool(np.asarray(root.consistent))

    doms, chs = [], []
    for v in range(4):  # assign queen 0 to rows 0..3
        d = root_dom.copy()
        d[0, :] = False
        d[0, v] = True
        doms.append(d)
        ch = np.zeros((8,), bool)
        ch[0] = True
        chs.append(ch)
    doms = np.stack(doms)
    chs = np.stack(chs)

    batch = prepared.enforce_batch(doms, chs)
    for i in range(4):
        one = prepared.enforce(doms[i], chs[i])
        assert bool(np.asarray(batch.consistent[i])) == bool(np.asarray(one.consistent))
        if bool(np.asarray(one.consistent)):
            np.testing.assert_array_equal(
                np.asarray(batch.dom[i]), np.asarray(one.dom)
            )


# --- registry / API -------------------------------------------------------


def test_registry_contents():
    assert set(ENGINES) >= {"einsum", "full", "pallas_dense", "pallas_packed", "sharded", "ac3"}
    for legacy in ("rtac", "rtac_full"):  # removed after the deprecation release
        with pytest.raises(ValueError, match="unknown engine"):
            get_engine(legacy)


def test_unknown_engine_raises():
    with pytest.raises(ValueError, match="unknown engine"):
        get_engine("does_not_exist")


# --- prepare-once contract (acceptance criterion) --------------------------


class CountingEngine(Engine):
    """Test double: delegates to an inner engine, counting ``prepare`` calls."""

    name = "counting"

    def __init__(self, inner: Engine):
        self.inner = inner
        self.count_unit = inner.count_unit
        self.prepare_calls = 0

    def prepare(self, csp) -> PreparedNetwork:
        self.prepare_calls += 1
        inner_prepared = self.inner.prepare(csp)
        return PreparedNetwork(self, csp, inner_prepared)

    def _prepare_payload(self, csp):  # pragma: no cover - prepare() overridden
        raise AssertionError

    def enforce(self, prepared, dom, changed0=None):
        return prepared.payload.enforce(dom, changed0)

    def enforce_batch(self, prepared, doms, changed0=None):
        return prepared.payload.enforce_batch(doms, changed0)


@pytest.mark.parametrize("batched", [True, False])
def test_prepare_called_exactly_once_per_mac_solve(batched):
    eng = CountingEngine(get_engine("einsum"))
    csp = nqueens_csp(8)
    sol, stats = mac_solve(csp, engine=eng, batched_children=batched)
    assert sol is not None
    assert stats.n_assignments > 1  # many enforcements happened...
    assert eng.prepare_calls == 1  # ...but the network was prepared ONCE


# --- kernel-shim network memoization (per-CSP cache) ------------------------


def test_kernel_prepare_memoized_per_csp():
    csp = random_csp(10, 6, 0.6, 0.4, seed=5)
    net1, _, dims1 = ops.prepare_dense(csp)
    net2, _, dims2 = ops.prepare_dense(csp)
    assert dims1 == dims2
    assert net1[0] is net2[0]  # same prepared cons2 object — cache hit

    other = random_csp(10, 6, 0.6, 0.4, seed=6)
    net3, _, _ = ops.prepare_dense(other)
    assert net3[0] is not net1[0]  # different CSP — different network

    # same cons object, different mask → must MISS (the network embeds mask)
    import jax.numpy as jnp

    relaxed = csp._replace(mask=jnp.zeros_like(csp.mask))
    net4, _, _ = ops.prepare_dense(relaxed)
    assert net4[1] is not net1[1]
    assert not np.asarray(net4[1]).any()  # built from the relaxed mask

    pk1, _, _ = ops.prepare_packed(csp)
    pk2, _, _ = ops.prepare_packed(csp)
    assert pk1[0] is pk2[0]


# --- interpret or compile: one decision, from the backend --------------------


def test_interpret_mode_follows_the_backend(monkeypatch):
    import jax

    assert ops.interpret_mode() is True  # the tests run on the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.interpret_mode() is False
    assert get_engine("pallas_packed").interpret is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops.interpret_mode()


@pytest.mark.parametrize("name", ["pallas_dense", "pallas_packed"])
def test_service_ladder_stepped_rung_inherits_the_engine_mode(name):
    from repro.service import SolverService

    primary = get_engine(name)
    primary.interpret = False  # as constructed on a TPU
    ladder = SolverService._build_ladder(primary)
    assert [getattr(e, "name", None) for e in ladder] == [name, name, "einsum"]
    stepped = ladder[1]
    assert stepped.fixpoint == "stepped" and not stepped.fused_fixpoint
    assert stepped.interpret is False
