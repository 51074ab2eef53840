"""Pallas kernel validation — interpret-mode vs the pure-jnp oracle (ref.py).

Per instructions: sweep shapes/dtypes and assert allclose (here: exact equality
— the kernels are boolean) against the oracle, plus hypothesis-random CSPs and
end-to-end fixpoint equality. The stacked (instance-axis-in-the-grid) kernel
variants are validated row-by-row: every row must equal the oracle applied to
that row's OWN network.

The whole module is `pytest.mark.pallas`: interpret mode executes kernel
bodies in Python, so these run in CI's dedicated pallas leg, not the main
tier-1 matrix.
"""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import enforce, random_csp
from repro.core.engine import pad_changed, pad_dom
from repro.engines import get_engine
from repro.kernels import ops
from repro.kernels.ref import (
    pack_bits_ref,
    revise_packed_ref,
    revise_ref,
)

pytestmark = pytest.mark.pallas

SHAPE_SWEEP = [
    # (n_vars, dom_size, block_rx, block_ry): the variable axis pads to a
    # multiple of max(block_rx, block_ry)
    (4, 3, 4, 4),
    (8, 5, 8, 8),
    (10, 6, 8, 8),
    (16, 8, 8, 8),
    (16, 8, 4, 8),
    (16, 8, 8, 4),
    (24, 33, 8, 8),  # d > 32: multi-word bitpack
    (12, 64, 4, 4),
]


def _changed_patterns(n, seed):
    rng = np.random.default_rng(seed)
    return [
        np.ones(n, bool),
        rng.random(n) < 0.5,
        np.eye(n, dtype=bool)[rng.integers(n)],
    ]


@pytest.mark.parametrize("n,d,brx,bry", SHAPE_SWEEP)
def test_dense_kernel_matches_oracle(n, d, brx, bry):
    csp = random_csp(n, d, density=0.6, tightness=0.4, seed=n * 100 + d)
    net, dom_p, (n_p, d_p) = ops.prepare_dense(csp, max(brx, bry))
    rf = ops.revise_fn("dense", True)
    for changed in _changed_patterns(n, seed=d):
        ch = jnp.asarray(changed)
        oracle = revise_ref(csp.cons, csp.mask, csp.dom, ch)
        got = rf(net, dom_p, jnp.pad(ch, (0, n_p - n)))[:n, :d]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


@pytest.mark.parametrize("n,d,brx,bry", SHAPE_SWEEP)
def test_packed_kernel_matches_oracle(n, d, brx, bry):
    csp = random_csp(n, d, density=0.6, tightness=0.4, seed=n * 100 + d)
    net, dom_p, (n_p, d_p, w) = ops.prepare_packed(csp, max(brx, bry))
    rf = ops.revise_fn("packed", True)
    for changed in _changed_patterns(n, seed=d):
        ch = jnp.asarray(changed)
        oracle = revise_ref(csp.cons, csp.mask, csp.dom, ch)
        got = rf(net, dom_p, jnp.pad(ch, (0, n_p - n)))[:n, :d]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle))


# --- stacked kernels: R rows, each against its OWN network -------------------

STACK_SWEEP = [
    (8, 5, 8, 8),
    (16, 8, 8, 8),
    (16, 8, 4, 8),
    (24, 33, 8, 8),  # d > 32: multi-word bitpack
]


def _stacked_fixture(n, d, brx, bry, prepare):
    """3 networks, 5 rows via idx [2,0,1,2,0], mixed changed patterns."""
    csps = [random_csp(n, d, 0.6, 0.4, seed=300 + i) for i in range(3)]
    prepared = [prepare(c, max(brx, bry)) for c in csps]
    dims = prepared[0][2]
    cons_g = jnp.stack([p[0][0] for p in prepared])
    mask_g = jnp.stack([p[0][1] for p in prepared])
    idx = np.array([2, 0, 1, 2, 0], np.int32)
    rng = np.random.default_rng(n * 7 + d)
    doms = np.stack([np.asarray(csps[j].dom) for j in idx])
    changed = rng.random((len(idx), n)) < 0.5
    changed[0] = True  # one all-changed row (the root-propagation shape)
    return csps, (cons_g, mask_g), dims, idx, doms, changed


@pytest.mark.parametrize("n,d,brx,bry", STACK_SWEEP)
def test_stacked_dense_rows_match_oracle(n, d, brx, bry):
    csps, (cons_g, mask_g), (n_p, d_p), idx, doms, changed = _stacked_fixture(
        n, d, brx, bry, ops.prepare_dense
    )
    rf = ops.rows_fn("dense", True)
    dom_p = pad_dom(jnp.asarray(doms), n_p, d_p)
    ch_p = pad_changed(jnp.asarray(changed), n, n_p, batch=(len(idx),))
    got = np.asarray(rf((cons_g[idx], mask_g[idx]), dom_p, ch_p))
    for row, j in enumerate(idx):
        oracle = revise_ref(
            csps[j].cons, csps[j].mask, jnp.asarray(doms[row]), jnp.asarray(changed[row])
        )
        np.testing.assert_array_equal(got[row, :n, :d], np.asarray(oracle))


@pytest.mark.parametrize("n,d,brx,bry", STACK_SWEEP)
def test_stacked_packed_rows_match_oracle(n, d, brx, bry):
    csps, (cons_g, mask_g), (n_p, d_p, w), idx, doms, changed = _stacked_fixture(
        n, d, brx, bry, ops.prepare_packed
    )
    rf = ops.rows_fn("packed", True)
    dom_p = pad_dom(jnp.asarray(doms), n_p, d_p)
    ch_p = pad_changed(jnp.asarray(changed), n, n_p, batch=(len(idx),))
    got = np.asarray(rf((cons_g[idx], mask_g[idx]), dom_p, ch_p))
    for row, j in enumerate(idx):
        oracle = revise_ref(
            csps[j].cons, csps[j].mask, jnp.asarray(doms[row]), jnp.asarray(changed[row])
        )
        np.testing.assert_array_equal(got[row, :n, :d], np.asarray(oracle))


def test_enforce_rows_generic_matches_solo_recurrence_counts():
    """The stacked fixpoint freezes converged/wiped-out rows: per-row domains,
    verdicts AND recurrence counts equal solo `enforce_generic` runs even
    though the while_loop runs until the slowest row converges."""
    n, d = 10, 6
    csps = [random_csp(n, d, 0.7, 0.5, seed=40 + i) for i in range(3)]
    prepared = [ops.prepare_packed(c) for c in csps]
    n_p, d_p, w = prepared[0][2]
    tables = (
        jnp.stack([p[0][0] for p in prepared]),
        jnp.stack([p[0][1] for p in prepared]),
    )
    rf = ops.rows_fn("packed", True)
    idx = np.array([0, 1, 2, 1], np.int32)
    doms = np.stack([np.asarray(csps[j].dom) for j in idx])
    doms[3, 0, 1:] = False  # a row that starts near wipeout
    from repro.core import rtac

    res = rtac.enforce_rows_generic(
        tables,
        pad_dom(jnp.asarray(doms), n_p, d_p),
        pad_changed(None, n, n_p, batch=(len(idx),)),
        jnp.asarray(idx),
        revise_rows_fn=rf,
    )
    for row, j in enumerate(idx):
        solo = rtac.enforce_generic(
            prepared[j][0],
            pad_dom(jnp.asarray(doms[row]), n_p, d_p),
            pad_changed(None, n, n_p),
            revise_fn=ops.revise_fn("packed", True),
        )
        assert bool(np.asarray(res.consistent)[row]) == bool(np.asarray(solo.consistent))
        assert int(np.asarray(res.n_recurrences)[row]) == int(np.asarray(solo.n_recurrences))
        if bool(np.asarray(solo.consistent)):
            np.testing.assert_array_equal(
                np.asarray(res.dom)[row], np.asarray(solo.dom)
            )


def test_packed_oracle_matches_dense_oracle():
    """The bitpacked formulation itself (ref-level) is equivalent."""
    csp = random_csp(9, 37, density=0.7, tightness=0.5, seed=11)
    ch = jnp.ones((9,), jnp.bool_)
    dense = revise_ref(csp.cons, csp.mask, csp.dom, ch)
    cons_pk = pack_bits_ref(csp.cons)
    dom_pk = pack_bits_ref(csp.dom)
    packed = revise_packed_ref(cons_pk, csp.mask, dom_pk, ch)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(packed))


def test_pack_bits_roundtrip_values():
    bits = jnp.asarray(np.random.default_rng(0).random((5, 70)) < 0.5)
    words = pack_bits_ref(bits)
    assert words.shape == (5, 3)
    # unpack manually and compare
    un = (
        (words[..., :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    ).astype(bool).reshape(5, 96)[:, :70]
    np.testing.assert_array_equal(np.asarray(un), np.asarray(bits))


@settings(max_examples=15, deadline=None)
@given(
    st.integers(3, 12),
    st.integers(2, 9),
    st.floats(0.2, 1.0),
    st.floats(0.2, 0.7),
    st.integers(0, 999),
)
def test_end_to_end_kernel_enforcement(n, d, dens, tight, seed):
    csp = random_csp(n, d, dens, tight, seed)
    ref = enforce(csp.cons, csp.mask, csp.dom)
    for engine in ("pallas_dense", "pallas_packed"):
        got = get_engine(engine).prepare(csp).enforce()
        assert bool(got.consistent) == bool(ref.consistent)
        assert int(got.n_recurrences) == int(ref.n_recurrences)
        if bool(ref.consistent):
            np.testing.assert_array_equal(np.asarray(got.dom), np.asarray(ref.dom))
