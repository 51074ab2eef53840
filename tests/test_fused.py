"""Fused in-kernel fixpoint validation — interpret-mode parity sweeps.

The fused kernel (`rtac_support.fixpoint_rows`, dense and packed encodings)
runs the WHOLE AC recurrence inside one `pl.pallas_call`; the stepped path
(`rtac.enforce_rows_generic` around per-iteration revise kernels) is the
oracle. Parity must be bit-identical — domains, verdicts, AND per-row
recurrence counts — on odd/padded shapes (n, d, W not multiples of the
padding blocks), across every schedule the autotuner may pick (the instance
tiling ``block_r``). Also covers the `kernels/ref.py` single-revise oracle
chained on the host, engine/solve_many-level fused-vs-stepped equality, and
the autotune cache round-trip.

All `pytest.mark.pallas` (interpret mode executes kernel bodies in Python),
run in CI's dedicated pallas leg.
"""

import json

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import random_csp, rtac
from repro.core.engine import pad_changed, pad_dom
from repro.core.search import solve_many
from repro.engines import get_engine
from repro.kernels import autotune, ops, rtac_support
from repro.kernels.ref import revise_ref

pytestmark = pytest.mark.pallas

# (n_vars, dom_size, block_rx, block_ry) — the variable axis pads to a
# multiple of max(block_rx, block_ry); odd n/d so every case exercises the
# padding boundary; (24, 33) is multi-word bitpack, (12, 64) exactly 2 words
SHAPE_SWEEP = [
    (4, 3, 4, 4),
    (10, 6, 8, 8),
    (16, 8, 4, 8),
    (24, 33, 8, 8),
    (12, 64, 4, 4),
]

#: fused schedules every case sweeps (block_r). 4 rows means 1, 2 and 4 tile
#: exactly and 8 exercises `effective_block_r`'s fallback.
SCHEDULES = [1, 2, 4, 8]


def _rows_fixture(n, d, brx, bry, prepare):
    """3 networks, 4 rows via idx [0,1,2,1]; row 3 starts near wipeout and the
    seed mixes root (all-changed) with sparse patterns."""
    csps = [random_csp(n, d, 0.7, 0.5, seed=40 + i) for i in range(3)]
    prepared = [prepare(c, max(brx, bry)) for c in csps]
    dims = prepared[0][2]
    tables = (
        jnp.stack([p[0][0] for p in prepared]),
        jnp.stack([p[0][1] for p in prepared]),
    )
    idx = np.array([0, 1, 2, 1], np.int32)
    doms = np.stack([np.asarray(csps[j].dom) for j in idx])
    doms[3, 0, 1:] = False
    changed = np.ones((len(idx), n), dtype=bool)
    changed[1] = np.random.default_rng(n * 13 + d).random(n) < 0.5
    return csps, tables, dims, idx, doms, changed


def _stepped_oracle(tables, dims, idx, dom_p, ch_p, rows_fn):
    return rtac.enforce_rows_generic(
        tables, dom_p, ch_p, jnp.asarray(idx), revise_rows_fn=rows_fn
    )


def _fused_matches_stepped(kind, n, d, brx, bry):
    prepare = ops.prepare_dense if kind == "dense" else ops.prepare_packed
    csps, tables, dims, idx, doms, changed = _rows_fixture(n, d, brx, bry, prepare)
    n_p, d_p = dims[0], dims[1]
    r = len(idx)
    dom_p = pad_dom(jnp.asarray(doms), n_p, d_p)
    ch_p = pad_changed(jnp.asarray(changed), n, n_p, batch=(r,))
    ref = _stepped_oracle(tables, dims, idx, dom_p, ch_p, ops.rows_fn(kind, True))
    operands = ops.kernel_operands((tables[0][idx], tables[1][idx]), dom_p, ch_p)
    for block_r in SCHEDULES:
        br = autotune.effective_block_r(block_r, r)
        got_dom, got_cons, got_k = rtac_support.fixpoint_rows(
            *operands, encoding=kind, d=d_p, block_r=br, interpret=True
        )
        np.testing.assert_array_equal(
            np.asarray(got_dom).reshape(r, n_p, d_p).astype(bool),
            np.asarray(ref.dom),
        )
        np.testing.assert_array_equal(
            np.asarray(got_cons)[:, 0, 0].astype(bool), np.asarray(ref.consistent)
        )
        np.testing.assert_array_equal(
            np.asarray(got_k)[:, 0, 0], np.asarray(ref.n_recurrences)
        )


@pytest.mark.parametrize("n,d,brx,bry", SHAPE_SWEEP)
def test_dense_fused_bit_identical_to_stepped(n, d, brx, bry):
    _fused_matches_stepped("dense", n, d, brx, bry)


@pytest.mark.parametrize("n,d,brx,bry", SHAPE_SWEEP)
def test_packed_fused_bit_identical_to_stepped(n, d, brx, bry):
    _fused_matches_stepped("packed", n, d, brx, bry)


@pytest.mark.parametrize("n,d,brx,bry", [(10, 6, 8, 8), (24, 33, 8, 8)])
def test_fused_rows_fn_matches_ref_oracle_chain(n, d, brx, bry):
    """Independent oracle: chain `kernels/ref.py`'s single revise on the host
    (the pure-jnp Prop. 2 tensor form, no Pallas) to a fixpoint per row and
    compare the fused result row-by-row — counts included."""
    csps, tables, (n_p, d_p, w), idx, doms, changed = _rows_fixture(
        n, d, brx, bry, ops.prepare_packed
    )
    r = len(idx)
    dom_p = pad_dom(jnp.asarray(doms), n_p, d_p)
    ch_p = pad_changed(jnp.asarray(changed), n, n_p, batch=(r,))
    fused = ops.fixpoint_rows_fn("packed", True)(
        (tables[0][idx], tables[1][idx]), dom_p, ch_p
    )
    for row, j in enumerate(idx):
        dom = jnp.asarray(doms[row])
        ch = jnp.asarray(changed[row])
        consistent, k = True, 0
        while True:
            if not bool(jnp.all(jnp.any(dom, axis=-1))):
                consistent = False
                break
            if not bool(jnp.any(ch)):
                break
            viol = revise_ref(csps[j].cons, csps[j].mask, dom, ch)
            new_dom = dom & ~viol
            ch = jnp.any(new_dom != dom, axis=-1)
            dom = new_dom
            k += 1
        assert bool(np.asarray(fused.consistent)[row]) == consistent
        assert int(np.asarray(fused.n_recurrences)[row]) == k
        if consistent:
            np.testing.assert_array_equal(
                np.asarray(fused.dom)[row, :n, :d], np.asarray(dom)
            )


@pytest.mark.parametrize("engine", ["pallas_dense", "pallas_packed"])
def test_engine_enforce_many_fused_equals_stepped(engine):
    csps = [random_csp(9, 5, 0.6, 0.5, seed=70 + i) for i in range(4)]
    doms = jnp.stack([c.dom for c in csps])
    ef = get_engine(engine, fixpoint="fused")
    es = get_engine(engine, fixpoint="stepped")
    rf = ef.enforce_many(ef.prepare_many(csps), doms)
    rs = es.enforce_many(es.prepare_many(csps), doms)
    np.testing.assert_array_equal(np.asarray(rf.dom), np.asarray(rs.dom))
    np.testing.assert_array_equal(
        np.asarray(rf.consistent), np.asarray(rs.consistent)
    )
    np.testing.assert_array_equal(
        np.asarray(rf.n_recurrences), np.asarray(rs.n_recurrences)
    )


def test_solve_many_fused_equals_stepped_and_bills_one_launch_per_round():
    csps = [random_csp(9, 5, 0.6, 0.5, seed=7 + i) for i in range(4)]
    out = {}
    for mode in ("fused", "stepped"):
        tel = {}
        sols, stats = solve_many(
            csps, engine=get_engine("pallas_packed", fixpoint=mode), telemetry=tel
        )
        out[mode] = (sols, stats, tel)
    sols_f, stats_f, tel_f = out["fused"]
    sols_s, stats_s, tel_s = out["stepped"]
    assert sols_f == sols_s
    assert [st.recurrences for st in stats_f] == [st.recurrences for st in stats_s]
    assert tel_f["rounds"] == tel_s["rounds"]
    # the tentpole claim: fused bills exactly one launch per lockstep round;
    # stepped bills the per-round max recurrence depth (strictly more here)
    assert tel_f["fused_fixpoint"] and not tel_s["fused_fixpoint"]
    assert tel_f["launches"] == tel_f["rounds"]
    assert tel_f["launches_per_round"] == 1.0
    assert tel_s["launches"] > tel_s["rounds"]
    assert all(st.launches >= 1 for st in stats_f)


def test_fixpoint_mode_validation_and_env_default(monkeypatch):
    with pytest.raises(ValueError):
        get_engine("pallas_packed", fixpoint="nope")
    monkeypatch.setenv("REPRO_PALLAS_FIXPOINT", "stepped")
    assert get_engine("pallas_packed").fused_fixpoint is False
    monkeypatch.delenv("REPRO_PALLAS_FIXPOINT")
    assert get_engine("pallas_packed").fused_fixpoint is True


# --- autotune cache ----------------------------------------------------------


def test_autotune_cache_roundtrip(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.reset()
    try:
        cfg = autotune.tune("packed", 16, 8, r=2, repeats=1, path=path)
        key = autotune.bucket_key("packed", 16, 8, 2)
        assert key.startswith(autotune.device_kind() + "/")
        payload = json.loads(path.read_text())
        assert payload["schema"] == autotune.SCHEMA
        assert payload["configs"][key] == cfg.to_dict()
        # a fresh in-memory table reloads the winner from disk
        autotune.reset()
        got = autotune.get_config("packed", 16, 8, 2)
        assert got == cfg
        # ensure_tuned is a pure cache hit now — no re-timing
        assert autotune.ensure_tuned("packed", 16, 8, 2, path=path) == cfg
    finally:
        autotune.reset()


def test_autotune_untuned_bucket_falls_back_to_engine_defaults(tmp_path, monkeypatch):
    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path / "missing.json"))
    autotune.reset()
    try:
        cfg = autotune.get_config("dense", 16, 8, 4)
        assert cfg.block_r == rtac_support.max_block_r("dense", 16, 8) == 8
        assert autotune.fused_block_r("dense", 16, 8, 6) == 6
    finally:
        autotune.reset()


def test_autotune_sanitizes_stale_tiles_and_block_r():
    # a cached block_r over the VMEM budget is clamped to it; every candidate
    # fits the budget, so every candidate compiles
    stale = autotune.TuneConfig(block_r=8)
    assert rtac_support.max_block_r("dense", 128, 32) < 8
    fixed = autotune._sanitize(stale, "dense", 128, 32)
    assert fixed.block_r == rtac_support.max_block_r("dense", 128, 32)
    assert all(
        c.block_r <= fixed.block_r
        for c in autotune.candidate_configs("dense", 128, 32, 64)
    )
    assert autotune.effective_block_r(8, 6) == 6
    assert autotune.effective_block_r(8, 5) == 5
    assert autotune.effective_block_r(4, 6) == 3
    assert autotune.effective_block_r(8, 8) == 8
