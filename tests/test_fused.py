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

import jax
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
    fused = ops.fixpoint_rows_fn("packed", True)(tables, dom_p, ch_p, jnp.asarray(idx))
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


# --- the streamed fixpoint (networks larger than VMEM) -----------------------

#: (case, n_vars, dom_size, ty): W=1 and W=2; 24 variables in tiles of 16
#: (the last tile pulled back to overlap the first) and of 8; d_p 8 and 40
#: settle each tile over all lanes, d_p 16 and 64 over its own lane window
STREAM_CASES = [
    ("w1_ragged_last_tile", 20, 6, 16),
    ("w1_windowed_ragged", 20, 11, 16),
    ("w2_three_tiles", 24, 33, 8),
    ("w2_windowed", 12, 64, 8),
]


def _stream_reference(csp, dom, ch, starts, ty):
    """Host chain of `revise_ref` sweeps: (dom, consistent, k, revised,
    tiles read), where a sweep reads each tile holding a seeded variable."""
    dom, ch = jnp.asarray(dom), jnp.asarray(ch)
    k = revised = tiles = 0
    if not bool(jnp.all(jnp.any(dom, axis=-1))):
        return dom, False, 0, 0, 0
    while bool(jnp.any(ch)):
        seeded = np.asarray(ch)
        revised += int(seeded.sum())
        tiles += sum(bool(seeded[s:s + ty].any()) for s in starts)
        new_dom = dom & ~revise_ref(csp.cons, csp.mask, dom, ch)
        ch = jnp.any(new_dom != dom, axis=-1)
        dom = new_dom
        k += 1
        if not bool(jnp.all(jnp.any(dom, axis=-1))):
            return dom, False, k, revised, tiles
    return dom, True, k, revised, tiles


@pytest.mark.parametrize("case,n,d,ty", STREAM_CASES, ids=[c[0] for c in STREAM_CASES])
def test_streamed_fixpoint_bit_identical_to_in_vmem_and_stepped(case, n, d, ty, monkeypatch):
    """The streamed launch, run at a small shape by a small VMEM budget
    handed to the plan, against the in-VMEM kernel, the stepped oracle and
    a host chain of `revise_ref` sweeps: rows of three networks through the
    index, root rows, a sparse seed, one seeded variable, a row wiped out at
    the start and a row with no seed (both inactive). Through the dispatch
    (`ops.fixpoint_rows_fn`, under that budget), rows repeated at the end of
    the round, as padding repeats them, take their row's result and read
    nothing."""
    csps = [random_csp(n, d, 0.7, 0.5, seed=90 + i) for i in range(3)]
    prepared = [ops.prepare_packed(c) for c in csps]
    n_p, d_p, w = prepared[0][2]
    assert w == (2 if d > 32 else 1)
    budget = rtac_support.stream_vmem_bytes(n_p, d_p, ty)
    assert rtac_support.max_block_r("packed", n_p, d_p, budget) == 0
    assert rtac_support.stream_ty(n_p, d_p, budget) == ty
    starts = rtac_support.stream_tile_starts(n_p, ty)
    assert (n_p % ty != 0) == (starts[-1] % ty != 0)
    tables = (jnp.stack([p[0][0] for p in prepared]), jnp.stack([p[0][1] for p in prepared]))
    idx = np.array([0, 1, 2, 1, 0, 2], np.int32)
    doms = np.stack([np.asarray(csps[j].dom) for j in idx])
    doms[3, 0, 1:] = False
    doms[4, 2, :] = False  # wiped out before the first sweep
    changed = np.ones((len(idx), n), dtype=bool)  # rows 0, 3, 4: roots
    changed[1] = np.random.default_rng(n + d).random(n) < 0.5
    changed[2] = np.arange(n) == n - 1  # one variable, in the last tile
    changed[5] = False
    r = len(idx)
    dom_p = pad_dom(jnp.asarray(doms), n_p, d_p)
    ch_p = pad_changed(jnp.asarray(changed), n, n_p, batch=(r,))
    operands = ops.kernel_operands((tables[0][idx], tables[1][idx]), dom_p, ch_p)
    in_vmem = rtac_support.fixpoint_rows(*operands, encoding="packed", d=d_p, block_r=1,
                                         interpret=True)
    ok = np.asarray(jnp.all(jnp.any(dom_p, axis=-1), axis=-1)).astype(np.int32)
    got = rtac_support.fixpoint_rows_stream(
        tables[0], ops.stream_mask_tiles(tables[1], d_p, ty), jnp.asarray(idx),
        operands[1], operands[2] * ok[:, None, None], ok.reshape(r, 1, 1),
        d=d_p, ty=ty, interpret=True,
    )
    for a, b in zip(in_vmem, got[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    stepped = _stepped_oracle(tables, None, idx, dom_p, ch_p, ops.rows_fn("packed", True))
    np.testing.assert_array_equal(np.asarray(got[0]).reshape(r, n_p, d_p).astype(bool),
                                  np.asarray(stepped.dom))
    np.testing.assert_array_equal(np.asarray(got[1])[:, 0, 0].astype(bool),
                                  np.asarray(stepped.consistent))
    np.testing.assert_array_equal(np.asarray(got[2])[:, 0, 0], np.asarray(stepped.n_recurrences))
    revised, tiles = np.asarray(got[3])[:, 0, 0], np.asarray(got[4])[:, 0, 0]
    for row, j in enumerate(idx):
        dom, ok, k, rev, nt = _stream_reference(
            csps[j], doms[row], changed[row], starts, ty)
        assert (bool(np.asarray(got[1])[row, 0, 0]), int(np.asarray(got[2])[row, 0, 0])) == (ok, k)
        assert (int(revised[row]), int(tiles[row])) == (rev, nt), row
        if ok:
            np.testing.assert_array_equal(
                np.asarray(got[0])[row, 0].reshape(n_p, d_p)[:n, :d].astype(bool), np.asarray(dom))
    assert revised[4] == revised[5] == tiles[4] == tiles[5] == 0  # the inactive rows
    assert revised[0] >= n and tiles[0] >= len(starts)  # a root revises every variable

    monkeypatch.setattr(rtac_support, "VMEM_BUDGET", budget)
    padded = np.concatenate([np.arange(r), [r - 1, r - 1]])
    res = ops.fixpoint_rows_fn("packed", True)(tables, dom_p[padded], ch_p[padded], idx[padded])
    np.testing.assert_array_equal(np.asarray(res.dom), np.asarray(stepped.dom)[padded])
    np.testing.assert_array_equal(np.asarray(res.consistent), np.asarray(stepped.consistent)[padded])
    np.testing.assert_array_equal(np.asarray(res.n_recurrences),
                                  np.asarray(stepped.n_recurrences)[padded])
    np.testing.assert_array_equal(np.asarray(res.revised), np.r_[revised, 0, 0])
    tile_bytes = rtac_support.stream_tile_bytes(n_p, d_p, ty)
    np.testing.assert_array_equal(np.asarray(res.streamed), np.r_[tiles, 0, 0] * tile_bytes)


def test_fused_dispatch_streams_only_where_no_row_fits_vmem(monkeypatch):
    """`fixpoint_rows_fn` picks the streamed launch by shape alone: at the
    frb cells' kernel shapes today's kernel (after its per-row gather), at
    (600, 32) and under a budget no row fits, the streamed one, handed the
    stacked tables; the result then carries revised and streamed."""

    def launched(n_p, d_p, w, rows=8, b=3):
        fix = ops.fixpoint_rows_fn("packed", True)
        nets = (jax.ShapeDtypeStruct((b, w, n_p, n_p * d_p), jnp.int32),
                jax.ShapeDtypeStruct((b, n_p, n_p), jnp.uint8))
        args = (jax.ShapeDtypeStruct((rows, n_p, d_p), jnp.bool_),
                jax.ShapeDtypeStruct((rows, n_p), jnp.bool_),
                jax.ShapeDtypeStruct((rows,), jnp.int32))
        out = jax.eval_shape(fix, nets, *args)
        text = str(jax.make_jaxpr(fix)(nets, *args))
        return ("rtac_fixpoint_packed_stream" in text, "rtac_fixpoint_packed" in text,
                out.revised is not None)

    for n_p, d_p, w in [(64, 32, 1), (104, 40, 2)]:  # frb50's bucket, frb100
        assert rtac_support.max_block_r("packed", n_p, d_p) > 0
        assert launched(n_p, d_p, w) == (False, True, False)
    assert rtac_support.max_block_r("packed", 600, 32) == 0
    assert rtac_support.stream_ty(600, 32) == 64
    assert launched(600, 32, 1) == (True, True, True)
    monkeypatch.setattr(rtac_support, "VMEM_BUDGET", rtac_support.stream_vmem_bytes(24, 8, 8))
    assert launched(24, 8, 1) == (True, True, True)
