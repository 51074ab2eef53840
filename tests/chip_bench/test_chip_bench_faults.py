"""A whole run, off the chip, with the timed path broken underneath: each
fault a cell can have must turn ``correct`` false. (The cells run on one
chip, so there is no exchange between chips to leave out.)"""

from __future__ import annotations

import functools
import time

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import tiny_suite

from benchmarks.chip import cells, run

SEED = 2**31 + 999
CELLS = ["frb50-poisson", "frb100-batch24"]


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_suite(tmp_path_factory.mktemp("tiny"), "einsum")


def _drive(root, workload):
    return run.drive(cells.load(workload, root=root), SEED, 1.0, False, time.monotonic())


@functools.lru_cache(maxsize=None)
def _unchanged(fix):
    """A frontier fix whose propagation returns its state unchanged: the
    assignment lands, the closure never runs."""
    from repro.core.rtac import EnforceResult

    def assign_only(net_g, doms, var, val, idx):
        r, _, d = doms.shape
        onehot = jnp.arange(d)[None, :] == val[:, None]
        assigned = doms.at[jnp.arange(r), jnp.maximum(var, 0)].set(onehot)
        dom = jnp.where((var < 0)[:, None, None], doms, assigned)
        return EnforceResult(dom, jnp.ones((r,), bool), jnp.zeros((r,), jnp.int32))

    return assign_only


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tiny, workload):
    assert _drive(tiny, workload)["correct"] is True


@pytest.mark.parametrize("workload", CELLS)
def test_propagation_that_returns_its_state_unchanged(tiny, workload, monkeypatch):
    from repro.core import engine

    step = engine._frontier_step

    def broken(*args, fix, want_alt=False):
        return step(*args, fix=_unchanged(fix), want_alt=want_alt)

    monkeypatch.setattr(engine, "_frontier_step", broken)
    out = _drive(tiny, workload)
    assert out["correct"] is False
    assert out["checks"]["invalid_solutions"]["value"] + out["checks"]["mismatched"]["value"] > 0


def test_half_of_each_batch_left_out(tiny, monkeypatch):
    from repro.core import search

    solve_many = search.solve_many

    def half(csps, **kw):
        sols, sts = solve_many(csps[: len(csps) // 2], **kw)
        return sols, sts

    monkeypatch.setattr(search, "solve_many", half)
    out = _drive(tiny, "frb100-batch24")
    assert out["correct"] is False
    assert out["checks"]["unanswered"]["value"] == out["attempted"] // 2


def test_half_of_the_requests_left_out(tiny, monkeypatch):
    from repro.service import SolverService

    submit = SolverService.submit

    def drop_every_other(self, csp, **kw):
        req = submit(self, csp, **kw)
        if req.id % 2:
            self.cancel(req)
        return req

    monkeypatch.setattr(SolverService, "submit", drop_every_other)
    out = _drive(tiny, "frb50-poisson")
    assert out["correct"] is False and out["checks"]["unanswered"]["value"] > 0


@pytest.mark.parametrize("workload", CELLS)
def test_an_answer_altered_where_it_is_produced(tiny, workload, monkeypatch):
    from repro.core.engine import FrontierTable

    extract = FrontierTable.extract

    def altered(self, key, row):
        dom = np.array(extract(self, key, row))
        dom[0] = np.roll(dom[0], 1)  # the first variable's value moves on
        return dom

    monkeypatch.setattr(FrontierTable, "extract", altered)
    out = _drive(tiny, workload)
    assert out["correct"] is False
