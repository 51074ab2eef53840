"""The traffic generator: Model RB instances and arrival schedules are fixed
by the seed, and every seed offers the same amount and mix of work."""

from __future__ import annotations

import math

import numpy as np
import pytest

from benchmarks.chip import traffic

BIG = 2**31 + 987_654_321  # the driver's seeds exceed 32 signed bits
POISSON = {"rate_rps": 40.0}
ZIPF = {"rate_rps": 40.0, "pool": {"size": 4096, "zipf_s": 0.99}}


@pytest.mark.parametrize("n, d, m, q", [(50, 23, 544, 132), (100, 40, 1281, 400)])
def test_model_rb_at_the_frb_widths(n, d, m, q):
    # BHOSLIB: r = 0.8 / ln(4/3) and p = 0.25, its threshold p_cr
    inst = traffic.model_rb((BIG, 0, 5), n, 0.8, 0.8 / math.log(4 / 3), 0.25)
    assert inst.cons.shape == (n, n, d, d) and inst.dom.all()
    assert np.array_equal(inst.mask, inst.mask.T) and not inst.mask.diagonal().any()
    assert inst.mask.sum() == 2 * m  # m distinct scopes
    xs, ys = np.nonzero(inst.mask)
    rel = inst.cons[xs, ys]
    assert ((~rel).sum(axis=(1, 2)) == q).all()  # exactly q disallowed tuples
    assert np.array_equal(inst.cons[xs, ys], inst.cons[ys, xs].transpose(0, 2, 1))
    assert not inst.cons[~inst.mask].any()


def test_model_rb_is_fixed_by_its_seed():
    a = traffic.model_rb((BIG, 0, 1), 20, 0.8, 0.7, 0.6)
    b = traffic.model_rb((BIG, 0, 1), 20, 0.8, 0.7, 0.6)
    c = traffic.model_rb((BIG, 0, 2), 20, 0.8, 0.7, 0.6)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a.cons, c.cons)


@pytest.mark.parametrize("mix", [POISSON, ZIPF], ids=["poisson", "zipf"])
def test_schedules_are_fixed_by_the_seed(mix):
    a = traffic.open_loop(BIG, (traffic.WINDOW,), mix, 20.0)
    b = traffic.open_loop(BIG, (traffic.WINDOW,), mix, 20.0)
    c = traffic.open_loop(BIG + 1, (traffic.WINDOW,), mix, 20.0)
    w = traffic.open_loop(BIG, (traffic.WARMUP, 1), mix, 20.0)
    assert a == b and a != c and a != w
    # every seed offers the same count of requests
    for arrivals in (a, c):
        assert len(arrivals) == 800
        due = [x.due for x in arrivals]
        assert due == sorted(due) and 0 <= due[0] and due[-1] < 20.0
    if "pool" not in mix:
        assert len({x.instance for x in a}) == len(a)  # every instance unique


def test_zipf_pool_is_skewed_and_shared_with_the_warmup():
    a = traffic.open_loop(BIG, (traffic.WINDOW,), ZIPF, 50.0)
    w = traffic.open_loop(BIG, (traffic.WARMUP, 1), ZIPF, 50.0)
    ranks = np.array([x.instance[-1] for x in a])
    assert all(x.instance[:2] == (BIG, traffic.POOL) for x in a + w)
    counts = np.bincount(ranks, minlength=4096)
    assert counts[0] == counts.max() and counts[0] > 10 * counts[100:200].mean()
    assert len(set(ranks)) < len(ranks)  # requests repeat


def test_zipf_draws_follow_the_law():
    rng = np.random.default_rng(0)
    ranks = traffic.zipf_ranks(rng, 200_000, 8, 1.0)
    weights = 1 / np.arange(1, 9)
    expect = weights / weights.sum()
    got = np.bincount(ranks, minlength=8) / len(ranks)
    assert np.allclose(got, expect, atol=0.005)


def test_batches_are_fresh_instances():
    a = traffic.batch_instances(BIG, traffic.WINDOW, 0, 8)
    b = traffic.batch_instances(BIG, traffic.WINDOW, 1, 8)
    w = traffic.batch_instances(BIG, traffic.WARMUP, 0, 8)
    assert len(set(a) | set(b) | set(w)) == 24
