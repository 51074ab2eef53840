"""The cell whose networks stream from HBM (`rb600-batch8`): its least-bytes
count, its three metric readers, its files, and its path through
`solve_many` against the plain reference."""

from __future__ import annotations

import math

import pytest

from benchmarks.chip import cells, reference, stream_bytes, traffic
from repro import obs
from repro.core.csp import CSP
from repro.core.search import solve_many
from repro.kernels import ops, rtac_support

KERNEL = "rtac_fixpoint_packed"


def test_least_bytes_at_the_cells_kernel_shape():
    assert stream_bytes.config_dims("rb600_dense_batch") == (600, 32)
    assert stream_bytes.revised_var_bytes(600, 32) == 96_000
    assert stream_bytes.row_bytes(600, 32) == 153_600 + 2_400 + 8
    assert stream_bytes.least_bytes(600, 32, 10, 2) == 960_000 + 2 * 156_008
    # W = 2 words a domain double the words, not the mask
    assert stream_bytes.revised_var_bytes(104, 40) == 2 * 4160 * 4 + 4160


def _record(**counters):
    return {
        "counters": dict({"driver.rounds": 10, "driver.rows": 100}, **counters),
        "trace": {"kernel_s": {KERNEL: 0.9}},
        "kernel": KERNEL,
        "hbm_bytes_per_s": 819e9,
    }


def test_the_three_readers_on_a_synthetic_record():
    readers = cells.load("rb600-batch8").readers
    rec = _record(**{"kernel.revised_vars": 5_000, "kernel.stream_bytes": 100 * 60 * 2**20})
    assert readers["stream_kernel_ms_per_round.stream"](rec) == pytest.approx(90.0)
    least_s = (5_000 * 96_000 + 100 * 156_008) / 819e9
    assert readers["rtac_fixpoint_stream_roofline.stream"](rec) == pytest.approx(
        100 * least_s / 0.9)
    assert readers["stream_mib_per_row.stream"](rec) == pytest.approx(60.0)
    # a run whose kernel never streamed (the in-VMEM kernel, or a program
    # without the streamed launch) leaves both stream metrics out
    bare = _record()
    assert readers["rtac_fixpoint_stream_roofline.stream"](bare) is None
    assert readers["stream_mib_per_row.stream"](bare) is None


def test_the_cell_loads_with_its_metrics():
    cell = cells.load("rb600-batch8")
    assert {m["name"] for m in cell.end_to_end} == {"solve_rate", "setup_s"}
    assert {m["name"] for m in cell.per_layer} >= {
        "stream_kernel_ms_per_round.stream", "rtac_fixpoint_stream_roofline.stream",
        "stream_mib_per_row.stream"}
    assert cell.chips == 1 and cell.traffic == {"batch": 8}
    prob = cell.config["problem"]
    assert traffic.model_rb_shape(prob["n"], prob["alpha"], prob["r"]) == (32, 89_813)
    assert prob["p"] == pytest.approx(1 - math.exp(-prob["alpha"] / prob["r"]))
    assert round(prob["p"] * 32 * 32) == 23
    assert cell.config["entry"] == "solve_many" and cell.config["engine"] == "pallas_packed"
    assert rtac_support.max_block_r("packed", 600, 32) == 0  # it must stream


def test_solve_many_streams_and_answers_as_the_reference(monkeypatch):
    """Model RB at (20, 11), kernel shape (24, 16), under a VMEM budget no
    row fits: `solve_many` takes the streamed launch (its counters move) and
    every answer equals the reference's: two solutions after backtracks and
    a budget stop."""
    n_p, d_p = ops.kernel_dims("packed", 20, 11)[:2]
    monkeypatch.setattr(rtac_support, "VMEM_BUDGET", rtac_support.stream_vmem_bytes(n_p, d_p, 8))
    assert rtac_support.max_block_r("packed", n_p, d_p) == 0
    insts = [traffic.model_rb((7, i), 20, 0.8, 0.8 / math.log(4 / 3), 0.2) for i in (0, 1, 3)]
    before = obs.REGISTRY.snapshot()["counters"]
    sols, sts = solve_many([CSP(i.cons, i.mask, i.dom) for i in insts],
                           engine="pallas_packed", max_assignments=60)
    after = obs.REGISTRY.snapshot()["counters"]
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("kernel.revised_vars", "kernel.stream_bytes", "driver.rows")}
    assert moved["kernel.revised_vars"] >= moved["driver.rows"] > 0
    tile = rtac_support.stream_tile_bytes(n_p, d_p, 8)
    assert moved["kernel.stream_bytes"] > 0 and moved["kernel.stream_bytes"] % tile == 0
    answers = [reference.Answer(s, st.n_assignments, st.n_backtracks, bool(st.exhausted))
               for s, st in zip(sols, sts)]
    expected = [reference.solve(i.cons, i.mask, i.dom, 60) for i in insts]
    assert answers == expected
    assert [a.exhausted for a in answers] == [False, True, False]
    assert all(a.n_backtracks > 0 for a in answers)
