"""The plain reference agrees with the program's MAC search exactly, and its
control, a propagation weaker than arc consistency, does not."""

from __future__ import annotations

import math

import numpy as np
import pytest

from benchmarks.chip import reference, run, traffic

# tightness at 0.9, 1.0 and 1.1 times the threshold of r = 0.7, so the
# searches are solved, refuted and stopped on their budget
LEVELS = tuple(h * (1.0 - math.exp(-0.8 / 0.7)) for h in (0.9, 1.0, 1.1))


def _instances(n: int, count: int, seed: int = 2**31 + 77):
    return [traffic.model_rb((seed, 0, i), n, 0.8, 0.7, LEVELS[i % 3]) for i in range(count)]


@pytest.fixture(scope="module")
def program_answers():
    from repro.core.csp import CSP
    from repro.core.search import solve_many

    insts = _instances(20, 9)
    sols, sts = solve_many([CSP(*i) for i in insts], engine="einsum", max_assignments=40)
    return insts, [run.answer_of(s, st) for s, st in zip(sols, sts)]


def test_reference_equals_the_program(program_answers):
    insts, answers = program_answers
    want = [reference.solve(*i, budget=40) for i in insts]
    assert answers == want
    # the sample holds every kind of verdict: solved, refuted, budget-stopped
    assert any(a.solution is not None for a in want)
    assert any(a.solution is None and not a.exhausted for a in want)
    assert any(a.exhausted for a in want)
    assert run.mismatches(list(zip(insts, answers)), 40) == 0


def test_control_fails_the_comparison(program_answers):
    """The control: the reference in the program's place with one revise
    sweep per assignment instead of the full closure."""
    insts, _ = program_answers
    control = [reference.solve(*i, budget=40, max_sweeps=1) for i in insts]
    checks = run.compare(insts, control, 40, sample=list(range(len(insts))))
    assert checks["mismatched"]["value"] > checks["mismatched"]["limit"]


def test_solutions_are_checked_against_every_constraint():
    inst = next(i for i in _instances(14, 9)
                if reference.solve(*i, budget=None).solution is not None)
    sol = reference.solve(*inst, budget=None).solution
    assert reference.satisfies(*inst, sol)
    x, y = map(int, np.argwhere(inst.mask)[0])
    bad = list(sol)
    bad[x] = next(a for a in range(inst.dom.shape[1]) if not inst.cons[x, y, a, sol[y]])
    assert not reference.satisfies(*inst, bad)
    assert not reference.satisfies(*inst, sol[:-1])


def test_budget_stop_counts_the_assignment_that_passes_it():
    inst = _instances(30, 2)[1]  # at the threshold: the search runs into the budget
    answer = reference.solve(*inst, budget=25)
    assert answer.exhausted and answer.n_assignments == 26 and answer.solution is None


def test_control_readings_at_a_tiny_cell(tmp_path):
    from conftest import tiny_suite

    from benchmarks.chip import cells, control

    root = tiny_suite(tmp_path, "einsum")
    for workload in ("frb50-poisson", "frb100-batch24"):
        got = control.readings(cells.load(workload, root=root), 2**31 + 5, 4.0)
        assert got["compared"] > 0 and got["mismatched"] > 0
