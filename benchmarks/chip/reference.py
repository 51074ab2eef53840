"""The plain reference: MAC search with an exact arc-consistency closure.

Written from the semantics alone, with numpy and nothing of the program, to
decide whether what the timed path answered is right. A search is Alg. 2 of
the paper with the program's documented policy:

- the root is closed under arc consistency; a wiped-out root is unsatisfiable;
- a node branches on the first unassigned variable of least remaining domain
  (MRV), trying its values in ascending order;
- each assignment counts once, before its closure is computed; the search
  stops, inconclusive, on the assignment that passes the budget;
- a value whose subtree holds no solution counts one backtrack.

The closure is the unique largest arc-consistent sub-domain, computed here by
Jacobi sweeps over bitset domains (one uint64 per variable, so d ≤ 64): a
sweep revises every arc whose far end changed in the previous sweep.
``max_sweeps`` cuts the closure short; only the control uses it, to show that
a propagation weaker than arc consistency fails the comparison.
"""

from __future__ import annotations

from typing import List, NamedTuple, Optional

import numpy as np


class Answer(NamedTuple):
    """What a search answers: the solution, or None, and its counts."""

    solution: Optional[List[int]]
    n_assignments: int
    n_backtracks: int
    exhausted: bool  # stopped on the assignment budget


class _Budget(Exception):
    pass


class Network:
    """One instance's arcs as bitsets: ``sup[e, a]`` holds, for arc e = (x, y),
    the values b of y that support value a of x."""

    def __init__(self, cons: np.ndarray, mask: np.ndarray, dom: np.ndarray):
        cons, mask, dom = (np.asarray(a, dtype=bool) for a in (cons, mask, dom))
        n, d = dom.shape
        if d > 64:
            raise ValueError(f"bitset reference holds d <= 64 values, got {d}")
        self.n, self.d = n, d
        self.bits = np.left_shift(np.uint64(1), np.arange(d, dtype=np.uint64))
        self.x, self.y = np.nonzero(mask)
        rel = cons[self.x, self.y]  # (E, d, d) allowed (a, b)
        self.sup = np.bitwise_or.reduce(rel * self.bits, axis=2)  # (E, d)
        self.dom0 = np.bitwise_or.reduce(dom * self.bits, axis=1)  # (n,)

    def close(self, dom: np.ndarray, changed: np.ndarray,
              max_sweeps: Optional[int] = None) -> Optional[np.ndarray]:
        """The AC closure of ``dom`` after ``changed`` variables shrank, or
        None on a wipe-out."""
        sweeps = 0
        while changed.any():
            if max_sweeps is not None and sweeps == max_sweeps:
                break
            sweeps += 1
            arcs = np.nonzero(changed[self.y])[0]
            has = (self.sup[arcs] & dom[self.y[arcs], None]) != 0  # (k, d)
            lost = np.bitwise_or.reduce(~has * self.bits, axis=1)
            kill = np.zeros(self.n, dtype=np.uint64)
            np.bitwise_or.at(kill, self.x[arcs], lost)
            new = dom & ~kill
            changed = new != dom
            dom = new
            if (dom == 0).any():
                return None
        return dom

    def solve(self, budget: Optional[int], max_sweeps: Optional[int] = None) -> Answer:
        """MAC search from the instance's domain, at most ``budget``
        assignments (None: unbounded)."""
        root = self.close(self.dom0.copy(), np.ones(self.n, dtype=bool), max_sweeps)
        if root is None:
            return Answer(None, 0, 0, False)
        assigned = np.zeros(self.n, dtype=bool)
        count = {"assign": 0, "back": 0}
        big = np.iinfo(np.int64).max

        def dfs(dom: np.ndarray) -> Optional[List[int]]:
            if assigned.all():
                return [int(v).bit_length() - 1 for v in dom]
            sizes = np.bitwise_count(dom).astype(np.int64)
            var = int(np.argmin(np.where(assigned, big, sizes)))
            word = int(dom[var])
            values = [a for a in range(self.d) if word >> a & 1]
            assigned[var] = True
            seed = np.zeros(self.n, dtype=bool)
            seed[var] = True
            try:
                for a in values:
                    count["assign"] += 1
                    if budget and count["assign"] > budget:
                        raise _Budget
                    child = dom.copy()
                    child[var] = self.bits[a]
                    closed = self.close(child, seed, max_sweeps)
                    if closed is not None:
                        sol = dfs(closed)
                        if sol is not None:
                            return sol
                    count["back"] += 1
                return None
            finally:
                assigned[var] = False

        try:
            sol = dfs(root)
        except _Budget:
            return Answer(None, count["assign"], count["back"], True)
        return Answer(sol, count["assign"], count["back"], False)


def solve(cons, mask, dom, budget: Optional[int], max_sweeps: Optional[int] = None) -> Answer:
    return Network(cons, mask, dom).solve(budget, max_sweeps)


def satisfies(cons, mask, dom, solution: List[int]) -> bool:
    """Whether ``solution`` is a full assignment inside the domains that
    every constraint allows."""
    cons, mask, dom = (np.asarray(a, dtype=bool) for a in (cons, mask, dom))
    n = dom.shape[0]
    sol = np.asarray(solution, dtype=np.int64)
    if sol.shape != (n,) or (sol < 0).any() or (sol >= dom.shape[1]).any():
        return False
    if not dom[np.arange(n), sol].all():
        return False
    xs, ys = np.nonzero(mask)
    return bool(cons[xs, ys, sol[xs], sol[ys]].all())
