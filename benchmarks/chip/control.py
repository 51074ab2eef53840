"""Readings of the check's control: the reference put in the program's place
with a propagation weaker than arc consistency (one revise sweep per
assignment, `reference.Network.solve` with ``max_sweeps=1``), the shortcut a
later change to the fixpoint might take. It must fail the ``mismatched``
comparison that a sound run passes with 0.

    python -m benchmarks.chip.control --workload frb50-poisson --seeds 1,2,3 --seconds 20

For each seed it draws the instances a run of the cell compares (the same
seeded sample, at the cell's size) and prints how many answers of the control
differ from the reference's. It runs on the host; no device is used.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[2]
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import cells, reference, run, traffic  # noqa: E402


def sampled_instances(cell, seed: int, seconds: float):
    """The instances a run of ``cell`` at ``seed`` would hold to the
    reference: the seeded sample of an open-loop window, or, for batches,
    the sample's size of the window's first instances."""
    cfg = cell.config
    size = int(cfg["check"]["sample"])
    if cfg["entry"] == "service":
        arrivals = traffic.open_loop(seed, (traffic.WINDOW,), cell.traffic, seconds)
        for i in run._sample(seed, len(arrivals), size):
            yield traffic.instance(cfg["problem"], arrivals[i].instance)
        return
    batch = int(cell.traffic["batch"])
    for index in range(-(-size // batch)):
        yield from run.build_batch(cfg, seed, traffic.WINDOW, index, batch)[: size - index * batch]


def readings(cell, seed: int, seconds: float) -> dict:
    budget = int(cell.config["search"]["max_assignments"])
    pairs = [(inst, reference.solve(inst.cons, inst.mask, inst.dom, budget, max_sweeps=1))
             for inst in sampled_instances(cell, seed, seconds)]
    return {"workload": cell.name, "seed": seed, "compared": len(pairs),
            "mismatched": run.mismatches(pairs, budget)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cells.load(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(cell, seed, args.seconds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
