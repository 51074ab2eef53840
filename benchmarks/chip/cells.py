"""Find a cell's configuration, traffic mix and metric readers by name.

`BENCHMARK.json` at the checkout's root names everything; the files live
under ``benchmarks/chip/``:

- ``configs/<config>.json``: one deployment (the ``file`` of its entry);
- ``traffic/<mix>.json``: one traffic mix, read by `traffic`;
- ``metrics/<metric>.py``: one reader per per-layer metric, a module with
  ``read(record) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, NamedTuple, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH_DIR = Path("benchmarks") / "chip"


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    readers: Dict[str, Callable[[dict], Optional[float]]]


def load_reader(path: Path) -> Callable[[dict], Optional[float]]:
    """The ``read`` function of one metric file (names hold dots, so the
    module is loaded from its path rather than imported)."""
    if not path.is_file():
        raise FileNotFoundError(f"no reader for metric {path.stem!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"chip_metric_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def load(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of the `BENCHMARK.json` under ``root``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    mix = json.loads((root / BENCH_DIR / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [
        m for m in bench["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)
    ]
    readers = {
        m["name"]: load_reader(root / BENCH_DIR / "metrics" / f"{m['name']}.py")
        for m in per_layer
    }
    return Cell(name, int(w["chips"]), config, mix, e2e, per_layer, readers)
