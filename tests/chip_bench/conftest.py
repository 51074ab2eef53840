"""Make the benchmark package and the program importable from these tests."""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for path in (ROOT / "src", ROOT):
    if str(path) not in sys.path:
        sys.path.insert(0, str(path))


import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def short_warmup(monkeypatch):
    """Warm-ups of a size the CPU runs in a second."""
    from benchmarks.chip import run

    monkeypatch.setattr(run, "WARM_SECONDS", 1.0)
    monkeypatch.setattr(run, "WARM_BURST", 8)


def tiny_suite(root: Path, engine: str) -> Path:
    """A copy of the benchmark at a size the CPU runs in seconds: n = 10
    (d = 7), short budgets, a slow stream, batches of 4, a 64 KiB network
    cache and 8 initial slots."""
    shutil.copytree(ROOT / "benchmarks" / "chip", root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    chip = root / "benchmarks" / "chip"
    for path in (chip / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["engine"] = engine
        cfg["problem"].update(n=10, d=7)
        cfg["search"]["max_assignments"] = 30
        cfg["check"]["sample"] = 64
        if cfg["entry"] == "service":
            cfg["service"].update(cache_bytes=64 << 10, initial_slots=8)
        path.write_text(json.dumps(cfg))
    for path in (chip / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        if "rate_rps" in mix:
            mix["rate_rps"] = 8.0
        else:
            mix["batch"] = 4
        path.write_text(json.dumps(mix))
    return root

