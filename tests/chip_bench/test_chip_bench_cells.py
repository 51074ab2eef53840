"""Cells, configurations, traffic mixes and metric readers are found by the
names in BENCHMARK.json, and a new one is a new file plus an entry."""

from __future__ import annotations

import json
import re
import shutil
from pathlib import Path

import pytest

from benchmarks.chip import cells

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_loads_with_its_files(workload):
    cell = cells.load(workload)
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer and set(cell.readers) == {m["name"] for m in cell.per_layer}
    assert cell.config["entry"] in ("service", "solve_many")
    assert cell.chips == 1
    if cell.config["entry"] == "service":
        assert float(cell.traffic["rate_rps"]) > 0
    else:
        assert int(cell.traffic["batch"]) > 0


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        cells.load("no-such-cell")


def test_benchmark_file_keeps_to_its_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    for path in BENCH["paths"]:
        assert (ROOT / path).is_dir()
    every = BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"] + BENCH["per_layer"]
    assert all(NAME.match(e["name"]) for e in every)
    assert len({e["name"] for e in every}) == len(every)
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file() and c["file"].startswith(BENCH["paths"][0])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for m in BENCH["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["moves"] in e2e
        for w in m["workloads"]:
            assert w in e2e[m["moves"]].get("workloads", [w])
        assert (ROOT / "benchmarks/chip/metrics" / f"{m['name']}.py").is_file()


def _copy_suite(tmp_path: Path) -> Path:
    shutil.copytree(ROOT / "benchmarks" / "chip", tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def _snapshot(root: Path) -> dict:
    return {p: p.read_bytes() for p in (root / "benchmarks" / "chip").rglob("*") if p.is_file()}


def test_a_new_cell_mix_config_and_metric_are_new_files_only(tmp_path):
    root = _copy_suite(tmp_path)
    before = _snapshot(root)
    chip = root / "benchmarks" / "chip"
    cfg = json.loads((chip / "configs" / "frb50_service.json").read_text())
    cfg.update(name="frb30_service", problem=dict(cfg["problem"], n=30, d=16))
    (chip / "configs" / "frb30_service.json").write_text(json.dumps(cfg))
    (chip / "traffic" / "poisson-slow.json").write_text(json.dumps(
        {"rate_rps": 2.0}))
    (chip / "metrics" / "setup_share.service.py").write_text(
        "def read(record):\n    return 7.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "frb30_service", "source": cfg["source"],
                             "file": "benchmarks/chip/configs/frb30_service.json",
                             "reduced": [], "why": "a smaller stream"})
    bench["workloads"].append({"name": "frb30-slow", "config": "frb30_service",
                               "traffic": "poisson-slow", "chips": 1, "why": "slow"})
    for m in bench["end_to_end"]:
        if "frb50-poisson" in m.get("workloads", []):
            m["workloads"].append("frb30-slow")
    bench["per_layer"].append({"name": "setup_share.service", "unit": "%",
                               "better": "lower", "source": "host_clock", "layer": "service",
                               "moves": "p50_latency_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    after = _snapshot(root)
    assert all(after[p] == data for p, data in before.items())  # nothing edited
    cell = cells.load("frb30-slow", root=root)
    assert cell.config["problem"]["n"] == 30 and cell.traffic["rate_rps"] == 2.0
    assert cell.readers["setup_share.service"]({}) == 7.0
    # a metric without a workloads key reaches every cell reporting what it moves
    assert "setup_share.service" in cells.load("frb50-poisson", root=root).readers
    assert "setup_share.service" not in cells.load("frb100-batch24", root=root).readers


def test_a_metric_without_its_reader_is_refused(tmp_path):
    root = _copy_suite(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "ghost.service", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "service",
                               "moves": "p50_latency_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(FileNotFoundError):
        cells.load("frb50-poisson", root=root)
