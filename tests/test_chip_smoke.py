"""``chip_smoke.py`` off the chip: its phases at a tiny size on the CPU
(Pallas interpreted), and its entry point's refusal of any platform but TPU.
The script's full-size run is a chip run; these keep its code paths honest
between chip runs."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("engine", ["einsum", "pallas_packed", "pallas_dense"])
def test_one_chip_phases_agree_with_ac3_at_tiny_size(chip_smoke, engine):
    records = list(
        chip_smoke.one_chip_phases(seed=0, budget=20, count=3, n=10, engines=(engine,))
    )
    assert [r["phase"] for r in records] == ["closures", "solve_many", "service"]
    for rec in records:
        assert rec["agree"], rec
        assert rec["engine"] == engine and rec["instances"] == 3
    service = records[-1]
    assert service["demotions"] == service["failed"] == service["shed"] == 0
    assert service["levels"] == [0]
    # interpreted on the CPU, so the chip-only checks must refuse the record
    if engine.startswith("pallas"):
        assert records[0]["interpret"] is True
        assert not chip_smoke.chip_checks(records[0])


def test_entry_point_refuses_the_cpu(chip_smoke, capsys):
    assert chip_smoke.main([]) == 1
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {
        "ok": False, "device": {"platform": "cpu", "kind": "cpu", "count": 1}
    }
