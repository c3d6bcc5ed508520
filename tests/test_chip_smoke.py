"""``chip_smoke.py`` at a tiny size on the CPU backend, kernels in
interpret mode: the pallas-vs-numpy comparison and the device-path
counters it asserts on the chip hold here too, and so does the probe's
own check against the host map. The VMEM probe bound is lowered so that
a small pre-seeded map already crosses it."""

import importlib.util
from pathlib import Path

import pytest

from repro.kernels import hashmap_probe


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_body_matches_reference(chip_smoke, monkeypatch):
    monkeypatch.setattr(hashmap_probe, "VMEM_SLOT_BOUND", 1 << 13)
    lines = []
    worst = chip_smoke.smoke(ticks=3, events=256, requests=2,
                             request_examples=64, probe_caps=(16, 1 << 12),
                             log=lines.append)
    assert worst["pred"] <= chip_smoke.PRED_ATOL
    assert any("placement=hbm" in ln for ln in lines)
    assert any("placement=vmem" in ln for ln in lines)
    for cap in (16, 1 << 12):
        for placement in ("vmem", "hbm"):
            assert any(f"probe cap={cap} placement={placement}:" in ln
                       for ln in lines), (cap, placement)


def test_chip_smoke_refuses_cpu(chip_smoke, capsys):
    assert chip_smoke.main() != 0
    assert "'cpu'" in capsys.readouterr().err
