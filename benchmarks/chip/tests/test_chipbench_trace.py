"""Trace reduction, the table of peaks, and the configuration files."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.chip import peaks, trace  # noqa: E402
from repro.configs import get_config  # noqa: E402

CONFIGS = ROOT / "benchmarks" / "chip" / "configs"
#: 0.33 s of a traced run of dsc33b-8l.code-open on one TPU v5e: five
#: ticks, one of them admitting a request
RECORDED = Path(__file__).resolve().parent / "data" / "dsc33b-8l.xplane.pb.gz"


def test_recorded_chip_trace():
    devices, host = trace.read_xplane(RECORDED)
    assert list(devices) == ["/device:TPU:0"]
    assert sorted({n for n, _, _ in host}) == sorted(trace.SPANS)
    r = trace.reduce(devices, host)
    assert r["window_s"] == pytest.approx(0.325681041)
    assert r["busy_s"] == pytest.approx(0.302822511)
    assert r["idle_share"] == pytest.approx(1 - 0.302822511 / 0.325681041)
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert max(idle, key=idle.get) == "tick.decode"
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) == 10
    # each operation is named by its program and its HLO name
    assert all(n.startswith("jit_") and ":%" in n for n, _ in r["device_ops"])


def test_reduce_counts_union_and_attributes_idle_time():
    devices = {"/device:TPU:0": [
        (10, 30, "fusion.1"), (20, 40, "fusion.2"),   # overlap: busy 10-40
        (60, 70, "fusion.1"), (95, 120, "copy.3")]}    # copy clipped at 100
    host = [("governor", 0, 5), ("tick.decode", 5, 50),
            ("driver", 50, 55), ("tick.admit", 55, 100)]
    r = trace.reduce(devices, host)
    busy = 30 + 10 + 5
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(busy * 1e-9)
    assert r["idle_share"] == pytest.approx(1 - busy / 100)
    idle = dict(r["idle_gaps"])
    assert idle["governor"] == pytest.approx(5e-9)
    assert idle["tick.decode"] == pytest.approx((45 - 30) * 1e-9)
    assert idle["driver"] == pytest.approx(5e-9)
    assert idle["tick.admit"] == pytest.approx((45 - 15) * 1e-9)
    assert sum(idle.values()) == pytest.approx((100 - busy) * 1e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx(30e-9)
    assert ops["copy.3"] == pytest.approx(5e-9)


def test_reduce_keeps_the_stretch_of_the_first_seconds():
    """A trace of the whole run is reduced over the spans that begin
    within ``seconds`` of the first one."""
    devices = {"/device:TPU:0": [(0, 40, "fusion.1"), (60, 90, "fusion.2")]}
    host = [("tick.decode", 0, 50), ("tick.decode", 50, 100)]
    r = trace.reduce(devices, host, seconds=40e-9)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["busy_s"] == pytest.approx(40e-9)
    assert dict(r["device_ops"]) == {"fusion.1": pytest.approx(40e-9)}
    assert trace.reduce(devices, host)["busy_s"] == pytest.approx(70e-9)


def test_reduce_averages_devices_and_needs_both_sides():
    host = [("tick.decode", 0, 10)]
    two = {"a": [(0, 10, "x")], "b": [(0, 4, "x")]}
    assert trace.reduce(two, host)["busy_s"] == pytest.approx(7e-9)
    assert trace.reduce({}, host) is None
    assert trace.reduce(two, []) is None


def test_unknown_device_kind_is_an_error():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peak("TPU v99")


@pytest.mark.parametrize("name", ["dsc33b-8l", "internvl2-1b"])
def test_config_is_the_repo_config_but_for_what_it_says(name):
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    base = get_config(conf["starts_from"])
    for key, value in conf["model"].items():
        if key in conf["changed_from_repo_config"]:
            assert getattr(base, key) != value, key
        else:
            assert getattr(base, key) == value, key
    for key in ("source", "reduced", "assumed", "deployment", "engine",
                "correct"):
        assert key in conf
