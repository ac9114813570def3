"""The trace reduction, on a small hand-made trace whose numbers are
worked out by hand below, and on an excerpt of a TPU v5e trace of the
fp32 training cell: one whole train step (op names cut to 72
characters, times from the step's start)."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks.chip.lib import trace as tr

NS = 1e-9


def _ev(kind, where, name, s, e):
    return tr.Event(kind, where, name, float(s), float(e))


HAND = [
    _ev("host", "python", "bench.window", 0, 100),
    _ev("host", "python", "bench.train.step", 0, 50),
    _ev("host", "python", "bench.train.step", 50, 100),
    _ev("host", "python", "PjitFunction(step)", 0, 5),
    _ev("host", "python", "TransferToHost", 40, 50),
    _ev("host", "python", "PjitFunction(step)", 50, 55),
    # chip 0: one op starts before the window and is clipped to [0, 2]
    _ev("device", "0", "fusion.1", -10, 2),
    _ev("device", "0", "jvp_conv1d_fwd_.3", 5, 20),
    _ev("device", "0", "transpose_jvp_conv1d_bwd_data__.1", 20, 30),
    _ev("device", "0", "fusion.12", 30, 40),
    _ev("device", "0", "all-reduce.5", 38, 44),
    _ev("device", "0", "transpose_jvp_conv1d_bwd_weight__.2", 55, 90),
    # chip 1
    _ev("device", "1", "jvp_conv1d_fwd_.3", 5, 25),
    _ev("device", "1", "all-reduce.5", 25, 35),
]


def test_hand_trace():
    s = tr.summarize(HAND)
    assert s["devices"] == 2
    assert s["window_s"] == pytest.approx(100 * NS)
    # chip 0 busy [0,2] + [5,44] + [55,90] = 76; chip 1 [5,35] = 30
    assert s["busy_s"] == pytest.approx(53 * NS)
    assert s["kernel_s"] == pytest.approx({
        "conv1d_fwd": 35 * NS, "conv1d_bwd_data": 10 * NS,
        "conv1d_bwd_weight": 35 * NS})
    # chip 0: all-reduce [38,44] under fusion.12 until 40 -> 4 exposed;
    # chip 1: [25,35] alone -> 10
    assert s["collective_s"] == pytest.approx(8 * NS)
    assert s["collective_exposed_s"] == pytest.approx(7 * NS)
    ops = dict(s["device_ops"])
    assert ops["all-reduce"] == pytest.approx(16 * NS)
    assert ops["fusion"] == pytest.approx(12 * NS)
    # chip 0's gaps: [2,5] under the first step's dispatch, [44,55] while
    # the host copies back, [90,100] with nothing on the host
    gaps = dict(s["idle_gaps"])
    assert gaps == pytest.approx({
        "bench.train.step / PjitFunction(step)": 3 * NS,
        "bench.train.step / TransferToHost": 11 * NS,
        "bench.train.step / -": 10 * NS})


def test_dp_collective_exposed_ms_is_a_chip_mean_per_step():
    """Of the hand trace's two steps, 7 ns a chip exposed on average."""
    from benchmarks.chip.lib import spec as specmod

    read = specmod.layer_reader("dp_collective_exposed_ms")
    assert read({"trace": tr.summarize(HAND), "steps": 2}) == \
        pytest.approx(1e3 * 3.5 * NS)
    no_collective = [e for e in HAND if not tr.is_collective(e.name)]
    assert read({"trace": tr.summarize(no_collective), "steps": 2}) is None
    assert read({"trace": None, "steps": 2}) is None


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError):
        tr.summarize([e for e in HAND if e.name != tr.WINDOW_SPAN])
    with pytest.raises(ValueError):
        tr.summarize([e for e in HAND if e.kind == "host"])


def test_stems():
    assert tr.stem("transpose_jvp_conv1d_bwd_weight__.25") == \
        "conv1d_bwd_weight"
    assert tr.stem("copy.12.3") == "copy"
    assert tr.stem("%pad.662 = f32[64,16,60928]{2,1,0} pad(f32[64,15,60000]"
                   " %slice.144)") == "pad"
    assert tr.is_collective("all-reduce-start.2")
    assert not tr.is_collective("fusion.3")


EXCERPT = Path(__file__).parent / "data" / "trace_train_v5e.json"


def test_recorded_train_excerpt():
    events = [tr.Event(*e) for e in json.loads(EXCERPT.read_text())]
    s = tr.summarize(events)
    assert s["devices"] == 1
    assert 0 < s["busy_s"] <= s["window_s"]
    kernel = sum(s["kernel_s"].values())
    assert all(v > 0 for v in s["kernel_s"].values())
    assert kernel <= s["busy_s"] * s["devices"]
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(
        s["window_s"] - s["busy_s"])
    assert s["collective_exposed_s"] == s["collective_s"] == 0.0
    # HLO instructions are named by the text before " = "
    assert "pad" in dict(s["device_ops"])
