"""Device time by layer scope (``lib/scopes.py``), on a small hand-made
trace whose numbers are worked out by hand below, and on one whole train
step of a TPU v5e trace of the fp32 training cell that keeps each op's
``op_name`` (op names cut to 72 characters, times from the step's
start)."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from benchmarks.chip import run
from benchmarks.chip.lib import scopes, trace as tr

NS = 1e-9
FWD = "jit(train_step)/jvp({})/conv1d_fwd/pallas_call"
BWD = "jit(train_step)/transpose(jvp({}))/conv1d_bwd_weight/pallas_call"


def _ev(kind, where, name, s, e, op_name=""):
    return scopes.ScopedEvent(kind, where, name, float(s), float(e), op_name)


HAND = [
    _ev("host", "python", "bench.window", 0, 100),
    # clipped to [0, 4]
    _ev("device", "0", "%pad.1 = f32[..] pad(...)", -6, 4,
        "jit(train_step)/jvp(layer.stem)/jit(_pad)/pad"),
    _ev("device", "0", "%conv1d_fwd.1 = custom-call", 4, 20,
        FWD.format("layer.stem")),
    _ev("device", "0", "%conv1d_fwd.2 = custom-call", 20, 50,
        FWD.format("layer.res3.conv1")),
    _ev("device", "0", "%slice.4 = slice", 50, 55,
        "jit(train_step)/transpose(jvp(layer.res3.conv1))/slice"),
    _ev("device", "0", "%conv1d_bwd_weight.3 = custom-call", 55, 70,
        BWD.format("layer.head_peak")),
    _ev("device", "0", "%fusion.9 = fusion", 70, 78,
        "jit(train_step)/step.optimizer/mul"),
    _ev("device", "0", "%fusion.10 = fusion", 78, 80,
        "jit(train_step)/transpose(jvp(step.loss))/mul"),
    _ev("device", "0", "%all-reduce.1 = all-reduce", 80, 86,
        "jit(train_step)/transpose(jvp(layer.res0.conv2))/psum"),
    _ev("device", "0", "%copy.2 = copy", 86, 90, "x"),
    # after the window: not counted
    _ev("device", "0", "%copy.3 = copy", 100, 120, "x"),
]


def test_hand_trace_by_class():
    s = scopes.split(HAND)
    assert s["devices"] == 1
    assert {k: s[k] for k in scopes.CLASSES} == pytest.approx({
        "edge": 31 * NS, "body": 30 * NS, "glue": 9 * NS,
        "update": 10 * NS, "unscoped": 10 * NS})


def test_scope_is_the_innermost_layer_or_step_name():
    assert scopes.scope(
        "jit(train_step)/transpose(jvp(layer.res10.conv2))/conv1d_bwd_data"
        "/pallas_call") == "layer.res10.conv2"
    assert scopes.scope("jit(step)/step.guard/select_n") == "step.guard"
    assert scopes.scope("jit(step)/player.x/add") is None
    assert scopes.scope("") is None
    assert scopes.classify("%conv1d_fwd.7 = custom-call",
                           FWD.format("layer.head_signal")) == "edge"
    assert scopes.classify("%conv1d_fwd.7 = custom-call", "") == "body"


def test_a_program_without_scopes_reads_none():
    bare = [e._replace(op_name="") for e in HAND]
    assert scopes.split(bare) is None
    with pytest.raises(ValueError):
        scopes.split([e for e in HAND if e.kind == "device"])


def test_readers_without_a_trace_read_none():
    from benchmarks.chip.lib import spec as specmod

    for name in ("train_conv_edge_ms", "train_glue_ms", "train_update_ms",
                 "setup_kernel_build_s"):
        assert specmod.layer_reader(name)({"trace": None, "steps": 5}) \
            is None


def test_trace_dir_is_the_runs():
    assert scopes.TRACE_DIR == run.TRACE_DIR


EXCERPT = Path(__file__).parent / "data" / "trace_train_v5e_scopes.json"


def test_recorded_train_step_by_scope():
    events = [scopes.ScopedEvent(*e) for e in json.loads(EXCERPT.read_text())]
    s = scopes.split(events)
    summary = tr.summarize([tr.Event(*e[:5]) for e in events])
    # the classes cover the step's busy time
    assert sum(s[k] for k in scopes.CLASSES) == pytest.approx(
        summary["busy_s"], rel=1e-3)
    # every kernel, pad and slice carries its layer's scope; what is left
    # unscoped is the compiler's own (layout copies of the step's
    # arguments, copies with no name), under 0.5 ms of the 2 s step
    unscoped = [tr.stem(e.name) for e in events if e.kind == "device"
                and scopes.classify(e.name, e.op_name) == "unscoped"]
    assert not {"pad", "slice", *tr.CONV_STEMS} & set(unscoped)
    assert s["unscoped"] < 5e-4
    assert s["edge"] + s["body"] == pytest.approx(
        sum(summary["kernel_s"].values()), rel=1e-9)
    assert 0 < s["edge"] < s["body"]
    assert s["glue"] > 0 and s["update"] > 0
