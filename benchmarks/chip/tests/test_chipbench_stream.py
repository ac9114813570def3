"""The stream driver on the tests' own stream cell (``tests/data``),
cut down for the CPU: what the server streams agrees
with the reference's one-shot causal forward, an open-loop mix runs from
its data file alone, and the check refuses the control and each fault a
stream cell can have."""
from __future__ import annotations

import jax
import pytest

from benchmarks.chip.lib import faults, judge

CELL = "stream.atacworks.c128"  # tests/data/stream_cell.json


def test_sound_run_is_correct(run_tiny):
    r, diag = run_tiny(CELL)
    assert r["correct"], r["checks"]
    assert diag["compiles_in_window"] == 0
    assert r["attempted"] >= 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"serve_columns_per_s",
                                 "stream_chunk_p95_ms", "setup_s"}


@pytest.mark.parametrize("arrivals", [
    {"kind": "poisson", "rate_per_s": 40.0},
    {"kind": "bursty", "rate_per_s": 40.0, "burst": 4}],
    ids=["poisson", "bursty"])
def test_open_loop_mix_needs_only_data(tiny, run_tiny, arrivals):
    spec = tiny(CELL)
    spec["traffic"]["arrivals"] = arrivals
    r, _ = run_tiny(CELL, spec=spec)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1


@pytest.mark.parametrize("fault", sorted(faults.STREAM))
def test_planted_fault_is_caught(run_tiny, fault):
    with faults.STREAM[fault]():
        r, _ = run_tiny(CELL)
    assert not r["correct"], r["checks"]


def test_control_is_not_correct(tiny):
    from benchmarks.chip.drivers.stream import StreamCell

    spec = tiny(CELL)
    cell = StreamCell(spec, jax.devices()[:1], 5)
    cell.warm_up()
    sample = cell.check_sample(cell.window(0.5)["finished"])
    assert sample
    ref = cell.reference_outputs(sample)
    low = cell.reference_outputs(
        sample, low=cell.ref.LOWER[spec["config"]["dtype"]])
    ok, checks = judge.verdict(judge.stream_numbers(low, ref),
                               spec["limits"])
    assert not ok, checks
