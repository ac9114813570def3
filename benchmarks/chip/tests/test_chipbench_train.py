"""Training cells, cut down for the CPU: the program's train step agrees
with the plain reference, and the check refuses the control and each
fault a one-chip training cell can have."""
from __future__ import annotations

import jax
import pytest

from benchmarks.chip.lib import faults, judge


@pytest.mark.parametrize("workload", ["train.atacworks.b64",
                                      "train.atacworks-bf16.b64"])
def test_sound_run_is_correct(run_tiny, workload):
    r, diag = run_tiny(workload)
    assert r["correct"], r["checks"]
    assert diag["compiles_in_window"] == 0
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks" and set(r["metrics"]) == {
        "train_segments_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in r["metrics"].values())


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
def test_planted_fault_is_caught(run_tiny, fault):
    with faults.TRAIN[fault]():
        r, _ = run_tiny("train.atacworks.b64")
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("workload", ["train.atacworks.b64",
                                      "train.atacworks-bf16.b64",
                                      "train.atacworks-bf16.dp4"])
def test_control_is_not_correct(tiny, workload):
    """The reference one precision step below the configuration's dtype,
    put in the program's place, fails the cell's limits (the four-chip
    cell's too: the reference's numbers do not depend on the chips it
    runs on)."""
    from benchmarks.chip.drivers.train import TrainCell

    spec = tiny(workload)
    cell = TrainCell(spec, jax.devices()[:1])
    _, pool, params0 = cell.start(5)
    ref = cell.reference_readings(params0, pool)
    low = cell.reference_readings(
        params0, pool, low=cell.ref.LOWER[spec["config"]["dtype"]])
    ok, checks = judge.verdict(judge.train_numbers(low, ref, params0),
                               spec["limits"])
    assert not ok, checks
