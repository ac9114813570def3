"""Cut-down cells for the CPU: the real cell's files, with the filter,
dilation, widths and counts made small enough for a test run."""
from __future__ import annotations

import json
import time
from pathlib import Path

import pytest

from benchmarks.chip.lib import spec as specmod

SECONDS = 0.5
# a stream cell of the tests' own, in tests/data, not in BENCHMARK.json
STREAM = "stream.atacworks.c128"


def stream_spec() -> dict:
    """The stream cell of ``tests/data/stream_cell.json``, laid out as
    ``lib.spec.load_cell`` lays out a benchmark cell."""
    data = json.loads((Path(__file__).parent / "data"
                       / "stream_cell.json").read_text())
    bench = specmod.load_benchmark()
    cfg = next(c for c in bench["configs"]
               if c["name"] == data["cell"]["config"])
    return {"cell": data["cell"],
            "config": json.loads((specmod.ROOT / cfg["file"]).read_text()),
            "traffic": data["traffic"], "limits": data["limits"],
            "end_to_end": data["end_to_end"],
            "per_layer": data["per_layer"]}


def tiny_spec(workload: str) -> dict:
    spec = stream_spec() if workload == STREAM else specmod.load_cell(workload)
    spec["config"].update(conv_filter=5, conv_dilation=2)
    t = spec["traffic"]
    if t["kind"] == "train":
        t.update(batch=8, width=512, pad=40)
    else:
        t.update(slots=2, chunk=16, history=16, warmup_steps=8,
                 check_streams=4,
                 lengths={"median": 64, "sigma": 1.0, "min": 32, "max": 256,
                          "n": 64, "strata": 8})
    return spec


@pytest.fixture
def tiny():
    return tiny_spec


@pytest.fixture
def run_tiny():
    """Run a cut-down cell through the whole harness, chip check aside:
    returns its result line and the window's diagnostics."""
    from benchmarks.chip import run

    def go(workload: str, *, seed: int = 2**31 + 17, spec=None, devices=1):
        spec = spec or tiny_spec(workload)
        spec["cell"] = dict(spec["cell"], chips=devices)
        return run.run_cell(workload, seed=seed, seconds=SECONDS,
                            trace=False, require_tpu=False, spec=spec,
                            t0=time.perf_counter())

    return go
