"""Cut-down cells for the CPU: the real cell's files, cut by its traffic
kind's driver (``tiny``) small enough for a test run."""
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


def tiny_spec(workload: str, root: Path = specmod.ROOT) -> dict:
    """The cell of ``BENCHMARK.json`` at ``root`` (or the tests' stream
    cell), cut down by its driver."""
    spec = (stream_spec() if workload == STREAM
            else specmod.load_cell(workload, root))
    return specmod.driver(spec["traffic"]["kind"]).tiny(spec)


@pytest.fixture
def tiny():
    return tiny_spec


@pytest.fixture
def run_tiny():
    """Run a cut-down cell through the whole harness, chip check aside:
    returns its result line and the window's diagnostics."""
    from benchmarks.chip import run

    def go(workload: str, *, seed: int = 2**31 + 17, spec=None, devices=1,
           seconds: float = SECONDS):
        spec = spec or tiny_spec(workload)
        spec["cell"] = dict(spec["cell"], chips=devices)
        return run.run_cell(workload, seed=seed, seconds=seconds,
                            trace=False, require_tpu=False, spec=spec,
                            t0=time.perf_counter())

    return go
