"""``BENCHMARK.json`` and the files it names: every name resolves to its
file, the configuration files state the program's configurations, and
each cell reports what its metrics need."""
from __future__ import annotations

import dataclasses
import re

import pytest

from benchmarks.chip.lib import spec as specmod

BENCH = specmod.load_benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.fullmatch(n), n
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    spec = specmod.load_cell(cell)
    reported = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in reported
        assert callable(specmod.layer_reader(m["name"]))
    assert spec["limits"]["numbers"]
    assert spec["traffic"]["kind"] in ("train", "stream")
    specmod.reference(spec["config"])


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_states_the_program_config(entry):
    """Nothing is cut: each file holds the registered configuration's
    numbers, and ``reduced`` is empty."""
    from repro import configs

    data = specmod.load_cell(next(
        w["name"] for w in BENCH["workloads"]
        if w["config"] == entry["name"]))["config"]
    registered = dataclasses.asdict(configs.get(data["arch"]))
    for key in ("dtype", "n_layers", "conv_channels", "conv_filter",
                "conv_dilation"):
        assert data[key] == registered[key], key
    assert entry["reduced"] == []
