"""``BENCHMARK.json`` and the files it names: every name resolves to its
file, the configuration files state the program's configurations, and
each cell reports what its metrics need.

The checks are functions of a checkout's root, so that the same checks
also run on a copy of the benchmark to which a configuration of another
architecture and a traffic kind of its own were added as new files and
appended list entries, and on copies with a configuration file that is
wrong."""
from __future__ import annotations

import dataclasses
import filecmp
import json
import re
import shutil
import sys
import time
import types
from pathlib import Path

import pytest

from benchmarks.chip.lib import spec as specmod

BENCH = specmod.load_benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
CELLS = [w["name"] for w in BENCH["workloads"]]
# keys of the registered program config that label it rather than size it
LABELS = ("name", "source")
# keys that size a layer's width, which no cut may change
WIDTH = re.compile(r"_dim|_rank|hidden|intermediate|latent|state|proj|"
                   r"head_|expand|channels|filter|per_tok|d_model|d_ff|"
                   r"d_inner")
DRIVER_FUNCTIONS = ("run", "calibrate", "tiny")


def check_names(bench: dict):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    cells = [w["name"] for w in bench["workloads"]]
    names = ([c["name"] for c in bench["configs"]] + cells
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs)), pairs
    for n in names:
        assert NAME.fullmatch(n), n
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(cells)


def check_cell(cell: str, root: Path):
    """The cell's files resolve, it reports ``setup_s``, another
    end-to-end metric and a per-layer metric that moves one of them, and
    its traffic kind has a driver."""
    spec = specmod.load_cell(cell, root)
    reported = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in reported and len(reported) >= 2
    assert spec["per_layer"]
    for m in spec["per_layer"]:
        assert m["moves"] in reported
        assert callable(specmod.layer_reader(m["name"], root))
    assert spec["limits"]["numbers"]
    driver = specmod.driver(spec["traffic"]["kind"])
    for fn in DRIVER_FUNCTIONS:
        assert callable(getattr(driver, fn, None)), (driver.__name__, fn)
    specmod.reference(spec["config"], root)


def check_config(entry: dict, bench: dict, root: Path):
    """Every key of the file that names a field of the registered program
    config holds the registered value, but the keys in ``reduced``: each
    of those holds the cut value, differs from the published value the
    file states beside it under ``published``, is no width, and its
    published value is the registered one."""
    from repro import configs

    data = specmod.load_cell(next(
        w["name"] for w in bench["workloads"]
        if w["config"] == entry["name"]), root)["config"]
    registered = dataclasses.asdict(configs.get(data["arch"]))
    reduced = entry["reduced"]
    published = data.get("published", {})
    compared = [k for k in data if k in registered and k not in LABELS]
    assert compared
    for key in compared:
        if key not in reduced:
            assert data[key] == registered[key], key
    assert set(published) == set(reduced), (sorted(published), reduced)
    for key in reduced:
        assert not WIDTH.search(key), f"{key} is a width"
        assert data[key] != published[key], key
        if key in registered:
            assert published[key] == registered[key], key


def test_top_level_keys_and_names():
    check_names(BENCH)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    check_cell(cell, specmod.ROOT)


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_states_the_program_config(entry):
    check_config(entry, BENCH, specmod.ROOT)


# --- a configuration of another architecture, entered by new files ---

LM_CELL = "train.zamba2-cut.seq4k"
LM_CONFIG = {"name": "zamba2-cut", "source": "https://huggingface.co/Zyphra/"
             "Zamba2-7B-Instruct/blob/main/config.json",
             "file": "benchmarks/chip/configs/zamba2-cut.json",
             "reduced": ["n_layers"], "why": "a cut-down hybrid model"}
LM_FILE = {"arch": "zamba2-7b", "reference": "lm_stub", "dtype": "bfloat16",
           "n_layers": 12, "d_model": 3584, "n_heads": 32, "d_ff": 14336,
           "vocab_size": 32000, "published": {"n_layers": 81}}


def _copy(tmp_path: Path) -> Path:
    """A checkout holding ``BENCHMARK.json`` and the benchmark's files."""
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(specmod.ROOT / "BENCHMARK.json", root)
    shutil.copytree(specmod.BENCH_DIR, specmod.bench_dir(root),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return root


def _write(path: Path, text: str):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def _add_lm_cell(root: Path, config_file: dict, reduced: list) -> dict:
    """Add the cut-down language model's cell to the copy at ``root`` as
    new files and appended entries alone; returns the new benchmark."""
    here = specmod.bench_dir(root)
    _write(root / LM_CONFIG["file"], json.dumps(config_file))
    _write(here / "traffic" / "lm_seq4k.json",
           json.dumps({"kind": "lmstub", "batch": 2, "seq": 4096}))
    _write(here / "limits" / f"{LM_CELL}.json",
           json.dumps({"numbers": {"loss_gap": {"limit": 1e-2}}}))
    _write(here / "references" / "lm_stub.py",
           '"""A reference stub."""\nLOWER = {"bfloat16": "float8_e4m3fn"}\n')
    _write(here / "layer_metrics" / "lm_step_ms.py",
           '"""Milliseconds a step."""\n\n\ndef read(r):\n'
           '    return 1e3 * r["window_s"] / r["steps"]\n')
    bench = specmod.load_benchmark(root)
    bench["configs"].append(dict(LM_CONFIG, reduced=reduced))
    bench["workloads"].append({"name": LM_CELL, "config": "zamba2-cut",
                               "traffic": "lm_seq4k", "chips": 1,
                               "why": "two 4,096-token sequences"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("train_segments_per_s", "train_idle"):
            m["workloads"].append(LM_CELL)
    bench["per_layer"].append({
        "name": "lm_step_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "model step",
        "moves": "train_segments_per_s", "workloads": [LM_CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return bench


def _lm_driver() -> types.ModuleType:
    """A driver for the traffic kind ``lmstub``, held in ``sys.modules``
    only: no file of the repository's ``drivers/`` defines it."""
    mod = types.ModuleType(f"{specmod.DRIVERS}.lmstub")

    def run(spec, devices, *, seed, seconds, trace_dir, marks, peaks):
        t = time.perf_counter()
        return {"e2e": {"train_segments_per_s": 2.0, "setup_s": 1.0},
                "attempted": 4, "failed": 0,
                "numbers": {"loss_gap": 1e-4}, "memory_peak_bytes": 1,
                "trace": None, "chips": len(devices), "peaks": peaks,
                "window_s": 4.0, "steps": 4, "window_t0": t, "diag": {}}

    def calibrate(spec, devices, seeds, control_seeds, seconds, log):
        for seed in seeds:
            log("program", seed, {"loss_gap": 1e-4 * seed},
                time.perf_counter())
            if seed in control_seeds:
                log("control", seed, {"loss_gap": 0.1 * seed},
                    time.perf_counter())

    def tiny(spec):
        spec["traffic"]["seq"] = 64
        return spec

    mod.run, mod.calibrate, mod.tiny = run, calibrate, tiny
    return mod


def test_another_architecture_enters_by_new_files(tmp_path, monkeypatch,
                                                  tiny):
    """A cut-down configuration of another architecture (``reduced`` and
    ``published`` not empty) and a traffic kind of its own pass every
    check above, load, cut down for the CPU, calibrate and run through
    the harness, with no file of the benchmark changed."""
    import jax

    from benchmarks.chip import calibrate, run

    root = _copy(tmp_path)
    bench = _add_lm_cell(root, LM_FILE, ["n_layers"])
    monkeypatch.setitem(sys.modules, f"{specmod.DRIVERS}.lmstub",
                        _lm_driver())

    # nothing of the benchmark changed: every file is as it was, and each
    # entry of BENCHMARK.json is the old one with names appended
    old = specmod.load_benchmark()
    for f in specmod.BENCH_DIR.rglob("*"):
        rel = f.relative_to(specmod.BENCH_DIR)
        if f.is_file() and not {"tests", "__pycache__"} & set(rel.parts):
            assert filecmp.cmp(f, specmod.bench_dir(root) / rel,
                               shallow=False), rel
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for was, now in zip(old[key], bench[key]):
            assert {k: v for k, v in now.items() if k != "workloads"} == \
                {k: v for k, v in was.items() if k != "workloads"}
            n = len(was.get("workloads", []))
            assert now.get("workloads", [])[:n] == was.get("workloads", [])

    check_names(bench)
    for cell in [w["name"] for w in bench["workloads"]]:
        check_cell(cell, root)
    for entry in bench["configs"]:
        check_config(entry, bench, root)

    spec = specmod.load_cell(LM_CELL, root)
    assert spec["config"]["n_layers"] == 12
    assert [m["name"] for m in spec["per_layer"]] == ["train_idle",
                                                      "lm_step_ms"]
    assert tiny(LM_CELL, root)["traffic"]["seq"] == 64
    rows, lower, upper = calibrate.readings(spec, jax.devices()[:1],
                                            [1, 2], {1}, 0.0)
    assert [r["kind"] for r in rows] == ["program", "control", "program"]
    assert lower == {"loss_gap": 2e-4} and upper == {"loss_gap": 0.1}
    result, _ = run.run_cell(LM_CELL, seed=3, seconds=0.1, trace=False,
                             require_tpu=False, spec=spec)
    assert result["correct"]
    assert set(result["metrics"]) == {"train_segments_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [
    "wrong_conv_channels", "cut_without_published", "cut_not_in_reduced",
    "width_in_reduced", "reduced_but_not_cut"])
def test_wrong_config_file_is_rejected(tmp_path, monkeypatch, fault):
    """The checks still fail an AtacWorks file that disagrees with the
    registered configuration, and a cut that is not listed, states no
    published value, changes a width or changes nothing."""
    root = _copy(tmp_path)
    monkeypatch.setitem(sys.modules, f"{specmod.DRIVERS}.lmstub",
                        _lm_driver())
    if fault == "wrong_conv_channels":
        path = root / "benchmarks/chip/configs/atacworks.json"
        data = json.loads(path.read_text())
        path.write_text(json.dumps(dict(data, conv_channels=16)))
        bench = specmod.load_benchmark(root)
        entry = bench["configs"][0]
    else:
        lm, reduced = dict(LM_FILE), ["n_layers"]
        if fault == "cut_without_published":
            del lm["published"]
        elif fault == "cut_not_in_reduced":
            reduced = []
        elif fault == "width_in_reduced":
            lm["d_model"] = 1792
            lm["published"] = dict(lm["published"], d_model=3584)
            reduced = ["n_layers", "d_model"]
        else:
            lm["n_layers"] = 81
        bench = _add_lm_cell(root, lm, reduced)
        entry = bench["configs"][-1]
    with pytest.raises(AssertionError):
        check_config(entry, bench, root)
