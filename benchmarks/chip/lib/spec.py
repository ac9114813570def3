"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

Every function takes the checkout's ``root``, so a test can load a copy of
the benchmark with files added to it.  A traffic mix's ``kind`` names its
driver, the module ``benchmarks.chip.drivers.<kind>``."""
from __future__ import annotations

import functools
import importlib
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
DRIVERS = "benchmarks.chip.drivers"


def bench_dir(root: Path = ROOT) -> Path:
    """This directory's place in the checkout at ``root``."""
    return Path(root) / BENCH_DIR.relative_to(ROOT)


class SpecError(ValueError):
    """A name in ``BENCHMARK.json`` has no file, or a file is malformed."""


def _read_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing benchmark file {path}") from None


def load_benchmark(root: Path = ROOT) -> dict:
    return _read_json(Path(root) / "BENCHMARK.json")


def _by_name(entries: list[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json "
                    f"(known: {sorted(e['name'] for e in entries)})")


def load_cell(workload: str, root: Path = ROOT) -> dict:
    """Everything one run needs: the cell, its configuration, its traffic
    mix, its correctness limits and the per-layer metrics it reports."""
    bench = load_benchmark(root)
    cell = _by_name(bench["workloads"], workload, "workload")
    cfg_entry = _by_name(bench["configs"], cell["config"], "config")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in moved)]
    here = bench_dir(root)
    return {
        "cell": cell,
        "config": _read_json(Path(root) / cfg_entry["file"]),
        "traffic": _read_json(here / "traffic" / f"{cell['traffic']}.json"),
        "limits": _read_json(here / "limits" / f"{workload}.json"),
        "end_to_end": e2e,
        "per_layer": layer,
    }


@functools.cache
def _load_module(path: Path, name: str):
    if not path.is_file():
        raise SpecError(f"missing benchmark module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(metric: str, root: Path = ROOT):
    """The ``read(record)`` function of ``layer_metrics/<metric>.py``."""
    mod = _load_module(bench_dir(root) / "layer_metrics" / f"{metric}.py",
                       f"_chipbench_metric_{metric.replace('.', '_')}")
    return mod.read


def reference(config: dict, root: Path = ROOT):
    """The plain reference module the configuration names."""
    name = config["reference"]
    return _load_module(bench_dir(root) / "references" / f"{name}.py",
                        f"_chipbench_reference_{name}")


def driver(kind: str):
    """The driver module of a traffic ``kind``: it builds, warms up and
    measures a cell (``run``), reads a cell's calibration (``calibrate``)
    and cuts a cell down for the CPU tests (``tiny``)."""
    try:
        return importlib.import_module(f"{DRIVERS}.{kind}")
    except ModuleNotFoundError as e:
        if e.name != f"{DRIVERS}.{kind}":
            raise
        raise SpecError(f"no driver for traffic kind {kind!r}") from None
