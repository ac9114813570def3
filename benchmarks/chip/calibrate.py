"""Readings that the correctness limits are set from, for one cell.

    python3 -m benchmarks.chip.calibrate --workload train.atacworks.b64 \
        --seeds 11,12,...  --control-seeds 11,12,13 --out readings.json

In one process, so the program compiles once, the driver of the cell's
traffic kind (``drivers/<kind>.py``, its ``calibrate``) reads:

* the program's readings on every seed in ``--seeds``: the timed path
  compared with the plain reference, as a run compares them (the lower
  readings of each limit);
* on each seed in ``--control-seeds``: the control, which is the
  reference computed one precision step below the configuration's dtype
  and put in the program's place, and each fault the cell can have,
  planted in the reference or in the program.

The upper reading of a number is the least that the control and the
faults give.  ``--seconds`` is the window per seed of a driver that needs
one (serving).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks.chip.lib import device, spec as specmod
from benchmarks.chip import run as runmod


def readings(spec: dict, devices, seeds, control_seeds, seconds: float):
    """The cell's driver's readings, as rows, with each number's lower
    reading (the largest over the program's rows) and upper reading (the
    least over the control's and the faults' rows)."""
    rows = []

    def log(kind, seed, numbers, t0, **extra):
        rows.append({"kind": kind, "seed": seed, "numbers": numbers,
                     "seconds": time.perf_counter() - t0, **extra})
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)

    specmod.driver(spec["traffic"]["kind"]).calibrate(
        spec, devices, seeds, control_seeds, seconds, log)
    lower = {}
    upper = {}
    for r in rows:
        for k, v in r["numbers"].items():
            if r["kind"] == "program":
                lower[k] = max(lower.get(k, 0.0), v)
            else:
                upper[k] = min(upper.get(k, float("inf")), v)
    return rows, lower, upper


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    spec = specmod.load_cell(args.workload)
    devices = device.chips(spec["cell"]["chips"])
    runmod._program_on_path()
    runmod._use_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows, lower, upper = readings(spec, devices, seeds, control,
                                  args.seconds)
    summary = {"workload": args.workload, "device": device.describe(devices),
               "lower": lower, "upper": upper, "rows": rows}
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"lower": lower, "upper": upper}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
