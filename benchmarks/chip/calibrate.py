"""Readings that the correctness limits are set from, for one cell.

    python3 -m benchmarks.chip.calibrate --workload train.atacworks.b64 \
        --seeds 11,12,...  --control-seeds 11,12,13 --out readings.json

In one process, so the program compiles once:

* the program's readings on every seed in ``--seeds``: the timed path
  compared with the plain reference, as a run compares them (the lower
  readings of each limit);
* on each seed in ``--control-seeds``: the control, which is the
  reference computed one precision step below the configuration's dtype
  and put in the program's place, and each fault the cell can have,
  planted in the reference (training: half of the batch left out; on a
  bf16 cell also the gradient exchange of four data shards left out) or
  in the program (serving: ring buffers left unchanged, an answer
  altered).  A train step that returns its state unchanged reads
  update_gap = 1 by construction and needs no run.

The upper reading of a number is the least that the control and the
faults give.  ``--seconds`` is the serving window per seed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from benchmarks.chip.lib import device, faults, judge, spec as specmod
from benchmarks.chip import run as runmod


def _train(spec, devices, seeds, control_seeds, log):
    from benchmarks.chip.drivers.train import TrainCell

    cell = TrainCell(spec, devices)
    low = cell.ref.LOWER[cell.config["dtype"]]
    for seed in seeds:
        t = time.perf_counter()
        state, pool, params0 = cell.start(seed)
        state, prog = cell.first_steps(state, pool)
        del state
        ref = cell.reference_readings(params0, pool)
        log("program", seed, judge.train_numbers(prog, ref, params0), t)
        if seed not in control_seeds:
            continue
        variants = {"control": dict(low=low),
                    "half_batch": dict(fault="half_batch")}
        if cell.config["dtype"] == "bfloat16":
            variants["no_exchange"] = dict(fault="no_exchange", dp=4)
        for name, kw in variants.items():
            t = time.perf_counter()
            other = cell.reference_readings(params0, pool, **kw)
            log(name, seed, judge.train_numbers(other, ref, params0), t)


def _stream(spec, devices, seeds, control_seeds, seconds, log):
    from benchmarks.chip.drivers.stream import StreamCell

    low = specmod.reference(spec["config"]).LOWER[spec["config"]["dtype"]]

    def session(seed):
        cell = StreamCell(spec, devices, seed)
        cell.warm_up()
        w = cell.window(seconds)
        return cell, cell.check_sample(w["finished"])

    for seed in seeds:
        t = time.perf_counter()
        cell, sample = session(seed)
        ref = cell.reference_outputs(sample)
        log("program", seed, judge.stream_numbers(
            [r.result() for r in sample], ref), t, streams=len(sample))
        if seed not in control_seeds:
            continue
        t = time.perf_counter()
        log("control", seed, judge.stream_numbers(
            cell.reference_outputs(sample, low=low), ref), t)
        for name, plant in faults.STREAM.items():
            t = time.perf_counter()
            with plant():
                bad, bad_sample = session(seed)
            log(name, seed, judge.stream_numbers(
                [r.result() for r in bad_sample],
                bad.reference_outputs(bad_sample)), t)


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
    rows = []

    def log(kind, seed, numbers, t0, **extra):
        rows.append({"kind": kind, "seed": seed, "numbers": numbers,
                     "seconds": time.perf_counter() - t0, **extra})
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)

    if spec["traffic"]["kind"] == "train":
        _train(spec, devices, seeds, control, log)
    else:
        _stream(spec, devices, seeds, control, args.seconds, log)
    lower = {}
    upper = {}
    for r in rows:
        for k, v in r["numbers"].items():
            if r["kind"] == "program":
                lower[k] = max(lower.get(k, 0.0), v)
            else:
                upper[k] = min(upper.get(k, float("inf")), v)
    summary = {"workload": args.workload, "device": device.describe(devices),
               "lower": lower, "upper": upper, "rows": rows}
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"lower": lower, "upper": upper}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
