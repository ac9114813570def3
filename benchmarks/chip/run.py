"""Run one benchmark cell and print its result as the last stdout line.

    python3 -m benchmarks.chip.run --workload train.atacworks.b64 \
        --seed 7 --seconds 10 --trace 0

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and the metrics are
the cell's per-layer metrics, each read by ``layer_metrics/<name>.py``
from the run's record.  Either way the run checks what the timed path
produced against the plain reference and prints each compared number
beside its limit, as the last lines of stderr and as the result's last
key.  Without a TPU, or with fewer chips than the cell asks for, the run
exits with code 3 and prints no result.

A driver's record holds ``e2e`` (the end-to-end values), ``attempted``,
``failed``, ``numbers`` (compared with ``limits/<workload>.json``),
``memory_peak_bytes``, ``trace`` (``lib.trace.summarize`` of the traced
window, or None), ``chips``, ``peaks``, ``window_s``, ``steps`` and the
kind's own counts; the per-layer readers take what they need from it.
The driver appends a (phase, time) mark to ``marks`` as each phase of its
set-up ends, the last where the window starts; the run prints on stderr
the seconds of each phase and what set-up compiled or loaded from the
persistent cache.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402

from benchmarks.chip.lib import device, judge, spec as specmod  # noqa: E402

TRACE_DIR = specmod.ROOT / ".bench_out" / "trace"


def _program_on_path() -> None:
    src = str(specmod.ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def _use_compile_cache() -> None:
    """The program's persistent cache, in the checkout; every program,
    however quick to compile, is kept, so every run after the first loads
    all it runs from there."""
    import jax
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class _CompileClock:
    """Host-clock times of the XLA compilations while it is open (each
    one a compile or a load from the persistent cache) with their
    seconds, and of the persistent cache's hits and misses."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE = {"/jax/compilation_cache/cache_hits": "cache_hits",
             "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.times: list[tuple[float, float]] = []
        self.cache: list[tuple[str, float]] = []
        self._monitoring = jax.monitoring
        self._monitoring.register_event_duration_secs_listener(self._on)
        self._monitoring.register_event_listener(self._count)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.times.append((time.perf_counter(), duration))

    def _count(self, event: str, **_) -> None:
        if event in self.CACHE:
            self.cache.append((self.CACHE[event], time.perf_counter()))

    def before(self, t: float) -> dict:
        """What was compiled or loaded before ``t``."""
        done = [d for s, d in self.times if s < t]
        out = {"programs": len(done), "compile_or_load_s": sum(done)}
        for name in self.CACHE.values():
            out[name] = sum(n == name and s < t for n, s in self.cache)
        return out

    def close(self) -> None:
        self._monitoring.unregister_event_duration_listener(self._on)
        self._monitoring.unregister_event_listener(self._count)


def _phases(marks: list[tuple[str, float]]) -> dict:
    """Seconds of each set-up phase, from consecutive (name, time) marks."""
    return {name: t - prev for (_, prev), (name, t) in zip(marks, marks[1:])}


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             require_tpu: bool = True, spec: dict | None = None,
             t0: float | None = None) -> tuple[dict, dict]:
    """One run of ``workload``: its result line, and diagnostics of the
    window (step times, compilations inside it).  ``spec`` (as
    ``lib.spec.load_cell`` returns it) and ``require_tpu`` let a test run
    a cut-down cell on the CPU; the command line always loads the cell and
    needs the chips."""
    marks = [("process", T0 if t0 is None else t0)]
    spec = spec or specmod.load_cell(workload)
    devices = device.chips(spec["cell"]["chips"], require_tpu=require_tpu)
    marks.append(("jax_and_chips", time.perf_counter()))
    _program_on_path()
    if require_tpu:
        _use_compile_cache()
    dev = device.describe(devices)
    peaks = device.PEAKS.get(dev["kind"])
    driver = importlib.import_module(
        f"benchmarks.chip.drivers.{spec['traffic']['kind']}")
    compiles = _CompileClock()
    marks.append(("harness", time.perf_counter()))
    try:
        record = driver.run(spec, devices, seed=seed, seconds=seconds,
                            trace_dir=TRACE_DIR if trace else None,
                            marks=marks, peaks=peaks)
    finally:
        compiles.close()
    t_win = record["window_t0"]
    record["diag"]["compiles_in_window"] = sum(
        t_win <= t <= t_win + record["window_s"] for t, _ in compiles.times)
    record["diag"]["setup"] = _phases(marks)
    record["diag"]["setup_programs"] = compiles.before(t_win)
    correct, checks = judge.verdict(record["numbers"], spec["limits"])
    for c in checks.values():
        if not math.isfinite(c["value"]):
            c["value"] = None
    metrics = {}
    if trace:
        for m in spec["per_layer"]:
            value = specmod.layer_reader(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": record["e2e"][m["name"]],
                                  "unit": m["unit"]}
    dev["memory_peak_bytes"] = record["memory_peak_bytes"]
    result = {"correct": correct, "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": dev}
    if trace:
        t = record["trace"]
        dev.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = {"device_ops": t["device_ops"],
                               "idle_gaps": t["idle_gaps"]}
    result["checks"] = checks
    return result, record["diag"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, diag = run_cell(args.workload, seed=args.seed,
                                seconds=args.seconds, trace=bool(args.trace))
    except device.NoChip as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 3
    print(f"window {json.dumps(diag)}", file=sys.stderr)
    for name, c in result["checks"].items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if ok else 'FAIL'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
