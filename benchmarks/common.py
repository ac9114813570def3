"""Shared benchmark utilities: wall-clock timing of jitted callables on the
host devices (1 CPU here), with compile excluded and block_until_ready.

The timing harness itself lives in ``repro.tune.measure`` so tuner
measurements and benchmark measurements stay comparable by construction.
"""
from __future__ import annotations


def time_fn(fn, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median seconds per call of an already-jitted fn."""
    from repro.tune.measure import median_time
    return median_time(fn, *args, iters=iters, warmup=warmup)


def conv1d_flops(N: int, C: int, K: int, S: int, Q: int) -> float:
    """MACs×2 of one forward conv1d (paper's efficiency denominator)."""
    from repro.roofline.flops import conv1d_flops as _f
    return _f(N, C, K, S, Q)


def efficiency(flops: float, sec: float) -> float:
    """Paper-style efficiency: achieved FLOP/s ÷ roofline peak of the
    device the benchmark ran on (repro.roofline)."""
    from repro.roofline.analysis import achieved_fraction_of_peak
    return achieved_fraction_of_peak(flops, sec)


def bench_entry(sec: float, *, flops: float | None = None,
                source: str = "", **extra) -> dict:
    """One benchmark row in the shared artifact schema: ``ms`` always;
    ``gflops``/``efficiency`` derived from ``flops`` when the row has a
    FLOP count (paper-style efficiency against the device's roofline
    peak); anything else rides along verbatim."""
    row = {"ms": sec * 1e3, "source": source, **extra}
    if flops is not None:
        row["gflops"] = flops / sec / 1e9
        row["efficiency"] = efficiency(flops, sec)
    return row


def write_bench_json(path: str, entries: dict) -> None:
    """Persist one benchmark's rows as a stable machine-readable artifact
    ``{"provenance": {...}, "entries": {problem key -> bench_entry row}}``,
    so the perf trajectory is tracked across PRs — CI uploads these from
    the smoke runs.  The provenance block (git sha, jax version, device
    kind, process index) is the same one stamped on telemetry logs
    (``repro.obs.provenance``): a bench number and a telemetry trace from
    one run are cross-attributable.  Writes are atomic (tmp + rename)."""
    import json
    import os
    import tempfile

    from repro.obs.provenance import provenance

    doc = {"provenance": provenance(), "entries": entries}
    d = os.path.dirname(os.path.abspath(path))
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".bench.tmp")
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    print(f"# wrote {len(entries)} entries -> {path}")
