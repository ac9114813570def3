"""Profiler capture, and the reduction of a trace to per-layer numbers.

A trace is reduced from a flat list of ``Event``s, so the reduction can be
checked on a small recorded trace without a chip:

* device events: the ops of each chip's "XLA Ops" line;
* host events: every host thread's events, among them the harness's own
  spans (``bench.*``, written with ``jax.profiler.TraceAnnotation``).

The traced window is the host span ``bench.window``; device time outside
it is not counted.  Busy time is the union of a chip's op intervals;
kernel time sums the ops whose names carry a stem; exposed collective time
is the part of a chip's collective ops during which no other op runs; an
idle gap is named by the innermost harness span and the innermost other
host event around its midpoint.
"""
from __future__ import annotations

import bisect
import contextlib
import re
import shutil
from pathlib import Path
from typing import NamedTuple

WINDOW_SPAN = "bench.window"
HARNESS_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")
CONV_STEMS = ("conv1d_fwd", "conv1d_bwd_data", "conv1d_bwd_weight")
TOP = 10
_SUFFIX = re.compile(r"(\.\d+)+$")


class Event(NamedTuple):
    kind: str        # "device" | "host"
    where: str       # device id, or host thread line name
    name: str
    start_ns: float
    end_ns: float


@contextlib.contextmanager
def capture(log_dir: Path):
    """Profile the body into ``log_dir`` (emptied first).  Python function
    tracing is off: only the runtime's events and the harness's spans."""
    import jax

    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(log_dir: Path) -> list[Event]:
    """Read the newest ``.xplane.pb`` under ``log_dir`` into events."""
    from jax.profiler import ProfileData

    paths = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no profile written under {log_dir}")
    data = ProfileData.from_file(str(paths[-1]))
    events = []
    for plane in data.planes:
        dev = DEVICE_PLANE.fullmatch(plane.name)
        if dev is None and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if dev is not None and line.name != OPS_LINE:
                continue
            kind, where = (("device", dev.group(1)) if dev is not None
                           else ("host", line.name))
            events.extend(Event(kind, where, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns)
                          for ev in line.events)
    return events


def stem(name: str) -> str:
    """An op's name without its numeric suffix; conv kernels by pass.  A
    TPU op's event name is its whole HLO instruction, ``%pad.662 = f32[..]
    pad(...)``: the name before `` = `` is kept."""
    if name.startswith("%"):
        name = name[1:].split(" = ", 1)[0]
    for s in CONV_STEMS:
        if s in name:
            return s
    return _SUFFIX.sub("", name)


def is_collective(name: str) -> bool:
    return any(w in name for w in COLLECTIVE_WORDS)


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _window(events) -> tuple[float, float]:
    spans = [e for e in events if e.kind == "host" and e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    return min(e.start_ns for e in spans), max(e.end_ns for e in spans)


class _Innermost:
    """Innermost (shortest) host event containing a point in time."""

    LOOK_BACK = 512

    def __init__(self, events):
        self.events = sorted(events, key=lambda e: e.start_ns)
        self.starts = [e.start_ns for e in self.events]
        self.longest = [e for e in self.events
                        if e.end_ns - e.start_ns > 1e9]

    def at(self, t: float) -> str | None:
        hi = bisect.bisect_right(self.starts, t)
        best = None
        for e in (*self.events[max(0, hi - self.LOOK_BACK):hi],
                  *self.longest):
            if e.start_ns <= t <= e.end_ns and (
                    best is None
                    or e.end_ns - e.start_ns < best.end_ns - best.start_ns):
                best = e
        return None if best is None else stem(best.name)


def summarize(events: list[Event]) -> dict:
    """Reduce a trace to the numbers the per-layer readers take.

    Times are seconds.  ``busy_s``, ``collective_s`` (the union of a
    chip's collective ops) and ``collective_exposed_s`` are means over the
    chips in the trace; ``kernel_s`` sums every chip's events per conv
    pass; ``device_ops`` and ``idle_gaps`` are the ten largest totals
    by op stem and by what the host was doing."""
    w0, w1 = _window(events)
    per_dev: dict[str, list[tuple[float, float, str]]] = {}
    for e in events:
        if e.kind != "device" or e.end_ns <= w0 or e.start_ns >= w1:
            continue
        per_dev.setdefault(e.where, []).append(
            (max(e.start_ns, w0), min(e.end_ns, w1), e.name))
    if not per_dev:
        raise ValueError("no device op ran inside the traced window")
    busy, collective, exposed = [], [], []
    kernel = {s: 0.0 for s in CONV_STEMS}
    ops: dict[str, float] = {}
    for ivs in per_dev.values():
        all_u = union((s, e) for s, e, _ in ivs)
        coll = union((s, e) for s, e, n in ivs if is_collective(n))
        other = union((s, e) for s, e, n in ivs if not is_collective(n))
        busy.append(length(all_u))
        collective.append(length(coll))
        exposed.append(length(coll) - overlap(coll, other))
        for s, e, n in ivs:
            k = stem(n)
            ops[k] = ops.get(k, 0.0) + (e - s)
            if k in kernel:
                kernel[k] += e - s
    n_dev = len(per_dev)

    first = per_dev[min(per_dev, key=int)]
    gaps, prev = [], w0
    for s, e in union((s, e) for s, e, _ in first):
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if w1 > prev:
        gaps.append((prev, w1))
    host = [e for e in events if e.kind == "host" and e.name != WINDOW_SPAN]
    spans = _Innermost(e for e in host if e.name.startswith(HARNESS_PREFIX))
    runtime = _Innermost(e for e in host
                         if not e.name.startswith(HARNESS_PREFIX))
    named: dict[str, float] = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        name = f"{spans.at(mid) or '-'} / {runtime.at(mid) or '-'}"
        named[name] = named.get(name, 0.0) + (e - s)

    def top(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {
        "devices": n_dev,
        "window_s": (w1 - w0) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "kernel_s": {k: v / 1e9 for k, v in kernel.items()},
        "collective_s": sum(collective) / n_dev / 1e9,
        "collective_exposed_s": sum(exposed) / n_dev / 1e9,
        "device_ops": top(ops),
        "idle_gaps": top(named),
    }
