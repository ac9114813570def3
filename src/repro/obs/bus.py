"""Process-local telemetry event bus with a JSONL sink.

One ``_Bus`` per process (module-level singleton), **off by default**: every
public hook checks ``_BUS is not None`` first, so a disabled hook is a
handful of bytecode ops (no dict building, no I/O, no jax interaction —
tested to stay under a microsecond in tests/test_obs.py).  Counters are
the exception: their in-memory totals are kept whether or not a sink is
open (each thread adds into a dict of its own: no lock, still under a
microsecond), so a caller that never opens a sink — the chip benchmark —
can read them; a record is written only while a sink is open.  Every
counter in the program fires at trace time, never inside a compiled step.  Nothing here changes a jaxpr:
trace-time hooks inside jitted code must never change what is compiled,
and enabling telemetry must never retrace.

Primitives (all but the counter totals no-ops while disabled):

  * ``span(name, **attrs)``        — context manager timing a region with a
                                     monotonic clock (``perf_counter``);
                                     spans nest via a thread-local stack and
                                     each record carries its parent id.
                                     While a sink is open the span also
                                     opens a ``jax.profiler.TraceAnnotation``
                                     of the same name, so a profile taken
                                     then shows it on the host timeline,
                                     beside the device ops.
  * ``span_event(name, dur, ...)`` — a span whose duration was measured by
                                     the caller (derived phases); it is
                                     written after the fact, so it has no
                                     profiler annotation.
  * ``counter(name, value)``       — monotonic increment; ``counters()``
                                     gives the running totals, and each
                                     increment is logged while a sink is
                                     open.
  * ``distinct(name, key)``        — a counter that counts each ``key`` the
                                     first time it is seen.
  * ``gauge(name, value)``         — point-in-time sample.
  * ``event(name)``                — zero-duration marker.

Every record is one JSON object per line (see ``repro.obs.schema`` for the
strict field contract); the first record of a log is the provenance block
(git sha, jax version, device kind, process index) shared with the
BENCH_*.json artifacts via ``benchmarks/common.py``.
"""
from __future__ import annotations

import json
import os
import threading
import time
from itertools import count
from typing import Any

ENV_TELEMETRY = "REPRO_TELEMETRY"
ENV_TELEMETRY_PATH = "REPRO_TELEMETRY_PATH"
DEFAULT_PATH = "repro_telemetry.jsonl"

_BUS: "_Bus | None" = None
# Counter totals and the keys ``distinct`` has seen, kept with or without
# a sink; ``reset_counters`` clears both.  Each thread adds into a totals
# dict of its own, so an increment takes no lock; ``counters`` sums them.
_LOCAL = threading.local()
_THREAD_TOTALS: list[dict[str, float]] = []
_SEEN: dict[str, set] = {}
_COUNT_LOCK = threading.Lock()


class _Bus:
    def __init__(self, path: str):
        self.path = path
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._f = open(path, "a")
        self._lock = threading.Lock()
        self._ids = count(1)
        self._local = threading.local()
        self.epoch = time.perf_counter()
        self.wall_epoch = time.time()
        from .provenance import provenance
        self.pid = int(provenance().get("process_index", 0))
        self.emit({"kind": "meta", "name": "provenance", "ts": 0.0,
                   "attrs": dict(provenance(), wall_epoch=self.wall_epoch)})

    # -- plumbing -----------------------------------------------------------

    def now(self) -> float:
        return time.perf_counter() - self.epoch

    def stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def emit(self, rec: dict[str, Any]) -> None:
        rec.setdefault("pid", getattr(self, "pid", 0))
        line = json.dumps(rec, default=str)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()

    def close(self) -> None:
        with self._lock:
            self._f.close()


class Span:
    """One timed region.  Emitted at ``__exit__``; ``attrs`` may be mutated
    inside the ``with`` block.  ``dur`` is readable after the block."""

    __slots__ = ("name", "attrs", "id", "parent", "_t0", "ts", "dur",
                 "_annotation")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs
        self.dur = None
        self._annotation = None

    def __enter__(self) -> "Span":
        bus = _BUS
        if bus is None:  # disabled between construction and entry
            self.id = self.parent = None
            self._t0 = time.perf_counter()
            return self
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        st = bus.stack()
        self.id = next(bus._ids)
        self.parent = st[-1] if st else None
        st.append(self.id)
        self.ts = bus.now()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.dur = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        bus = _BUS
        if bus is None or self.id is None:
            return
        st = bus.stack()
        if st and st[-1] == self.id:
            st.pop()
        bus.emit({"kind": "span", "name": self.name, "ts": self.ts,
                  "dur": self.dur, "id": self.id, "parent": self.parent,
                  "attrs": self.attrs})


class _NoopSpan:
    """Shared inert span for the disabled path: no allocation per call."""

    __slots__ = ()
    dur = None
    attrs: dict = {}

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NOOP_SPAN = _NoopSpan()


# ---------------------------------------------------------------------------
# Public hooks — every one starts with the `_BUS is None` fast path
# ---------------------------------------------------------------------------


def enabled() -> bool:
    """True when a telemetry sink is open (``enable`` / REPRO_TELEMETRY=1)."""
    return _BUS is not None


def enable(path: str | None = None) -> str:
    """Open a JSONL telemetry sink (appending) and turn every hook live.
    Re-enabling with a different path closes the previous sink first.
    Returns the resolved path."""
    global _BUS
    path = path or os.environ.get(ENV_TELEMETRY_PATH) or DEFAULT_PATH
    if _BUS is not None:
        if os.path.abspath(_BUS.path) == os.path.abspath(path):
            return _BUS.path
        disable()
    _BUS = _Bus(path)
    return path


def disable() -> None:
    """Close the sink; every hook reverts to its no-op fast path."""
    global _BUS
    if _BUS is not None:
        _BUS.close()
        _BUS = None


def log_path() -> str | None:
    return _BUS.path if _BUS is not None else None


def span(name: str, **attrs):
    """Context manager timing a region; nests via a thread-local stack.
    While a sink is open it is also a ``jax.profiler.TraceAnnotation``."""
    if _BUS is None:
        return _NOOP_SPAN
    return Span(name, attrs)


def span_event(name: str, dur: float, **attrs) -> None:
    """A span whose duration the caller measured (monotonic clock); parented
    under the current open span, stamped as ending now."""
    bus = _BUS
    if bus is None:
        return
    st = bus.stack()
    bus.emit({"kind": "span", "name": name, "ts": max(0.0, bus.now() - dur),
              "dur": float(dur), "id": next(bus._ids),
              "parent": st[-1] if st else None, "attrs": attrs})


def counter(name: str, value: float = 1, **attrs) -> None:
    """Add ``value`` to the total of ``name``; logged while a sink is
    open, totalled always."""
    try:
        mine = _LOCAL.totals
    except AttributeError:
        mine = _own_totals()
    mine[name] = mine.get(name, 0) + value
    bus = _BUS
    if bus is None:
        return
    bus.emit({"kind": "counter", "name": name, "ts": bus.now(),
              "value": value, "total": counters()[name], "attrs": attrs})


def _own_totals() -> dict[str, float]:
    """This thread's counter totals, made and registered on first use."""
    mine = _LOCAL.totals = {}
    with _COUNT_LOCK:
        _THREAD_TOTALS.append(mine)
    return mine


def distinct(name: str, key) -> None:
    """Count ``key`` (hashable) under counter ``name`` the first time it
    is seen."""
    seen = _SEEN.get(name)
    if seen is not None and key in seen:  # seen keys stay seen: no lock
        return
    with _COUNT_LOCK:
        seen = _SEEN.setdefault(name, set())
        new = key not in seen
        seen.add(key)
    if new:
        counter(name)


def gauge(name: str, value: float, **attrs) -> None:
    bus = _BUS
    if bus is None:
        return
    bus.emit({"kind": "gauge", "name": name, "ts": bus.now(),
              "value": float(value), "attrs": attrs})


def event(name: str, **attrs) -> None:
    bus = _BUS
    if bus is None:
        return
    bus.emit({"kind": "event", "name": name, "ts": bus.now(), "attrs": attrs})


def counters() -> dict[str, float]:
    """Snapshot of the in-process counter totals, sink or no sink: every
    thread's totals summed."""
    out: dict[str, float] = {}
    with _COUNT_LOCK:
        for totals in _THREAD_TOTALS:
            for name, value in dict(totals).items():  # an atomic copy
                out[name] = out.get(name, 0) + value
    return out


def reset_counters() -> None:
    """Zero every counter total and forget every ``distinct`` key.  An
    increment another thread has in flight may survive the reset."""
    with _COUNT_LOCK:
        for totals in _THREAD_TOTALS:
            totals.clear()
        _SEEN.clear()


def _env_enable() -> None:
    """Honor REPRO_TELEMETRY=1 at import time (how a launcher run under the
    env var starts logging without code changes)."""
    if os.environ.get(ENV_TELEMETRY) == "1" and _BUS is None:
        enable()
