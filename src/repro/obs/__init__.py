"""repro.obs — stack-wide telemetry: event bus, scoreboard, trace export.

A zero-dependency, process-local telemetry layer (DESIGN.md §14).  Off by
default; every hook's disabled path is a single ``is None`` check (no
allocation, no I/O — sub-microsecond, and **never** part of a jaxpr, so
toggling telemetry cannot retrace anything), except that counters keep
their totals (``counters()``) with or without a sink.  Enable with
``REPRO_TELEMETRY=1`` (sink path from ``REPRO_TELEMETRY_PATH``) or
explicitly:

    >>> import tempfile
    >>> from repro import obs
    >>> path = obs.enable(tempfile.mkstemp(suffix=".jsonl")[1])
    >>> with obs.span("demo.outer", note="hi"):
    ...     with obs.span("demo.inner"):
    ...         pass
    >>> obs.counter("demo.count", 2)
    >>> obs.counters()["demo.count"]
    2
    >>> obs.disable()
    >>> [r["name"] for r in obs.read_events(path)]  # spans emit at exit
    ['provenance', 'demo.inner', 'demo.outer', 'demo.count']
    >>> obs.read_events(path)[2]["attrs"]["note"]   # doctest: +ELLIPSIS
    'hi'

What gets instrumented where:
  * ``kernels/ops.py``     — a config event per conv1d pass, eager or
    traced (``conv1d.<pass>.trace``: backend, tiles, pipelining).
  * ``kernels/conv1d_brgemm.py`` — a ``kernels.build`` span per kernel
    build (each ``pallas_call``, at trace time under jit), and the
    counters ``kernels.build``, ``kernels.build_s`` (seconds in the call)
    and ``kernels.build_distinct`` (distinct kernel signatures).
  * ``repro.tune``         — cache hit/miss/legacy-upgrade counters and
    per-candidate search traces (predicted vs measured seconds).
  * ``launch/train.py``    — per-step spans (data / step), a measured
    phase breakdown (forward / backward / optimizer / psum), per-shard
    step-time gauges, health + straggler rollups.
  * ``train/serve_step.py``— request-level latency spans.

Device time is not measured here: the layer scopes of ``core/blocks.py``
(``layer.*``) and the step's ``step.*`` scopes name the device ops in a
profiler trace, and ``obs`` spans appear on that trace's host timeline.

Consumers: ``scripts/obs_report.py`` (scoreboard: p50/p99 per span, conv
configs per cell, tuner hit rate, cost-model error) and
``python -m repro.obs.trace_export`` (Chrome/Perfetto trace).  See
docs/observability.md.
"""
from __future__ import annotations

from .bus import (DEFAULT_PATH, ENV_TELEMETRY, ENV_TELEMETRY_PATH, Span,
                  counter, counters, disable, distinct, enable, enabled,
                  event, gauge, log_path, reset_counters, span, span_event,
                  _env_enable)
from .provenance import provenance
from .schema import read_events, validate

_env_enable()

__all__ = [
    "DEFAULT_PATH", "ENV_TELEMETRY", "ENV_TELEMETRY_PATH", "Span",
    "counter", "counters", "disable", "distinct", "enable", "enabled",
    "event", "gauge", "log_path", "provenance", "read_events",
    "reset_counters", "span", "span_event", "validate",
]
