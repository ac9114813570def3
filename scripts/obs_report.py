#!/usr/bin/env python
"""CLI for the telemetry scoreboard — a thin wrapper over repro.obs.report.

    PYTHONPATH=src python scripts/obs_report.py telemetry.jsonl
    PYTHONPATH=src python scripts/obs_report.py telemetry.jsonl --json
    PYTHONPATH=src python scripts/obs_report.py telemetry.jsonl --check

``--check`` is the CI smoke gate: nonzero exit unless the log contains
conv1d pass config records, a train-step phase breakdown, and tuner cache
counters.  See docs/observability.md.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.obs.report import main  # noqa: E402

if __name__ == "__main__":
    raise SystemExit(main())
