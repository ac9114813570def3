"""Chip benchmark of the AtacWorks training and stream-serving paths.

Run one cell from the root of a checkout:

    python3 -m benchmarks.chip.run --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

``BENCHMARK.json`` at the checkout root lists the cells.  Everything that
belongs to one configuration, traffic mix or per-layer metric lives in a
file of its own under this directory and is found by its name:
``configs/<config>.json``, ``traffic/<mix>.json``,
``layer_metrics/<metric>.py``, ``limits/<workload>.json`` and
``references/<reference>.py``.
"""
