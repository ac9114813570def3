"""One driver per traffic ``kind``: ``train`` and ``stream``.  A driver
builds the system under test for a cell, warms it up, runs the measured
window, and returns the run's record (see ``benchmarks.chip.run``)."""
