"""One driver per traffic ``kind``, the module ``drivers/<kind>.py``
(``train``, ``stream``).  A kind of traffic enters the benchmark with a
driver of its own and touches no other file.  A driver defines:

* ``run(spec, devices, *, seed, seconds, trace_dir, marks, peaks)``:
  builds the system under test for a cell, warms it up, runs the measured
  window and returns the run's record (see ``benchmarks.chip.run``);
* ``calibrate(spec, devices, seeds, control_seeds, seconds, log)``: the
  readings the cell's limits are set from (see
  ``benchmarks.chip.calibrate``), each passed to
  ``log(kind, seed, numbers, t0, **extra)``;
* ``tiny(spec)``: the cell cut down in place for a run on the CPU, for the
  tests."""
