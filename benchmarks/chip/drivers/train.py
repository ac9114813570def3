"""Training cells: the launcher's jitted AdamW train step at a fixed batch.

Set-up builds the step as ``repro.launch.train`` builds it (the
``shard_map`` data-parallel step over a (data, model) mesh when the cell
holds more than one chip), seeded weights and a pool of seeded coverage
batches on the device, compiles the step ahead of time for the pool's
shapes (a load from the persistent cache after a cell's first run), and
drives that compiled step through its first three steps on three
distinct batches, which feed the correctness check.  The window then runs the same step on the same state over the
pool, one step after another, each ended by reading its loss as the
launcher does, until ``seconds`` have passed.  The rate counts every
segment of every step in the window over the window's whole time.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.chip.lib import judge, traffic as gen, work
from benchmarks.chip.lib import spec as specmod

CHECK_STEPS = 3


def program_config(config: dict):
    """The program's registered configuration, as the file states it."""
    from repro import configs

    return dataclasses.replace(
        configs.get(config["arch"]), dtype=config["dtype"],
        n_layers=config["n_layers"], conv_channels=config["conv_channels"],
        conv_filter=config["conv_filter"],
        conv_dilation=config["conv_dilation"])


class TrainCell:
    """The compiled step, weights and data of one training cell."""

    def __init__(self, spec: dict, devices):
        from repro.models import sharding as shd
        from repro.train import train_step as ts

        self.config, self.traffic = spec["config"], spec["traffic"]
        self.ref = specmod.reference(self.config)
        self.cfg = program_config(self.config)
        self.devices = list(devices)
        self.dp = len(self.devices)
        t = self.traffic
        self.batch, self.width = t["batch"], t["width"]
        self.hyper = {"peak_lr": t["peak_lr"],
                      "warmup_steps": t["warmup_steps"],
                      "total_steps": t["total_steps"]}
        self.mesh = Mesh(np.array(self.devices).reshape(self.dp, 1),
                         ("data", "model"))
        self.replicated = NamedSharding(self.mesh, P())
        self.batch_sharding = NamedSharding(self.mesh,
                                            shd.batch_pspec(self.mesh))
        self.step = jax.jit(ts.make_train_step(
            self.cfg, accum_steps=1, mesh=self.mesh if self.dp > 1 else None,
            **self.hyper), donate_argnums=(0,))
        self._init = jax.jit(
            lambda k: ts.init_state(self.ref.init_params(k, self.config)),
            out_shardings=self.replicated)
        self.exe = None

    def start(self, seed: int):
        """Weights, optimizer state and the batch pool of ``seed``;
        returns (state, pool, host copy of the initial weights)."""
        k_params, k_data = gen.keys(seed, 2)
        state = self._init(jax.random.key(k_params))
        params0 = jax.device_get(state.params)
        pool = gen.coverage_pool(k_data, self.traffic, batch=self.batch,
                                 width=self.width,
                                 sharding=self.batch_sharding)
        return state, pool, params0

    def compile(self, state, pool, marks: list | None = None):
        """The timed step, compiled for the pool's shapes; every step of
        the check and the window runs this one executable.  ``marks``
        gets the end of tracing and lowering, and of the compile (or the
        load from the persistent cache)."""
        if self.exe is None:
            lowered = self.step.lower(state, pool[0])
            if marks is not None:
                marks.append(("step_trace_and_lower", time.perf_counter()))
            self.exe = lowered.compile()
            if marks is not None:
                marks.append(("step_compile_or_load", time.perf_counter()))
        return self.exe

    def temp_bytes(self) -> int:
        """The compiled step's temporary space on one chip."""
        stats = self.exe.memory_analysis()
        return 0 if stats is None else int(stats.temp_size_in_bytes)

    def first_steps(self, state, pool):
        """The first CHECK_STEPS steps, on pool batches 0, 1, 2: returns
        the state and the program's readings for the check."""
        exe = self.compile(state, pool)
        losses, first = [], None
        for k in range(CHECK_STEPS):
            state, metrics = exe(state, pool[k])
            losses.append(float(metrics["loss"]))
            if k == 0:
                # the optimizer's first moment after one step is
                # (1 - b1) times the gradient it took
                first = jax.tree.map(
                    lambda m: np.asarray(m, np.float32)
                    / np.float32(1 - self.ref.B1),
                    jax.device_get(state.opt.m))
        after = jax.tree.map(lambda p: np.asarray(p, np.float32),
                             jax.device_get(state.params))
        return state, {"losses": losses, "first_grad": first,
                       "params_after": after}

    def window(self, state, pool, seconds: float):
        """Steps until ``seconds`` have passed: returns the state and
        the host-clock end of each step, from the window's start."""
        ends, bad = [], 0
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while not ends or ends[-1] < seconds:
                with jax.profiler.TraceAnnotation("bench.train.step"):
                    state, metrics = self.exe(
                        state, pool[(CHECK_STEPS + len(ends)) % len(pool)])
                    loss = float(metrics["loss"])
                bad += not np.isfinite(loss)
                ends.append(time.perf_counter() - t0)
        return state, {"t0": t0, "ends": ends, "bad": bad}

    def reference_readings(self, params0, pool, *, low=None, fault=None,
                           dp=None):
        """The reference over the first steps' batches; ``low`` and
        ``fault`` make it a control or a planted fault (calibration)."""
        batches = [jax.device_get(pool[k]) for k in range(CHECK_STEPS)]
        ref = self.ref.Reference(self.config, self.devices, low=low)
        return ref.train(params0, batches, self.hyper, steps=CHECK_STEPS,
                         fault=fault, dp=dp or self.dp)

    def work_per_step(self) -> dict:
        return work.step_work(
            self.ref.layer_shapes(self.config), N=self.batch, Q=self.width,
            S=self.config["conv_filter"],
            dilation=self.config["conv_dilation"],
            bytes_per_elem=jnp.dtype(self.config["dtype"]).itemsize)


def run(spec: dict, devices, *, seed: int, seconds: float, trace_dir,
        marks: list, peaks) -> dict:
    from benchmarks.chip.lib import device, trace as tr

    cell = TrainCell(spec, devices)
    marks.append(("build", time.perf_counter()))
    state, pool, params0 = cell.start(seed)
    jax.block_until_ready(pool)
    marks.append(("weights_and_pool", time.perf_counter()))
    cell.compile(state, pool, marks)
    state, prog = cell.first_steps(state, pool)
    marks.append(("check_steps", time.perf_counter()))
    setup_s = marks[-1][1] - marks[0][1]
    if trace_dir is None:
        state, w = cell.window(state, pool, seconds)
        summary = None
    else:
        with tr.capture(trace_dir):
            state, w = cell.window(state, pool, seconds)
        summary = tr.summarize(tr.load(trace_dir))
    steps, elapsed = len(w["ends"]), w["ends"][-1]
    step_s = np.diff([0.0] + w["ends"])
    memory = device.memory_peak_bytes(devices, cell.temp_bytes())
    del state
    numbers = judge.train_numbers(prog, cell.reference_readings(params0, pool),
                                  params0)
    segments = steps * cell.batch
    work_step = cell.work_per_step()
    return {
        "e2e": {"train_segments_per_s": segments / elapsed,
                "setup_s": setup_s},
        "attempted": steps, "failed": w["bad"], "numbers": numbers,
        "memory_peak_bytes": memory, "trace": summary,
        "chips": len(devices), "peaks": peaks, "window_s": elapsed,
        "steps": steps, "segments": segments, "work_per_step": work_step,
        "flops_per_segment": work.total_flops(work_step) / cell.batch,
        "window_t0": w["t0"],
        "diag": {"step_s_min": float(step_s.min()),
                 "step_s_median": float(np.median(step_s)),
                 "step_s_max": float(step_s.max())},
    }
