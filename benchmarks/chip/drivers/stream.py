"""Stream-serving cells: ``repro.launch.serve.ConvStreamServer``.

Streams are coverage tracks with a fixed-length history that the server
prefills from.  The mix sets the slots, the chunk, the history, the
lognormal stream lengths and the arrivals: a closed loop keeps
``backlog`` streams queued (one is queued for each one admitted), an open
loop queues each stream when its arrival time comes.  Set-up makes the
weights, builds the server and runs ``warmup_steps`` steps, which compile
the chunk step, the prefill and the slot updates.  The window times every
``server.step()`` call from outside, admission and prefill included, until
``seconds`` have passed.

Correctness: a sample drawn from the seed of the streams that finished in
the window, the longest among them, is compared column by column with the
reference's one-shot causal forward over [history | track].
"""
from __future__ import annotations

import itertools
import time

import jax
import numpy as np

from benchmarks.chip.lib import faults, judge, traffic as gen
from benchmarks.chip.lib import spec as specmod
from benchmarks.chip.drivers.train import program_config


class StreamCell:
    """The server, weights and request source of one stream cell."""

    def __init__(self, spec: dict, devices, seed: int):
        from repro.launch import serve

        self.config, self.traffic = spec["config"], spec["traffic"]
        self.ref = specmod.reference(self.config)
        self.devices = list(devices)
        self.seed = seed
        t = self.traffic
        k_params, k_order = gen.keys(seed, 2)
        params = jax.jit(lambda k: self.ref.init_params(k, self.config))(
            jax.random.key(k_params))
        self.params = jax.device_get(params)
        self._request = serve.StreamRequest
        self.server = serve.ConvStreamServer(
            params, program_config(self.config), batch=t["slots"],
            chunk=t["chunk"], prompt_len=t["history"])
        rng = np.random.default_rng(k_order)
        self.lengths = itertools.cycle(gen.stream_lengths(t, rng))
        gaps = gen.arrival_gaps(t, rng, t["lengths"]["n"])
        self.gaps = None if gaps is None else itertools.cycle(gaps)
        self.requests: list = []
        self.next_due = 0.0
        self.t_start = time.perf_counter()

    def _submit(self):
        t = self.traffic
        hist, track = gen.stream_track(self.seed, len(self.requests),
                                       t["history"], next(self.lengths),
                                       t["coverage_rate"])
        req = self._request(len(self.requests), track, history=hist)
        self.requests.append(req)
        self.server.submit(req)

    def _feed(self):
        if self.gaps is None:
            while len(self.server.queue) < self.traffic["arrivals"]["backlog"]:
                self._submit()
            return
        now = time.perf_counter() - self.t_start
        while self.next_due <= now:
            self._submit()
            self.next_due += next(self.gaps)

    def step(self):
        """One server step: (seconds, streams admitted), or None when an
        open loop has nothing to serve yet and waits for the next
        arrival."""
        self._feed()
        if not self.server.queue and not any(self.server.slots):
            time.sleep(max(0.0, self.next_due
                           - (time.perf_counter() - self.t_start)))
            return None
        queued = len(self.server.queue)
        with jax.profiler.TraceAnnotation("bench.stream.step"):
            t0 = time.perf_counter()
            self.server.step()
            dt = time.perf_counter() - t0
        return dt, queued - len(self.server.queue)

    def warm_up(self):
        for _ in range(self.traffic["warmup_steps"]):
            self.step()

    def window(self, seconds: float) -> dict:
        done_before = {r.id for r in self.requests if r.done}
        pos_before = {r.id: r.pos for r in self.requests}
        times, admitted = [], []
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                out = self.step()
                if out is not None:
                    times.append(out[0])
                    admitted.append(out[1] > 0)
            elapsed = time.perf_counter() - t0
        columns = sum(r.pos - pos_before.get(r.id, 0) for r in self.requests)
        finished = [r for r in self.requests
                    if r.done and r.id not in done_before]
        return {"step_s": times, "admitted": admitted, "window_s": elapsed,
                "columns": columns, "finished": finished, "t0": t0}

    def check_sample(self, finished: list) -> list:
        """The streams to compare: the longest and a seeded sample."""
        if not finished:
            return []
        n = min(self.traffic["check_streams"], len(finished))
        longest = max(range(len(finished)),
                      key=lambda i: len(finished[i].track))
        rng = np.random.default_rng([self.seed % 2**63, 1])
        rest = [i for i in range(len(finished)) if i != longest]
        pick = [longest] + list(rng.choice(rest, n - 1, replace=False))
        return [finished[i] for i in pick]

    def reference_outputs(self, sample: list, *, low=None) -> list:
        """Reference (signal, peak) of each stream's track columns."""
        t = self.traffic
        width = t["history"] + t["lengths"]["max"]
        rows = np.zeros((len(sample), width), np.float32)
        for i, r in enumerate(sample):
            rows[i, :t["history"]] = r.history
            rows[i, t["history"]:t["history"] + len(r.track)] = r.track
        ref = self.ref.Reference(self.config, self.devices, low=low)
        sig, peak = ref.causal(self.params, rows)
        h = t["history"]
        return [(sig[i, h:h + len(r.track)], peak[i, h:h + len(r.track)])
                for i, r in enumerate(sample)]


def _whole(req) -> bool:
    sig, peak = req.result()
    return (len(sig) == len(req.track) and bool(np.isfinite(sig).all())
            and bool(np.isfinite(peak).all()))


def run(spec: dict, devices, *, seed: int, seconds: float, trace_dir,
        marks: list, peaks) -> dict:
    from benchmarks.chip.lib import device, trace as tr

    cell = StreamCell(spec, devices, seed)
    marks.append(("server_and_weights", time.perf_counter()))
    cell.warm_up()
    marks.append(("warmup_steps", time.perf_counter()))
    setup_s = marks[-1][1] - marks[0][1]
    if trace_dir is None:
        w = cell.window(seconds)
        summary = None
    else:
        with tr.capture(trace_dir):
            w = cell.window(seconds)
        summary = tr.summarize(tr.load(trace_dir))
    memory = device.memory_peak_bytes(devices)
    sample = cell.check_sample(w["finished"])
    numbers = (judge.stream_numbers([r.result() for r in sample],
                                    cell.reference_outputs(sample))
               if sample else {})
    times = np.asarray(w["step_s"])
    return {
        "e2e": {"serve_columns_per_s": w["columns"] / w["window_s"],
                "stream_chunk_p95_ms": float(np.percentile(times, 95)) * 1e3,
                "setup_s": setup_s},
        "attempted": len(w["finished"]),
        "failed": sum(not _whole(r) for r in w["finished"]),
        "numbers": numbers,
        "memory_peak_bytes": memory, "trace": summary,
        "chips": len(devices), "peaks": peaks, "window_s": w["window_s"],
        "steps": len(times), "step_s": w["step_s"],
        "admitted": w["admitted"], "columns": w["columns"],
        "window_t0": w["t0"],
        "diag": {"steps": len(times), "admitting_steps": int(sum(w["admitted"])),
                 "streams_finished": len(w["finished"]),
                 "streams_checked": len(sample)},
    }


def calibrate(spec: dict, devices, seeds, control_seeds, seconds, log):
    """Readings for the limits (``benchmarks.chip.calibrate``): on every
    seed a window of ``seconds`` and the sample it finished against the
    reference; on each control seed the control (the reference one
    precision step below the stated dtype) on the same sample, and each
    fault of ``lib.faults.STREAM`` planted in the program, in a session of
    its own."""
    low = specmod.reference(spec["config"]).LOWER[spec["config"]["dtype"]]

    def session(seed):
        cell = StreamCell(spec, devices, seed)
        cell.warm_up()
        w = cell.window(seconds)
        return cell, cell.check_sample(w["finished"])

    for seed in seeds:
        t = time.perf_counter()
        cell, sample = session(seed)
        ref = cell.reference_outputs(sample)
        log("program", seed, judge.stream_numbers(
            [r.result() for r in sample], ref), t, streams=len(sample))
        if seed not in control_seeds:
            continue
        t = time.perf_counter()
        log("control", seed, judge.stream_numbers(
            cell.reference_outputs(sample, low=low), ref), t)
        for name, plant in faults.STREAM.items():
            t = time.perf_counter()
            with plant():
                bad, bad_sample = session(seed)
            log(name, seed, judge.stream_numbers(
                [r.result() for r in bad_sample],
                bad.reference_outputs(bad_sample)), t)


def tiny(spec: dict) -> dict:
    """``spec`` cut down in place for a run on the CPU: a 5-tap filter at
    dilation 2, two slots of 16-column chunks, short streams."""
    spec["config"].update(conv_filter=5, conv_dilation=2)
    spec["traffic"].update(
        slots=2, chunk=16, history=16, warmup_steps=8, check_streams=4,
        lengths={"median": 64, "sigma": 1.0, "min": 32, "max": 256,
                 "n": 64, "strata": 8})
    return spec
