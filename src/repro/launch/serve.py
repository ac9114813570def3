"""Serving launcher — batched decode (LM families) and batched continuous
streaming (conv family).

LM families: build a cache of ``--prompt-len`` tokens (sequential
teacher-forced decode steps — production prefill is a separate fused step,
see train/serve_step.make_prefill_step), then generate ``--gen`` tokens
greedily, reporting per-step latency.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --batch 4 --prompt-len 16 --gen 16

Conv family (AtacWorks-style pileup denoising on live sequencer streams,
DESIGN.md §16): a continuous-serving loop over the *streaming* conv1d —
request queue, per-stream position tracking, padded-batch compaction so
ragged streams share one jitted ``(B, chunk)`` step — with per-chunk state
carried in per-layer ring buffers instead of re-running the stack's
receptive field (10 000 columns for the paper config) on every chunk.

    PYTHONPATH=src python -m repro.launch.serve --arch atacworks --smoke \
        --streams 6 --batch 4 --chunk 128 --prompt-len 64

Streaming is causal-only: ``--conv-padding same`` exits with an error (SAME
padding needs future context at every output — there is no streaming form;
serve full sequences through ``blocks.forward`` instead).
"""
from __future__ import annotations

import argparse
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs, obs
from repro.configs.base import reduced
from repro.launch.mesh import make_host_mesh
from repro.models import get_model, sharding as shd
from repro.train.serve_step import (make_cache, make_conv_prefill_step,
                                    make_conv_stream_state,
                                    make_conv_stream_step, make_serve_step,
                                    with_request_spans)


class StreamRequest:
    """One conv stream: ``track`` is the live input (1D float array) whose
    denoised outputs the client wants as they arrive; ``history`` is an
    optional already-observed prefix to prefill state from (its outputs are
    not re-served).  Results accumulate in ``signal``/``peak``."""

    def __init__(self, rid: int, track, history=None):
        self.id = rid
        self.track = np.asarray(track, np.float32)
        self.history = None if history is None else np.asarray(history,
                                                               np.float32)
        self.pos = 0  # next un-served track sample
        self.signal: list[np.ndarray] = []
        self.peak: list[np.ndarray] = []

    @property
    def done(self) -> bool:
        return self.pos >= len(self.track)

    def result(self) -> tuple[np.ndarray, np.ndarray]:
        return (np.concatenate(self.signal) if self.signal else np.zeros(0),
                np.concatenate(self.peak) if self.peak else np.zeros(0))


class ConvStreamServer:
    """Batched continuous streaming server for the conv family.

    ``batch`` slots share one jitted ``(B, chunk)`` stream step (state
    donated, ring buffers update in place).  Requests queue until a slot
    frees; admission zeroes the slot's ring buffers (zeros = fresh causal
    stream) and, when the request carries history, prefills them with one
    fused full-sequence pass — histories are LEFT-padded to a fixed
    ``prompt_len`` so every prefill shares one jit signature (leading
    zeros are inert: they are exactly the causal padding a fresh stream
    starts from).  Ragged stream lengths are handled by padded-batch
    compaction: the final short chunk of each stream rides in the shared
    batch with zero-padding, and only its ``valid`` leading columns are
    served back.  Idle slots stream zeros (their outputs are dropped).
    """

    def __init__(self, params, cfg, *, batch: int, chunk: int,
                 prompt_len: int = 0, backend=None, fused=None,
                 dtype=jnp.float32):
        self.params, self.cfg = params, cfg
        self.batch, self.chunk, self.prompt_len = batch, chunk, prompt_len
        self.dtype = dtype
        self.state = make_conv_stream_state(cfg, batch, dtype)
        self.slots: list[StreamRequest | None] = [None] * batch
        self.queue: deque[StreamRequest] = deque()
        self.chunk_times: list[float] = []
        self.chunks_run = 0
        self._step = with_request_spans(
            jax.jit(make_conv_stream_step(cfg, backend=backend, fused=fused),
                    donate_argnums=(1,)),
            "serve.conv.chunk", arch=cfg.name, batch=batch, chunk=chunk)
        self._prefill = with_request_spans(
            jax.jit(make_conv_prefill_step(cfg, backend=backend,
                                           fused=fused)),
            "serve.conv.prefill", arch=cfg.name, batch=1,
            prompt_len=prompt_len)

    def submit(self, req: StreamRequest) -> None:
        self.queue.append(req)

    def _reset_slot(self, i: int) -> None:
        self.state = jax.tree.map(lambda s: s.at[i].set(0), self.state)

    def _admit(self) -> None:
        for i in range(self.batch):
            if self.slots[i] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            self._reset_slot(i)
            if req.history is not None and self.prompt_len:
                hist = req.history[-self.prompt_len:]
                # left-pad to the fixed prefill signature; leading zeros
                # are the causal padding a fresh stream starts from
                hist = np.pad(hist, (self.prompt_len - len(hist), 0))
                _, pstate = self._prefill(
                    self.params, jnp.asarray(hist, self.dtype)[None])
                self.state = jax.tree.map(
                    lambda s, p: s.at[i].set(p[0]), self.state, pstate)
            self.slots[i] = req

    def step(self) -> int:
        """Admit waiting requests, run one padded-batch chunk step, scatter
        the valid outputs back per stream, retire finished streams.
        Returns the number of streams served this step."""
        self._admit()
        active = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        if not active:
            return 0
        batch_np = np.zeros((self.batch, self.chunk), np.float32)
        valid = np.zeros(self.batch, np.int64)
        for i, req in active:
            part = req.track[req.pos:req.pos + self.chunk]
            batch_np[i, :len(part)] = part
            valid[i] = len(part)
        t0 = time.perf_counter()
        (signal, peak), self.state = self._step(
            self.params, self.state, jnp.asarray(batch_np, self.dtype))
        signal, peak = np.asarray(signal), np.asarray(peak)
        self.chunk_times.append(time.perf_counter() - t0)
        self.chunks_run += 1
        for i, req in active:
            n = int(valid[i])
            req.signal.append(signal[i, :n])
            req.peak.append(peak[i, :n])
            req.pos += n
            if req.done:
                self.slots[i] = None
        return len(active)

    def run(self) -> list[StreamRequest]:
        """Drain the queue: loop ``step`` until every stream completes;
        returns the finished requests (in submission order)."""
        finished: list[StreamRequest] = []
        seen = list(self.queue) + [r for r in self.slots if r is not None]
        while any(self.slots) or self.queue:
            self.step()
        finished = [r for r in seen if r.done]
        return finished


def serve_conv(args, cfg) -> int:
    """The conv-family continuous-serving path (streaming, DESIGN.md §16)."""
    if args.conv_padding != "causal":
        raise SystemExit(
            f"conv serving: padding {args.conv_padding!r} has no streaming "
            "form — SAME needs future context at every output position. "
            "Serve full sequences one-shot via blocks.forward, or use "
            "--conv-padding causal")
    from repro.core import blocks

    model = get_model(cfg)
    params = model.init_params(jax.random.key(args.seed), cfg)
    rng = np.random.default_rng(args.seed)
    server = ConvStreamServer(params, cfg, batch=args.batch,
                              chunk=args.chunk, prompt_len=args.prompt_len)

    # synthetic live streams with ragged lengths (padded-batch compaction
    # is exercised by construction) and optional prefill history
    base = args.track_len
    for rid in range(args.streams):
        n = base + int(rng.integers(0, max(args.chunk, 2)))
        track = rng.normal(size=n).astype(np.float32)
        hist = (rng.normal(size=args.prompt_len).astype(np.float32)
                if args.prompt_len else None)
        server.submit(StreamRequest(rid, track, history=hist))

    t0 = time.perf_counter()
    done = server.run()
    wall = time.perf_counter() - t0
    times = np.asarray(server.chunk_times[1:] or server.chunk_times)
    served = sum(len(r.track) for r in done)
    print(f"served {len(done)} streams ({served} samples) in {wall:.2f}s: "
          f"chunk p50 {np.median(times) * 1e3:.1f} ms, "
          f"p99 {np.percentile(times, 99) * 1e3:.1f} ms, "
          f"{len(done) / wall:.1f} streams/s, {served / wall:.0f} samples/s")

    if args.smoke:
        # correctness spot-check: stream 0's chunked outputs must be
        # bitwise the one-shot causal forward over [history | track]
        req = done[0]
        full = (np.concatenate([req.history, req.track])
                if req.history is not None else req.track)
        sig, _ = blocks.forward(params, cfg, jnp.asarray(full)[None],
                                padding="CAUSAL")
        want = np.asarray(sig)[0, len(full) - len(req.track):]
        got = req.result()[0]
        assert np.array_equal(got, want), (
            "streaming serve diverged from the one-shot causal forward "
            f"(maxdiff {np.abs(got - want).max()})")
        print("smoke: stream 0 ≡ one-shot causal forward (bitwise)")
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    # conv-family streaming knobs
    ap.add_argument("--streams", type=int, default=8,
                    help="conv: number of queued streaming requests")
    ap.add_argument("--chunk", type=int, default=128,
                    help="conv: samples per streaming step (jit width)")
    ap.add_argument("--track-len", type=int, default=512,
                    help="conv: base stream length (lengths are ragged "
                         "above this to exercise padded-batch compaction)")
    ap.add_argument("--conv-padding", default="causal",
                    choices=["causal", "same"],
                    help="conv: only 'causal' can stream; 'same' exits "
                         "with a clear error (needs future context)")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write a telemetry JSONL log to PATH (same as "
                         "REPRO_TELEMETRY=1 + REPRO_TELEMETRY_PATH)")
    args = ap.parse_args(argv)

    if args.telemetry:
        obs.enable(args.telemetry)
    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    if cfg.family == "conv":
        return serve_conv(args, cfg)
    mesh = make_host_mesh(model=args.model_parallel)
    model = get_model(cfg)
    max_len = args.prompt_len + args.gen

    with mesh:
        params = model.init_params(jax.random.key(args.seed), cfg)
        pspecs = shd.param_pspecs(params, mesh)
        params = jax.tree.map(
            lambda p, s: jax.device_put(p, jax.sharding.NamedSharding(mesh, s)),
            params, pspecs)
        cache = make_cache(cfg, args.batch, max_len, dtype=jnp.float32)
        serve = with_request_spans(
            jax.jit(make_serve_step(cfg), donate_argnums=(1,)),
            "serve.decode_step", arch=cfg.name, batch=args.batch)

        rng = np.random.default_rng(args.seed)
        prompt = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
            jnp.int32)

        # prefill (sequential; cache-correct by construction)
        t0 = time.perf_counter()
        nxt = prompt[:, :1]
        with obs.span("serve.prefill", arch=cfg.name, batch=args.batch,
                      prompt_len=args.prompt_len):
            for t in range(args.prompt_len):
                nxt, cache, _ = serve(params, cache, prompt[:, t:t + 1],
                                      jnp.int32(t))
        print(f"prefill {args.prompt_len} tokens: "
              f"{time.perf_counter() - t0:.2f}s")

        # generate
        out = [nxt]
        times = []
        for t in range(args.prompt_len, max_len - 1):
            t0 = time.perf_counter()
            nxt, cache, logits = serve(params, cache, nxt, jnp.int32(t))
            times.append(time.perf_counter() - t0)
            out.append(nxt)
        toks = jnp.concatenate(out, axis=1)
        assert bool(jnp.isfinite(jnp.asarray(logits)).all()), "non-finite logits"
        print(f"generated {toks.shape} tokens; "
              f"median step {np.median(times) * 1e3:.1f} ms, "
              f"p99 {np.percentile(times, 99) * 1e3:.1f} ms")
        print("sample:", np.asarray(toks[0])[:16])
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    raise SystemExit(main())
