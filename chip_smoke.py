#!/usr/bin/env python3
"""Chip smoke test: AtacWorks training, a full-width correctness check and
streaming serving on one TPU, through the entry points a user calls.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # dp=4 shard_map training vs one chip

Phases, one printed line (or a few) each:
  (a) device    platform, device_kind and count; anything but a TPU fails.
  (b) training  ``repro.launch.train.run`` at the paper's shape (width
                60,000, batch 8, 6 steps) for ``atacworks`` (fp32, C=K=15)
                and ``atacworks-bf16`` (C=K=16): finite losses, and the
                compiled step's convs are Pallas kernels (tpu_custom_call),
                with no XLA convolution left in the program.
  (c) correct   forward and parameter gradients of the fp32 model at batch
                2, width 60,000, on the Pallas path, against the pure-jnp
                oracle at HIGHEST matmul precision.
  (d) serving   ``repro.launch.serve.ConvStreamServer`` streams 128-column
                chunks of 8 streams through the full-width causal model;
                stream 0 is checked against the one-shot causal forward.
With ``--four-chips`` only: ``atacworks-bf16`` training on a dp=4 mesh
through the launcher's shard_map path, per-device peak memory (every chip
must have held a shard), and the same run on one chip at the same global
batch and seed; the two loss trajectories must agree.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and does
not print it.  All work runs in this one process, which holds the chips.
Step and chunk times printed here are smoke timings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

WIDTH = 60_000      # 50k-wide segment + 5k halos (paper §4.2)
BATCH = 8
STEPS = 6
CHUNK = 128
STREAMS = 8

# Kernel vs oracle.  Forward outputs: max|kernel - oracle| / max|oracle|
# per output; both sides accumulate in fp32 (the kernel's fp32 dots run at
# HIGHEST precision, the oracle's einsums too), so ~1e-6 is expected, and
# one bf16 pass rounds every product to 8 mantissa bits (~4e-3).
# Gradients: ||g - g_oracle|| / ||g_oracle|| over the whole parameter
# gradient, which is noisier: a ReLU whose pre-activation lies within
# rounding of zero switches on one side only, so two correct fp32
# implementations differ there by whole terms, most in the small
# bias-gradient entries summed over 120,000 positions.  On a TPU v5e this
# check scores the fp32 kernels ~3e-6 forward and ~5e-4 gradient; XLA's
# conv at HIGHEST precision, an independent fp32 path, scores the same
# ~5e-4 gradient; XLA's conv at default precision (bf16 passes) scores
# ~3e-2 and ~1.5e-1: each bound fails the bf16-pass path by 30x or more.
FWD_BOUND = 1e-4
GRAD_BOUND = 5e-3
# dp=4 vs one chip, bf16 model, same global batch, data and init.  Step 0
# sees identical params and samples: only the order of the loss mean
# differs, so its losses agree to fp32 rounding; a shard lost, duplicated
# or double-counted moves the mean of 8 samples by percents.  Later steps
# drift further: the gradient all-reduce sums in another order, AdamW
# turns a last-bit difference in a near-zero gradient entry into a whole
# +-lr step, and a bf16 parameter then rounds the other way.  The
# trajectory bound leaves room for that drift over 6 steps (a 4-device
# CPU rehearsal drifted 6e-4 by step 2); both are relative to the loss.
FIRST_LOSS_BOUND = 1e-5
LOSS_BOUND = 5e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


# ---------------------------------------------------------------------------
# (a) device
# ---------------------------------------------------------------------------


def device_info() -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


# ---------------------------------------------------------------------------
# (b) training through the launcher
# ---------------------------------------------------------------------------


def _state_and_batch_shapes(cfg, batch: int, width: int):
    from repro.models import get_model
    from repro.train.train_step import init_state

    model = get_model(cfg)
    state = jax.eval_shape(
        lambda: init_state(model.init_params(jax.random.key(0), cfg)))
    vec = jax.ShapeDtypeStruct((batch, width), jnp.float32)
    return state, {"noisy": vec, "clean": vec,
                   "peaks": jax.ShapeDtypeStruct((batch, width), jnp.int8)}


def step_hlo(cfg, *, batch: int, width: int, steps: int) -> str:
    """Compiled text of the launcher's single-device train step (the same
    program ``launch.train.run`` jits, so a warm compile cache hits)."""
    from repro.train.train_step import make_train_step

    step = make_train_step(cfg, warmup_steps=max(2, steps // 10),
                           total_steps=steps)
    state, batch_shapes = _state_and_batch_shapes(cfg, batch, width)
    return jax.jit(step, donate_argnums=(0,)).lower(
        state, batch_shapes).compile().as_text()


def phase_train(arch: str, *, batch: int = BATCH, width: int = WIDTH,
                steps: int = STEPS) -> dict:
    from repro import configs
    from repro.launch import train

    summary = train.run(["--arch", arch, "--seq", str(width),
                         "--batch", str(batch), "--steps", str(steps)])
    losses = summary["losses"]
    check(summary["status"] == "done" and len(losses) == steps,
          f"{arch}: training ended {summary['status']!r} after "
          f"{len(losses)}/{steps} steps")
    check(all(np.isfinite(losses)), f"{arch}: non-finite loss {losses}")
    say("b", f"{arch}: losses {[round(v, 6) for v in losses]}")
    say("b", f"{arch}: smoke timing, not a benchmark number: steady step "
        f"{summary['steady_step_s']:.4f} s, "
        f"{summary['samples_per_s']:.2f} samples/s (median of "
        f"{steps - train.WARMUP_STEPS} post-warmup steps)")

    text = step_hlo(configs.get(arch), batch=batch, width=width, steps=steps)
    kernels = [ln for ln in text.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    passes = {p: sum(f"conv1d_{p}" in ln for ln in kernels)
              for p in ("fwd", "bwd_data", "bwd_weight")}
    n_xla_conv = text.count(" convolution(")
    check(all(passes.values()),
          f"{arch}: compiled step lacks Mosaic conv kernels {passes}")
    check(n_xla_conv == 0,
          f"{arch}: {n_xla_conv} XLA convolutions in the compiled step")
    say("b", f"{arch}: compiled step has {len(kernels)} tpu_custom_call "
        f"kernels {passes}, 0 XLA convolutions")
    return summary


# ---------------------------------------------------------------------------
# (c) full-width correctness against the oracle
# ---------------------------------------------------------------------------


def ref_forward(params, cfg, x):
    """The AtacWorks forward written directly on the pure-jnp oracle
    (``kernels/ref.py``, einsums at HIGHEST precision), independent of
    ``core/blocks.py``.  The 11 residual blocks run as a rematerialised
    scan, so the oracle's 51-tap loop compiles once per layer shape rather
    than 25 times (the unrolled oracle takes minutes to compile)."""
    from repro.kernels import ref

    d = cfg.conv_dilation
    span = (cfg.conv_filter - 1) * d

    def conv(p, h, activation="relu", residual=None, out_dtype=None):
        hp = jnp.pad(h, ((0, 0), (0, 0), (span // 2, span - span // 2)))
        return ref.conv1d_fused_ref(hp, p["w"], dilation=d, bias=p["b"],
                                    activation=activation, residual=residual,
                                    out_dtype=out_dtype)

    @jax.checkpoint
    def block(h, p):
        return conv(p["conv2"], conv(p["conv1"], h), residual=h), None

    h = conv(params["stem"], x[:, None, :])
    h, _ = jax.lax.scan(block, h,
                        jax.tree.map(lambda *a: jnp.stack(a), *params["res"]))
    signal = conv(params["head_signal"], h, out_dtype=jnp.float32)[:, 0]
    peak = conv(params["head_peak"], h, activation=None,
                out_dtype=jnp.float32)[:, 0]
    return signal, peak


@functools.partial(jax.jit, static_argnums=(0,))
def _fwd_and_grads(forward, params, x, cot):
    out, pull = jax.vjp(lambda p: forward(p, x), params)
    return out, pull(cot)[0]


def compare(forward, oracle, params, x, cot) -> dict:
    """Errors of ``forward`` against ``oracle``: max rel error of each
    output, norm-wise rel error of the whole parameter gradient, and each
    gradient tensor's max rel error."""
    (sig, peak), grads = _fwd_and_grads(forward, params, x, cot)
    (sig_r, peak_r), grads_r = _fwd_and_grads(oracle, params, x, cot)
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    leaves_r = jax.tree.leaves(grads_r)
    diff2 = sum(float(jnp.sum((g.astype(jnp.float32) - gr) ** 2))
                for (_, g), gr in zip(leaves, leaves_r))
    ref2 = sum(float(jnp.sum(gr.astype(jnp.float32) ** 2))
               for gr in leaves_r)
    return {
        "signal": rel_err(sig, sig_r), "peak": rel_err(peak, peak_r),
        "max_abs": float(max(np.abs(np.asarray(sig - sig_r)).max(),
                             np.abs(np.asarray(peak - peak_r)).max())),
        "grad": (diff2 / ref2) ** 0.5,
        "per_leaf": {jax.tree_util.keystr(path): rel_err(g, gr)
                     for (path, g), gr in zip(leaves, leaves_r)},
    }


def correctness_inputs(cfg, *, batch: int, width: int, seed: int = 0):
    """Seeded params (nonzero biases, so the fused bias epilogue counts),
    a synthetic coverage batch and output cotangents."""
    from repro.core import blocks
    from repro.data.synthetic import atacseq_batch

    key_p, key_b, key_c = jax.random.split(jax.random.key(seed), 3)
    params = blocks.init_params(key_p, cfg)
    for layer in [params["stem"], params["head_signal"], params["head_peak"],
                  *[c for blk in params["res"] for c in blk.values()]]:
        key_b, k = jax.random.split(key_b)
        layer["b"] = 0.1 * jax.random.normal(k, layer["b"].shape,
                                             layer["b"].dtype)
    x = jnp.asarray(atacseq_batch(np.random.default_rng(seed), batch,
                                  width=width)["noisy"])
    k1, k2 = jax.random.split(key_c)
    cot = (jax.random.normal(k1, (batch, width), jnp.float32),
           jax.random.normal(k2, (batch, width), jnp.float32))
    return params, x, cot


def phase_correct(*, batch: int = 2, width: int = WIDTH,
                  seed: int = 0) -> dict:
    from repro import configs
    from repro.core import blocks

    cfg = configs.get("atacworks")

    def kernel_forward(p, x):   # the timed path: the Pallas kernels
        return blocks.forward(p, cfg, x)

    def oracle_forward(p, x):
        return ref_forward(p, cfg, x)

    e = compare(kernel_forward, oracle_forward,
                *correctness_inputs(cfg, batch=batch, width=width, seed=seed))
    fwd_err = max(e["signal"], e["peak"])
    worst_leaf = max(e["per_leaf"], key=e["per_leaf"].get)
    check(np.isfinite(fwd_err) and np.isfinite(e["grad"]),
          "non-finite kernel vs oracle error")
    say("c", f"fp32 model, batch {batch}, width {width}, kernels vs "
        f"HIGHEST-precision oracle: forward max rel error {fwd_err:.3e} "
        f"(signal {e['signal']:.3e}, peak {e['peak']:.3e}); gradient "
        f"norm-wise rel error {e['grad']:.3e} over {len(e['per_leaf'])} "
        f"tensors; bounds {FWD_BOUND:g} / {GRAD_BOUND:g}")
    say("c", f"max abs output error {e['max_abs']:.3e}; largest "
        f"per-tensor gradient max rel error "
        f"{e['per_leaf'][worst_leaf]:.3e} at {worst_leaf} (not bounded, "
        "see GRAD_BOUND)")
    check(fwd_err <= FWD_BOUND and e["grad"] <= GRAD_BOUND,
          f"kernel vs oracle error forward {fwd_err:.3e} / gradient "
          f"{e['grad']:.3e} exceeds {FWD_BOUND:g} / {GRAD_BOUND:g}")
    return e


# ---------------------------------------------------------------------------
# (d) streaming serving
# ---------------------------------------------------------------------------


def phase_serve(*, streams: int = STREAMS, chunk: int = CHUNK,
                track_len: int = 4 * CHUNK, prompt_len: int = CHUNK,
                seed: int = 0) -> None:
    from repro import configs
    from repro.core import blocks
    from repro.launch.serve import ConvStreamServer, StreamRequest

    cfg = configs.get("atacworks")
    params = blocks.init_params(jax.random.key(seed), cfg)
    rng = np.random.default_rng(seed)
    server = ConvStreamServer(params, cfg, batch=streams, chunk=chunk,
                              prompt_len=prompt_len)
    for rid in range(streams):
        n = track_len + int(rng.integers(0, chunk))   # ragged last chunk
        server.submit(StreamRequest(
            rid, rng.poisson(2.0, n).astype(np.float32),
            history=rng.poisson(2.0, prompt_len).astype(np.float32)))
    done = server.run()
    check(len(done) == streams, f"{len(done)}/{streams} streams finished")
    for req in done:
        sig, peak = req.result()
        check(len(sig) == len(req.track) and np.isfinite(sig).all()
              and np.isfinite(peak).all(),
              f"stream {req.id}: {len(sig)}/{len(req.track)} samples, "
              "or non-finite outputs")
    req = done[0]
    full = np.concatenate([req.history, req.track])
    sig1, _ = jax.jit(lambda p, x: blocks.forward(p, cfg, x,
                                                  padding="CAUSAL"))(
        params, jnp.asarray(full)[None])
    want = np.asarray(sig1)[0, len(req.history):]
    err = rel_err(req.result()[0], want)
    served = sum(len(r.track) for r in done)
    times = np.asarray(server.chunk_times[1:])
    say("d", f"served {served} samples of {len(done)} streams in "
        f"{server.chunks_run} chunk steps of {chunk} columns "
        f"(batch {streams}, prefill {prompt_len}); stream 0 vs one-shot "
        f"causal forward: max rel error {err:.3e}")
    say("d", f"smoke timing, not a benchmark number: chunk p50 "
        f"{np.median(times) * 1e3:.2f} ms, max {times.max() * 1e3:.2f} ms")
    check(err <= FWD_BOUND,
          f"streamed output differs from the one-shot forward by {err:.3e}")


# ---------------------------------------------------------------------------
# --four-chips: dp=4 shard_map training vs one chip
# ---------------------------------------------------------------------------


def one_chip_losses(arch: str, *, batch: int, width: int, steps: int,
                    seed: int = 0, device=None) -> list[float]:
    """The launcher's step on ONE device, same data, init and schedule."""
    from repro import configs
    from repro.data.synthetic import make_batch
    from repro.models import get_model
    from repro.train.train_step import init_state, make_train_step

    cfg = configs.get(arch)
    device = device or jax.devices()[0]
    params = get_model(cfg).init_params(jax.random.key(seed), cfg)
    state = jax.device_put(init_state(params), device)
    step = jax.jit(make_train_step(cfg, warmup_steps=max(2, steps // 10),
                                   total_steps=steps), donate_argnums=(0,))
    losses = []
    for i in range(steps):
        b = jax.device_put(make_batch(cfg, batch, width, seed=seed + i),
                           device)
        state, metrics = step(state, b)
        losses.append(float(metrics["loss"]))
    return losses


def phase_four_chips(arch: str = "atacworks-bf16", *, batch: int = BATCH,
                     width: int = WIDTH, steps: int = STEPS) -> None:
    from repro.launch import train

    summary = train.run(["--arch", arch, "--seq", str(width),
                         "--batch", str(batch), "--steps", str(steps)])
    mesh = summary["mesh_history"][0]
    check(summary["status"] == "done" and mesh["dp"] == 4
          and len(summary["losses"]) == steps,
          f"dp=4 run: status {summary['status']!r}, mesh {mesh}")
    dp4 = summary["losses"]
    say("4", f"{arch} dp=4 shard_map, global batch {batch}: losses "
        f"{[round(v, 6) for v in dp4]}")
    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    say("4", "peak bytes in use per chip after the dp=4 run: "
        + ", ".join(f"{p / 2**20:.1f} MiB" for p in peaks))
    check(min(peaks) > 0.25 * max(peaks),
          f"uneven per-chip memory {peaks}: the shards did not spread")
    say("4", f"smoke timing, not a benchmark number: steady step "
        f"{summary['steady_step_s']:.4f} s, "
        f"{summary['samples_per_s']:.2f} samples/s")

    one = one_chip_losses(arch, batch=batch, width=width, steps=steps)
    say("4", f"{arch} one chip, global batch {batch}: losses "
        f"{[round(v, 6) for v in one]}")
    check(all(np.isfinite(dp4)) and all(np.isfinite(one)),
          "non-finite losses")
    rel = np.abs(np.asarray(dp4) - np.asarray(one)) / np.abs(one)
    say("4", f"|dp4 - one chip| / |one chip| loss difference: step 0 "
        f"{rel[0]:.3e} (bound {FIRST_LOSS_BOUND:g}), max over {steps} steps "
        f"{rel.max():.3e} (bound {LOSS_BOUND:g})")
    check(rel[0] <= FIRST_LOSS_BOUND and rel.max() <= LOSS_BOUND,
          f"dp=4 and one-chip losses differ by {rel[0]:.3e} at step 0, "
          f"{rel.max():.3e} at most")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the dp=4 shard_map training phase and "
                         "its one-chip comparison (needs 4 chips)")
    args = ap.parse_args(argv)

    dev = device_info()
    say("a", f"platform={dev['platform']} kind={dev['kind']!r} "
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print("chip_smoke: no TPU found; nothing was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if dev["count"] < want:
        print(f"chip_smoke: needs {want} chip(s), found {dev['count']}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    say("a", f"compilation cache: {use_compile_cache()}")
    try:
        if args.four_chips:
            phase_four_chips()
        else:
            phase_train("atacworks")
            phase_train("atacworks-bf16")
            phase_correct()
            phase_serve()
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
