"""Inputs made from ``--seed``: coverage batches for training, and the
streams, with their lengths and arrival times, for serving.

Every seed gets the same multiset of stream lengths and inter-arrival
gaps, drawn at the quantiles of the mix's distributions and put in an
order drawn from the seed: the seed changes the order and the data, not
the amount of work, so runs of different seeds are comparable.
"""
from __future__ import annotations

import functools
import math
from statistics import NormalDist

import jax
import jax.numpy as jnp
import numpy as np


def keys(seed: int, n: int) -> list[int]:
    """``n`` independent 32-bit words from a seed of any size."""
    return [int(w) for w in
            np.random.SeedSequence(seed).generate_state(n, np.uint32)]


def coverage_batch(key, *, batch: int, width: int, pad: int,
                   peak_rate: float, max_peaks: int):
    """One batch of synthetic ATAC-seq tracks, made on the device.

    clean: a sum of Gaussian bumps (height U(2, 25), sd U(150, 600)) at
    Poisson(peak_rate * inner width) sparse centres away from the halos;
    noisy: a Poisson draw at 15% of the clean coverage (low-coverage
    sequencing); peaks: 1 within one sd of a centre."""
    kn, kc, kw, kh, kp = jax.random.split(key, 5)
    n = jnp.clip(jax.random.poisson(kn, peak_rate * (width - 2 * pad),
                                    (batch,)), 1, max_peaks)
    centres = jax.random.randint(kc, (batch, max_peaks), pad, width - pad)
    sds = jax.random.uniform(kw, (batch, max_peaks), minval=150.0,
                             maxval=600.0)
    heights = jax.random.uniform(kh, (batch, max_peaks), minval=2.0,
                                 maxval=25.0)
    heights = jnp.where(jnp.arange(max_peaks)[None] < n[:, None], heights, 0.)
    t = jnp.arange(width, dtype=jnp.float32)[None]

    def bump(j, carry):
        clean, peaks = carry
        c, sd, h = (jax.lax.dynamic_slice_in_dim(a, j, 1, axis=1)
                    for a in (centres.astype(jnp.float32), sds, heights))
        clean = clean + h * jnp.exp(-0.5 * ((t - c) / sd) ** 2)
        peaks = peaks | ((jnp.abs(t - c) < sd) & (h > 0))
        return clean, peaks

    clean, peaks = jax.lax.fori_loop(
        0, max_peaks, bump, (jnp.zeros((batch, width), jnp.float32),
                             jnp.zeros((batch, width), bool)))
    noisy = jax.random.poisson(kp, jnp.maximum(0.15 * clean, 1e-3))
    return {"noisy": noisy.astype(jnp.float32), "clean": clean,
            "peaks": peaks.astype(jnp.int8)}


@functools.cache
def _pool_fn(n: int, sharding, **kw):
    @functools.partial(jax.jit, out_shardings=sharding)
    def make(key):
        return [coverage_batch(k, **kw) for k in jax.random.split(key, n)]
    return make


def coverage_pool(seed_word: int, traffic: dict, *, batch: int, width: int,
                  sharding=None) -> list[dict]:
    """``traffic["pool"]`` distinct batches, made in one jitted call."""
    make = _pool_fn(traffic["pool"], sharding, batch=batch, width=width,
                    pad=traffic["pad"], peak_rate=traffic["peak_rate"],
                    max_peaks=traffic["max_peaks"])
    return make(jax.random.key(seed_word))


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stream_lengths(traffic: dict, rng: np.random.Generator) -> np.ndarray:
    """Lognormal lengths at ``n`` quantiles, clipped, in a seeded order in
    which every block of ``strata`` consecutive streams holds one length
    from each of ``strata`` equal bands of the quantiles.  So any window
    that serves a few blocks serves about the same amount of work."""
    ln = traffic["lengths"]
    z = np.array([NormalDist().inv_cdf(u) for u in _grid(ln["n"])])
    lengths = np.exp(math.log(ln["median"]) + ln["sigma"] * z)
    lengths = np.clip(np.rint(lengths), ln["min"], ln["max"]).astype(int)
    bands = lengths.reshape(ln["strata"], -1)
    picks = np.array([rng.permutation(bands.shape[1]) for _ in bands])
    rows = np.arange(len(bands))
    return np.concatenate([rng.permutation(bands[rows, picks[:, b]])
                           for b in range(bands.shape[1])])


def arrival_gaps(traffic: dict, rng: np.random.Generator, n: int):
    """Seconds between arrivals of an open loop, or None for a closed one.

    ``poisson``: exponential gaps at ``rate_per_s``; ``bursty``: bursts of
    ``burst`` streams at once, the bursts exponential at rate/burst."""
    arr = traffic["arrivals"]
    if arr["kind"] == "closed":
        return None
    burst = arr.get("burst", 1) if arr["kind"] == "bursty" else 1
    if arr["kind"] not in ("poisson", "bursty"):
        raise ValueError(f"unknown arrival process {arr['kind']!r}")
    gaps = rng.permutation(-np.log1p(-_grid(n)) * burst / arr["rate_per_s"])
    gaps[np.arange(n) % burst != 0] = 0.0
    return gaps


def stream_track(seed: int, index: int, history: int, length: int,
                 rate: float):
    """Coverage counts of stream ``index``: (history, track)."""
    rng = np.random.default_rng([seed % 2**63, index])
    x = rng.poisson(rate, history + length).astype(np.float32)
    return x[:history], x[history:]
