"""Plain reference of the AtacWorks model (Lal et al. 2019; Chaudhary et al.
2021 §4.2), written from the published description in ``jax.numpy``.

It imports nothing of the program.  The layer graph: a stem conv 1->C,
(n_layers-3)/2 residual blocks of two C->C convs, and two C->1 heads, all
S taps at dilation d:

    h = relu(conv(x) + b)                                    stem
    h = relu(conv(relu(conv(h) + b1)) + b2 + h)              each block
    signal = relu(conv(h) + b),  peak_logits = conv(h) + b   heads

Each conv is S matrix products over shifted slices of the padded input
(the paper's Algorithm 1, a loop over taps), accumulated in float32 at
HIGHEST matmul precision; weights of any stored dtype are widened to float32, and each
layer's output keeps its input's dtype (float32 coverage in, float32 out),
as the configuration runs.  ``low`` names a lower dtype to round every
matmul operand to first: the control of a correctness check.

Training follows the program's recipe: loss = MSE(signal, clean) +
BCE(peak_logits, peaks), both means over all B*W positions; AdamW with
fp32 moments (b1 0.9, b2 0.95, eps 1e-8), decay 0.1 on matrices, global
norm clip 1.0, and a cosine schedule with linear warm-up to a tenth of the
peak.  Gradients are summed over blocks of rows, so a batch of any size
fits, and blocks go round-robin to the devices given.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
B1, B2, EPS = 0.9, 0.95, 1e-8
WEIGHT_DECAY, GRAD_CLIP, FINAL_FRAC = 0.1, 1.0, 0.1
# the dtype one step below each stated dtype: the control's precision
LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def n_blocks(cfg: dict) -> int:
    return (cfg["n_layers"] - 3) // 2


def layer_shapes(cfg: dict) -> list[tuple[int, int, bool]]:
    """(C_in, K, needs input gradient) of every conv, in forward order;
    the stem's input is data and needs none."""
    C = cfg["conv_channels"]
    return ([(1, C, False)] + [(C, C, True)] * (2 * n_blocks(cfg))
            + [(C, 1, True)] * 2)


def init_params(key, cfg: dict):
    """Seeded weights in the program's tree layout and stored dtype:
    N(0, 1/(C_in*S)) filters and 0.1 N(0, 1) biases, nonzero so that the
    fused bias epilogue is exercised."""
    dt = jnp.dtype(cfg["dtype"])
    C, S = cfg["conv_channels"], cfg["conv_filter"]
    keys = iter(jax.random.split(key, 2 * cfg["n_layers"]))

    def layer(c_in, c_out):
        w = jax.random.normal(next(keys), (S, c_out, c_in), jnp.float32)
        b = 0.1 * jax.random.normal(next(keys), (c_out,), jnp.float32)
        return {"w": (w * (c_in * S) ** -0.5).astype(dt), "b": b.astype(dt)}

    return {
        "stem": layer(1, C),
        "res": [{"conv1": layer(C, C), "conv2": layer(C, C)}
                for _ in range(n_blocks(cfg))],
        "head_signal": layer(C, 1),
        "head_peak": layer(C, 1),
    }


def _conv(h, p, *, d, causal, low, relu, residual=None):
    w = p["w"]
    S = w.shape[0]
    span = (S - 1) * d
    lo = span if causal else span // 2
    xp = jnp.pad(h, ((0, 0), (0, 0), (lo, span - lo)))

    def q(a):
        a = a.astype(low) if low else a
        return a.astype(jnp.float32)

    xq, wq = q(xp), q(w)
    Q = h.shape[-1]

    def tap(s, acc):
        return acc + jnp.einsum(
            "kc,ncq->nkq", wq[s],
            jax.lax.dynamic_slice_in_dim(xq, s * d, Q, axis=2),
            precision=HIGHEST)

    acc = jax.lax.fori_loop(
        0, S, tap, jnp.zeros((h.shape[0], w.shape[1], Q), jnp.float32))
    acc = acc + p["b"].astype(jnp.float32)[None, :, None]
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    if relu:
        acc = jnp.maximum(acc, 0.0)
    return acc.astype(h.dtype)


def forward(params, cfg: dict, x, *, causal: bool = False, low=None):
    """x: (B, W) coverage -> (signal, peak_logits), both (B, W) float32.
    The blocks run as a rematerialised scan, so the S-tap loop compiles
    once per layer shape."""
    kw = dict(d=cfg["conv_dilation"], causal=causal, low=low)
    h = _conv(x[:, None, :], params["stem"], relu=True, **kw)

    @jax.checkpoint
    def block(h, p):
        r = _conv(h, p["conv1"], relu=True, **kw)
        return _conv(r, p["conv2"], relu=True, residual=h, **kw), None

    if params["res"]:
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *params["res"])
        h, _ = jax.lax.scan(block, h, stacked)
    h = h.astype(jnp.float32)
    signal = _conv(h, params["head_signal"], relu=True, **kw)[:, 0]
    peak = _conv(h, params["head_peak"], relu=False, **kw)[:, 0]
    return signal, peak


def lr_at(step: int, *, peak_lr: float, warmup_steps: int,
          total_steps: int) -> float:
    """Linear warm-up, then cosine from the peak down to a tenth of it."""
    step = float(step)
    if step < warmup_steps:
        return peak_lr * step / max(warmup_steps, 1)
    t = min(max((step - warmup_steps)
                / max(total_steps - warmup_steps, 1), 0.0), 1.0)
    return peak_lr * (FINAL_FRAC + (1 - FINAL_FRAC)
                      * 0.5 * (1 + math.cos(math.pi * t)))


def _f32(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float32), tree)


class Reference:
    """The reference's compiled pieces for one configuration, on a list
    of devices.  ``low`` makes it the control (see the module doc)."""

    def __init__(self, cfg: dict, devices, *, low=None, block_rows: int = 8):
        self.cfg, self.devices, self.low = cfg, list(devices), low
        self.block_rows = block_rows

        def loss_sums(p32, x, clean, peaks, inv_count):
            signal, peak = forward(p32, cfg, x, low=low)
            labels = peaks.astype(jnp.float32)
            sq = jnp.sum((signal - clean) ** 2)
            bce = jnp.sum(jnp.maximum(peak, 0) - peak * labels
                          + jnp.log1p(jnp.exp(-jnp.abs(peak))))
            return (sq + bce) * inv_count

        self._grad = jax.jit(jax.value_and_grad(loss_sums))
        self._causal = jax.jit(
            lambda p, x: forward(p, cfg, x, causal=True, low=low))

    def _blocks(self, n_rows: int):
        for i, r0 in enumerate(range(0, n_rows, self.block_rows)):
            yield self.devices[i % len(self.devices)], r0, min(
                n_rows, r0 + self.block_rows)

    def loss_and_grad(self, params, batch: dict, rows=None):
        """Loss and float32 gradient of the mean over ``rows`` (default
        all) of ``batch``, taken w.r.t. the float32 values of ``params``."""
        rows = np.arange(len(batch["noisy"])) if rows is None else rows
        sub = {k: np.asarray(v)[rows] for k, v in batch.items()}
        inv = np.float32(1.0 / sub["noisy"].size)
        p32 = _f32(params)
        on_dev = {d: jax.device_put(p32, d) for d in self.devices}
        parts = []
        for d, r0, r1 in self._blocks(len(rows)):
            args = jax.device_put(
                (sub["noisy"][r0:r1], sub["clean"][r0:r1],
                 sub["peaks"][r0:r1]), d)
            parts.append(self._grad(on_dev[d], *args, inv))
        loss = sum(float(v) for v, _ in parts)
        grads = jax.tree.map(lambda *g: np.sum([np.asarray(a, np.float64)
                                                for a in g], axis=0),
                             *[g for _, g in parts])
        # the program hands the optimizer gradients in the params' dtype
        grads = jax.tree.map(
            lambda g, p: np.asarray(np.asarray(g, np.float32)
                                    .astype(np.asarray(p).dtype),
                                    np.float32), grads, params)
        return loss, grads

    def train(self, params0, batches, hyper: dict, *, steps: int = 3,
              fault: str | None = None, dp: int = 1) -> dict:
        """Follow the program's first ``steps`` AdamW steps from
        ``params0`` over ``batches``.  Returns each step's loss, the first
        gradient as the optimizer takes it (after clipping), and the
        parameters after the last step.

        ``fault`` plants a fault in the reference, for calibration:
        ``half_batch`` takes the mean over the first half of each batch;
        ``no_exchange`` leaves out the gradient exchange of ``dp`` data
        shards, each shard stepping on its own gradient, scaled by 1/dp,
        and reports shard 0's state."""
        shards = dp if fault == "no_exchange" else 1
        states = [{"p": params0, "m": jax.tree.map(np.zeros_like, _f32(params0)),
                   "v": jax.tree.map(np.zeros_like, _f32(params0))}
                  for _ in range(shards)]
        losses, first = [], None
        for k in range(steps):
            b = batches[k]
            n = len(b["noisy"])
            lr = lr_at(k, **hyper)
            loss_k = 0.0
            for s, st in enumerate(states):
                if fault == "half_batch":
                    rows = np.arange(n // 2)
                elif shards > 1:
                    rows = np.arange(s * n // shards, (s + 1) * n // shards)
                else:
                    rows = None
                loss, g = self.loss_and_grad(st["p"], b, rows)
                if shards > 1:
                    g = jax.tree.map(lambda a: a / shards, g)
                    loss = loss / shards
                loss_k += loss
                g = _adamw(st, g, k + 1, lr)
                if s == 0 and k == 0:
                    first = g
            losses.append(loss_k)
        return {"losses": losses, "first_grad": first,
                "params_after": _f32(states[0]["p"])}

    def causal(self, params, inputs: np.ndarray):
        """One-shot causal forward over rows of ``inputs`` (n, L) ->
        float32 (signal, peak_logits) arrays of the same shape."""
        on_dev = {d: jax.device_put(params, d) for d in self.devices}
        outs = [(r0, self._causal(on_dev[d],
                                  jax.device_put(inputs[r0:r1], d)))
                for d, r0, r1 in self._blocks(len(inputs))]
        sig = np.zeros(inputs.shape, np.float32)
        peak = np.zeros(inputs.shape, np.float32)
        for r0, (s, p) in outs:
            sig[r0:r0 + len(s)] = np.asarray(s)
            peak[r0:r0 + len(p)] = np.asarray(p)
        return sig, peak


def _adamw(st: dict, grads, count: int, lr: float):
    """One AdamW update of ``st`` in place; returns the clipped gradient."""
    g32 = _f32(grads)
    gnorm = np.sqrt(np.float32(sum(np.vdot(g, g) for g in
                                   jax.tree.leaves(g32))))
    scale = (np.float32(GRAD_CLIP / (gnorm + 1e-9)) if gnorm > GRAD_CLIP
             else np.float32(1.0))
    g32 = jax.tree.map(lambda g: g * scale, g32)
    b1c = np.float32(1 - B1 ** count)
    b2c = np.float32(1 - B2 ** count)
    st["m"] = jax.tree.map(lambda m, g: np.float32(B1) * m
                           + np.float32(1 - B1) * g, st["m"], g32)
    st["v"] = jax.tree.map(lambda v, g: np.float32(B2) * v
                           + np.float32(1 - B2) * (g * g), st["v"], g32)

    def step(p, m, v):
        p = np.asarray(p)
        upd = (m / b1c) / (np.sqrt(v / b2c) + np.float32(EPS))
        if p.ndim >= 2:
            upd = upd + np.float32(WEIGHT_DECAY) * p.astype(np.float32)
        return (p.astype(np.float32) - np.float32(lr) * upd).astype(p.dtype)

    st["p"] = jax.tree.map(step, st["p"], st["m"], st["v"])
    return g32
