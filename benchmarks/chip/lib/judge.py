"""The numbers that decide ``correct``, and the verdict against limits.

Training (per cell, over the first three steps of the timed step):

* ``loss_gap``: the largest |loss - ref| / |ref| over the steps;
* ``grad_gap``: over the parameter leaves, the largest gap between the
  norm of the program's first gradient, as the optimizer took it, and the
  reference's, over the larger of that leaf's reference norm and the
  median leaf's;
* ``update_gap``: the same gap for each leaf's change over the steps.
  Leaves whose reference gradient is under a thousandth of the median
  leaf's move by round-off alone and are left out.

Serving: ``signal_gap`` and ``peak_gap``, the largest over the checked
streams of max|served - ref| / max|ref| of the stream.
"""
from __future__ import annotations

import math

import jax
import numpy as np

QUIET_LEAF = 1e-3


def _leaves(tree) -> list[np.ndarray]:
    return [np.asarray(a, np.float64) for a in jax.tree.leaves(tree)]


def _norms(tree) -> np.ndarray:
    return np.array([np.linalg.norm(a) for a in _leaves(tree)])


def norm_gap(prog, ref, keep=None) -> float:
    p, r = _norms(prog), _norms(ref)
    keep = np.ones(len(r), bool) if keep is None else keep
    floor = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r)[keep] / floor[keep]))


def train_numbers(prog: dict, ref: dict, params0) -> dict:
    """``prog`` and ``ref`` each hold ``losses``, ``first_grad`` and
    ``params_after``; both trees have the layout of ``params0``."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    g_ref = _norms(ref["first_grad"])
    keep = g_ref >= QUIET_LEAF * np.median(g_ref)
    p0 = _leaves(params0)

    def change(after):
        return [a - b for a, b in zip(_leaves(after), p0)]

    return {
        "loss_gap": float(loss_gap),
        "grad_gap": norm_gap(prog["first_grad"], ref["first_grad"]),
        "update_gap": norm_gap(change(prog["params_after"]),
                               change(ref["params_after"]), keep),
    }


def stream_numbers(served: list, ref: list) -> dict:
    """``served`` and ``ref``: per stream, (signal, peak_logits) arrays."""
    out = {"signal_gap": 0.0, "peak_gap": 0.0}
    for (s, p), (rs, rp) in zip(served, ref):
        for key, got, want in (("signal_gap", s, rs), ("peak_gap", p, rp)):
            scale = max(float(np.max(np.abs(want))), 1e-30)
            gap = float(np.max(np.abs(np.asarray(got, np.float64) - want)))
            out[key] = max(out[key], gap / scale)
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every limited number must be finite and at most its limit; a number
    the run could not produce fails."""
    checks = {}
    ok = True
    for name, lim in limits["numbers"].items():
        v = numbers.get(name, math.nan)
        checks[name] = {"value": v, "limit": lim["limit"]}
        ok &= bool(math.isfinite(v) and v <= lim["limit"])
    return ok, checks
