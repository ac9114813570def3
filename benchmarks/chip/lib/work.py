"""Operations and bytes a dilated conv1d pass needs, at logical shapes.

Shapes are the model's own: channel counts as configured (15, not the 16
the kernels pad to), the output width Q of the layer, no width round-up.
So the work that a roofline share divides by stays the same whatever
tiling, padding or formulation the program picks.

The forward counts are copies of ``conv1d_flops`` and ``conv1d_min_bytes``
in the program's ``roofline/flops.py``; the two backward passes are added
here.  Dilation moves taps; it does not change the count.
"""
from __future__ import annotations

PASSES = ("fwd", "bwd_data", "bwd_weight")


def conv1d_flops(N: int, C: int, K: int, S: int, Q: int) -> float:
    """Multiply-adds times two of one pass: each pass is the same
    contraction, Out[k,q] = sum_{c,s} In[c, q + d*s] W[s,k,c], read three
    ways (forward, data gradient, weight gradient)."""
    return 2.0 * N * C * K * S * Q


def conv1d_min_bytes(pass_: str, N: int, C: int, K: int, S: int, Q: int,
                     dilation: int, bytes_per_elem: int) -> float:
    """Least HBM traffic of one pass: each operand read once, the result
    written once.  The input spans W = Q + (S-1)*dilation columns.

    fwd:        read x (N,C,W) and w (S,K,C), write y (N,K,Q)
    bwd_data:   read dy (N,K,Q) and w, write dx (N,C,W)
    bwd_weight: read x and dy, write dw (S,K,C)
    """
    W = Q + (S - 1) * dilation
    x, y, w = N * C * W, N * K * Q, S * K * C
    if pass_ not in PASSES:
        raise ValueError(f"unknown conv pass {pass_!r}")
    return float(bytes_per_elem * (x + y + w))


def step_work(layers, *, N: int, Q: int, S: int, dilation: int,
              bytes_per_elem: int, passes=PASSES) -> dict:
    """Per-pass totals over ``layers``, each ``(C, K, needs_dx)``: a layer
    whose input is data (the stem) needs no data gradient.  Returns
    ``{pass: {"flops": f, "bytes": b}}``; forward only when ``passes`` is
    ``("fwd",)``."""
    out = {p: {"flops": 0.0, "bytes": 0.0} for p in passes}
    for C, K, needs_dx in layers:
        for p in passes:
            if p == "bwd_data" and not needs_dx:
                continue
            out[p]["flops"] += conv1d_flops(N, C, K, S, Q)
            out[p]["bytes"] += conv1d_min_bytes(p, N, C, K, S, Q, dilation,
                                                bytes_per_elem)
    return out


def roofline_seconds(work: dict, peak: dict) -> float:
    """Least time the chip could take for ``work`` (``step_work``'s
    result): per pass the larger of its FLOP time and its byte time."""
    return sum(max(w["flops"] / peak["flops_per_s"],
                   w["bytes"] / peak["hbm_bytes_per_s"])
               for w in work.values())


def total_flops(work: dict) -> float:
    return sum(w["flops"] for w in work.values())
