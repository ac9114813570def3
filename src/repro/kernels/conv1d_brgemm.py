"""Pallas TPU kernels for the 1D dilated convolution layer (BRGEMM formulation).

TPU adaptation of Chaudhary et al. 2021 (see DESIGN.md §2).  The paper's
LIBXSMM batch-reduce GEMM becomes an unrolled tap loop of MXU matmuls that
accumulate into a single VMEM accumulator; the paper's cache blocking along
the width dimension (block = 64 for AVX-512 L1/L2) becomes width tiling
(block = WBLK, a multiple of the 128-lane TPU tile) with the *dilated
footprint* ``F = WBLK + (S-1)*d`` staged HBM->VMEM once per tile and reused
by all S taps.

Three kernels behind one plan-driven entry (``conv1d_pass``), mirroring
the paper's Algorithms 2-4:
  * ``conv1d_fwd``          - Alg. 2 (also used for Alg. 3 / bwd-data with
                              flipped+transposed weights, see ops.py)
  * ``conv1d_bwd_weight``   - Alg. 4 (sequential-grid accumulation, the TPU
                              analogue of the paper's shared weight-gradient
                              buffer across width blocks)
  * ``depthwise_conv1d_fwd`` / ``depthwise_conv1d_bwd_weight`` - the grouped
                              (C == K) variant used by Mamba2/Zamba2 causal
                              convs; runs on the VPU instead of the MXU.

Every ``pallas_call`` carries a stable ``name`` (``conv1d_fwd``,
``conv1d_bwd_data``, ``conv1d_bwd_weight``, ``dwconv1d_fwd``,
``dwconv1d_bwd_data``, ``dwconv1d_bwd_weight``) so a profiler trace can
find each pass.

**Mosaic layout rules.**  The staged footprint is rounded up to whole
128-lane tiles (``footprint``) and every window starts at a multiple of
WBLK, so each staging copy is tile-aligned; the callers' width contract
``Wp = Qp + (S-1)*d`` is kept and the tail up to the rounded footprint is
zero-padded here.  Each tap is a *static* lane slice
``x_ref[i, :, pl.ds(s*d, WBLK)]`` read straight from the VMEM ref.  The
channel/filter dims must be multiples of the dtype's sublane tile
(``sublane_tile``: 8 rows for 32-bit, 16 for 16-bit) when compiling for
the chip: ``kernels/ops.py`` zero-pads them and slices the results back.

The dense kernels support two **formulations** of the BRGEMM contraction
(DESIGN.md §12), selected by ``alg``:

  * ``tap_loop``   — the S-step unrolled batch-reduce above: one
                     (KB, C)×(C, WBLK) matmul per tap.  For skinny channel
                     counts (the paper's C=K=15 genomics layers) each tap
                     uses ~(C/128)·(KB/128) of the 128×128 MXU.
  * ``tap_packed`` — stacks the S dilated width-slices of the staged
                     footprint into one (S·C, WBLK) VMEM operand and
                     contracts it against the host-packed (KB, S·C) weight
                     tile in a **single** MXU matmul with contraction S·C
                     (51·16 = 816 ≈ 6 full MXU passes instead of 51
                     near-empty ones).  The price is the VMEM copy that
                     materialises the packed operand.

Both formulations support **batch folding** (``nblk``): the grid batch
axis advances ``nblk`` samples per cell and their width tiles are
concatenated into the GEMM width dimension, so small-N, small-Q problems
still present a wide (nblk·WBLK) operand to the MXU and amortise the tap
block staging over nblk samples.  ``repro.tune`` searches both axes per
pass; without a tuner, ``kernels/ops.py`` (``pick_alg``) packs a pass
whose packed GEMM dimension is under one MXU tile, as every AtacWorks
pass but the heads' weight gradient is, and keeps the tap loop
otherwise.  Called directly, the kernels default to ``tap_loop``,
``nblk=1``.

Staging has two schedules, selected by ``pipe`` (DESIGN.md §15):

  * ``pipe = 0``  — the footprint is an element-indexed (overlapping
                    window) BlockSpec; Pallas's own pipeline stages it.
  * ``pipe >= 2`` — the footprint (and the cotangent tile, for
                    bwd-weight) rotates through a ``pipe``-deep VMEM
                    scratch via ``pltpu.make_async_copy`` so the next
                    tile's DMA is in flight while the current tile
                    contracts, and the forward's fused-epilogue store
                    streams out through a 2-slot buffer behind the next
                    matmul.  In interpret mode the staging falls back to
                    synchronous copies through the same buffers
                    (``REPRO_PIPE_FORCE_ASYNC=1`` forces the real schedule
                    for tests).

Both schedules run the same contraction code — same tap order, same fp32
accumulation — so they are bit-identical.

All kernels accept fp32 or bf16 inputs and accumulate in fp32
(``preferred_element_type``), matching the AVX-512-BF16 contract.

Every forward kernel supports a **fused epilogue** on the fp32 accumulator
tile, applied before the output store (DESIGN.md §10):

    y = act(conv + bias + residual)

with ``bias`` broadcast along width, ``residual`` an output-shaped tensor
staged tile-by-tile, and ``act`` one of ``repro.kernels.epilogue``'s
activations.  ``save_preact=True`` additionally stores the fp32
pre-activation ``u = conv + bias + residual`` as a second output — the VJP
(ops.py) needs it to evaluate ``act'(u)`` for non-ReLU-trivial activations.
The bwd-weight kernels optionally emit ``dbias`` (the reduction of the
cotangent over batch and width) as a second output, fused into the same
sequential-grid accumulation as the weight gradient.

Shape contract (callers — see ops.py — arrange the padding):
  x    : (N, C, Wp)   with Wp = Qp + (S-1)*d, Qp % WBLK == 0
  w    : (S, K, C)    K % kblk == 0
  bias : (K,)         residual: (N, K, Qp)
  out  : (N, K, Qp)
"""
from __future__ import annotations

import functools
import os
import time
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import obs as _obs
from .epilogue import ACTIVATIONS, canon, signature

ALGS = ("tap_loop", "tap_packed")   # dense contraction formulations (§12)
LANES = 128                          # TPU vreg lane count

# Force the real async-DMA schedule even in interpret mode (the schedule-
# equivalence tests use this; by default interpret runs the synchronous
# staging fallback — the interpreter completes "async" copies inline, so
# the lookahead schedule is pure bookkeeping there, DESIGN.md §15).
ENV_FORCE_ASYNC = "REPRO_PIPE_FORCE_ASYNC"


def canon_pipe(pipe) -> int:
    """Normalize the pipeline-depth knob: None/0/1 -> 0 (the synchronous
    kernel — a 1-deep "pipeline" has no lookahead), >= 2 -> that depth."""
    p = int(pipe or 0)
    return p if p >= 2 else 0


def sublane_tile(dtype) -> int:
    """Rows of one native (rows x 128) VMEM tile: 8 for 32-bit dtypes, 16
    for 16-bit.  Channel/filter dims that sit on the sublane axis of a
    kernel block must be multiples of it when compiling for the chip."""
    return 32 // jnp.dtype(dtype).itemsize


def footprint(wblk: int, S: int, dilation: int) -> int:
    """Staged footprint width: the dilated window ``WBLK + (S-1)*d``
    rounded up to whole 128-lane tiles (the tail is zeros no tap reads)."""
    F = wblk + (S - 1) * dilation
    return -(-F // LANES) * LANES


def _stage_width(x, Qp: int, wblk: int, Fp: int):
    """Zero-pad x's width so the last tile's rounded footprint is in
    bounds: Wx = (Qp/WBLK - 1)*WBLK + Fp >= Qp + (S-1)*d."""
    need = Qp - wblk + Fp
    W = x.shape[-1]
    return x if W >= need else jnp.pad(x, ((0, 0), (0, 0), (0, need - W)))


def _check_tiling(interpret: bool, wblk: int, **rows):
    """Refuse, with the reason, a compiled call whose blocks break the
    chip's tiling; interpret mode accepts any shape."""
    if interpret:
        return
    if wblk % LANES:
        raise ValueError(f"wblk={wblk} must be a multiple of {LANES} lanes")
    for what, (n, dtype) in rows.items():
        if n % sublane_tile(dtype):
            raise ValueError(
                f"{what}={n} is not a multiple of the {jnp.dtype(dtype).name}"
                f" sublane tile ({sublane_tile(dtype)}); kernels/ops.py pads "
                "channel and filter dims before calling the kernels")


def _lane_offset(t, wblk: int):
    """Element offset of width tile ``t``, marked as a multiple of WBLK so
    Mosaic can prove the staging copy is lane-aligned."""
    off = t * wblk
    return off if isinstance(off, int) else pl.multiple_of(off, wblk)


def _sync_staging(interpret: bool) -> bool:
    return interpret and os.environ.get(ENV_FORCE_ASYNC) != "1"


class _MultiCopy:
    """Start/wait a group of async copies as one unit (the bwd-weight
    kernels stage the footprint and the cotangent tile per grid step)."""

    def __init__(self, copies):
        self._copies = copies

    def start(self):
        for c in self._copies:
            c.start()

    def wait(self):
        for c in self._copies:
            c.wait()


def _pipe_schedule(step, total: int, depth: int, make_copy, sync: bool):
    """Rotating-buffer staging schedule over a sequential grid axis
    (DESIGN.md §15).  Tile ``t`` lives in slot ``t % depth``.

    Async (compiled TPU, or interpret under ``REPRO_PIPE_FORCE_ASYNC=1``):
    the first step starts tiles ``0..depth-2`` (warmup); every step starts
    tile ``step+depth-1`` — the slot it overwrites was consumed at step
    ``step-1`` — then waits tile ``step`` before computing from it, so
    ``depth-1`` copies are always in flight behind the contraction.

    Sync (the interpret fallback): copy tile ``step`` at use through the
    same rotating buffers — identical data flow, no lookahead.
    """
    if sync:
        c = make_copy(step)
        c.start()
        c.wait()
        return

    @pl.when(step == 0)
    def _warmup():
        for j in range(min(depth - 1, total)):
            make_copy(j).start()

    @pl.when(step + (depth - 1) < total)
    def _ahead():
        make_copy(step + (depth - 1)).start()

    make_copy(step).wait()


def _store_wait_slot(qt, make_copy, sync: bool):
    """Before writing store-buffer slot ``qt % 2``: wait for the store
    issued two tiles ago (the previous occupant of the slot)."""
    if sync:
        return

    @pl.when(qt >= 2)
    def _reuse():
        make_copy(qt - 2).wait()


def _store_start(qt, q_tiles: int, make_copy, sync: bool):
    """Issue tile ``qt``'s output store; the copy drains behind tile
    ``qt+1``'s matmul.  The final width step waits out the (up to) two
    stores still in flight."""
    c = make_copy(qt)
    c.start()
    if sync:
        c.wait()
        return

    @pl.when(qt == q_tiles - 1)
    def _drain():
        @pl.when(qt >= 1)
        def _prev():
            make_copy(qt - 1).wait()
        make_copy(qt).wait()


def default_cblk(C: int, cap: int = 512, align: int = 1) -> int:
    """Depthwise channel-tile default: the largest divisor of C that is
    <= cap and a multiple of ``align`` (C itself when C <= cap or no such
    divisor exists).  (``min(C, cap)`` is wrong for any C > cap not
    divisible by cap — e.g. C=768 tripped the ``C % cblk == 0`` contract.)
    Shared with ``tune.space``'s legality/VMEM accounting so the tuner and
    the untuned default agree on the tile actually run."""
    if C <= cap:
        return C
    divs = [d for d in range(align, cap + 1, align) if C % d == 0]
    return max(divs) if divs else C


def conv1d_pass(pass_: str, *args, depthwise: bool = False, **kw):
    """Single plan-driven entry over the three kernels (Algs. 2-4).

    ``pass_`` ∈ {'fwd', 'bwd_data', 'bwd_weight'} selects the kernel for
    the dense or (``depthwise=True``) grouped variant; everything else is
    forwarded verbatim.  bwd-data reuses the forward BRGEMM — Alg. 3 *is*
    Alg. 2 on the zero-padded cotangent with flipped, transposed weights;
    the caller (ops.py) arranges that operand transform.  Per-pass tile
    configs resolved by ``repro.tune`` (wblk + kblk/cblk) arrive here as
    plain kwargs, so the tuner, the ops-layer VJP, and a direct caller all
    drive the same dispatch.

    Each call is one kernel build: under jit it traces one ``pallas_call``
    (Mosaic lowers it later, with the step).  It runs in a
    ``kernels.build`` span and adds to the ``repro.obs`` counters
    ``kernels.build``, ``kernels.build_packed`` (the builds that contract
    in the ``tap_packed`` formulation), ``kernels.build_s`` (seconds in
    the call) and ``kernels.build_distinct`` (distinct signatures), all at
    trace time, never inside a compiled step.
    """
    prefix = "dwconv1d" if depthwise else "conv1d"
    if pass_ == "bwd_weight":
        fn = depthwise_conv1d_bwd_weight if depthwise else conv1d_bwd_weight
    elif pass_ in ("fwd", "bwd_data"):
        fn = depthwise_conv1d_fwd if depthwise else conv1d_fwd
    else:
        raise ValueError(f"unknown conv pass {pass_!r}")
    name = f"{prefix}_{pass_}"
    attrs = _build_attrs(name, args, kw) if _obs.enabled() else {}
    t0 = time.perf_counter()
    with _obs.span("kernels.build", **attrs):
        out = fn(*args, name=name, **kw)
    _obs.counter("kernels.build")
    if kw.get("alg") == "tap_packed":
        _obs.counter("kernels.build_packed")
    _obs.counter("kernels.build_s", time.perf_counter() - t0)
    _obs.distinct("kernels.build_distinct", _build_signature(name, args, kw))
    return out


_TILE_KEYS = ("wblk", "kblk", "cblk", "nblk", "alg", "pipe")


def _operand(v):
    """An argument as it enters a build signature: arrays by shape and
    dtype, everything else as given."""
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        return tuple(v.shape), jnp.dtype(v.dtype).name
    return v


def _build_signature(name: str, args, kw) -> tuple:
    return (name, tuple(_operand(a) for a in args),
            tuple(sorted((k, _operand(v)) for k, v in kw.items())))


def _build_attrs(name: str, args, kw) -> dict:
    """The ``kernels.build`` span's attrs: pass, operand shapes, dtype,
    tiles and epilogue."""
    if name.endswith("bwd_weight"):
        epilogue = "dbias" if kw.get("with_dbias") else "none"
    else:
        epilogue = signature(kw.get("bias") is not None,
                             kw.get("activation"),
                             kw.get("residual") is not None)
    return dict(kernel=name,
                shapes=",".join("x".join(map(str, a.shape)) for a in args),
                dtype=jnp.dtype(args[0].dtype).name, epilogue=epilogue,
                **{k: kw[k] for k in _TILE_KEYS if k in kw})


def _compiler_params(dimension_semantics: Sequence[str]):
    return pltpu.CompilerParams(dimension_semantics=tuple(dimension_semantics))


def _footprint_spec(block_rows: tuple[int, int], index_map):
    """Overlapping-window BlockSpec of the staged footprint.

    Adjacent width tiles' footprints overlap by ``Fp - WBLK`` elements, so
    the window axis is indexed in *elements*; Pallas requires every block
    dim to be element-indexed then, so ``index_map`` returns element
    offsets for all three axes.  ``block_rows`` is (leading, rows, Fp)."""
    return pl.BlockSpec(tuple(pl.Element(b) for b in block_rows), index_map)


# ---------------------------------------------------------------------------
# Contraction bodies, shared by both staging schedules
# ---------------------------------------------------------------------------


def _epilogue_on_acc(acc, b_ref, r, activation: str):
    """Bias + residual + activation on the fp32 accumulator tile.

    Returns (pre-activation u, activated y), both fp32.  b_ref is a
    (FB, 1) tile broadcast along width; ``r`` an (already batch-folded)
    output-shaped array, or None.
    """
    if b_ref is not None:
        acc = acc + b_ref[...].astype(jnp.float32)
    if r is not None:
        acc = acc + r.astype(jnp.float32)
    return acc, ACTIVATIONS[activation](acc)


def _folded_tap(x_ref, s: int, dilation: int, wblk: int, nblk: int):
    """Width-slice of the staged footprint for tap ``s``, batch-folded:
    (C, nblk·WBLK) — each sample's (C, WBLK) slice, read from the VMEM ref
    at a static lane offset, concatenated along the GEMM width dim."""
    cols = [x_ref[i, :, pl.ds(s * dilation, wblk)] for i in range(nblk)]
    return cols[0] if nblk == 1 else jnp.concatenate(cols, axis=1)


def _pack_taps(x_ref, S: int, dilation: int, wblk: int, nblk: int):
    """The tap-packed operand for the compiled (TPU) path: stack the S
    dilated width-slices of the staged footprint into one (S·C, nblk·WBLK)
    VMEM array, tap-major rows (row s·C + c is channel c of tap s)
    matching the host-packed (KB, S·C) weight tile — S window copies,
    native VMEM data movement."""
    return jnp.concatenate(
        [_folded_tap(x_ref, s, dilation, wblk, nblk) for s in range(S)],
        axis=0)


def _gather_taps(x_ref, S: int, dilation: int, wblk: int, nblk: int):
    """The tap-packed operand for the interpret (XLA:CPU) path, as a
    (C, S, nblk·WBLK) block: one fused gather over an iota index matrix
    per folded sample instead of S separate window-slice ops (which
    dominate when the kernel body runs as a plain XLA program), consumed
    via a multi-dimension ``dot_general`` so no transpose is ever
    materialised."""
    C = x_ref.shape[1]
    idx = (jax.lax.broadcasted_iota(jnp.int32, (S, wblk), 0) * dilation
           + jax.lax.broadcasted_iota(jnp.int32, (S, wblk), 1)).reshape(-1)
    parts = [jnp.take(x_ref[i], idx, axis=1).reshape(C, S, wblk)
             for i in range(nblk)]
    return parts[0] if nblk == 1 else jnp.concatenate(parts, axis=2)


def _mxu_operands(a, b):
    """Cast both operands to their common dtype (Mosaic's matmul takes one
    dtype; a bf16 weight against the fp32 stem input widens exactly) and
    pick the contraction precision: fp32 contracts at full fp32 on the MXU
    (Mosaic's default may take bf16 passes), bf16 needs no flag."""
    dt = jnp.promote_types(a.dtype, b.dtype)
    prec = jax.lax.Precision.HIGHEST if dt == jnp.float32 else None
    return a.astype(dt), b.astype(dt), prec


def _dot(a, b):
    """a (M, C) · b (C, W) -> (M, W), fp32 accumulation."""
    a, b, prec = _mxu_operands(a, b)
    return jnp.dot(a, b, precision=prec, preferred_element_type=jnp.float32)


def _dot_general(a, b, dims):
    a, b, prec = _mxu_operands(a, b)
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=prec,
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """a (M, W) · b (R, W)ᵀ -> (M, R) fp32, contracting the width dims —
    the MXU's transposed-rhs form, no transpose materialised."""
    return _dot_general(a, b, ((1,), (1,)))


def _fwd_acc(x_ref, w_ref, *, S: int, dilation: int, wblk: int, nblk: int,
             alg: str, gather: bool):
    """acc (KB, nblk·WBLK) fp32 for one tile.  tap_loop: S small GEMMs
    (the BRGEMM batch-reduce); tap_packed: one GEMM with contraction S·C
    against the host-packed (KB, S·C) tile."""
    if alg == "tap_packed":
        if gather:
            xg = _gather_taps(x_ref, S, dilation, wblk, nblk)   # (C, S, nW)
            wv = w_ref[...].reshape(w_ref.shape[0], S, -1)      # (KB, S, C)
            return _dot_general(wv, xg, ((1, 2), (1, 0)))
        return _dot(w_ref[...], _pack_taps(x_ref, S, dilation, wblk, nblk))
    acc = jnp.zeros((w_ref.shape[1], nblk * wblk), jnp.float32)
    for s in range(S):  # the BRGEMM batch-reduce dimension (unrolled taps)
        acc += _dot(w_ref[s], _folded_tap(x_ref, s, dilation, wblk, nblk))
    return acc


def _bwd_w_accumulate(o_ref, g, x_ref, *, S: int, dilation: int, wblk: int,
                      nblk: int, alg: str, gather: bool):
    """o_ref += this tile's weight-gradient contribution.  tap_loop: S
    (K, nW)×(nW, C) GEMMs into the (S, K, C) block; tap_packed: one
    (K, nW)×(nW, S·C) GEMM into the tap-major (K, S·C) block."""
    if alg == "tap_packed":
        if gather:
            xg = _gather_taps(x_ref, S, dilation, wblk, nblk)   # (C, S, nW)
            dwp = _dot_general(g, xg, ((1,), (2,)))
            o_ref[...] += dwp.transpose(0, 2, 1).reshape(g.shape[0], -1)
        else:
            o_ref[...] += _dot_nt(g, _pack_taps(x_ref, S, dilation, wblk,
                                                nblk))
        return
    for s in range(S):  # S small GEMMs per width block (Alg. 4 line 4)
        o_ref[s] += _dot_nt(g, _folded_tap(x_ref, s, dilation, wblk, nblk))


def _fold(ref, nblk: int):
    """(nblk, R, WBLK) tile -> (R, nblk·WBLK), matching ``_folded_tap``'s
    sample order along the GEMM width dimension."""
    return (ref[0] if nblk == 1 else
            jnp.concatenate([ref[i] for i in range(nblk)], axis=1))


# ---------------------------------------------------------------------------
# Forward (Algorithm 2) — also the bwd-data engine (Algorithm 3)
# ---------------------------------------------------------------------------


def _fwd_kernel(*refs, S: int, dilation: int, wblk: int, nblk: int, alg: str,
                gather: bool, activation: str, has_bias: bool,
                has_residual: bool, save_preact: bool):
    """One (n-fold, k-tile, q-tile) grid cell.

    x_ref : (nblk, C, Fp)    dilated footprints of nblk samples (VMEM)
    w_ref : (S, KB, C)       all taps of this filter tile  [tap_loop]
            (KB, S*C)        host-packed filter tile       [tap_packed]
    b_ref : (KB, 1)          bias tile            (iff has_bias)
    r_ref : (nblk, KB, WBLK) residual tile        (iff has_residual)
    o_ref : (nblk, KB, WBLK)
    u_ref : (nblk, KB, WBLK) fp32 pre-activation  (iff save_preact)
    """
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_residual else None
    o_ref = next(it)
    u_ref = next(it) if save_preact else None

    acc = _fwd_acc(x_ref, w_ref, S=S, dilation=dilation, wblk=wblk,
                   nblk=nblk, alg=alg, gather=gather)
    r = _fold(r_ref, nblk) if has_residual else None
    u, y = _epilogue_on_acc(acc, b_ref, r, activation)
    for i in range(nblk):  # unfold the GEMM width back into per-sample tiles
        blk = slice(i * wblk, (i + 1) * wblk)
        if save_preact:
            u_ref[i] = u[:, blk]
        o_ref[i] = y[:, blk].astype(o_ref.dtype)


def _fwd_kernel_pipe(*refs, S: int, dilation: int, wblk: int, nblk: int,
                     kblk: int, alg: str, gather: bool, activation: str,
                     has_bias: bool, has_residual: bool, save_preact: bool,
                     pipe: int, q_tiles: int, Fp: int, sync: bool):
    """Software-pipelined ``_fwd_kernel`` (DESIGN.md §15).

    x and the activated output live in ANY (HBM on TPU); the dilated
    footprint rotates through a ``pipe``-deep VMEM scratch so tile i+1's
    DMA is in flight while tile i contracts, and the epilogue store of
    tile i streams out behind tile i+1's matmul through a 2-slot buffer.
    The width axis is sequential ("arbitrary") — the rotation needs
    in-order tiles; batch/filter stay parallel.  Weight/bias/residual
    tiles keep the native Blocked pipeline (they are revisited, not
    refetched, across the width sweep).
    """
    it = iter(refs)
    x_hbm, w_ref = next(it), next(it)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_residual else None
    o_hbm = next(it)
    u_ref = next(it) if save_preact else None
    xbuf, xsem, obuf, osem = next(it), next(it), next(it), next(it)

    n, kt, qt = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def x_copy(t):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(n * nblk, nblk), :,
                     pl.ds(_lane_offset(t, wblk), Fp)],
            xbuf.at[t % pipe], xsem.at[t % pipe])

    _pipe_schedule(qt, q_tiles, pipe, x_copy, sync)
    acc = _fwd_acc(xbuf.at[qt % pipe], w_ref, S=S, dilation=dilation,
                   wblk=wblk, nblk=nblk, alg=alg, gather=gather)
    r = _fold(r_ref, nblk) if has_residual else None
    u, y = _epilogue_on_acc(acc, b_ref, r, activation)

    def o_copy(t):
        return pltpu.make_async_copy(
            obuf.at[t % 2],
            o_hbm.at[pl.ds(n * nblk, nblk), pl.ds(kt * kblk, kblk),
                     pl.ds(_lane_offset(t, wblk), wblk)],
            osem.at[t % 2])

    _store_wait_slot(qt, o_copy, sync)
    for i in range(nblk):  # unfold the GEMM width back into per-sample tiles
        blk = slice(i * wblk, (i + 1) * wblk)
        if save_preact:
            u_ref[i] = u[:, blk]
        obuf[qt % 2, i] = y[:, blk].astype(obuf.dtype)
    _store_start(qt, q_tiles, o_copy, sync)


def conv1d_fwd(
    x: jax.Array,
    w: jax.Array,
    *,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    activation: str | None = None,
    save_preact: bool = False,
    dilation: int = 1,
    wblk: int = 256,
    kblk: int | None = None,
    alg: str = "tap_loop",
    nblk: int = 1,
    pipe: int = 0,
    out_dtype=None,
    interpret: bool = False,
    name: str = "conv1d_fwd",
):
    """BRGEMM forward pass.  x: (N, C, Qp + (S-1)*d), w: (S, K, C) -> (N, K, Qp).

    Fused epilogue: ``out = act(conv + bias + residual)`` on the fp32
    accumulator (bias: (K,), residual: (N, K, Qp)).  With ``save_preact``
    returns ``(out, preact)`` where preact is the fp32 ``conv+bias+residual``.

    ``alg`` selects the contraction formulation (``tap_loop`` /
    ``tap_packed``, see module docstring); ``nblk`` folds that many samples
    into the GEMM width dimension (requires ``N % nblk == 0``).

    ``pipe >= 2`` runs the software-pipelined kernel body (DESIGN.md §15):
    the dilated footprint rotates through a ``pipe``-deep VMEM scratch via
    async copies so tile i+1's DMA overlaps tile i's contraction, and the
    fused-epilogue store streams behind the next tile's matmul.  Bit-
    identical to the synchronous kernel (same tap order, same fp32
    accumulation); in interpret mode the staging falls back to synchronous
    copies through the same buffers.  ``name`` labels the kernel in
    profiler traces (``conv1d_bwd_data`` when it runs Alg. 3).
    """
    N, C, Wp = x.shape
    S, K, Cw = w.shape
    assert C == Cw, (C, Cw)
    assert alg in ALGS, alg
    assert N % nblk == 0, (N, nblk)
    Qp = Wp - (S - 1) * dilation
    assert Qp % wblk == 0, (Qp, wblk)
    kblk = kblk or K
    assert K % kblk == 0, (K, kblk)
    out_dtype = out_dtype or x.dtype
    _check_tiling(interpret, wblk, C=(C, x.dtype), kblk=(kblk, out_dtype))
    Fp = footprint(wblk, S, dilation)
    x = _stage_width(x, Qp, wblk, Fp)
    grid = (N // nblk, K // kblk, Qp // wblk)
    activation = canon(activation)
    pipe = canon_pipe(pipe)

    if alg == "tap_packed":
        # host-side pre-pack: (S, K, C) -> (K, S*C), so the kernel's single
        # matmul contracts tap-major packed rows without an in-kernel
        # weight relayout (done once, amortised over the whole grid)
        w_in = w.transpose(1, 0, 2).reshape(K, S * C)
        w_spec = pl.BlockSpec((kblk, S * C), lambda n, kt, qt: (kt, 0))
    else:
        w_in = w
        w_spec = pl.BlockSpec((S, kblk, C), lambda n, kt, qt: (0, kt, 0))
    tile = pl.BlockSpec((nblk, kblk, wblk), lambda n, kt, qt: (n, kt, qt))
    if pipe:
        x_spec = pl.BlockSpec(memory_space=pl.ANY)
        y_spec = pl.BlockSpec(memory_space=pl.ANY)
    else:
        x_spec = _footprint_spec(
            (nblk, C, Fp),
            lambda n, kt, qt: (n * nblk, 0, _lane_offset(qt, wblk)))
        y_spec = tile
    in_specs = [x_spec, w_spec]
    inputs = [x, w_in]
    if bias is not None:
        assert bias.shape == (K,), (bias.shape, K)
        in_specs.append(pl.BlockSpec((kblk, 1), lambda n, kt, qt: (kt, 0)))
        inputs.append(bias.reshape(K, 1))
    if residual is not None:
        assert residual.shape == (N, K, Qp), (residual.shape, (N, K, Qp))
        in_specs.append(tile)
        inputs.append(residual)
    out_specs = [y_spec]
    out_shape = [jax.ShapeDtypeStruct((N, K, Qp), out_dtype)]
    if save_preact:
        out_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct((N, K, Qp), jnp.float32))

    common = dict(S=S, dilation=dilation, wblk=wblk, nblk=nblk, alg=alg,
                  gather=interpret, activation=activation,
                  has_bias=bias is not None,
                  has_residual=residual is not None, save_preact=save_preact)
    if pipe:
        kernel = functools.partial(_fwd_kernel_pipe, kblk=kblk, pipe=pipe,
                                   q_tiles=Qp // wblk, Fp=Fp,
                                   sync=_sync_staging(interpret), **common)
        scratch = [pltpu.VMEM((pipe, nblk, C, Fp), x.dtype),
                   pltpu.SemaphoreType.DMA((pipe,)),
                   pltpu.VMEM((2, nblk, kblk, wblk), out_dtype),
                   pltpu.SemaphoreType.DMA((2,))]
        dims = ("parallel", "parallel", "arbitrary")
    else:
        kernel = functools.partial(_fwd_kernel, **common)
        scratch = []
        dims = ("parallel", "parallel", "parallel")
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if save_preact else out_specs[0],
        out_shape=out_shape if save_preact else out_shape[0],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(dims),
        interpret=interpret,
        name=name,
    )(*inputs)


# ---------------------------------------------------------------------------
# Backward weight (Algorithm 4)
# ---------------------------------------------------------------------------


def _bwd_w_kernel(x_ref, g_ref, o_ref, *dbias_ref, S: int, dilation: int,
                  wblk: int, nblk: int, alg: str, gather: bool,
                  with_dbias: bool):
    """Grid (N/nblk, Q_tiles), both sequential ("arbitrary"): the gradient
    output block is revisited every step and accumulated into — the paper's
    shared weight-gradient buffer across width blocks and batch threads.

    x_ref : (nblk, C, Fp), g_ref : (nblk, K, WBLK),
    o_ref : (S, K, C) fp32 [tap_loop] or (K, S*C) fp32 [tap_packed — one
    (K, nblk·WBLK)×(nblk·WBLK, S·C) GEMM per grid step; the wrapper
    unpacks], dbias_ref : (K, 1) fp32 (iff with_dbias) — the fused
    bias-gradient reduction sum_{n,q} g, sharing the cotangent tile
    already in VMEM.
    """
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        if with_dbias:
            dbias_ref[0][...] = jnp.zeros_like(dbias_ref[0])

    g = _fold(g_ref, nblk)  # (K, nblk*WBLK)
    _bwd_w_accumulate(o_ref, g, x_ref, S=S, dilation=dilation, wblk=wblk,
                      nblk=nblk, alg=alg, gather=gather)
    if with_dbias:
        dbias_ref[0][...] += jnp.sum(g.astype(jnp.float32), axis=-1,
                                     keepdims=True)


def _bwd_w_kernel_pipe(*refs, S: int, dilation: int, wblk: int, nblk: int,
                       alg: str, gather: bool, with_dbias: bool, pipe: int,
                       nq: int, total: int, Fp: int, sync: bool):
    """Software-pipelined ``_bwd_w_kernel``: both operand tiles (footprint
    + cotangent) rotate through ``pipe``-deep VMEM scratch, indexed by the
    flattened sequential step ``n·nq + qt`` — the whole grid is one
    in-order stream, so the rotation spans batch-fold boundaries too.  The
    resident fp32 gradient block stays on the native Blocked path."""
    it = iter(refs)
    x_hbm, g_hbm = next(it), next(it)
    o_ref = next(it)
    dbias_ref = next(it) if with_dbias else None
    xbuf, xsem, gbuf, gsem = next(it), next(it), next(it), next(it)

    step = pl.program_id(0) * nq + pl.program_id(1)

    def copies(t):
        slot = t % pipe
        a, b = t // nq, t % nq
        return _MultiCopy([
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(a * nblk, nblk), :,
                         pl.ds(_lane_offset(b, wblk), Fp)],
                xbuf.at[slot], xsem.at[slot]),
            pltpu.make_async_copy(
                g_hbm.at[pl.ds(a * nblk, nblk), :,
                         pl.ds(_lane_offset(b, wblk), wblk)],
                gbuf.at[slot], gsem.at[slot])])

    _pipe_schedule(step, total, pipe, copies, sync)

    @pl.when(step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        if with_dbias:
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

    g = _fold(gbuf.at[step % pipe], nblk)      # (K, nblk*WBLK)
    _bwd_w_accumulate(o_ref, g, xbuf.at[step % pipe], S=S, dilation=dilation,
                      wblk=wblk, nblk=nblk, alg=alg, gather=gather)
    if with_dbias:
        dbias_ref[...] += jnp.sum(g.astype(jnp.float32), axis=-1,
                                  keepdims=True)


def conv1d_bwd_weight(
    x: jax.Array,
    gout: jax.Array,
    *,
    S: int,
    dilation: int = 1,
    wblk: int = 256,
    alg: str = "tap_loop",
    nblk: int = 1,
    pipe: int = 0,
    with_dbias: bool = False,
    interpret: bool = False,
    name: str = "conv1d_bwd_weight",
):
    """BRGEMM weight gradient.  x: (N, C, Qp+(S-1)d), gout: (N, K, Qp) -> (S, K, C) fp32.

    ``with_dbias`` fuses the bias gradient (the (K,) reduction of gout over
    batch and width) into the same pass and returns ``(dw, dbias)``.
    ``alg='tap_packed'`` accumulates the tap-major packed (K, S*C) gradient
    in one GEMM per grid step (unpacked to (S, K, C) on the host);
    ``nblk`` folds samples into the GEMM width as in ``conv1d_fwd``.
    """
    N, C, Wp = x.shape
    Ng, K, Qp = gout.shape
    assert N == Ng and Qp % wblk == 0 and Wp == Qp + (S - 1) * dilation
    assert alg in ALGS, alg
    assert N % nblk == 0, (N, nblk)
    _check_tiling(interpret, wblk, C=(C, x.dtype), K=(K, gout.dtype))
    Fp = footprint(wblk, S, dilation)
    x = _stage_width(x, Qp, wblk, Fp)
    grid = (N // nblk, Qp // wblk)
    packed = alg == "tap_packed"
    pipe = canon_pipe(pipe)

    if packed:
        out_specs = pl.BlockSpec((K, S * C), lambda n, qt: (0, 0))
        out_shape = jax.ShapeDtypeStruct((K, S * C), jnp.float32)
    else:
        out_specs = pl.BlockSpec((S, K, C), lambda n, qt: (0, 0, 0))
        out_shape = jax.ShapeDtypeStruct((S, K, C), jnp.float32)
    if with_dbias:
        out_specs = [out_specs, pl.BlockSpec((K, 1), lambda n, qt: (0, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((K, 1), jnp.float32)]

    common = dict(S=S, dilation=dilation, wblk=wblk, nblk=nblk, alg=alg,
                  gather=interpret, with_dbias=with_dbias)
    if pipe:
        nq = Qp // wblk
        kernel = functools.partial(
            _bwd_w_kernel_pipe, pipe=pipe, nq=nq, total=(N // nblk) * nq,
            Fp=Fp, sync=_sync_staging(interpret), **common)
        in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY)]
        scratch = [pltpu.VMEM((pipe, nblk, C, Fp), x.dtype),
                   pltpu.SemaphoreType.DMA((pipe,)),
                   pltpu.VMEM((pipe, nblk, K, wblk), gout.dtype),
                   pltpu.SemaphoreType.DMA((pipe,))]
    else:
        kernel = functools.partial(_bwd_w_kernel, **common)
        in_specs = [
            _footprint_spec((nblk, C, Fp), lambda n, qt: (
                n * nblk, 0, _lane_offset(qt, wblk))),
            pl.BlockSpec((nblk, K, wblk), lambda n, qt: (n, 0, qt)),
        ]
        scratch = []

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(("arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(x, gout)
    dw, db = out if with_dbias else (out, None)
    if packed:  # unpack (K, S*C) tap-major rows back to the (S, K, C) layout
        dw = dw.reshape(K, S, C).transpose(1, 0, 2)
    if with_dbias:
        return dw, db.reshape(K)
    return dw


# ---------------------------------------------------------------------------
# Depthwise (grouped, C == K) variant — Mamba2 / Zamba2 causal conv
# ---------------------------------------------------------------------------


def _dw_acc(x_ref, w_ref, *, S: int, dilation: int, wblk: int):
    """(CB, WBLK) fp32 VPU fma chain.  x_ref: (1, CB, Fp) staged footprint;
    w_ref: (CB, S) channel-major taps, read one (CB, 1) column per tap."""
    acc = jnp.zeros((x_ref.shape[1], wblk), jnp.float32)
    for s in range(S):
        acc += (w_ref[:, pl.ds(s, 1)].astype(jnp.float32)
                * x_ref[0, :, pl.ds(s * dilation, wblk)].astype(jnp.float32))
    return acc


def _dw_fwd_kernel(*refs, S: int, dilation: int, wblk: int, activation: str,
                   has_bias: bool, has_residual: bool, save_preact: bool):
    """x_ref: (1, CB, Fp), w_ref: (CB, S), [b_ref: (CB, 1)],
    [r_ref: (1, CB, WBLK)], o_ref: (1, CB, WBLK), [u_ref]."""
    it = iter(refs)
    x_ref, w_ref = next(it), next(it)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_residual else None
    o_ref = next(it)
    u_ref = next(it) if save_preact else None

    acc = _dw_acc(x_ref, w_ref, S=S, dilation=dilation, wblk=wblk)
    u, y = _epilogue_on_acc(acc, b_ref,
                            r_ref[0] if has_residual else None, activation)
    if save_preact:
        u_ref[0] = u
    o_ref[0] = y.astype(o_ref.dtype)


def _dw_fwd_kernel_pipe(*refs, S: int, dilation: int, wblk: int, cblk: int,
                        activation: str, has_bias: bool, has_residual: bool,
                        save_preact: bool, pipe: int, q_tiles: int, Fp: int,
                        sync: bool):
    """Software-pipelined ``_dw_fwd_kernel``: same rotation/streaming as
    the dense forward, on (1, cblk, ·) tiles of the VPU fma chain."""
    it = iter(refs)
    x_hbm, w_ref = next(it), next(it)
    b_ref = next(it) if has_bias else None
    r_ref = next(it) if has_residual else None
    o_hbm = next(it)
    u_ref = next(it) if save_preact else None
    xbuf, xsem, obuf, osem = next(it), next(it), next(it), next(it)

    n, ct, qt = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def x_copy(t):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(n, 1), pl.ds(ct * cblk, cblk),
                     pl.ds(_lane_offset(t, wblk), Fp)],
            xbuf.at[t % pipe], xsem.at[t % pipe])

    _pipe_schedule(qt, q_tiles, pipe, x_copy, sync)
    acc = _dw_acc(xbuf.at[qt % pipe], w_ref, S=S, dilation=dilation,
                  wblk=wblk)
    u, y = _epilogue_on_acc(acc, b_ref,
                            r_ref[0] if has_residual else None, activation)
    if save_preact:
        u_ref[0] = u

    def o_copy(t):
        return pltpu.make_async_copy(
            obuf.at[t % 2],
            o_hbm.at[pl.ds(n, 1), pl.ds(ct * cblk, cblk),
                     pl.ds(_lane_offset(t, wblk), wblk)],
            osem.at[t % 2])

    _store_wait_slot(qt, o_copy, sync)
    obuf[qt % 2, 0] = y.astype(obuf.dtype)
    _store_start(qt, q_tiles, o_copy, sync)


def depthwise_conv1d_fwd(
    x: jax.Array,
    w: jax.Array,
    *,
    bias: jax.Array | None = None,
    residual: jax.Array | None = None,
    activation: str | None = None,
    save_preact: bool = False,
    dilation: int = 1,
    wblk: int = 256,
    cblk: int | None = None,
    pipe: int = 0,
    out_dtype=None,
    interpret: bool = False,
    name: str = "dwconv1d_fwd",
):
    """Depthwise forward.  x: (N, C, Qp+(S-1)d), w: (S, C) -> (N, C, Qp).

    Same fused epilogue contract as ``conv1d_fwd`` with bias: (C,) and
    residual: (N, C, Qp); ``save_preact`` returns ``(out, preact)``.
    """
    N, C, Wp = x.shape
    S, Cw = w.shape
    assert C == Cw
    Qp = Wp - (S - 1) * dilation
    assert Qp % wblk == 0
    cblk = cblk or default_cblk(C)
    assert C % cblk == 0, (C, cblk)
    out_dtype = out_dtype or x.dtype
    _check_tiling(interpret, wblk, cblk=(cblk, x.dtype),
                  out_cblk=(cblk, out_dtype))
    Fp = footprint(wblk, S, dilation)
    x = _stage_width(x, Qp, wblk, Fp)
    grid = (N, C // cblk, Qp // wblk)
    activation = canon(activation)
    pipe = canon_pipe(pipe)

    tile = pl.BlockSpec((1, cblk, wblk), lambda n, ct, qt: (n, ct, qt))
    # channel-major taps: the kernel reads one (cblk, 1) column per tap
    w_spec = pl.BlockSpec((cblk, S), lambda n, ct, qt: (ct, 0))
    if pipe:
        x_spec = pl.BlockSpec(memory_space=pl.ANY)
        y_spec = pl.BlockSpec(memory_space=pl.ANY)
        dims = ("parallel", "parallel", "arbitrary")
    else:
        x_spec = _footprint_spec((1, cblk, Fp), lambda n, ct, qt: (
            n, ct * cblk, _lane_offset(qt, wblk)))
        y_spec = tile
        dims = ("parallel", "parallel", "parallel")
    in_specs = [x_spec, w_spec]
    inputs = [x, w.T]
    if bias is not None:
        assert bias.shape == (C,), (bias.shape, C)
        in_specs.append(pl.BlockSpec((cblk, 1), lambda n, ct, qt: (ct, 0)))
        inputs.append(bias.reshape(C, 1))
    if residual is not None:
        assert residual.shape == (N, C, Qp), (residual.shape, (N, C, Qp))
        in_specs.append(tile)
        inputs.append(residual)

    out_specs = [y_spec]
    out_shape = [jax.ShapeDtypeStruct((N, C, Qp), out_dtype)]
    if save_preact:
        out_specs.append(tile)
        out_shape.append(jax.ShapeDtypeStruct((N, C, Qp), jnp.float32))

    common = dict(S=S, dilation=dilation, wblk=wblk, activation=activation,
                  has_bias=bias is not None,
                  has_residual=residual is not None, save_preact=save_preact)
    if pipe:
        kernel = functools.partial(
            _dw_fwd_kernel_pipe, cblk=cblk, pipe=pipe, q_tiles=Qp // wblk,
            Fp=Fp, sync=_sync_staging(interpret), **common)
        scratch = [pltpu.VMEM((pipe, 1, cblk, Fp), x.dtype),
                   pltpu.SemaphoreType.DMA((pipe,)),
                   pltpu.VMEM((2, 1, cblk, wblk), out_dtype),
                   pltpu.SemaphoreType.DMA((2,))]
    else:
        kernel = functools.partial(_dw_fwd_kernel, **common)
        scratch = []

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs if save_preact else out_specs[0],
        out_shape=out_shape if save_preact else out_shape[0],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(dims),
        interpret=interpret,
        name=name,
    )(*inputs)


def _dw_bwd_w_accumulate(o_ref, g, x_ref, *, S: int, dilation: int,
                         wblk: int):
    """o_ref (CB, S) += per-tap width reductions of g * tap, one (CB, 1)
    column per tap."""
    for s in range(S):
        b = x_ref[0, :, pl.ds(s * dilation, wblk)].astype(jnp.float32)
        o_ref[:, pl.ds(s, 1)] += jnp.sum(g * b, axis=-1, keepdims=True)


def _dw_bwd_w_kernel(x_ref, g_ref, o_ref, *dbias_ref, S: int, dilation: int,
                     wblk: int, with_dbias: bool):
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    @pl.when(first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        if with_dbias:
            dbias_ref[0][...] = jnp.zeros_like(dbias_ref[0])

    g = g_ref[0].astype(jnp.float32)  # (CB, WBLK)
    _dw_bwd_w_accumulate(o_ref, g, x_ref, S=S, dilation=dilation, wblk=wblk)
    if with_dbias:
        dbias_ref[0][...] += jnp.sum(g, axis=-1, keepdims=True)


def _dw_bwd_w_kernel_pipe(*refs, S: int, dilation: int, wblk: int, cblk: int,
                          with_dbias: bool, pipe: int, nq: int, nc: int,
                          total: int, Fp: int, sync: bool):
    """Software-pipelined ``_dw_bwd_w_kernel``: footprint + cotangent tiles
    rotate on the flattened (n·nq + qt)·nc + ct sequential step."""
    it = iter(refs)
    x_hbm, g_hbm = next(it), next(it)
    o_ref = next(it)
    dbias_ref = next(it) if with_dbias else None
    xbuf, xsem, gbuf, gsem = next(it), next(it), next(it), next(it)

    step = ((pl.program_id(0) * nq + pl.program_id(1)) * nc
            + pl.program_id(2))
    first = (pl.program_id(0) == 0) & (pl.program_id(1) == 0)

    def copies(t):
        slot = t % pipe
        n, r = t // (nq * nc), t % (nq * nc)
        qi, ci = r // nc, r % nc
        return _MultiCopy([
            pltpu.make_async_copy(
                x_hbm.at[pl.ds(n, 1), pl.ds(ci * cblk, cblk),
                         pl.ds(_lane_offset(qi, wblk), Fp)],
                xbuf.at[slot], xsem.at[slot]),
            pltpu.make_async_copy(
                g_hbm.at[pl.ds(n, 1), pl.ds(ci * cblk, cblk),
                         pl.ds(_lane_offset(qi, wblk), wblk)],
                gbuf.at[slot], gsem.at[slot])])

    _pipe_schedule(step, total, pipe, copies, sync)

    @pl.when(first)  # each (cblk, S) block zeroed at its first visit
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        if with_dbias:
            dbias_ref[...] = jnp.zeros_like(dbias_ref)

    g = gbuf[step % pipe, 0].astype(jnp.float32)  # (CB, WBLK)
    _dw_bwd_w_accumulate(o_ref, g, xbuf.at[step % pipe], S=S,
                         dilation=dilation, wblk=wblk)
    if with_dbias:
        dbias_ref[...] += jnp.sum(g, axis=-1, keepdims=True)


def depthwise_conv1d_bwd_weight(
    x: jax.Array,
    gout: jax.Array,
    *,
    S: int,
    dilation: int = 1,
    wblk: int = 256,
    cblk: int | None = None,
    pipe: int = 0,
    with_dbias: bool = False,
    interpret: bool = False,
    name: str = "dwconv1d_bwd_weight",
):
    """Depthwise weight gradient -> (S, C) fp32.

    ``with_dbias`` fuses the (C,) bias-gradient reduction into the same
    sequential-grid pass and returns ``(dw, dbias)``.
    """
    N, C, Wp = x.shape
    Ng, Cg, Qp = gout.shape
    assert N == Ng and C == Cg and Qp % wblk == 0
    cblk = cblk or default_cblk(C)
    assert C % cblk == 0
    _check_tiling(interpret, wblk, cblk=(cblk, x.dtype),
                  g_cblk=(cblk, gout.dtype))
    Fp = footprint(wblk, S, dilation)
    x = _stage_width(x, Qp, wblk, Fp)
    grid = (N, Qp // wblk, C // cblk)
    pipe = canon_pipe(pipe)

    # channel-major (C, S) gradient: the kernel accumulates one (cblk, 1)
    # column per tap; transposed back to (S, C) below
    out_specs = pl.BlockSpec((cblk, S), lambda n, qt, ct: (ct, 0))
    out_shape = jax.ShapeDtypeStruct((C, S), jnp.float32)
    if with_dbias:
        out_specs = [out_specs, pl.BlockSpec((cblk, 1), lambda n, qt, ct: (ct, 0))]
        out_shape = [out_shape, jax.ShapeDtypeStruct((C, 1), jnp.float32)]

    if pipe:
        nq, nc = Qp // wblk, C // cblk
        kernel = functools.partial(
            _dw_bwd_w_kernel_pipe, S=S, dilation=dilation, wblk=wblk,
            cblk=cblk, with_dbias=with_dbias, pipe=pipe, nq=nq, nc=nc,
            total=N * nq * nc, Fp=Fp, sync=_sync_staging(interpret))
        in_specs = [pl.BlockSpec(memory_space=pl.ANY),
                    pl.BlockSpec(memory_space=pl.ANY)]
        scratch = [pltpu.VMEM((pipe, 1, cblk, Fp), x.dtype),
                   pltpu.SemaphoreType.DMA((pipe,)),
                   pltpu.VMEM((pipe, 1, cblk, wblk), gout.dtype),
                   pltpu.SemaphoreType.DMA((pipe,))]
    else:
        kernel = functools.partial(
            _dw_bwd_w_kernel, S=S, dilation=dilation, wblk=wblk,
            with_dbias=with_dbias)
        in_specs = [
            _footprint_spec((1, cblk, Fp), lambda n, qt, ct: (
                n, ct * cblk, _lane_offset(qt, wblk))),
            pl.BlockSpec((1, cblk, wblk), lambda n, qt, ct: (n, ct, qt)),
        ]
        scratch = []

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=name,
    )(x, gout)
    dw, db = out if with_dbias else (out, None)
    if with_dbias:
        return dw.T, db.reshape(C)
    return dw.T
