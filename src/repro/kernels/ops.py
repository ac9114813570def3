"""Public, jit-friendly ops wrapping the Pallas BRGEMM conv1d kernels.

``conv1d`` / ``depthwise_conv1d`` are the layer-facing entry points:
  * padding modes VALID (paper's pre-padded contract), SAME, CAUSAL
  * a **fused epilogue** ``y = act(conv + bias + residual)`` applied on the
    kernel's fp32 accumulator tile (DESIGN.md §10) — bias-add, activation
    (relu/gelu/silu), and residual-add never round-trip through HBM as
    separate ops
  * backend dispatch: 'pallas' (TPU target / interpret on CPU),
    'xla' (lax.conv_general_dilated — the vendor-library baseline and the
    fast CPU path; the epilogue is applied as fp32 jnp ops, same math),
    'ref' (readable oracle), 'auto' (per-shape choice of backend AND tile
    sizes via the tuning subsystem, repro.tune — fused and unfused
    instances of a shape tune independently, keyed by the epilogue
    signature)
  * a ``jax.custom_vjp`` that binds the paper's Alg. 3 (bwd-data via the fwd
    BRGEMM kernel on flipped+transposed weights) and Alg. 4 (bwd-weight
    kernel) into autodiff, extended for the epilogue: the activation
    gradient masks the cotangent (against the fp32 pre-activation saved by
    the forward when the activation is non-trivial), ``dbias`` is a fused
    reduction inside the bwd-weight kernel, and ``dresidual`` is the masked
    cotangent passed through.
  * **per-pass execution configs** (``PassConfig``): each backward pass of
    the custom VJP runs its own resolved (backend, wblk, kblk/cblk) — under
    ``backend='auto'`` the tuning subsystem resolves all three passes
    through their own ``ConvProblem`` keys (bwd-data over the transposed
    (C↔K) GEMM it actually runs, bwd-weight over its sequential grid)
    instead of the backward inheriting the forward's tiles; without a plan
    the bwd-data filter tile falls back to the divisor-of-C ``pick_kblk``
    ladder rather than running untiled.
  * **data-parallel gradient reduction** (``grad_reduce_axes``,
    DESIGN.md §13): inside a ``shard_map`` whose named axes shard the
    batch, the weight/bias gradients of a batch-replicated parameter are
    *partial* sums — each shard only saw its local samples.  Passing the
    mesh axis name(s) fuses a ``lax.psum`` of (dw, dbias) directly after
    the bwd-weight pass, on the kernel's fp32 accumulator, so the
    all-reduce of one layer overlaps the backward compute of the layers
    below it.  ``dx``/``dresidual`` stay local (they are batch-sharded).
    The same contract holds on every backend: the xla/ref paths (no
    custom VJP) reduce through an identity-with-psum-cotangent wrapper on
    w/bias.  ``kernels/sharded.py`` wraps all of this into batch-sharded
    entry points.
  * **model-axis sharded contraction** (``model_reduce_axes``,
    DESIGN.md §17): inside a ``shard_map`` that shards the *filter*
    dimension K over a tensor-parallel mesh axis, the forward and
    bwd-weight passes are psum-free (each shard owns its filter rows),
    but bwd-data contracts over the sharded K — each shard's ``dx`` is a
    partial sum.  Passing the model axis name(s) finishes that
    contraction with a ``lax.psum`` fused after the bwd-data pass;
    ``model_reduce_chunks`` > 1 splits it across disjoint width chunks so
    chunk i's all-reduce overlaps chunk i+1's contraction (the §15
    machinery applied to the activation-gradient collective).  Dense
    only: a channel-group-sharded depthwise conv has no cross-shard
    contraction, so ``depthwise_conv1d`` rejects the argument.

Blocking bookkeeping lives here: width is padded up to a multiple of the
width tile WBLK and sliced back, mirroring the paper's "block length 64"
discipline with TPU-native tile sizes.
"""
from __future__ import annotations

import functools
import os
from typing import Literal, NamedTuple

import jax
import jax.numpy as jnp

from . import conv1d_brgemm as _k
from . import epilogue as _ep
from . import ref as _ref
from .. import obs as _obs

Padding = Literal["VALID", "SAME", "CAUSAL"]

# Per-channel-row VMEM footprint cap for the static tile ladder: one width
# tile stages F = WBLK + (S-1)*d elements per channel row (16 KiB fp32 at
# 4096).  ``repro.tune.space`` imports this so the tuner's legality filter
# and the untuned ladder agree on what "fits".
MAX_FOOTPRINT_ELEMS = 4096


def _interpret_default() -> bool:
    """Pallas runs compiled on a TPU and interpreted everywhere else —
    decided per call from the platform the program is built for, never at
    import."""
    return jax.default_backend() != "tpu"


def default_backend() -> str:
    env = os.environ.get("REPRO_CONV_BACKEND")
    if env:
        return env
    # Pallas is the TPU target; on CPU the honest fast path is XLA's conv
    # (interpret-mode Pallas is a correctness tool, not a perf tool).
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _obs_conv(pass_: str, thunk, *, attrs):
    """Run one conv1d pass, logging its resolved config under telemetry
    (repro.obs, DESIGN.md §14) as a ``conv1d.<pass>.trace`` event, eager
    or traced alike.  Nothing is timed here: the host cannot time a pass
    inside a compiled program, and the device trace names each kernel by
    pass and layer scope.  No jnp ops are added, so enabling telemetry
    cannot retrace or alter a jaxpr; the disabled path is a single
    ``enabled()`` check before any dict is built."""
    if _obs.enabled():
        _obs.event(f"conv1d.{pass_}.trace", **attrs)
    return thunk()


class PassConfig(NamedTuple):
    """Resolved execution config of one pass of the custom VJP (hashable —
    it travels inside the nondiff ``_FusedSpec``).  ``blk2`` is the pass's
    second tile knob: the filter tile of the pass's GEMM on the dense path
    (tiles K for the forward, C for bwd-data's transposed GEMM, unused for
    bwd-weight), cblk on the depthwise path.  ``alg`` selects the dense
    contraction formulation (tap_loop / tap_packed, DESIGN.md §12) and
    ``nblk`` the batch fold; both default to the historical kernel (None ->
    tap_loop / 1) so legacy 3-tuples keep converting."""
    backend: str = "pallas"      # 'pallas' | 'xla'
    wblk: int | None = None
    blk2: int | None = None
    alg: str | None = None       # 'tap_loop' | 'tap_packed' (dense pallas)
    nblk: int | None = None      # batch fold (dense pallas)
    pipe: int | None = None      # software-pipeline depth (None/0 -> sync)


def _as_pass_cfg(cfg) -> PassConfig | None:
    if cfg is None or isinstance(cfg, PassConfig):
        return cfg
    return PassConfig(*cfg)


def _resolve_auto(x, *, C, K, S, dilation, padding, wblk, kblk, depthwise,
                  epilogue="none"):
    """backend='auto': ask the tuner (repro.tune) for a full per-pass plan.

    Runs at trace time on static shape info only.  All three passes (fwd,
    bwd_data, bwd_weight) resolve through their own ``ConvProblem`` keys.
    The forward: cache hit -> cached winner; miss -> measured search iff
    REPRO_TUNE=1, else the heuristic default.  The backward passes resolve
    from the cache or the static defaults only — an in-place measured
    search here would tune gradients the program may never compute
    (forward-only inference traces this same path); measured backward
    entries come from ``scripts/tune.py`` or an explicit
    ``tune.get_config(..., pass_=..., allow_measure=True)``.  Explicit
    wblk/kblk args still win over the tuner's forward choice.
    ``epilogue`` is the fusion signature (epilogue.signature) — part of
    every pass's cache key, so a fused conv never reuses the unfused
    instance's tiles.

    Returns ``(backend, wblk, kblk, alg, nblk, pipe, (bwd_data_cfg,
    bwd_weight_cfg))``.
    """
    from repro import tune  # late import: tune.measure calls back into ops

    N = x.shape[0]
    Q = x.shape[-1] - (S - 1) * dilation
    kw = dict(N=N, C=C, K=K, S=S, dilation=dilation, Q=Q, dtype=x.dtype,
              padding=padding, depthwise=depthwise, epilogue=epilogue)
    fwd = tune.get_config(**kw)
    bwd = []
    for p in ("bwd_data", "bwd_weight"):
        cfg = tune.get_config(**kw, pass_=p, allow_measure=False)
        bwd.append(PassConfig(cfg.backend, cfg.wblk, cfg.kblk, cfg.alg,
                              cfg.nblk, cfg.pipe))
    return (fwd.backend, wblk or fwd.wblk, kblk or fwd.kblk, fwd.alg,
            fwd.nblk, fwd.pipe, tuple(bwd))


def _pad_amounts(S: int, dilation: int, padding: Padding) -> tuple[int, int]:
    span = (S - 1) * dilation
    if padding == "VALID":
        return 0, 0
    if padding == "SAME":
        return span // 2, span - span // 2
    if padding == "CAUSAL":
        return span, 0
    raise ValueError(padding)


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def pick_wblk(Q: int, S: int, dilation: int) -> int:
    """Width-tile choice (the paper's 'block length' adapted to TPU lanes).

    Largest multiple of the 128-lane tile that (a) the problem width fills
    and (b) keeps the dilated footprint ``F = WBLK + (S-1)*d`` under the
    per-row VMEM cap shared with ``tune.space`` (MAX_FOOTPRINT_ELEMS) —
    huge spans fall through to the 128 floor rather than staging
    multi-MiB windows per channel row.
    """
    span = (S - 1) * dilation
    for cand in (512, 256, 128):
        if Q >= cand and cand + span <= MAX_FOOTPRINT_ELEMS:
            return cand
    return 128


def pick_kblk(n_filters: int) -> int:
    """Divisor-of-n ladder for a pass's filter tile — the static fallback
    when no tuned per-pass config exists (notably bwd-data, whose
    transposed GEMM tiles C, not the K its forward tuned for).  Largest
    ladder entry dividing ``n_filters``; the dimension itself (untiled)
    only when nothing on the ladder divides it."""
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if n_filters % cand == 0:
            return cand
    return n_filters


def _legal_nblk(nblk: int | None, N: int) -> int:
    """A batch fold is usable only when it divides the batch; anything else
    (including a tuned nblk applied to a different batch at trace time)
    falls back to the unfolded kernel."""
    return nblk if nblk and N % nblk == 0 else 1


def _pad_axis(a, axis: int, size: int):
    """Zero-pad ``a`` along ``axis`` up to ``size`` (None passes through)."""
    if a is None or a.shape[axis] == size:
        return a
    pads = [(0, 0)] * a.ndim
    pads[axis] = (0, size - a.shape[axis])
    return jnp.pad(a, pads)


def _kernel_pass(pass_: str, x, b, *, depthwise: bool = False, bias=None,
                 residual=None, kblk=None, cblk=None, out_dtype=None, **kw):
    """Run one Pallas pass with the channel and filter dims zero-padded to
    the sublane tile of the widest-tiled dtype involved (8 rows for fp32,
    16 for bf16), then slice the results back.

    Zero input channels add exact zeros to every contraction and zero
    filters only produce rows that are dropped, so results are unchanged;
    the padding is what lets the AtacWorks layers (C=K=15, the stem's
    C_in=1, the heads' K=1) compile for Mosaic.  ``b`` is the weight for
    fwd/bwd_data and the cotangent for bwd_weight.  A kblk/cblk that does
    not tile the padded dim falls back to a legal one."""
    dtypes = [x.dtype, b.dtype] + [a.dtype for a in (residual,)
                                   if a is not None]
    if out_dtype is not None:
        dtypes.append(out_dtype)
    sub = max(_k.sublane_tile(d) for d in dtypes)
    C = x.shape[1]
    Cp = _round_up(C, sub)
    x = _pad_axis(x, 1, Cp)

    def tile(blk, n):
        return blk if blk and n % blk == 0 and blk % sub == 0 else None

    if depthwise:
        kw["cblk"] = tile(cblk, Cp) or _k.default_cblk(Cp, align=sub)
    if pass_ == "bwd_weight":
        n = b.shape[1]
        out = _k.conv1d_pass(pass_, x, _pad_axis(b, 1, _round_up(n, sub)),
                             depthwise=depthwise, **kw)
        dw, db = out if isinstance(out, tuple) else (out, None)
        dw = dw[:, :C] if depthwise else dw[:, :n, :C]
        return dw if db is None else (dw, db[:n])
    if depthwise:
        n, n_pad, w = C, Cp, _pad_axis(b, 1, Cp)
    else:
        n = b.shape[1]
        n_pad = _round_up(n, sub)
        w = _pad_axis(_pad_axis(b, 2, Cp), 1, n_pad)
        kw["kblk"] = tile(kblk, n_pad) or n_pad
    out = _k.conv1d_pass(pass_, x, w, depthwise=depthwise,
                         bias=_pad_axis(bias, 0, n_pad),
                         residual=_pad_axis(residual, 1, n_pad),
                         out_dtype=out_dtype, **kw)
    if isinstance(out, tuple):
        return tuple(o[:, :n] for o in out)
    return out[:, :n]


def _pipe_attrs(pipe, *, pass_, N, C, K, S, dilation, Q, dtype, depthwise,
                wblk, kblk, alg, nblk) -> dict:
    """Telemetry attrs for the pipelining axis of one pallas pass
    (DESIGN.md §15): ``pipelined``/``pipe_depth`` record what was
    dispatched; ``overlap_frac`` is the model-derived fraction of the
    per-grid-step staged-copy time hidden behind the contraction
    (``tune.cost.copy_hiding_fraction`` — the same roofline terms the
    tuner ranks with), 0 for a synchronous kernel.  Interpret-mode
    execution realises none of it (the fallback stages synchronously);
    the honest container signal is the measured pipe-vs-sync race."""
    p = int(pipe or 0)
    out = dict(pipelined=p >= 2, pipe_depth=p, overlap_frac=0.0)
    if p >= 2 and _obs.enabled():
        try:
            from repro import tune
            from repro.tune import cost as _cost
            prob = tune.ConvProblem(
                N=N, C=C, K=K, S=S, dilation=dilation, Q=Q,
                dtype=jnp.dtype(dtype).name, depthwise=depthwise,
                pass_=pass_)
            out["overlap_frac"] = _cost.copy_hiding_fraction(
                prob, wblk=wblk, kblk=kblk, alg=alg, nblk=nblk, pipe=p,
                device_kind=tune.device_kind())
        except Exception:
            pass  # attrs must never break the pass
    return out


def _chunk_ranges(n: int, chunks: int) -> list[tuple[int, int]]:
    """Split ``n`` units into ``chunks`` contiguous, near-even [lo, hi)
    ranges (clamped to at most one unit per chunk)."""
    chunks = max(1, min(int(chunks), n))
    base, rem = divmod(n, chunks)
    out, lo = [], 0
    for i in range(chunks):
        hi = lo + base + (1 if i < rem else 0)
        out.append((lo, hi))
        lo = hi
    return out


def _chunked_psum_bwd_weight(run_range, ranges, axes):
    """Chunked collective/compute overlap for the fused gradient reduction
    (DESIGN.md §15): ``run_range(lo, hi)`` computes the bwd-weight partial
    (dw or (dw, dbias)) over width units [lo, hi); each partial is psum'd
    the moment it exists — chunk i's all-reduce has no data dependency on
    chunk i+1's contraction, so XLA's async collectives overlap them —
    and the reduced partials sum to the full gradient (fp32 throughout;
    only the summation order differs from the single-psum path)."""
    total = None
    for lo, hi in ranges:
        part = jax.lax.psum(run_range(lo, hi), axes)
        total = part if total is None else jax.tree.map(jnp.add, total, part)
    return total


def _static_axis_size(axes) -> int:
    """Product of the named mesh axis sizes, resolved statically from the
    trace's axis env (``psum`` of a Python literal folds to a constant
    under shard_map); 0 when no axis context is available."""
    try:
        n = 1
        for a in axes:
            n *= jax.lax.psum(1, a)
        return int(n)
    except Exception:
        return 0


def _model_psum_event(arr, axes, *, chunk: int, chunks: int, cell=None):
    """Record one model-axis activation all-reduce as a ``conv.psum.model``
    event (the psum itself runs inside jit/shard_map tracing, so a timed
    span is impossible — chunk index, payload bytes, and the mesh extent
    in the attrs are what ``obs.report`` aggregates, DESIGN.md §17)."""
    if _obs.enabled():
        _obs.event("conv.psum.model", axes=",".join(axes), chunk=chunk,
                   chunks=chunks, mp=_static_axis_size(axes),
                   bytes=int(arr.size) * jnp.dtype(arr.dtype).itemsize,
                   **(cell or {}))


def _model_psum(dx, axes, *, cell=None):
    """Single (unchunked) model-axis psum finishing a K-sharded bwd-data
    contraction: each shard's ``dx`` summed only its local filter rows."""
    _model_psum_event(dx, axes, chunk=0, chunks=1, cell=cell)
    return jax.lax.psum(dx, axes)


def _chunked_psum_bwd_data(run_range, ranges, axes, *, cell=None):
    """Chunked model-axis all-reduce of the bwd-data pass (DESIGN.md §17).

    Under K-sharding each shard's dx is a *partial* contraction (its local
    filter rows only).  ``run_range(lo, hi)`` computes the dx columns of
    width-chunk [lo, hi); each chunk is psum'd over the model axes the
    moment it exists — chunk i's all-reduce has no data dependency on
    chunk i+1's contraction, so XLA's async collectives overlap them —
    and the reduced chunks concatenate back along width.  Unlike the
    bwd-weight chunking (which *sums* partials, reordering the fp32
    accumulation), the chunks here are disjoint column ranges: every
    output column sums the identical operand set in the identical order,
    so the result is bitwise equal to the single-psum path when chunk
    boundaries respect the kernel's width tiling."""
    parts = []
    for i, (lo, hi) in enumerate(ranges):
        part = run_range(lo, hi)
        _model_psum_event(part, axes, chunk=i, chunks=len(ranges), cell=cell)
        parts.append(jax.lax.psum(part, axes))
    return jnp.concatenate(parts, axis=-1)


def _dtype_name(a) -> str | None:
    return None if a is None else jnp.dtype(a.dtype).name


def _axes_tuple(axes) -> tuple[str, ...] | None:
    """Canonicalize a ``grad_reduce_axes`` argument (str | sequence | None)
    to a hashable tuple of mesh axis names."""
    if axes is None:
        return None
    if isinstance(axes, str):
        return (axes,)
    axes = tuple(axes)
    return axes or None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _psum_cotangent(axes: tuple[str, ...], p):
    """Identity on the primal; ``lax.psum`` over ``axes`` on the cotangent.

    The data-parallel reduction hook for the backends without a custom VJP
    (xla/ref): wrapping a batch-replicated parameter makes its gradient —
    produced by XLA's own conv transpose — all-reduce across the batch
    shards, matching the fused reduction the Pallas VJP performs itself."""
    return p


def _psum_cotangent_fwd(axes, p):
    return p, None


def _psum_cotangent_bwd(axes, _, g):
    return (jax.lax.psum(g, axes),)


_psum_cotangent.defvjp(_psum_cotangent_fwd, _psum_cotangent_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _model_psum_cotangent(axes: tuple[str, ...], cell, p):
    """``_psum_cotangent`` for the model-axis dx reduction on the xla/ref
    top-level paths, emitting the same ``conv.psum.model`` telemetry
    record the custom-VJP paths do (``cell`` is the layer identity as a
    hashable tuple of attrs — nondiff args must hash)."""
    return p


def _model_psum_cotangent_fwd(axes, cell, p):
    return p, None


def _model_psum_cotangent_bwd(axes, cell, _, g):
    return (_model_psum(g, axes, cell=dict(cell)),)


_model_psum_cotangent.defvjp(_model_psum_cotangent_fwd,
                             _model_psum_cotangent_bwd)


class _FusedSpec(NamedTuple):
    """Static (hashable) configuration of one fused conv instance — the
    nondiff argument of the custom_vjp s.  ``blk2`` is kblk for the dense
    path, cblk for the depthwise path.  Dtypes travel as names so the spec
    stays hashable; bias_dtype/residual_dtype double as has-bias/has-residual
    flags for the bwd rule.  ``bwd_data``/``bwd_weight`` are the resolved
    per-pass configs (None -> static fallback derived in the bwd rule);
    ``alg``/``nblk`` are the forward's dense formulation + batch fold.
    ``reduce_axes`` names the mesh axes the weight/bias gradients psum over
    (the data-parallel shard_map path, §13); None = single-device.
    ``pipe`` is the forward kernel's software-pipeline depth (0 = the
    synchronous kernel, §15); ``reduce_chunks`` splits the fused gradient
    all-reduce into that many width chunks, psum'd as each chunk's
    bwd-weight partial completes so collective time hides behind the
    remaining contraction (1 = the PR 5 single fused psum).
    ``model_axes`` names the mesh axes the *filter dimension* K is sharded
    over (tensor parallelism, §17): bwd-data's dx is then a partial
    contraction finished with a psum over those axes, chunked across
    ``model_chunks`` disjoint width ranges (1 = one psum)."""
    dilation: int
    wblk: int
    blk2: int | None
    interpret: bool
    activation: str
    bias_dtype: str | None
    residual_dtype: str | None
    out_dtype: str | None
    bwd_data: PassConfig | None = None
    bwd_weight: PassConfig | None = None
    alg: str = "tap_loop"
    nblk: int = 1
    reduce_axes: tuple[str, ...] | None = None
    pipe: int = 0
    reduce_chunks: int = 1
    model_axes: tuple[str, ...] | None = None
    model_chunks: int = 1

    @property
    def out_jnp_dtype(self):
        return jnp.dtype(self.out_dtype) if self.out_dtype else None


# ---------------------------------------------------------------------------
# Dense conv1d with custom VJP over the three BRGEMM kernels
# ---------------------------------------------------------------------------


def _plain_fwd_padded(x, w, dilation, wblk, kblk, interpret,
                      pass_: str = "fwd", alg: str = "tap_loop",
                      nblk: int = 1, pipe: int = 0):
    """Epilogue-free forward: x (N, C, W) already logically padded; returns
    (N, K, Q) via the Pallas kernel, handling width round-up to the tile
    size.  Also the bwd-data engine (Alg. 3, ``pass_='bwd_data'``)."""
    N, C, W = x.shape
    S, K, _ = w.shape
    span = (S - 1) * dilation
    Q = W - span
    Qp = _round_up(Q, wblk)
    if Qp + span > W:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Qp + span - W)))
    out = _kernel_pass(pass_, x, w, dilation=dilation, wblk=wblk,
                       kblk=kblk, alg=alg, nblk=_legal_nblk(nblk, N),
                       pipe=pipe, interpret=interpret)
    return out[:, :, :Q]


def _fused_fwd_padded(spec: _FusedSpec, x, w, bias, residual,
                      save_preact: bool = False):
    """Fused forward with width round-up: pads x (and the residual) to the
    tile multiple, runs the kernel, slices back.  With ``save_preact``
    returns (y, fp32 preact) for the VJP's activation gradient."""
    N, C, W = x.shape
    S, K, _ = w.shape
    span = (S - 1) * spec.dilation
    Q = W - span
    Qp = _round_up(Q, spec.wblk)
    if Qp + span > W:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Qp + span - W)))
    if residual is not None and Qp > Q:
        residual = jnp.pad(residual, ((0, 0), (0, 0), (0, Qp - Q)))
    out = _kernel_pass(
        "fwd", x, w, bias=bias, residual=residual, activation=spec.activation,
        save_preact=save_preact, dilation=spec.dilation, wblk=spec.wblk,
        kblk=spec.blk2, alg=spec.alg, nblk=spec.nblk, pipe=spec.pipe,
        out_dtype=spec.out_jnp_dtype, interpret=spec.interpret)
    if save_preact:
        y, u = out
        return y[:, :, :Q], u[:, :, :Q]
    return out[:, :, :Q]


def _needs_preact(activation: str) -> bool:
    """ReLU's gradient mask is derivable from the (already materialised)
    output — only curved activations (gelu/silu) need the fp32
    pre-activation stored as a second kernel output."""
    return activation not in ("none", "relu")


def _vjp_fwd_saved(spec: _FusedSpec, y, u):
    """What the fwd rule saves for the activation gradient: nothing for a
    linear epilogue, the output itself for relu, the fp32 preact otherwise."""
    if spec.activation == "none":
        return None
    return y if spec.activation == "relu" else u


def _epilogue_cotangent(spec: _FusedSpec, saved, gout):
    """du = act'(·) * gout, elementwise, in gout's dtype.  ``saved`` is
    ``_vjp_fwd_saved``'s tensor; identity when the epilogue is linear."""
    if spec.activation == "none":
        return gout
    if spec.activation == "relu":
        return jnp.where(saved > 0, gout, jnp.zeros_like(gout))
    _, act_vjp = jax.vjp(_ep.ACTIVATIONS[spec.activation], saved)
    (du,) = act_vjp(gout.astype(saved.dtype))
    return du.astype(gout.dtype)


def _epilogue_param_grads(spec: _FusedSpec, dwout, du, reduced: bool = False):
    """Unpack the bwd-weight kernel result into (dw, dbias) in the primal
    dtypes, and derive dresidual (the masked cotangent passed through).

    Under data parallelism (``spec.reduce_axes``) this is where the
    gradient all-reduce fuses: one ``lax.psum`` of the (dw, dbias) pair,
    immediately downstream of the bwd-weight kernel and still on its fp32
    accumulator — per layer, so the reduce of layer *l* overlaps the
    backward compute of layers < l (DESIGN.md §13).  With
    ``spec.reduce_chunks > 1`` the bwd rule instead psums per width chunk
    (``_chunked_psum_bwd_weight``) and hands the already-reduced result in
    with ``reduced=True``.  ``dresidual`` is the batch-sharded cotangent
    pass-through and stays local."""
    if spec.bias_dtype is not None:
        dw, db = dwout
    else:
        dw, db = dwout, None
    if spec.reduce_axes and not reduced:
        if db is not None:
            dw, db = jax.lax.psum((dw, db), spec.reduce_axes)
        else:
            dw = jax.lax.psum(dw, spec.reduce_axes)
    dbias = db.astype(jnp.dtype(spec.bias_dtype)) if db is not None else None
    dres = (du.astype(jnp.dtype(spec.residual_dtype))
            if spec.residual_dtype is not None else None)
    return dw, dbias, dres


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv1d_pallas(spec: _FusedSpec, x, w, bias, residual):
    return _fused_fwd_padded(spec, x, w, bias, residual)


def _conv1d_pallas_fwd(spec, x, w, bias, residual):
    # (bias and residual themselves are not saved: dbias/dresidual depend
    # only on the masked cotangent.)
    if _needs_preact(spec.activation):
        y, u = _fused_fwd_padded(spec, x, w, bias, residual, save_preact=True)
    else:
        y, u = _fused_fwd_padded(spec, x, w, bias, residual), None
    return y, (x, w, _vjp_fwd_saved(spec, y, u))


def _xla_conv1d_bwd_weight(x, du, *, dilation, with_dbias):
    """Vendor-library formulation of Alg. 4 (+ the dbias reduction), the
    bwd-weight engine when the pass's tuned backend is 'xla'."""
    dw = _ref.conv1d_bwd_weight_ref(x, du, dilation=dilation)
    if with_dbias:
        return dw, jnp.sum(du.astype(jnp.float32), axis=(0, 2))
    return dw


def _conv1d_pallas_bwd(spec, res, gout):
    x, w, saved = res
    S, K, C = w.shape
    d = spec.dilation
    span = (S - 1) * d
    N, Cx, W = x.shape
    Q = W - span
    # --- epilogue gradient (identity when the epilogue has no activation)
    du = _epilogue_cotangent(spec, saved, gout)
    # --- Alg. 3: bwd-data = fwd BRGEMM on zero-padded du with flipped,
    # transposed weights (the paper's (S, C, K) layout) — the transposed
    # (C<->K) GEMM, run under its *own* resolved config, not the forward's.
    bd = spec.bwd_data or PassConfig("pallas", spec.wblk, None)
    g_pad = jnp.pad(du, ((0, 0), (0, 0), (span, span)))
    w_flip = w[::-1].transpose(0, 2, 1)  # (S, C, K)
    cell = dict(N=N, C=C, K=K, S=S, dilation=d, Q=Q,
                dtype=jnp.dtype(x.dtype).name, depthwise=False)
    if bd.backend == "xla":
        bd_attrs = dict(backend="xla")
        if spec.model_axes and spec.model_chunks > 1 and W > 1:
            # K is device-sharded (§17): finish the partial contraction
            # with the model-axis psum, chunked on raw output columns
            ranges = _chunk_ranges(W, spec.model_chunks)
            bd_thunk = lambda: _chunked_psum_bwd_data(  # noqa: E731
                lambda a, b: _ref._xla_conv1d_f32(
                    g_pad[:, :, a:b + span], w_flip, d),
                ranges, spec.model_axes, cell=cell)
            bd_attrs["model_chunks"] = len(ranges)
        elif spec.model_axes:
            bd_thunk = lambda: _model_psum(  # noqa: E731
                _ref._xla_conv1d_f32(g_pad, w_flip, d), spec.model_axes,
                cell=cell)
        else:
            bd_thunk = lambda: _ref._xla_conv1d_f32(g_pad, w_flip, d)  # noqa: E731
    else:
        # the pass's filter tile must divide C (bwd-data's filter count);
        # a kblk tuned for K need not — fall back to the divisor ladder
        kblk = bd.blk2 if bd.blk2 and C % bd.blk2 == 0 else pick_kblk(C)
        bd_pipe = _k.canon_pipe(bd.pipe)
        bd_wblk = bd.wblk or spec.wblk
        bd_run = lambda: _plain_fwd_padded(  # noqa: E731
            g_pad, w_flip, d, bd_wblk, kblk,
            spec.interpret, pass_="bwd_data",
            alg=bd.alg or "tap_loop", nblk=bd.nblk or 1, pipe=bd_pipe)
        bd_attrs = dict(backend="pallas", wblk=bd_wblk,
                        kblk=kblk, alg=bd.alg or "tap_loop",
                        nblk=bd.nblk or 1,
                        **_pipe_attrs(bd_pipe, pass_="bwd_data", N=N, C=C,
                                      K=K, S=S, dilation=d, Q=Q,
                                      dtype=x.dtype, depthwise=False,
                                      wblk=bd_wblk, kblk=kblk,
                                      alg=bd.alg or "tap_loop",
                                      nblk=bd.nblk or 1))
        Wp = _round_up(W, bd_wblk)
        nw = Wp // bd_wblk
        if spec.model_axes and spec.model_chunks > 1 and nw > 1:
            # chunk boundaries in units of the pass's width tile, so every
            # chunk keeps the kernel's tiling and stays bitwise equal to
            # the single-psum path (disjoint columns, identical tap order)
            gp2 = (jnp.pad(g_pad,
                           ((0, 0), (0, 0),
                            (0, Wp + span - g_pad.shape[-1])))
                   if Wp + span > g_pad.shape[-1] else g_pad)
            ranges = _chunk_ranges(nw, spec.model_chunks)
            bd_thunk = lambda: _chunked_psum_bwd_data(  # noqa: E731
                lambda a, b: _plain_fwd_padded(
                    gp2[:, :, a * bd_wblk:b * bd_wblk + span], w_flip, d,
                    bd_wblk, kblk, spec.interpret, pass_="bwd_data",
                    alg=bd.alg or "tap_loop", nblk=bd.nblk or 1,
                    pipe=bd_pipe),
                ranges, spec.model_axes, cell=cell)[:, :, :W]
            bd_attrs["model_chunks"] = len(ranges)
        elif spec.model_axes:
            bd_thunk = lambda: _model_psum(  # noqa: E731
                bd_run(), spec.model_axes, cell=cell)
        else:
            bd_thunk = bd_run
    if spec.model_axes:
        bd_attrs["model_axes"] = ",".join(spec.model_axes)
    # bwd-data contracts over K and produces all W output columns
    dx = _obs_conv("bwd_data", bd_thunk, attrs=dict(bd_attrs, **cell))
    dx = dx.astype(x.dtype)
    # --- Alg. 4: bwd-weight kernel (fp32 accumulation), with the bias
    # gradient fused into the same sequential-grid pass when bias exists —
    # again under its own per-pass config.
    bw = spec.bwd_weight or PassConfig("pallas", spec.wblk, None)
    with_dbias = spec.bias_dtype is not None
    reduced = False
    if bw.backend == "xla":
        bw_thunk = lambda: _xla_conv1d_bwd_weight(  # noqa: E731
            x, du, dilation=d, with_dbias=with_dbias)
        bw_attrs = dict(backend="xla")
        if spec.reduce_axes and spec.reduce_chunks > 1:
            # chunked collective/compute overlap (§15): psum each width
            # chunk's partial the moment it exists
            ranges = _chunk_ranges(Q, spec.reduce_chunks)
            bw_thunk = lambda: _chunked_psum_bwd_weight(  # noqa: E731
                lambda a, b: _xla_conv1d_bwd_weight(
                    x[:, :, a:b + span], du[:, :, a:b],
                    dilation=d, with_dbias=with_dbias),
                ranges, spec.reduce_axes)
            bw_attrs["reduce_chunks"] = len(ranges)
            reduced = True
    else:
        wblk = bw.wblk or spec.wblk
        bw_nblk = _legal_nblk(bw.nblk, N)
        bw_alg = bw.alg or "tap_loop"
        bw_pipe = _k.canon_pipe(bw.pipe)
        Qp = _round_up(Q, wblk)
        xp = (jnp.pad(x, ((0, 0), (0, 0), (0, Qp + span - W)))
              if Qp + span > W else x)
        gp = jnp.pad(du, ((0, 0), (0, 0), (0, Qp - Q))) if Qp > Q else du

        def bw_range(a, b):
            # width-tile-aligned slice: chunk boundaries are [lo, hi) in
            # units of wblk tiles, so every chunk keeps the kernel's tiling
            return _kernel_pass(
                "bwd_weight", xp[:, :, a * wblk:b * wblk + span],
                gp[:, :, a * wblk:b * wblk], S=S, dilation=d, wblk=wblk,
                alg=bw_alg, nblk=bw_nblk, pipe=bw_pipe,
                with_dbias=with_dbias, interpret=spec.interpret)

        bw_attrs = dict(backend="pallas", wblk=wblk, alg=bw_alg,
                        nblk=bw_nblk,
                        **_pipe_attrs(bw_pipe, pass_="bwd_weight", N=N, C=C,
                                      K=K, S=S, dilation=d, Q=Q,
                                      dtype=x.dtype, depthwise=False,
                                      wblk=wblk, kblk=None, alg=bw_alg,
                                      nblk=bw_nblk))
        nq = Qp // wblk
        if spec.reduce_axes and spec.reduce_chunks > 1 and nq > 1:
            ranges = _chunk_ranges(nq, spec.reduce_chunks)
            bw_thunk = lambda: _chunked_psum_bwd_weight(  # noqa: E731
                bw_range, ranges, spec.reduce_axes)
            bw_attrs["reduce_chunks"] = len(ranges)
            reduced = True
        else:
            bw_thunk = lambda: bw_range(0, nq)  # noqa: E731
    dwout = _obs_conv(
        "bwd_weight", bw_thunk,
        attrs=dict(bw_attrs, N=N, C=C, K=K, S=S, dilation=d, Q=Q,
                   dtype=jnp.dtype(x.dtype).name, depthwise=False))
    dw, dbias, dres = _epilogue_param_grads(spec, dwout, du, reduced=reduced)
    return dx, dw.astype(w.dtype), dbias, dres


_conv1d_pallas.defvjp(_conv1d_pallas_fwd, _conv1d_pallas_bwd)


def conv1d(
    x: jax.Array,
    w: jax.Array,
    *,
    bias: jax.Array | None = None,
    activation: str | None = None,
    residual: jax.Array | None = None,
    dilation: int = 1,
    padding: Padding = "SAME",
    backend: str | None = None,
    wblk: int | None = None,
    kblk: int | None = None,
    alg: str | None = None,
    nblk: int | None = None,
    pipe: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
    bwd_data_cfg=None,
    bwd_weight_cfg=None,
    grad_reduce_axes=None,
    grad_reduce_chunks: int | None = None,
    model_reduce_axes=None,
    model_reduce_chunks: int | None = None,
) -> jax.Array:
    """1D dilated convolution with fused epilogue, paper semantics.

    x: (N, C, W), w: (S, K, C) -> (N, K, Q); Q == W for SAME/CAUSAL,
    Q = W - (S-1)*dilation for VALID.

    Example (shapes only — default backend, CPU-safe)::

        >>> import jax, jax.numpy as jnp
        >>> from repro.kernels import ops
        >>> x = jnp.ones((2, 8, 64))           # (N, C, W)
        >>> w = jnp.ones((3, 4, 8))            # (S, K, C)
        >>> ops.conv1d(x, w, dilation=2, padding="SAME").shape
        (2, 4, 64)
        >>> ops.conv1d(x, w, dilation=2, padding="VALID").shape
        (2, 4, 60)
        >>> y = ops.conv1d(x, w, bias=jnp.zeros(4), activation="relu",
        ...                dilation=2, padding="SAME")
        >>> y.shape
        (2, 4, 64)

    Epilogue (all optional, applied on the fp32 accumulator in this order):
    ``y = act(conv + bias + residual)`` with bias (K,), activation one of
    relu/gelu/silu, residual (N, K, Q).  ``out_dtype`` overrides the output
    dtype (default x.dtype) without an extra cast op.

    ``alg`` pins the dense contraction formulation (``tap_loop`` /
    ``tap_packed``, DESIGN.md §12) and ``nblk`` the batch fold of the
    forward kernel; both default to the tuner's choice under
    backend='auto' and to the historical kernel otherwise.  ``pipe`` pins
    the forward's software-pipeline depth (DESIGN.md §15): 0/1 the
    synchronous kernel, >= 2 the double-buffered async-copy variant —
    numerically identical, tuner-selected under backend='auto'.

    backend='auto' asks the tuning subsystem (``repro.tune``) to pick the
    backend and tile sizes **per pass**: the forward's, plus each backward
    pass's own resolved config for the custom VJP; see ``_resolve_auto``.
    ``bwd_data_cfg``/``bwd_weight_cfg`` (a ``PassConfig`` or a
    ``(backend, wblk, kblk[, alg, nblk])`` tuple) pin a backward pass
    explicitly, winning over the tuner — the knob ``tune.measure`` uses to
    time one pass's candidate inside a ``jax.vjp`` instance.

    ``grad_reduce_axes`` (a mesh axis name or tuple of names) marks this
    call as running *inside* a ``shard_map`` that shards the batch over
    those axes: the weight/bias gradients are all-reduced over them, fused
    after the bwd-weight pass (DESIGN.md §13).  Use
    ``kernels.sharded.sharded_conv1d`` for the wrapped spelling.
    ``grad_reduce_chunks`` > 1 splits that fused all-reduce into width
    chunks psum'd as each bwd-weight partial completes, overlapping
    collective time with the remaining contraction (DESIGN.md §15).

    ``model_reduce_axes`` marks the call as *filter-sharded* (tensor
    parallelism, DESIGN.md §17): w/bias hold only this shard's K rows,
    sharded over those mesh axes.  Forward and bwd-weight need no
    collective (each shard owns its filter slice); bwd-data contracts
    over the sharded K, so dx is finished with a ``lax.psum`` over the
    model axes fused after the bwd-data pass.  ``model_reduce_chunks``
    > 1 splits that psum across disjoint width chunks, overlapping chunk
    i's all-reduce with chunk i+1's contraction (bitwise equal to the
    single psum on the pallas path — disjoint columns, identical tap
    order).  Use ``kernels.sharded.model_sharded_conv1d`` for the wrapped
    spelling; composes with ``grad_reduce_axes`` on a 2D (data, model)
    mesh.
    """
    backend = backend or default_backend()
    activation = _ep.canon(activation)
    grad_reduce_axes = _axes_tuple(grad_reduce_axes)
    model_reduce_axes = _axes_tuple(model_reduce_axes)
    bwd_data_cfg = _as_pass_cfg(bwd_data_cfg)
    bwd_weight_cfg = _as_pass_cfg(bwd_weight_cfg)
    S, K, C = w.shape
    lo, hi = _pad_amounts(S, dilation, padding)
    if lo or hi:
        x = jnp.pad(x, ((0, 0), (0, 0), (lo, hi)))
    Q = x.shape[-1] - (S - 1) * dilation
    if bias is not None:
        assert bias.shape == (K,), (bias.shape, K)
    if residual is not None:
        assert residual.shape == (x.shape[0], K, Q), \
            (residual.shape, (x.shape[0], K, Q))
    if backend == "auto":
        (backend, wblk, kblk, auto_alg, auto_nblk, auto_pipe,
         (auto_bd, auto_bw)) = _resolve_auto(
            x, C=C, K=K, S=S, dilation=dilation, padding=padding,
            wblk=wblk, kblk=kblk, depthwise=False,
            epilogue=_ep.signature(bias is not None, activation,
                                   residual is not None))
        alg = alg or auto_alg
        nblk = nblk or auto_nblk
        pipe = pipe if pipe is not None else auto_pipe
        bwd_data_cfg = bwd_data_cfg or auto_bd
        bwd_weight_cfg = bwd_weight_cfg or auto_bw
    if backend in ("ref", "xla") and grad_reduce_axes:
        # no custom VJP on these paths: reduce the parameter cotangents
        # through the identity-psum wrapper instead (same math, same axes)
        w = _psum_cotangent(grad_reduce_axes, w)
        if bias is not None:
            bias = _psum_cotangent(grad_reduce_axes, bias)
    if backend in ("ref", "xla") and model_reduce_axes:
        # same trick for the K-sharded contraction: dx all-reduces over
        # the model axes (single psum — the chunked overlap is a property
        # of the custom-VJP pallas/xla PassConfig path)
        cell = (("N", x.shape[0]), ("C", C), ("K", K), ("S", S),
                ("dilation", dilation), ("Q", Q),
                ("dtype", jnp.dtype(x.dtype).name), ("depthwise", False))
        x = _model_psum_cotangent(model_reduce_axes, cell, x)
    N = x.shape[0]
    attrs = dict(backend=backend, N=N, C=C, K=K, S=S, dilation=dilation,
                 Q=Q, dtype=jnp.dtype(x.dtype).name, depthwise=False)
    if backend == "ref":
        thunk = lambda: _ref.conv1d_fused_ref(  # noqa: E731
            x, w, dilation=dilation, bias=bias, activation=activation,
            residual=residual, out_dtype=out_dtype)
    elif backend == "xla":
        def thunk():
            u = _ep.apply_ref(_ref._xla_conv1d_f32(x, w, dilation), bias=bias,
                              residual=residual, activation=activation)
            return u.astype(out_dtype or x.dtype)
    elif backend == "pallas":
        wblk = wblk or pick_wblk(Q, S, dilation)
        interpret = _interpret_default() if interpret is None else interpret
        spec = _FusedSpec(dilation, wblk, kblk, interpret, activation,
                          _dtype_name(bias), _dtype_name(residual),
                          jnp.dtype(out_dtype).name if out_dtype else None,
                          bwd_data_cfg, bwd_weight_cfg,
                          alg or "tap_loop", _legal_nblk(nblk, x.shape[0]),
                          grad_reduce_axes, _k.canon_pipe(pipe),
                          int(grad_reduce_chunks or 1)
                          if grad_reduce_axes else 1,
                          model_axes=model_reduce_axes,
                          model_chunks=int(model_reduce_chunks or 1)
                          if model_reduce_axes else 1)
        attrs.update(alg=spec.alg, nblk=spec.nblk, wblk=wblk, kblk=kblk,
                     **_pipe_attrs(spec.pipe, pass_="fwd", N=N, C=C, K=K,
                                   S=S, dilation=dilation, Q=Q,
                                   dtype=x.dtype, depthwise=False,
                                   wblk=wblk, kblk=kblk, alg=spec.alg,
                                   nblk=spec.nblk))
        thunk = lambda: _conv1d_pallas(spec, x, w, bias, residual)  # noqa: E731
    else:
        raise ValueError(f"unknown conv backend {backend!r}")
    return _obs_conv("fwd", thunk, attrs=attrs)


# ---------------------------------------------------------------------------
# Streaming (chunked causal) conv1d — ring-buffer state, zero recompute
# ---------------------------------------------------------------------------


def conv_stream_state(batch: int, c_in: int, S: int, dilation: int,
                      dtype=jnp.float32) -> jax.Array:
    """Fresh per-layer streaming state: the last ``(S-1)*dilation`` input
    columns the causal conv's receptive field reaches back over, zeros when
    no history exists yet (zeros ARE the causal left-padding, so a fresh
    state is exactly the CAUSAL one-shot contract).  Shape
    ``(batch, c_in, (S-1)*dilation)``."""
    return jnp.zeros((batch, c_in, (S - 1) * dilation), dtype)


def _stream_call(conv_fn, x, w, state, span, kwargs):
    """Shared streaming engine: prepend the carried footprint, run ONE
    VALID-padded pass over ``span + W_chunk`` columns (Q = W_chunk — only
    the new positions are computed, nothing in the warm-up region is
    redone), and slide the ring buffer to the last ``span`` inputs."""
    N, C, W = x.shape
    assert state.shape == (N, C, span), \
        (f"streaming state shape {state.shape} does not match "
         f"(N={N}, C_in={C}, span={span})")
    if state.dtype != x.dtype:
        raise ValueError(
            f"streaming state dtype {state.dtype} != chunk dtype {x.dtype}; "
            "init the state with the stream's input dtype")
    xc = jnp.concatenate([state, x], axis=-1) if span else x
    y = conv_fn(xc, w, padding="VALID", **kwargs)
    new_state = xc[:, :, xc.shape[-1] - span:]
    return y, new_state


def conv1d_streaming(
    x: jax.Array,
    w: jax.Array,
    *,
    state: jax.Array,
    bias: jax.Array | None = None,
    activation: str | None = None,
    residual: jax.Array | None = None,
    dilation: int = 1,
    backend: str | None = None,
    wblk: int | None = None,
    kblk: int | None = None,
    alg: str | None = None,
    nblk: int | None = None,
    pipe: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One streaming step of a *causal* dilated conv1d: compute the outputs
    for a new chunk only, carrying O((S-1)*dilation) state instead of
    re-running the receptive field.

    x: (N, C, W_chunk) new input columns; ``state``: the ring buffer from
    :func:`conv_stream_state` (fresh stream) or the previous step's return.
    Returns ``(y, new_state)`` with y (N, K, W_chunk) — **bitwise** equal
    (fp32; allclose in bf16) to the same columns of a one-shot
    ``conv1d(full_x, w, padding="CAUSAL")``: the concatenated
    ``[state | chunk]`` window feeds every output position exactly the taps
    the full sequence would, through the same tuned kernels (tap order, fp32
    accumulation, fused epilogue all inherited; ``backend='auto'`` resolves
    the (N, Q=W_chunk, padding=VALID, epilogue) instance from the tuning
    cache — pre-populate with ``scripts/tune.py --figset serving``).

    Example (state round-trip, shapes only)::

        >>> import jax, jax.numpy as jnp
        >>> from repro.kernels import ops
        >>> w = jnp.ones((3, 4, 4))                 # (S, K, C)
        >>> st = ops.conv_stream_state(2, 4, S=3, dilation=2)
        >>> st.shape                                # (N, C, (S-1)*d)
        (2, 4, 4)
        >>> y, st = ops.conv1d_streaming(jnp.ones((2, 4, 16)), w, state=st,
        ...                              dilation=2)
        >>> y.shape, st.shape
        ((2, 4, 16), (2, 4, 4))
    """
    S, K, C = w.shape
    return _stream_call(
        conv1d, x, w, state, (S - 1) * dilation,
        dict(bias=bias, activation=activation, residual=residual,
             dilation=dilation, backend=backend, wblk=wblk, kblk=kblk,
             alg=alg, nblk=nblk, pipe=pipe, out_dtype=out_dtype,
             interpret=interpret))


def depthwise_conv1d_streaming(
    x: jax.Array,
    w: jax.Array,
    *,
    state: jax.Array,
    bias: jax.Array | None = None,
    activation: str | None = None,
    residual: jax.Array | None = None,
    dilation: int = 1,
    backend: str | None = None,
    wblk: int | None = None,
    cblk: int | None = None,
    pipe: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Streaming step of the causal depthwise conv1d (the Mamba2/Zamba2
    decode conv, here with the dilation axis kept general); same state
    contract and equivalence guarantee as :func:`conv1d_streaming`."""
    S, C = w.shape
    return _stream_call(
        depthwise_conv1d, x, w, state, (S - 1) * dilation,
        dict(bias=bias, activation=activation, residual=residual,
             dilation=dilation, backend=backend, wblk=wblk, cblk=cblk,
             pipe=pipe, out_dtype=out_dtype, interpret=interpret))


# ---------------------------------------------------------------------------
# Depthwise conv1d (Mamba2/Zamba2 causal conv)
# ---------------------------------------------------------------------------


def _dw_plain_fwd_padded(x, w, dilation, wblk, cblk, interpret,
                         pass_: str = "fwd", pipe: int = 0):
    N, C, W = x.shape
    S, _ = w.shape
    span = (S - 1) * dilation
    Q = W - span
    Qp = _round_up(Q, wblk)
    if Qp + span > W:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Qp + span - W)))
    out = _kernel_pass(pass_, x, w, depthwise=True, dilation=dilation,
                       wblk=wblk, cblk=cblk, pipe=pipe, interpret=interpret)
    return out[:, :, :Q]


def _dw_fused_fwd_padded(spec: _FusedSpec, x, w, bias, residual,
                         save_preact: bool = False):
    N, C, W = x.shape
    S, _ = w.shape
    span = (S - 1) * spec.dilation
    Q = W - span
    Qp = _round_up(Q, spec.wblk)
    if Qp + span > W:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Qp + span - W)))
    if residual is not None and Qp > Q:
        residual = jnp.pad(residual, ((0, 0), (0, 0), (0, Qp - Q)))
    out = _kernel_pass(
        "fwd", x, w, depthwise=True, bias=bias, residual=residual,
        activation=spec.activation, save_preact=save_preact,
        dilation=spec.dilation, wblk=spec.wblk, cblk=spec.blk2,
        pipe=spec.pipe, out_dtype=spec.out_jnp_dtype,
        interpret=spec.interpret)
    if save_preact:
        y, u = out
        return y[:, :, :Q], u[:, :, :Q]
    return out[:, :, :Q]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dw_conv1d_pallas(spec: _FusedSpec, x, w, bias, residual):
    return _dw_fused_fwd_padded(spec, x, w, bias, residual)


def _dw_conv1d_pallas_fwd(spec, x, w, bias, residual):
    if _needs_preact(spec.activation):
        y, u = _dw_fused_fwd_padded(spec, x, w, bias, residual,
                                    save_preact=True)
    else:
        y, u = _dw_fused_fwd_padded(spec, x, w, bias, residual), None
    return y, (x, w, _vjp_fwd_saved(spec, y, u))


def _xla_dw_bwd_weight(x, du, *, dilation, with_dbias):
    """Vendor-library formulation of the depthwise Alg. 4 (+ dbias)."""
    dw = _ref.depthwise_conv1d_bwd_weight_ref(x, du, dilation=dilation)
    if with_dbias:
        return dw, jnp.sum(du.astype(jnp.float32), axis=(0, 2))
    return dw


def _dw_legal_cblk(cblk, C):
    """A cblk is usable only if it divides C; None lets the kernel pick."""
    return cblk if cblk and C % cblk == 0 else None


def _dw_conv1d_pallas_bwd(spec, res, gout):
    x, w, saved = res
    S, C = w.shape
    d = spec.dilation
    span = (S - 1) * d
    N, _, W = x.shape
    Q = W - span
    du = _epilogue_cotangent(spec, saved, gout)
    # --- bwd-data on flipped taps, under its own per-pass config
    bd = spec.bwd_data or PassConfig("pallas", spec.wblk, spec.blk2)
    g_pad = jnp.pad(du, ((0, 0), (0, 0), (span, span)))
    if bd.backend == "xla":
        bd_thunk = lambda: _ref._xla_depthwise_conv1d_f32(  # noqa: E731
            g_pad, w[::-1], d)
        bd_attrs = dict(backend="xla")
    else:
        cblk = _dw_legal_cblk(bd.blk2, C) or _dw_legal_cblk(spec.blk2, C)
        bd_pipe = _k.canon_pipe(bd.pipe)
        bd_thunk = lambda: _dw_plain_fwd_padded(  # noqa: E731
            g_pad, w[::-1], d, bd.wblk or spec.wblk, cblk,
            spec.interpret, pass_="bwd_data", pipe=bd_pipe)
        bd_attrs = dict(backend="pallas", wblk=bd.wblk or spec.wblk,
                        cblk=cblk,
                        **_pipe_attrs(bd_pipe, pass_="bwd_data", N=N, C=C,
                                      K=C, S=S, dilation=d, Q=Q,
                                      dtype=x.dtype, depthwise=True,
                                      wblk=bd.wblk or spec.wblk, kblk=cblk,
                                      alg=None, nblk=1))
    dx = _obs_conv(
        "bwd_data", bd_thunk,
        attrs=dict(bd_attrs, N=N, C=C, K=C, S=S, dilation=d, Q=Q,
                   dtype=jnp.dtype(x.dtype).name, depthwise=True))
    dx = dx.astype(x.dtype)
    # --- bwd-weight (sequential grid), under its own per-pass config
    bw = spec.bwd_weight or PassConfig("pallas", spec.wblk, spec.blk2)
    with_dbias = spec.bias_dtype is not None
    reduced = False
    if bw.backend == "xla":
        bw_thunk = lambda: _xla_dw_bwd_weight(  # noqa: E731
            x, du, dilation=d, with_dbias=with_dbias)
        bw_attrs = dict(backend="xla")
        if spec.reduce_axes and spec.reduce_chunks > 1:
            ranges = _chunk_ranges(Q, spec.reduce_chunks)
            bw_thunk = lambda: _chunked_psum_bwd_weight(  # noqa: E731
                lambda a, b: _xla_dw_bwd_weight(
                    x[:, :, a:b + span], du[:, :, a:b],
                    dilation=d, with_dbias=with_dbias),
                ranges, spec.reduce_axes)
            bw_attrs["reduce_chunks"] = len(ranges)
            reduced = True
    else:
        wblk = bw.wblk or spec.wblk
        Qp = _round_up(Q, wblk)
        xp = (jnp.pad(x, ((0, 0), (0, 0), (0, Qp + span - W)))
              if Qp + span > W else x)
        gp = jnp.pad(du, ((0, 0), (0, 0), (0, Qp - Q))) if Qp > Q else du
        cblk = _dw_legal_cblk(bw.blk2, C) or _dw_legal_cblk(spec.blk2, C)
        bw_pipe = _k.canon_pipe(bw.pipe)

        def bw_range(a, b):
            return _kernel_pass(
                "bwd_weight", xp[:, :, a * wblk:b * wblk + span],
                gp[:, :, a * wblk:b * wblk], depthwise=True, S=S,
                dilation=d, wblk=wblk, cblk=cblk, pipe=bw_pipe,
                with_dbias=with_dbias, interpret=spec.interpret)

        bw_attrs = dict(backend="pallas", wblk=wblk, cblk=cblk,
                        **_pipe_attrs(bw_pipe, pass_="bwd_weight", N=N,
                                      C=C, K=C, S=S, dilation=d, Q=Q,
                                      dtype=x.dtype, depthwise=True,
                                      wblk=wblk, kblk=cblk, alg=None,
                                      nblk=1))
        nq = Qp // wblk
        if spec.reduce_axes and spec.reduce_chunks > 1 and nq > 1:
            ranges = _chunk_ranges(nq, spec.reduce_chunks)
            bw_thunk = lambda: _chunked_psum_bwd_weight(  # noqa: E731
                bw_range, ranges, spec.reduce_axes)
            bw_attrs["reduce_chunks"] = len(ranges)
            reduced = True
        else:
            bw_thunk = lambda: bw_range(0, nq)  # noqa: E731
    dwout = _obs_conv(
        "bwd_weight", bw_thunk,
        attrs=dict(bw_attrs, N=N, C=C, K=C, S=S, dilation=d, Q=Q,
                   dtype=jnp.dtype(x.dtype).name, depthwise=True))
    dw, dbias, dres = _epilogue_param_grads(spec, dwout, du, reduced=reduced)
    return dx, dw.astype(w.dtype), dbias, dres


_dw_conv1d_pallas.defvjp(_dw_conv1d_pallas_fwd, _dw_conv1d_pallas_bwd)


def depthwise_conv1d(
    x: jax.Array,
    w: jax.Array,
    *,
    bias: jax.Array | None = None,
    activation: str | None = None,
    residual: jax.Array | None = None,
    dilation: int = 1,
    padding: Padding = "CAUSAL",
    backend: str | None = None,
    wblk: int | None = None,
    cblk: int | None = None,
    pipe: int | None = None,
    out_dtype=None,
    interpret: bool | None = None,
    bwd_data_cfg=None,
    bwd_weight_cfg=None,
    grad_reduce_axes=None,
    grad_reduce_chunks: int | None = None,
    model_reduce_axes=None,
) -> jax.Array:
    """Depthwise 1D conv with fused epilogue.  x: (N, C, W), w: (S, C)
    -> (N, C, Q); bias (C,), residual (N, C, Q), same epilogue order as
    ``conv1d``.  All backends follow one dtype rule: fp32 accumulation /
    epilogue math, output in ``out_dtype`` or x.dtype (whatever the weight
    dtype — the mixed-dtype contract shared with the dense path).

    backend='auto' defers to the tuning subsystem, as in ``conv1d``, and
    resolves each backward pass's config through its own problem key;
    ``bwd_data_cfg``/``bwd_weight_cfg`` pin a pass explicitly.
    ``grad_reduce_axes`` marks the call as batch-sharded inside a
    ``shard_map``: weight/bias gradients all-reduce over the named mesh
    axes, fused after the bwd-weight pass (DESIGN.md §13);
    ``grad_reduce_chunks`` > 1 chunks that psum across width partials
    (DESIGN.md §15).  ``pipe`` pins the software-pipeline depth as in
    ``conv1d``.

    ``model_reduce_axes`` is *rejected* here: a channel-group-sharded
    depthwise conv (x and w both sharded on C over the model axis) has no
    cross-shard contraction — each output channel reads only its own
    input channel, so dx stays local and no model-axis collective exists
    on any pass (DESIGN.md §17).

    Example (Mamba2-style causal conv, shapes only)::

        >>> import jax.numpy as jnp
        >>> from repro.kernels import ops
        >>> x = jnp.ones((2, 16, 64))          # (N, C, W)
        >>> w = jnp.ones((4, 16))              # (S, C)
        >>> ops.depthwise_conv1d(x, w, padding="CAUSAL").shape
        (2, 16, 64)
        >>> ops.depthwise_conv1d(x, w, bias=jnp.zeros(16),
        ...                      activation="silu").shape
        (2, 16, 64)
    """
    if _axes_tuple(model_reduce_axes):
        raise ValueError(
            "depthwise_conv1d has no model-axis contraction to reduce: "
            "under channel-group sharding every output channel depends "
            "only on its own input channel, so dx/dw/dbias all stay local "
            "to the shard — shard x and w on C over the model axis and "
            "drop model_reduce_axes (DESIGN.md §17)")
    backend = backend or default_backend()
    activation = _ep.canon(activation)
    grad_reduce_axes = _axes_tuple(grad_reduce_axes)
    bwd_data_cfg = _as_pass_cfg(bwd_data_cfg)
    bwd_weight_cfg = _as_pass_cfg(bwd_weight_cfg)
    S, C = w.shape
    lo, hi = _pad_amounts(S, dilation, padding)
    if lo or hi:
        x = jnp.pad(x, ((0, 0), (0, 0), (lo, hi)))
    Q = x.shape[-1] - (S - 1) * dilation
    if bias is not None:
        assert bias.shape == (C,), (bias.shape, C)
    if residual is not None:
        assert residual.shape == (x.shape[0], C, Q), \
            (residual.shape, (x.shape[0], C, Q))
    if backend == "auto":
        # depthwise kernels have no alg/nblk axes — drop the dense knobs
        (backend, wblk, cblk, _, _, auto_pipe,
         (auto_bd, auto_bw)) = _resolve_auto(
            x, C=C, K=C, S=S, dilation=dilation, padding=padding,
            wblk=wblk, kblk=cblk, depthwise=True,
            epilogue=_ep.signature(bias is not None, activation,
                                   residual is not None))
        pipe = pipe if pipe is not None else auto_pipe
        bwd_data_cfg = bwd_data_cfg or auto_bd
        bwd_weight_cfg = bwd_weight_cfg or auto_bw
    if backend in ("ref", "xla") and grad_reduce_axes:
        w = _psum_cotangent(grad_reduce_axes, w)
        if bias is not None:
            bias = _psum_cotangent(grad_reduce_axes, bias)
    N = x.shape[0]
    attrs = dict(backend=backend, N=N, C=C, K=C, S=S, dilation=dilation,
                 Q=Q, dtype=jnp.dtype(x.dtype).name, depthwise=True)
    if backend == "ref":
        thunk = lambda: _ref.depthwise_conv1d_fused_ref(  # noqa: E731
            x, w, dilation=dilation, bias=bias, activation=activation,
            residual=residual, out_dtype=out_dtype)
    elif backend == "xla":
        def thunk():
            u = _ep.apply_ref(_ref._xla_depthwise_conv1d_f32(x, w, dilation),
                              bias=bias, residual=residual,
                              activation=activation)
            return u.astype(out_dtype or x.dtype)
    elif backend == "pallas":
        wblk = wblk or pick_wblk(Q, S, dilation)
        interpret = _interpret_default() if interpret is None else interpret
        spec = _FusedSpec(dilation, wblk, cblk, interpret, activation,
                          _dtype_name(bias), _dtype_name(residual),
                          jnp.dtype(out_dtype).name if out_dtype else None,
                          bwd_data_cfg, bwd_weight_cfg,
                          reduce_axes=grad_reduce_axes,
                          pipe=_k.canon_pipe(pipe),
                          reduce_chunks=int(grad_reduce_chunks or 1)
                          if grad_reduce_axes else 1)
        attrs.update(wblk=wblk, cblk=cblk,
                     **_pipe_attrs(spec.pipe, pass_="fwd", N=N, C=C, K=C,
                                   S=S, dilation=dilation, Q=Q,
                                   dtype=x.dtype, depthwise=True,
                                   wblk=wblk, kblk=cblk, alg=None, nblk=1))
        thunk = lambda: _dw_conv1d_pallas(spec, x, w, bias, residual)  # noqa: E731
    else:
        raise ValueError(f"unknown conv backend {backend!r}")
    return _obs_conv("fwd", thunk, attrs=attrs)
