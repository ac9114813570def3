"""Roofline term derivation from the compiled dry-run artifact.

CPU container, TPU v5e target: wall-time cannot be measured, so the three
roofline terms are *derived* from the SPMD-compiled per-device module:

  compute_s    = HLO_FLOPs_per_device / peak_FLOP/s          (197 Tbf16)
  memory_s     = HLO_bytes_per_device / HBM_bw               (819 GB/s)
  collective_s = collective_bytes_per_device / link_bw       (50 GB/s)

Probing discipline
------------------
``HloCostAnalysis`` counts a while-loop body ONCE regardless of trip count
(verified in tests/test_roofline.py), so costs of anything inside a
``lax.scan`` — the layer stack, the grad-accumulation loop, the chunked-
attention loop — are invisible to a naive reading.  The probe system
therefore lowers reduced-DEPTH configs with every structural loop removed:

  * ``unroll_layers=True``  — python loop over layers AND over the chunked-
                              attention q-chunks (models/common.py),
  * ``unroll_accum=True``   — python loop over microbatches, probed at
                              accum ∈ {1, 2} with the real microbatch size,

and solves a small linear system for the per-layer-type / per-microbatch
costs, which are then combined at the true depth and accumulation count
(``full_row``).  The full-depth scanned compile is still what proves the
cell compiles and supplies ``memory_analysis`` (exact — buffer sizes do not
depend on trip counts).

Collective bytes are NOT in cost_analysis: ``collective_bytes`` parses the
post-partitioning HLO text with a two-pass (definition → operand-name)
resolver and sums operand bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute / ragged-all-to-all.

Known residual approximations (documented in EXPERIMENTS.md §Roofline):
  * the SSD inter-chunk recurrence of mamba2/zamba2 is a scan over T/chunk
    steps whose body is light elementwise state math; its HBM traffic is
    re-added analytically (``ssd_scan_correction``),
  * 'bytes accessed' counts HLO operand bytes, not unique post-fusion HBM
    traffic — an upper bound on the memory term.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, NamedTuple

import numpy as np

# --- TPU v5e-like hardware constants (per chip) ---------------------------
PEAK_FLOPS = 197e12      # bf16 FLOP/s
HBM_BW = 819e9           # bytes/s
ICI_BW = 50e9            # bytes/s per link
HBM_PER_CHIP = 16 * 2**30


# --- Per-device roofline peaks (shared with repro.tune.cost) ---------------


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops_per_s: float
    bytes_per_s: float


# Per-chip peaks keyed by the exact ``device_kind`` JAX reports.  TPU
# figures: Google Cloud TPU documentation, system architecture pages
# ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e"): dense bf16 matmul FLOP/s and
# HBM bandwidth per chip.  "cpu" is not a published peak: it is a nominal
# figure for the host CPU, so the tuner's cost model can rank candidates
# off-chip; it is never a device metric.
DEVICE_PEAKS = {
    "TPU v4": Peaks(275e12, 1200e9),
    "TPU v5 lite": Peaks(197e12, 819e9),     # TPU v5e
    "TPU v5": Peaks(459e12, 2765e9),          # TPU v5p
    "TPU v6 lite": Peaks(918e12, 1640e9),     # TPU v6e (Trillium)
    "cpu": Peaks(1e11, 5e10),
}


def peaks_for(device_kind: str) -> Peaks:
    """The roofline peaks of ``device_kind``; a kind not in
    ``DEVICE_PEAKS`` is an error, never a guess."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no roofline peaks for device_kind {device_kind!r}; add its "
            f"published figures to DEVICE_PEAKS (known: "
            f"{sorted(DEVICE_PEAKS)})") from None


def achieved_fraction_of_peak(flops: float, sec: float,
                              device_kind: str | None = None) -> float:
    """Paper-style *efficiency*: achieved FLOP/s ÷ the device's roofline
    peak — how Figures 4-6 report every measurement.  ``device_kind``
    defaults to the first jax device (the machine the benchmark ran on)."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    return (flops / max(sec, 1e-30)) / peaks_for(device_kind).flops_per_s

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")
# definition line: [ROOT] %name = <type> <opcode>(
_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*"
    r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\](?:\{[^}]*\})?))\s+"
    r"([a-z][\w-]*)\(")
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9a-z]+)?)\[([0-9,]*)\]")
_OPERAND_RE = re.compile(r"%([\w.-]+)")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dtype, 4)
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-op-kind operand bytes summed over every collective instruction
    in the (per-device, post-SPMD) HLO module text.

    HLO prints operands as bare ``%name`` references, so sizes are resolved
    two-pass: first every definition's name → result bytes, then each
    collective's operand list is looked up.  Async pairs are counted at the
    ``-start`` op only.
    """
    sizes: dict[str, int] = {}
    colls: list[tuple[str, str]] = []  # (kind, operand_text)
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, opcode = m.groups()
        sizes[name] = _type_bytes(type_str)
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base in _COLL_KINDS and not opcode.endswith("-done"):
            # operand list: from the call's '(' to its matching ')'
            start = m.end() - 1
            depth, i = 0, start
            while i < len(line):
                if line[i] == "(":
                    depth += 1
                elif line[i] == ")":
                    depth -= 1
                    if depth == 0:
                        break
                i += 1
            colls.append((base, line[start:i + 1]))
    out: dict[str, int] = {k: 0 for k in _COLL_KINDS}
    for kind, operands in colls:
        out[kind] += sum(sizes.get(n, 0)
                         for n in _OPERAND_RE.findall(operands))
    out["total"] = sum(out[k] for k in _COLL_KINDS)
    out["count"] = len(colls)
    return out


# ops whose output (and operands) actually cross HBM on TPU; pure
# elementwise / convert / broadcast / bitcast chains fuse into their
# consumers and never materialise
_MATERIALIZING = {
    "dot", "convolution", "fusion", "custom-call", "copy", "copy-start",
    "dynamic-slice", "dynamic-update-slice", "gather", "scatter", "reduce",
    "reduce-window", "sort", "concatenate", "pad", "rng", "rng-bit-generator",
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "ragged-all-to-all", "cholesky", "triangular-solve",
}
_COMP_RE = re.compile(r"^(ENTRY\s+)?%?[\w.-]+(?:\s+\([^)]*\))?\s*(?:->.*)?\{\s*$")


def hlo_traffic_bytes(hlo_text: str) -> float:
    """Estimated per-device HBM traffic of one module.

    ``cost_analysis()['bytes accessed']`` sums operand bytes of EVERY
    instruction — including converts/broadcasts/elementwise chains that TPU
    fusion keeps in registers — and overstates HBM traffic by ~10×.  This
    model counts output + operand bytes only for ops that materialise a
    buffer (dots, fusions, copies, slices, reduces, collectives), plus
    entry-computation parameter reads once.  Elementwise producers feeding a
    materialising op are attributed through the operand resolution.
    """
    sizes: dict[str, int] = {}
    total = 0.0
    in_entry = False
    for line in hlo_text.splitlines():
        if _COMP_RE.match(line):
            in_entry = line.lstrip().startswith("ENTRY")
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, opcode = m.groups()
        nbytes = _type_bytes(type_str)
        sizes[name] = nbytes
        base = opcode[:-6] if opcode.endswith("-start") else opcode
        if base == "parameter":
            if in_entry:
                total += nbytes
            continue
        if base not in _MATERIALIZING:
            continue
        total += nbytes  # output write
        start = m.end() - 1
        depth, i = 0, start
        while i < len(line):
            if line[i] == "(":
                depth += 1
            elif line[i] == ")":
                depth -= 1
                if depth == 0:
                    break
            i += 1
        total += sum(sizes.get(n, 0)
                     for n in _OPERAND_RE.findall(line[start:i + 1]))
    return total


def compile_metrics(compiled) -> dict[str, Any]:
    """flops / bytes / collective bytes of one compiled per-device module."""
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    coll = collective_bytes(text)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": hlo_traffic_bytes(text),
        "bytes_raw": float(cost.get("bytes accessed", 0.0)),
        "coll_bytes": float(coll["total"]),
        "coll_by_kind": coll,
    }


# ---------------------------------------------------------------------------
# Probe plans
# ---------------------------------------------------------------------------


class Probe(NamedTuple):
    cfg: Any        # reduced-depth, unroll_layers=True, attn_chunk=0
    shape: Any      # possibly reduced-batch ShapeConfig
    accum: int      # grad-accum steps (train probes; 1 otherwise)


def _probe_cfg(cfg, **depth):
    return dataclasses.replace(cfg, unroll_layers=True, **depth)


def probe_plan(cfg, shape, accum_full: int):
    """Returns (probes, rows, full_row): lowering each probe and solving
    ``rows @ coef = metrics`` gives per-layer-type costs; the true cell's
    metric is ``full_row @ coef``."""
    r = dataclasses.replace
    train = shape.kind == "train"
    if cfg.family == "conv":
        # python-loop (unrolled) network, accum=1: the full compile is exact
        return [Probe(cfg, shape, accum_full)], [[1.0]], [1.0]

    mb = shape.global_batch // accum_full if train else shape.global_batch
    A = accum_full

    def probe(accum=1, **depth):
        sh = r(shape, global_batch=accum * mb) if train else shape
        return Probe(_probe_cfg(cfg, **depth), sh, accum if train else 1)

    if cfg.family == "moe" and cfg.moe.first_dense_layers > 0:
        nd, nm = cfg.moe.first_dense_layers, cfg.n_layers - cfg.moe.first_dense_layers
        m = cfg.moe
        dep = lambda d, L: dict(n_layers=L, moe=r(m, first_dense_layers=d))
        probes = [probe(1, **dep(1, 2)), probe(1, **dep(1, 3)),
                  probe(1, **dep(2, 3))]
        rows = [[1, 1, 1, 1], [1, 1, 1, 2], [1, 1, 2, 1]]
        if train:
            probes.append(probe(2, **dep(1, 2)))
            rows.append([1, 2, 2, 2])
        else:
            rows = [row[:1] + row[2:] for row in rows]
        full = [1, A, A * nd, A * nm] if train else [1, nd, nm]
        return probes, rows, full

    if cfg.family == "encdec":
        dep = lambda e, d: dict(n_encoder_layers=e, n_layers=d)
        probes = [probe(1, **dep(1, 1)), probe(1, **dep(2, 1)),
                  probe(1, **dep(1, 2))]
        rows = [[1, 1, 1, 1], [1, 1, 2, 1], [1, 1, 1, 2]]
        if train:
            probes.append(probe(2, **dep(1, 1)))
            rows.append([1, 2, 2, 2])
        else:
            rows = [row[:1] + row[2:] for row in rows]
        full = ([1, A, A * cfg.n_encoder_layers, A * cfg.n_layers] if train
                else [1, cfg.n_encoder_layers, cfg.n_layers])
        return probes, rows, full

    if cfg.family == "hybrid":
        # per-layer mamba cost + per-application shared-block cost; probe
        # depths 6/7/12 (napp = 1/1/2) keep the two separable
        a = cfg.attn_every
        napp_full = len([i for i in range(cfg.n_layers) if i % a == a - 1])
        probes = [probe(1, n_layers=a), probe(1, n_layers=a + 1),
                  probe(1, n_layers=2 * a)]
        rows = [[1, 1, a, 1], [1, 1, a + 1, 1], [1, 1, 2 * a, 2]]
        if train:
            probes.append(probe(2, n_layers=a))
            rows.append([1, 2, 2 * a, 2])
        else:
            rows = [row[:1] + row[2:] for row in rows]
        full = ([1, A, A * cfg.n_layers, A * napp_full] if train
                else [1, cfg.n_layers, napp_full])
        return probes, rows, full

    # single scanned stack (dense / vlm / ssm / moe nd=0)
    probes = [probe(1, n_layers=1), probe(1, n_layers=2)]
    rows = [[1, 1, 1], [1, 1, 2]]
    if train:
        probes.append(probe(2, n_layers=1))
        rows.append([1, 2, 2])
    else:
        rows = [row[:1] + row[2:] for row in rows]
    full = [1, A, A * cfg.n_layers] if train else [1, cfg.n_layers]
    return probes, rows, full


def extrapolate(probe_metrics: list[dict], rows: list[list[float]],
                full_row: list[float]) -> dict[str, float]:
    """Linear solve per metric; returns full-depth metrics."""
    keys = ("flops", "bytes", "bytes_raw", "coll_bytes")
    A = np.asarray(rows, np.float64)
    f = np.asarray(full_row, np.float64)
    out = {}
    for k in keys:
        b = np.asarray([m[k] for m in probe_metrics], np.float64)
        coef, *_ = np.linalg.lstsq(A, b, rcond=None)
        out[k] = float(max(0.0, f @ coef))
    return out


def flash_correction(cfg, shape, n_chips: int) -> dict[str, float]:
    """Analytic adjustment for ``attn_impl='flash'`` cells.

    The probe lowers flash attention as a traffic-equivalent surrogate
    (q/k/v read + o write — the TPU kernel's true HBM footprint), so the
    MXU flops of the softmax(QKᵀ)V itself are missing from the HLO count;
    they have an exact closed form and are re-added here.  The backward
    recompute's extra q/k/v reads are likewise added to bytes."""
    if getattr(cfg, "attn_impl", "chunked") != "flash" \
            or shape.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0, "coll_bytes": 0.0}
    from repro.roofline import flops as rf
    B, T = shape.global_batch, shape.seq_len
    if cfg.family == "vlm":
        T = shape.seq_len  # image+text total
    fwd = rf._attn_seq_flops(cfg, B, T, causal=True)
    if shape.kind == "train":
        # fwd + remat-recompute + bwd(2×fwd) under remat; 3× without
        mult = 4.0 if cfg.remat else 3.0
        n_layers_attn = cfg.n_layers
        qkv_bytes = 2 * B * T * (cfg.q_dim + 2 * cfg.kv_dim) * n_layers_attn
        extra_bytes = 2.0 * qkv_bytes  # recompute + bwd re-reads
    else:
        mult, extra_bytes = 1.0, 0.0
    return {"flops": fwd * mult / n_chips,
            "bytes": extra_bytes / n_chips, "coll_bytes": 0.0}


def ssd_scan_correction(cfg, shape, n_chips: int) -> dict[str, float]:
    """Per-device HBM traffic of the SSD inter-chunk recurrence, which the
    cost analysis sees once but runs T/chunk times (mamba2/zamba2,
    train/prefill only).  ~3 state-sized touches per step, ×3 passes for
    train (fwd + remat-recompute + bwd)."""
    if cfg.family not in ("ssm", "hybrid") or shape.kind == "decode":
        return {"flops": 0.0, "bytes": 0.0, "coll_bytes": 0.0}
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    H = d_inner // s.head_dim
    nc = shape.seq_len // s.chunk
    state_elems = shape.global_batch * H * s.d_state * s.head_dim / n_chips
    passes = 3 if shape.kind == "train" else 1
    extra = cfg.n_layers * max(0, nc - 1) * 3 * state_elems * 4 * passes
    return {"flops": cfg.n_layers * nc * 3 * state_elems * passes,
            "bytes": extra, "coll_bytes": 0.0}


# ---------------------------------------------------------------------------
# Terms
# ---------------------------------------------------------------------------


def roofline_terms(metrics: dict[str, float], n_chips: int,
                   model_flops: float,
                   model_bytes: float = 0.0) -> dict[str, float]:
    """metrics are PER-DEVICE; model_flops/model_bytes are GLOBAL useful
    work per step.  ``roofline_fraction`` = (time an ideal implementation
    needs, i.e. max of the compute and memory floors) / (time the compiled
    program's dominant term forces) — 1.0 means the program sits on its
    achievable roofline."""
    compute_s = metrics["flops"] / PEAK_FLOPS
    memory_s = metrics["bytes"] / HBM_BW
    coll_s = metrics["coll_bytes"] / ICI_BW
    dominant_s = max(compute_s, memory_s, coll_s)
    names = {coll_s: "collective", memory_s: "memory", compute_s: "compute"}
    ideal_compute_s = model_flops / (n_chips * PEAK_FLOPS)
    ideal_memory_s = model_bytes / (n_chips * HBM_BW)
    ideal_s = max(ideal_compute_s, ideal_memory_s)
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": names[dominant_s],
        "dominant_s": dominant_s,
        "model_flops": model_flops,
        "ideal_compute_s": ideal_compute_s,
        "ideal_memory_s": ideal_memory_s,
        "hlo_flops_global": metrics["flops"] * n_chips,
        "useful_ratio": model_flops / max(metrics["flops"] * n_chips, 1.0),
        "roofline_fraction": ideal_s / max(dominant_s, 1e-30),
    }
