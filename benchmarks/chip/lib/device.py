"""The chips a run holds, their description, and their published peaks."""
from __future__ import annotations

# Published peaks of one chip, by ``device_kind``.  TPU v5e ("TPU v5 lite"):
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s.  A kind missing here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
}


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def peaks(kind: str) -> dict:
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


def chips(n: int, *, require_tpu: bool = True) -> list:
    """The first ``n`` devices; without ``n`` TPU chips this raises."""
    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < n):
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{len(devices)} {devices[0].platform} device(s)")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} device(s), found {len(devices)}")
    return devices[:n]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices, temp_bytes: int = 0) -> int:
    """Peak bytes in use on the fullest chip: the runtime's peak of the
    buffers it tracks (arguments, outputs, everything resident), plus
    ``temp_bytes``, the timed executable's compiled temporary space, which
    the TPU runtime's statistic leaves out."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak + temp_bytes
