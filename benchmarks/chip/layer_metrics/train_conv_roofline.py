"""Share of the conv kernels' roofline, in percent.

Over every conv pass of the steps in the traced window: the least time the
chip could take (per pass the larger of its FLOPs over the bf16 peak and
its least bytes over the HBM bandwidth, at logical shapes), over the
device time of the ops whose names carry conv1d_fwd, conv1d_bwd_data or
conv1d_bwd_weight, summed over the chips."""
from benchmarks.chip.lib import work


def read(r):
    t = r["trace"]
    if t is None or r["peaks"] is None:
        return None
    kernel_s = sum(t["kernel_s"].values())
    if kernel_s <= 0:
        return None
    least = work.roofline_seconds(r["work_per_step"], r["peaks"])
    return 100.0 * r["steps"] * least / kernel_s
