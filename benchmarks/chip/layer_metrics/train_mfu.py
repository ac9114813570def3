"""Model FLOP/s utilization of the whole train step, in percent.

The FLOPs the step requires per 60,000-column segment (forward, data
gradient and weight gradient of every conv at logical shapes; the stem's
data gradient is not required, its input being data) times the segments
per second of the run, over the chips' combined published bf16 peak.  The
bf16 peak serves both dtypes.  Host clock: the run's own window."""


def read(r):
    if r["peaks"] is None or r["window_s"] <= 0:
        return None
    rate = r["segments"] / r["window_s"]
    return 100.0 * r["flops_per_segment"] * rate / (
        r["chips"] * r["peaks"]["flops_per_s"])
