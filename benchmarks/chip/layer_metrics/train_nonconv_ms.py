"""Device time per train step outside the conv kernels, in milliseconds:
the ops layer's pads, casts and VJP glue, the loss and the optimizer.
Busy time of a chip (union of its op intervals in the traced window)
less its conv-kernel time, per step, averaged over the chips."""


def read(r):
    t = r["trace"]
    if t is None or r["steps"] <= 0:
        return None
    conv = sum(t["kernel_s"].values()) / t["devices"]
    return 1e3 * (t["busy_s"] - conv) / r["steps"]
