"""Device time per train step of the conv kernels of the stem and the two
heads, in milliseconds: the kernels under the ``layer.stem`` and
``layer.head_*`` scopes, forward and backward, from the traced window,
averaged over the chips.  These layers have one input or one output
channel, padded to the sublane tile."""
from benchmarks.chip.lib import scopes


def read(r):
    return scopes.per_step_ms(r, "edge")
