"""Device time per train step of the ops layer's work around the conv
kernels, in milliseconds: every op under a ``layer.*`` scope that is not a
conv kernel or a collective (channel and width pads, slices, casts,
activation-gradient masks), from the traced window, averaged over the
chips."""
from benchmarks.chip.lib import scopes


def read(r):
    return scopes.per_step_ms(r, "glue")
