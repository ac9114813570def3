"""Device time per train step of the loss, the optimizer and the
non-finite guard, in milliseconds: every op under the ``step.loss``,
``step.optimizer`` and ``step.guard`` scopes, forward and backward, from
the traced window, averaged over the chips."""
from benchmarks.chip.lib import scopes


def read(r):
    return scopes.per_step_ms(r, "update")
