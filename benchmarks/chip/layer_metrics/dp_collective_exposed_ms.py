"""Exposed collective time per train step, in milliseconds: the part of a
chip's collective ops (the all-reduces of the data-parallel exchange) in
which no other op runs on that chip, from the traced window, averaged over
the chips.  None where no collective op ran."""


def read(r):
    t = r["trace"]
    if t is None or r["steps"] <= 0 or t["collective_s"] <= 0:
        return None
    return 1e3 * t["collective_exposed_s"] / r["steps"]
