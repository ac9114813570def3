"""Median host-clock time of the window's server steps that admitted no
stream, in milliseconds: the chunk step alone."""
import statistics


def read(r):
    plain = [s for s, a in zip(r["step_s"], r["admitted"]) if not a]
    return 1e3 * statistics.median(plain) if plain else None
