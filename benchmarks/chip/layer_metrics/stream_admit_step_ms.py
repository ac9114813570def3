"""Median host-clock time of the window's server steps that admitted at
least one stream, in milliseconds: slot reset, prefill and state scatter
on top of the chunk step."""
import statistics


def read(r):
    adm = [s for s, a in zip(r["step_s"], r["admitted"]) if a]
    return 1e3 * statistics.median(adm) if adm else None
