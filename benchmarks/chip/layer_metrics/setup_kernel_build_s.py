"""Seconds the program spent building conv kernels in set-up: its
``kernels.build_s`` counter, the time inside each ``pallas_call`` while
the train step is traced (Mosaic's lowering comes later and is not in
it).  None where the program keeps no such counter."""


def read(r):
    if r["trace"] is None:
        return None
    from repro import obs

    return obs.counters().get("kernels.build_s")
