"""Faults planted in the program under test, to show that the correctness
check catches them.  Each is a context manager that patches one factory of
the program for as long as it is open; a cell built inside it runs the
broken path through the same harness as a sound run."""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(module, name: str, wrap):
    orig = getattr(module, name)
    setattr(module, name, wrap(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def train_state_unchanged():
    """The train step returns the state it was given."""
    from repro.train import train_step as ts

    def wrap(make):
        def make_faulty(*a, **kw):
            step = make(*a, **kw)

            def faulty(state, batch):
                return state, step(state, batch)[1]
            return faulty
        return make_faulty
    return _patched(ts, "make_train_step", wrap)


def train_half_batch():
    """The loss leaves out the second half of each (local) batch and
    takes the mean over the rest."""
    from repro.core import blocks

    def wrap(loss_fn):
        def faulty(params, cfg, batch, **kw):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return loss_fn(params, cfg, half, **kw)
        return faulty
    return _patched(blocks, "loss_fn", wrap)


def train_no_exchange():
    """The data-parallel step leaves out the gradient all-reduce: each
    chip steps on its own shard's gradient."""
    from repro.train import data_parallel

    def wrap(make_loss_fn):
        def faulty(cfg, **kw):
            kw.update(grad_reduce_axes=None, grad_reduce_chunks=None)
            return make_loss_fn(cfg, **kw)
        return faulty
    return _patched(data_parallel, "make_loss_fn", wrap)


def stream_state_unchanged():
    """The stream step returns the ring buffers it was given."""
    from repro.launch import serve

    def wrap(make):
        def make_faulty(cfg, **kw):
            step = make(cfg, **kw)

            def faulty(params, state, chunk):
                return step(params, state, chunk)[0], state
            return faulty
        return make_faulty
    return _patched(serve, "make_conv_stream_step", wrap)


def stream_answer_altered():
    """The stream step adds 1 to the first served signal column of every
    slot."""
    from repro.launch import serve

    def wrap(make):
        def make_faulty(cfg, **kw):
            step = make(cfg, **kw)

            def faulty(params, state, chunk):
                (signal, peak), new = step(params, state, chunk)
                return (signal.at[:, 0].add(1.0), peak), new
            return faulty
        return make_faulty
    return _patched(serve, "make_conv_stream_step", wrap)


TRAIN = {"state_unchanged": train_state_unchanged,
         "half_batch": train_half_batch}
STREAM = {"state_unchanged": stream_state_unchanged,
          "answer_altered": stream_answer_altered}
