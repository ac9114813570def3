"""Layer scopes and kernel-build counters of the AtacWorks train step.

A small fp32 train step (filter 5, dilation 2, batch 2, width 256) with
every conv on the Pallas kernels (interpret mode on the CPU) is traced
once and compiled ahead of time.  The compiled HLO's metadata must name
the layer of every conv kernel op, pad and slice, forward and backward,
and the optimizer's ops must carry ``step.optimizer``: the device trace
reads these names.  The trace must count one kernel build per Pallas
call, across fewer distinct kernel signatures.
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from repro import configs, obs
from repro.core import blocks
from repro.train import train_step as ts

B, W = 2, 256
SCOPE = re.compile(r"(?<![\w.])((?:layer|step)\.\w+(?:\.\w+)*)")
INSTR = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
KERNEL = re.compile(r"conv1d_(fwd|bwd_data|bwd_weight)")
LAYERS = ({blocks.STEM, blocks.HEAD_SIGNAL, blocks.HEAD_PEAK}
          | {blocks.res_scope(i, c) for i in range(blocks.N_RES_BLOCKS)
             for c in (1, 2)})


@pytest.fixture(scope="module")
def step(monkeypatch_module):
    """The traced step, its build counters, and its compiled HLO text."""
    monkeypatch_module.setenv("REPRO_CONV_BACKEND", "pallas")
    cfg = dataclasses.replace(configs.get("atacworks"), conv_filter=5,
                              conv_dilation=2)
    state = ts.init_state(blocks.init_params(jax.random.key(0), cfg))
    batch = {k: jnp.ones((B, W)) for k in ("noisy", "clean", "peaks")}
    obs.reset_counters()
    traced = jax.jit(ts.make_train_step(cfg)).trace(state, batch)
    counts = obs.counters()
    obs.reset_counters()
    hlo = traced.lower().compile().as_text()
    return traced.jaxpr, counts, hlo


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


def _instructions(hlo: str):
    """(opcode, op_name or None) of every HLO instruction."""
    for line in hlo.splitlines():
        m = INSTR.match(line)
        if m:
            on = OP_NAME.search(line)
            yield m.group(1), on.group(1) if on else None


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_kernels_pads_and_slices_carry_one_layer_scope(step):
    _, _, hlo = step
    seen = Counter()
    for opcode, op_name in _instructions(hlo):
        if opcode in ("pad", "slice", "custom-call") or KERNEL.search(
                op_name or ""):
            layers = [s for s in SCOPE.findall(op_name or "")
                      if s.startswith("layer.")]
            assert len(layers) == 1, (opcode, op_name)
            seen[layers[0]] += 1
    assert set(seen) == LAYERS
    # the backward of a layer carries its scope too
    assert re.search(
        r"transpose\(jvp\(layer\.res3\.conv1\)\)/conv1d_bwd_data", hlo)


def test_optimizer_ops_carry_step_optimizer(step):
    from jax._src import source_info_util

    jaxpr, _, hlo = step
    # the update runs at the step's top level, where name stacks are whole
    adamw = [e for e in jaxpr.eqns
             if (f := source_info_util.user_frame(e.source_info.traceback))
             and f.file_name.endswith("optim/adamw.py")]
    assert adamw
    for e in adamw:
        assert "step.optimizer" in str(e.source_info.name_stack), e
    compiled = Counter(s for _, on in _instructions(hlo)
                       for s in SCOPE.findall(on or "")
                       if s.startswith("step."))
    assert set(compiled) == {"step.loss", "step.optimizer", "step.guard"}


def test_build_counters_count_every_pallas_call(step):
    jaxpr, counts, _ = step
    calls = Counter(KERNEL.search(str(e.params["name"])).group(0)
                    for e in _eqns(jaxpr) if e.primitive.name == "pallas_call")
    n_layers = 1 + 2 * blocks.N_RES_BLOCKS + 2
    # every layer's three passes are traced; the stem's data gradient is
    # traced too (the compiler drops it: its input is data)
    assert calls == {"conv1d_fwd": n_layers, "conv1d_bwd_data": n_layers,
                     "conv1d_bwd_weight": n_layers}
    assert counts["kernels.build"] == sum(calls.values())
    assert 0 < counts["kernels.build_distinct"] < counts["kernels.build"]
    assert counts["kernels.build_s"] > 0
