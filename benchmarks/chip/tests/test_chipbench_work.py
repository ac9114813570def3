"""Conv work counts and the peak table."""
from __future__ import annotations

import pytest

from benchmarks.chip.lib import device, spec as specmod, work
from repro.roofline import flops as program_flops

SHAPES = [(8, 15, 15, 51, 60_000, 8), (64, 16, 16, 51, 60_000, 8),
          (2, 1, 15, 51, 4096, 8), (3, 15, 1, 5, 100, 2)]


@pytest.mark.parametrize("N,C,K,S,Q,d", SHAPES)
def test_forward_agrees_with_program_counts(N, C, K, S, Q, d):
    assert work.conv1d_flops(N, C, K, S, Q) == \
        program_flops.conv1d_flops(N, C, K, S, Q)
    for b in (2, 4):
        assert work.conv1d_min_bytes("fwd", N, C, K, S, Q, d, b) == \
            program_flops.conv1d_min_bytes(N, C, K, S, Q, d, b)


def test_step_work_at_logical_shapes():
    """The counts use the configured channels (15, not the 16 the kernels
    pad to) and the layer's own width, so padding or formulation cannot
    move them; the stem has no data-gradient pass."""
    cfg = specmod.load_cell("train.atacworks.b64")["config"]
    layers = specmod.reference(cfg).layer_shapes(cfg)
    assert len(layers) == 25
    w = work.step_work(layers, N=1, Q=60_000, S=51, dilation=8,
                       bytes_per_elem=4)
    per_fwd = 2.0 * 60_000 * 51 * (1 * 15 + 22 * 15 * 15 + 2 * 15)
    assert w["fwd"]["flops"] == per_fwd
    assert w["bwd_weight"]["flops"] == per_fwd
    assert w["bwd_data"]["flops"] == per_fwd - 2.0 * 60_000 * 51 * 15
    assert work.total_flops(w) == pytest.approx(91.66e9, rel=1e-3)
    padded = work.step_work([(16 if c == 15 else c, 16 if k == 15 else k, n)
                             for c, k, n in layers], N=1, Q=60_000, S=51,
                            dilation=8, bytes_per_elem=4)
    assert work.total_flops(padded) > work.total_flops(w)


def test_roofline_takes_the_binding_term_of_each_pass():
    peak = device.peaks("TPU v5 lite")
    w = {"fwd": {"flops": 197e12, "bytes": 0.0},
         "bwd_data": {"flops": 0.0, "bytes": 819e9 * 2},
         "bwd_weight": {"flops": 197e12, "bytes": 819e9 * 3}}
    assert work.roofline_seconds(w, peak) == pytest.approx(6.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        device.peaks("cpu")
