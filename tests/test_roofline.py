"""Roofline machinery: HLO collective/traffic parsing, the scan-counted-
once premise, probe extrapolation, and term construction."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.roofline import analysis as ra
from repro.roofline import flops as rf

HLO = """\
HloModule test

%fused_computation.1 (param_0: f32[64,64]) -> f32[64,64] {
  %param_0 = f32[64,64]{1,0} parameter(0)
  ROOT %mul = f32[64,64]{1,0} multiply(%param_0, %param_0)
}

ENTRY %main (p0: f32[64,64], p1: bf16[128]) -> f32[64,64] {
  %p0 = f32[64,64]{1,0} parameter(0)
  %p1 = bf16[128]{0} parameter(1)
  %ag = bf16[2048]{0} all-gather(%p1), replica_groups=[16,16]<=[256]
  %ar = f32[64,64]{1,0} all-reduce(%p0), to_apply=%add
  %cp = f32[64,64]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
  %rs-start = f32[4,64]{1,0} reduce-scatter-start(%cp), dimensions={0}
  %rs-done = f32[4,64]{1,0} reduce-scatter-done(%rs-start)
  ROOT %fus = f32[64,64]{1,0} fusion(%cp), kind=kLoop, calls=%fused_computation.1
}
"""


class TestCollectiveParser:
    def test_kinds_and_bytes(self):
        out = ra.collective_bytes(HLO)
        f = 64 * 64 * 4
        assert out["all-gather"] == 128 * 2          # operand bf16[128]
        assert out["all-reduce"] == f                # operand f32[64,64]
        assert out["collective-permute"] == f
        assert out["reduce-scatter"] == f            # -start counted once
        assert out["count"] == 4
        assert out["total"] == 128 * 2 + 3 * f

    def test_traffic_model_skips_elementwise_and_nested_params(self):
        t = ra.hlo_traffic_bytes(HLO)
        f = 64 * 64 * 4
        # entry params once + collectives (out+operand) + fusion (out+operand)
        expected = (f + 128 * 2) + (2048 * 2 + 128 * 2) + 2 * f + 2 * f \
            + (4 * 64 * 4 + f) + 2 * f
        assert t == expected


class TestScanPremise:
    def test_cost_analysis_counts_while_body_once(self):
        """The premise the whole probe system rests on."""
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None
            return jax.lax.scan(body, x, None, length=10)[0]

        s = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        c = jax.jit(f).lower(s, s).compile()
        flops = c.cost_analysis().get("flops", 0.0)
        one_matmul = 2 * 64 ** 3
        assert flops < 2.5 * one_matmul, (
            "XLA now multiplies while bodies by trip count — remove the "
            "probe extrapolation in roofline/analysis.py")


class TestExtrapolate:
    def test_linear_solve_exact(self):
        rows = [[1, 1, 1], [1, 1, 2], [1, 2, 2]]
        coef = np.array([5.0, 3.0, 2.0])  # base, per-accum, per-layer
        metrics = [{"flops": float(r @ coef), "bytes": 0.0, "bytes_raw": 0.0,
                    "coll_bytes": 0.0} for r in np.asarray(rows)]
        full = ra.extrapolate(metrics, rows, [1, 16, 16 * 36])
        assert np.isclose(full["flops"], 5 + 16 * 3 + 576 * 2)


class TestTerms:
    def test_dominant_and_fraction(self):
        m = {"flops": 197e12, "bytes": 819e9 / 2, "coll_bytes": 0.0}
        t = ra.roofline_terms(m, n_chips=4, model_flops=4 * 197e12 / 2)
        assert t["dominant"] == "compute"
        assert np.isclose(t["compute_s"], 1.0)
        assert np.isclose(t["roofline_fraction"], 0.5)

    def test_memory_floor_counts_for_decode(self):
        m = {"flops": 1.0, "bytes": 819e9, "coll_bytes": 0.0}
        t = ra.roofline_terms(m, n_chips=1, model_flops=1.0,
                              model_bytes=819e9 / 2)
        assert t["dominant"] == "memory"
        assert np.isclose(t["roofline_fraction"], 0.5)


class TestModelFlops:
    def test_param_counts_positive_for_all_archs(self):
        from repro import configs
        for name in configs.names():
            cfg = configs.get(name)
            assert cfg.param_count() > 0, name
            assert cfg.active_param_count() <= cfg.param_count(), name

    def test_deepseek_param_count_near_671b(self):
        from repro import configs
        n = configs.get("deepseek-v3-671b").param_count()
        assert 6.0e11 < n < 7.5e11, n

    def test_qwen3_8b_param_count(self):
        from repro import configs
        n = configs.get("qwen3-8b").param_count()
        assert 7.0e9 < n < 9.5e9, n

    def test_moe_active_well_below_total(self):
        from repro import configs
        cfg = configs.get("moonshot-v1-16b-a3b")
        # assigned config is 48L (vs HF's 27L) -> ~28B total; active stays
        # ~6x smaller (top-6 of 64 experts)
        assert 2e9 < cfg.active_param_count() < 5.5e9
        assert 2e10 < cfg.param_count() < 3.2e10
        assert cfg.param_count() > 4 * cfg.active_param_count()


class TestPeaks:
    def test_exact_device_kind(self):
        v5e = ra.peaks_for("TPU v5 lite")
        assert (v5e.flops_per_s, v5e.bytes_per_s) == (197e12, 819e9)
        # "TPU v5" is the v5p, not a prefix match of the v5e's kind
        assert ra.peaks_for("TPU v5").flops_per_s != v5e.flops_per_s

    def test_unknown_device_kind_raises(self):
        import pytest

        for kind in ("TPU v5e", "tpu", "TPU v7x"):
            with pytest.raises(KeyError, match="no roofline peaks"):
                ra.peaks_for(kind)
