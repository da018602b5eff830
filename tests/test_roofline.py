"""Roofline machinery: collective parser, wire-byte factors, flop counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES, get_config
from repro.roofline.analysis import (Roofline, model_flops,
                                     parse_collectives, peaks)

V5E = peaks("TPU v5 lite")

HLO_SAMPLE = """
HloModule test
%add { ... }
ENTRY %main {
  %ar = f32[1024,512]{1,0} all-reduce(%x), channel_id=1, replica_groups=[32,16]<=[512], use_global_device_ids=true, to_apply=%add
  %ag = bf16[256,256]{1,0} all-gather(%y), channel_id=2, replica_groups=[16,32]<=[512], dimensions={0}
  %rs = f32[64,64]{1,0} reduce-scatter(%z), channel_id=3, replica_groups=[2,256]<=[512], to_apply=%add
  %cp = f32[128]{0} collective-permute(%w), channel_id=4, source_target_pairs={{0,1}}
  %nothing = f32[8,8]{1,0} add(%a, %b)
}
"""


class TestCollectiveParser:
    def test_kinds_and_counts(self):
        st = parse_collectives(HLO_SAMPLE)
        assert set(st.by_kind) == {"all-reduce", "all-gather",
                                   "reduce-scatter", "collective-permute"}
        assert all(v["count"] == 1 for v in st.by_kind.values())

    def test_wire_byte_factors(self):
        st = parse_collectives(HLO_SAMPLE)
        ar = 1024 * 512 * 4
        assert st.by_kind["all-reduce"]["wire"] == pytest.approx(
            2 * 15 / 16 * ar)
        ag = 256 * 256 * 2
        assert st.by_kind["all-gather"]["wire"] == pytest.approx(
            31 / 32 * ag)
        rs = 64 * 64 * 4
        assert st.by_kind["reduce-scatter"]["wire"] == pytest.approx(
            255 * rs)
        assert st.by_kind["collective-permute"]["wire"] == 128 * 4

    def test_real_compiled_hlo_has_collectives(self):
        """End-to-end on a real sharded executable (1-device degenerate
        mesh still emits no collectives — use replica groups check only
        when devices > 1, so here just assert the parse is clean)."""
        st = parse_collectives("no collectives here")
        assert st.wire_bytes == 0 and st.by_kind == {}


class TestScanAccounting:
    def test_cost_analysis_counts_scan_body_once(self):
        """The measured fact that motivates the dry-run's depth
        extrapolation (EXPERIMENTS.md §Dry-run): XLA cost analysis does
        NOT multiply a while-loop body by its trip count."""

        def scanned(x, w):
            return jax.lax.scan(lambda c, _: (jnp.tanh(c @ w), None), x,
                                None, length=8)[0]

        def unrolled(x, w):
            for _ in range(8):
                x = jnp.tanh(x @ w)
            return x

        xs = jax.ShapeDtypeStruct((64, 128), jnp.float32)
        ws = jax.ShapeDtypeStruct((128, 128), jnp.float32)
        f_scan = jax.jit(scanned).lower(xs, ws).compile().cost_analysis()
        f_unr = jax.jit(unrolled).lower(xs, ws).compile().cost_analysis()
        assert f_unr["flops"] == pytest.approx(8 * f_scan["flops"], rel=0.01)

    def test_depth_extrapolation_is_exact_for_identical_layers(self):
        """cost(L) is affine in L when layers are identical: c1 + (L-1)·Δ."""

        def model(n):
            def f(x, w):
                for _ in range(n):
                    x = jnp.tanh(x @ w)
                return x.sum()
            return f

        xs = jax.ShapeDtypeStruct((32, 64), jnp.float32)
        ws = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        cost = lambda n: jax.jit(model(n)).lower(
            xs, ws).compile().cost_analysis()["flops"]
        c1, c2, c5 = cost(1), cost(2), cost(5)
        assert c5 == pytest.approx(c1 + 4 * (c2 - c1), rel=0.01)


class TestModelFlops:
    def test_dense_6nd(self):
        cfg = get_config("yi_6b")
        mf = model_flops(cfg, SHAPES["train_4k"], 256)
        n = cfg.n_params
        tokens = 4096 * 256
        assert mf == pytest.approx(6 * n * tokens / 256)

    def test_moe_uses_active_params(self):
        cfg = get_config("dbrx_132b")
        assert cfg.n_active_params() < 0.35 * cfg.n_params
        mf = model_flops(cfg, SHAPES["train_4k"], 256)
        assert mf == pytest.approx(6 * cfg.n_active_params() * 4096 * 256
                                   / 256)

    def test_param_counts_plausible(self):
        # total params should be in the ballpark of the checkpoint names
        expect = {"yi_6b": (5e9, 8e9), "qwen3_8b": (6e9, 10e9),
                  "qwen2_5_14b": (12e9, 17e9), "granite_3_8b": (7e9, 10e9),
                  "deepseek_v2_lite_16b": (13e9, 18e9),
                  "dbrx_132b": (115e9, 145e9),
                  "musicgen_medium": (1e9, 2.5e9), "rwkv6_7b": (6e9, 9e9),
                  "internvl2_1b": (0.4e9, 1.2e9),
                  "zamba2_2_7b": (2e9, 3.6e9)}
        for aid, (lo, hi) in expect.items():
            n = get_config(aid).n_params
            assert lo <= n <= hi, f"{aid}: {n / 1e9:.2f}B not in [{lo / 1e9}, {hi / 1e9}]"

    def test_roofline_bottleneck(self):
        r = Roofline(flops=1e15, hbm_bytes=1e12, wire_bytes=1e9,
                     compute_s=1e15 / V5E.flops, memory_s=1e12 / V5E.hbm_bw,
                     collective_s=1e9 / V5E.link_bw, bottleneck="compute",
                     peak_flops=V5E.flops, model_flops=5e14)
        assert r.step_s == pytest.approx(1e15 / V5E.flops)
        assert 0.4 < r.useful_ratio <= 0.5


class TestPeaks:
    def test_v5e_peaks_are_the_published_ones(self):
        assert (V5E.flops, V5E.hbm_bw) == (197e12, 819e9)
        assert "TPU v5e" in V5E.source

    def test_unknown_device_kind_raises(self):
        with pytest.raises(ValueError, match="no published peaks"):
            peaks("cpu")
