"""Roofline/HLO analysis: parser correctness on synthetic HLO + validation
of the text cost model against XLA's cost_analysis on loop-free graphs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import HloModule
from repro.analysis.roofline import (
    HW,
    RooflineReport,
    collective_bytes_from_hlo,
    model_flops_for,
)
from repro.configs import get_config, shapes as shp

jax.config.update("jax_platform_name", "cpu")

SYNTH_HLO = """
HloModule test

%add.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

%cond.1 (p: (s32[], f32[128,256])) -> pred[] {
  %p = (s32[], f32[128,256]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body.1 (p: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %p = (s32[], f32[128,256]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[128,256]{1,0} get-tuple-element(%p), index=1
  %ar = f32[128,256]{1,0} all-reduce(%x), replica_groups={}, to_apply=%add.1
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[128,256]) tuple(%ni, %ar)
}

ENTRY %main (arg: f32[128,256]) -> f32[128,256] {
  %arg = f32[128,256]{1,0} parameter(0)
  %w = f32[256,512]{1,0} parameter(1)
  %d = f32[128,512]{1,0} dot(%arg, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ag = f32[128,512]{1,0} all-gather(%d), replica_groups={}, dimensions={1}
  %zero = s32[] constant(0)
  %t0 = (s32[], f32[128,256]) tuple(%zero, %arg)
  %loop = (s32[], f32[128,256]) while(%t0), condition=%cond.1, body=%body.1
  ROOT %out = f32[128,256]{1,0} get-tuple-element(%loop), index=1
}
"""


class TestHloTextModel:
    def test_collective_bytes_with_loop_multiplier(self):
        out = collective_bytes_from_hlo(SYNTH_HLO)
        # all-gather operand: 128x512 f32 = 256 KiB (x1)
        assert out["all-gather"] == 128 * 512 * 4
        # all-reduce inside the while: 128x256 f32 x 10 trips
        assert out["all-reduce"] == 128 * 256 * 4 * 10
        assert out["total"] == out["all-gather"] + out["all-reduce"]

    def test_dot_flops_and_trip_counts(self):
        mod = HloModule(SYNTH_HLO)
        assert mod.dot_flops() == 2 * 128 * 512 * 256
        assert any(abs(v - 10.0) < 1e-9 for v in mod.while_summary().values())

    def test_matches_xla_cost_analysis_loop_free(self):
        """On a loop-free jitted graph the text model's dot flops must match
        XLA's cost_analysis (the decode-graph validation)."""
        a = jnp.zeros((64, 128), jnp.float32)
        b = jnp.zeros((128, 256), jnp.float32)
        compiled = jax.jit(lambda a, b: a @ b).lower(a, b).compile()
        mod = HloModule(compiled.as_text())
        xla = compiled.cost_analysis()["flops"]
        assert abs(mod.dot_flops() - xla) / xla < 0.01

    def test_loop_flops_corrected_vs_xla(self):
        """With a scan, the text model must exceed XLA's (undercounted) flops
        by ~ the trip count."""
        w = jnp.zeros((8, 64, 64), jnp.float32)
        x = jnp.zeros((4, 64), jnp.float32)

        def f(w, x):
            def body(h, wi):
                return jnp.tanh(h @ wi), None
            h, _ = jax.lax.scan(body, x, w)
            return h

        compiled = jax.jit(f).lower(w, x).compile()
        mod = HloModule(compiled.as_text())
        xla = compiled.cost_analysis()["flops"]
        ratio = mod.dot_flops() / max(xla, 1)
        assert 4.0 < ratio <= 9.0, ratio  # ~8 iterations


class TestRooflineReport:
    def _report(self, **kw):
        base = dict(
            arch="a", shape="train_4k", mesh="16x16", chips=256,
            device_flops=1e12, device_bytes=1e11, collective_bytes=1e9,
            collective_by_kind={}, model_flops=2.56e14, peak_memory_bytes=1e9,
        )
        base.update(kw)
        return RooflineReport(**base)

    def test_terms_and_bottleneck(self):
        r = self._report()
        assert abs(r.t_compute - 1e12 / 197e12) < 1e-12
        assert abs(r.t_memory - 1e11 / 819e9) < 1e-9
        assert abs(r.t_collective - 1e9 / 50e9) < 1e-9
        assert r.bottleneck == "memory"

    def test_useful_ratio(self):
        r = self._report()
        assert abs(r.useful_flops_ratio - 2.56e14 / (1e12 * 256)) < 1e-9

    def test_roofline_fraction_compute_bound_perfect(self):
        # all terms compute, useful == total => fraction 1
        r = self._report(
            device_flops=1e12, device_bytes=0.0, collective_bytes=0.0,
            model_flops=1e12 * 256,
        )
        assert abs(r.roofline_fraction - 1.0) < 1e-9


class TestModelFlops:
    def test_train_is_6nd(self):
        cfg = get_config("deepseek-7b")
        f = model_flops_for(cfg, shp.TRAIN_4K)
        want = 6.0 * cfg.active_params() * 256 * 4096
        assert abs(f - want) / want < 1e-9

    def test_decode_counts_kv_span(self):
        cfg = get_config("mixtral-8x22b")  # SWA window 4096
        f = model_flops_for(cfg, shp.DECODE_32K)
        # attention span capped at the window, not the 32k cache
        per_layer_kv = 2 * 2 * cfg.num_heads * cfg.head_dim * 4096
        assert f > 2.0 * cfg.active_params() * 128
        assert f < (2.0 * cfg.active_params() + 56 * per_layer_kv * 2) * 128

    def test_moe_uses_active_params(self):
        cfg = get_config("llama4-scout-17b-a16e")
        f = model_flops_for(cfg, shp.TRAIN_4K)
        assert f < 6.0 * cfg.total_params() * 256 * 4096 / 3
