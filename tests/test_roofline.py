"""Roofline machinery: the trip-count-aware HLO cost analyzer on known
programs, collective wire factors, analytic traffic model sanity."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models.config import SHAPES_BY_NAME
from repro.roofline.analysis import Roofline, model_flops_estimate
from repro.roofline.analytic import traffic
from repro.roofline.hlo_cost import HloCostModel, analyze_hlo_text


def test_nested_scan_flops_exact():
    x = jnp.zeros((64, 64))
    w = jnp.zeros((5, 64, 64))

    def f(x, w):
        def inner(c, wi):
            c2 = jax.lax.scan(lambda a, _: (a @ wi, None), c,
                              jnp.arange(3))[0]
            return c2, None
        return jax.lax.scan(inner, x, w)[0]

    c = jax.jit(f).lower(x, w).compile()
    cost = analyze_hlo_text(c.as_text())
    assert cost.flops == pytest.approx(2 * 64 ** 3 * 15, rel=1e-6)


def test_unrolled_matches_xla():
    x = jnp.zeros((32, 32))

    def f(x):
        for _ in range(4):
            x = x @ x
        return x

    c = jax.jit(f).lower(x).compile()
    cost = analyze_hlo_text(c.as_text())
    ca = c.cost_analysis()
    assert cost.flops == pytest.approx(float(ca["flops"]), rel=0.05)


def test_collective_wire_factors():
    hlo = """
HloModule m, is_scheduled=true

ENTRY %main (p: f32[64,128]) -> f32[64,128] {
  %p = f32[64,128]{1,0} parameter(0)
  %ar = f32[64,128]{1,0} all-reduce(%p), replica_groups=[2,4]<=[8], to_apply=%add
  %ag = f32[64,128]{1,0} all-gather(%ar), replica_groups=[2,4]<=[8], dimensions={0}
  ROOT %cp = f32[64,128]{1,0} collective-permute(%ag), source_target_pairs={{0,1}}
}
"""
    cost = analyze_hlo_text(hlo)
    bytes_ = 64 * 128 * 4
    want = bytes_ * (2 * 3 / 4) + bytes_ * (3 / 4) + bytes_ * 1.0
    assert cost.coll_wire_bytes == pytest.approx(want)
    assert cost.coll_counts == {"all-reduce": 1, "all-gather": 1,
                                "collective-permute": 1}


def test_while_trip_count_multiplies_collectives():
    hlo = """
HloModule m

%body (p: (s32[], f32[128])) -> (s32[], f32[128]) {
  %p = (s32[], f32[128]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[128]{0} get-tuple-element(%p), index=1
  %ar = f32[128]{0} all-reduce(%x), replica_groups=[1,4]<=[4], to_apply=%add
  ROOT %t = (s32[], f32[128]) tuple(%i, %ar)
}

ENTRY %main (a: f32[128]) -> f32[128] {
  %a = f32[128]{0} parameter(0)
  %c = s32[] constant(0)
  %t0 = (s32[], f32[128]) tuple(%c, %a)
  %w = (s32[], f32[128]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  ROOT %out = f32[128]{0} get-tuple-element(%w), index=1
}
"""
    cost = analyze_hlo_text(hlo)
    assert cost.coll_counts["all-reduce"] == 7
    assert cost.coll_wire_bytes == pytest.approx(7 * 128 * 4 * (2 * 3 / 4))


def test_roofline_dominant_and_ratio():
    r = Roofline(name="x", device_kind="TPU v5 lite", chips=4,
                 flops_per_device=197e12,
                 bytes_per_device=819e9 * 2, collective_wire_bytes=50e9 / 2,
                 collective_counts={}, memory_stats={}, model_flops=197e12)
    assert r.compute_s == pytest.approx(1.0)
    assert r.memory_s == pytest.approx(2.0)
    assert r.collective_s == pytest.approx(0.5)
    assert r.dominant == "memory"
    assert r.useful_flops_ratio == pytest.approx(0.25)
    # a chip without published peaks is an error, not a v5e default
    with pytest.raises(ValueError, match="no published peaks"):
        dataclasses.replace(r, device_kind="cpu")


def test_model_flops_estimate_rules():
    cfg = get_config("mixtral-8x7b")
    tr = model_flops_estimate(cfg, SHAPES_BY_NAME["train_4k"])
    pf = model_flops_estimate(cfg, SHAPES_BY_NAME["prefill_32k"])
    dc = model_flops_estimate(cfg, SHAPES_BY_NAME["decode_32k"])
    n_active = cfg.active_param_count()
    assert tr == pytest.approx(6 * n_active * 4096 * 256)
    assert pf == pytest.approx(2 * n_active * 32768 * 32)
    assert dc == pytest.approx(2 * n_active * 128)


@pytest.mark.parametrize("shape_name", list(SHAPES_BY_NAME))
def test_analytic_traffic_positive_and_ordered(shape_name):
    cfg = get_config("codeqwen1.5-7b")
    shape = SHAPES_BY_NAME[shape_name]
    tb = traffic(cfg, shape, data_ax=16, model_ax=16)
    assert tb.total > 0
    # more chips on the model axis must not increase per-device traffic
    tb_wide = traffic(cfg, shape, data_ax=16, model_ax=32)
    assert tb_wide.total <= tb.total * 1.01


def test_hlo_parser_handles_tuple_shapes_with_comments():
    hlo = """
HloModule m

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %t = (f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, f32[8]{0}, /*index=5*/f32[8]{0}) tuple(%a, %a, %a, %a, %a, %a)
  ROOT %g = f32[8]{0} get-tuple-element(%t), index=5
}
"""
    model = HloCostModel(hlo)
    assert model.entry == "main"
    instrs = {i.name: i for i in model.computations["main"]}
    assert "t" in instrs and instrs["t"].opcode == "tuple"
