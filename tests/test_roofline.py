"""Roofline machinery: HLO collective parser + analytic flop validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.masked_adam import MaskedAdamState, init_state
from repro.launch.steps import make_train_step
from repro.models.registry import build
from repro.roofline.analysis import (collective_bytes_from_hlo,
                                     kernel_roofline_fraction,
                                     roofline_terms)
from repro.roofline.analytic import ShapeSpec, analytic_cost


def test_collective_parser_flat():
    hlo = """
HloModule test

ENTRY %main (p: f32[8,16]) -> f32[8,16] {
  %p = f32[8,16] parameter(0)
  %ag = f32[16,16] all-gather(%p), replica_groups={}
  %ar = f32[8,16]{1,0} all-reduce(%p), to_apply=%add
  ROOT %out = f32[8,16] add(%ar, %ar)
}
"""
    got = collective_bytes_from_hlo(hlo)
    assert got["totals"]["all-gather"] == 16 * 16 * 4
    assert got["totals"]["all-reduce"] == 8 * 16 * 4
    assert got["counts"]["all-gather"] == 1


def test_collective_parser_scan_aware():
    """A collective inside a while body counts trip-count times."""
    hlo = """
HloModule test

%body.1 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]) parameter(0)
  %ag = f32[8] all-gather(%gte), replica_groups={}
  ROOT %t = (s32[], f32[4]) tuple(%i, %gte)
}

%cond.1 (arg: (s32[], f32[4])) -> pred[] {
  %arg = (s32[], f32[4]) parameter(0)
  %c = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4] parameter(0)
  %w = (s32[], f32[4]) while(%init), condition=%cond.1, body=%body.1
  ROOT %out = f32[4] get-tuple-element(%w), index=1
}
"""
    got = collective_bytes_from_hlo(hlo)
    assert got["totals"]["all-gather"] == 7 * 8 * 4
    assert got["counts"]["all-gather"] == 7


def test_analytic_matches_hlo_on_unrolled_smoke():
    """On a small, fully-unrolled, unchunked config the analytic FLOP model
    must track XLA's own count within modeling tolerance."""
    B, S = 2, 64
    cfg = get_smoke("gemma-2b").replace(
        scan_unroll=True, attn_q_chunk=S, attn_kv_chunk=S, remat=False
    )
    model = build(cfg)
    params = model.abstract()
    opt = MaskedAdamState(
        m=params,
        v=jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), params),
        count=jax.ShapeDtypeStruct((), jnp.int32),
    )
    mask = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bool_), params)
    batch = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    step = make_train_step(model)
    compiled = jax.jit(step).lower(params, opt, mask, batch).compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo_flops = float(cost["flops"])
    ana = analytic_cost(cfg, ShapeSpec(kind="train", seq_len=S, global_batch=B))
    assert ana["flops"] == pytest.approx(hlo_flops, rel=0.35)


def test_roofline_terms_bottleneck():
    v5e = "TPU v5 lite"
    t = roofline_terms(flops=1e18, hbm_bytes=1e12, collective_bytes=1e12,
                       chips=256, device_kind=v5e)
    assert t["bottleneck"] == "compute"
    assert t["t_compute_s"] > t["t_memory_s"]
    assert t["t_compute_s"] == 1e18 / (256 * 197e12)
    t2 = roofline_terms(flops=1e12, hbm_bytes=1e13, collective_bytes=1e9,
                        chips=256, device_kind=v5e)
    assert t2["bottleneck"] == "memory"
    assert t2["t_memory_s"] == 1e13 / (256 * 819e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_roofline_unknown_device_kind_raises(kind):
    """Peaks come from the device-kind table only: a kind missing from it
    is an error, never a silent v5e default."""
    with pytest.raises(ValueError, match="no published peaks"):
        roofline_terms(flops=1.0, hbm_bytes=1.0, collective_bytes=1.0,
                       chips=1, device_kind=kind)
    with pytest.raises(ValueError, match="no published peaks"):
        kernel_roofline_fraction(1024, 1e-3, device_kind=kind)
