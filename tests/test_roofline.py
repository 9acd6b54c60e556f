"""Validates the HLO-text cost model (launch/hlo_cost.py) that feeds the
roofline analysis: trip-count-corrected FLOPs against closed-form 6ND,
collective wire-byte factors, and the Roofline term arithmetic."""
import jax
import pytest

from repro.config import ShapeConfig, TrainConfig, get_arch
from repro.launch.hlo_cost import analyze_hlo, parse_computations, _trip_count
from repro.launch.mesh import make_mesh
from repro.launch.roofline import Roofline, parse_collectives
from repro.launch import specs as S
from repro.models.counting import count_active_params
from repro.models.sharding import use_activation_mesh
from repro.train.steps import make_train_step


# --------------------------------------------------------------------------
# synthetic-HLO unit tests (no compilation)
# --------------------------------------------------------------------------

_WHILE_HLO = """
%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %y = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %c1 = s32[] constant(1)
  %j = s32[] add(%i, %c1)
  ROOT %t = (s32[], f32[8,8]) tuple(%j, %y)
}

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[8,8]) tuple(%z, %a)
  %w = (s32[], f32[8,8]) while(%init), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_while_trip_count_multiplies_flops():
    mc = analyze_hlo(_WHILE_HLO)
    # one 8x8x8 dot per trip, 7 trips: 2*8*8*8*7
    assert mc.flops == pytest.approx(2 * 8 * 8 * 8 * 7)


def test_trip_count_parse():
    comps, _ = parse_computations(_WHILE_HLO)
    assert _trip_count(comps["cond"]) == 7


_COLL_HLO = """
ENTRY %main (a: f32[128]) -> f32[128] {
  %a = f32[128]{0} parameter(0)
  %ag = f32[128]{0} all-gather(%a), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[128]{0} all-reduce(%ag), replica_groups=[2,4]<=[8], to_apply=%add
  ROOT %cp = f32[128]{0} collective-permute(%ar), source_target_pairs={{0,1},{1,0}}
}
"""


def test_collective_wire_bytes_ring_factors():
    mc = analyze_hlo(_COLL_HLO)
    n = 128 * 4  # f32[128]
    # AG over g=4: N*(g-1)/g ; AR over g=4: 2N*(g-1)/g ; permute: N
    assert mc.wire_by_kind["all-gather"] == pytest.approx(n * 3 / 4)
    assert mc.wire_by_kind["all-reduce"] == pytest.approx(2 * n * 3 / 4)
    assert mc.wire_by_kind["collective-permute"] == pytest.approx(n)
    assert mc.coll_count == {"all-gather": 1, "all-reduce": 1, "collective-permute": 1}
    # the simple (bodies-once) parser agrees on a loop-free module
    stats = parse_collectives(_COLL_HLO)
    assert stats.wire_bytes == pytest.approx(mc.wire_bytes)


_DUS_HLO = """
%fused_dus (p0: f32[1024,64], p1: f32[1,64], p2: s32[]) -> f32[1024,64] {
  %p0 = f32[1024,64]{1,0} parameter(0)
  %p1 = f32[1,64]{1,0} parameter(1)
  %p2 = s32[] parameter(2)
  %z = s32[] constant(0)
  ROOT %dus = f32[1024,64]{1,0} dynamic-update-slice(%p0, %p1, %p2, %z)
}

ENTRY %main (cache: f32[1024,64], new: f32[1,64], i: s32[]) -> f32[1024,64] {
  %cache = f32[1024,64]{1,0} parameter(0)
  %new = f32[1,64]{1,0} parameter(1)
  %i = s32[] parameter(2)
  ROOT %f = f32[1024,64]{1,0} fusion(%cache, %new, %i), kind=kLoop, calls=%fused_dus
}
"""


def test_inplace_dus_counts_slice_not_buffer():
    """KV-cache append traffic = the update slice, not the whole cache."""
    mc = analyze_hlo(_DUS_HLO)
    slice_bytes = 1 * 64 * 4 + 4  # update row + index scalar
    assert mc.traffic_bytes <= 2 * slice_bytes  # and NOT ~2 * 256 KiB


# --------------------------------------------------------------------------
# closed-form 6ND validation on a real compiled train step
# --------------------------------------------------------------------------


def test_flops_match_6nd_closed_form():
    cfg = get_arch("granite-8b", smoke=True)
    tcfg = TrainConfig(microbatches=2)
    shape = ShapeConfig("t", 128, 8, "train")
    mesh = make_mesh((1, 1), ("data", "model"))  # 1 device: 6ND needs no SPMD
    with use_activation_mesh(mesh):
        fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0,))
        lowered = fn.lower(
            S.state_specs(cfg, tcfg, mesh), S.input_specs(cfg, shape, mesh)
        )
        compiled = lowered.compile()
    mc = analyze_hlo(compiled.as_text())
    model_flops_per_dev = 6 * count_active_params(cfg) * shape.global_batch * shape.seq_len / mesh.size
    ratio = mc.flops / model_flops_per_dev
    # fwd+bwd = 6ND; remat re-runs fwd (~ +1/3); attention scores are extra.
    # Gross under/over-counting (the cost_analysis() while-body bug is ~40x)
    # would fall far outside this band.
    assert 1.0 <= ratio <= 2.5, ratio
    # cost_analysis undercounts this scanned program (sanity that the fix
    # matters): while bodies once => less than the closed form.
    assert float(compiled.cost_analysis().get("flops", 0)) < model_flops_per_dev


def test_roofline_terms():
    r = Roofline(
        flops_per_device=197e12,  # exactly 1s of compute
        hbm_bytes_per_device=819e9 * 2,  # 2s of memory
        wire_bytes_per_device=50e9 / 2,  # 0.5s of collective
        model_flops_total=197e12 * 4,
        num_devices=8,
    )
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(2.0)
    assert r.t_collective == pytest.approx(0.5)
    assert r.dominant == "memory"
    assert r.bound_time == pytest.approx(2.0)
    assert r.mfu_upper_bound == pytest.approx(197e12 * 4 / (8 * 197e12 * 2.0))
