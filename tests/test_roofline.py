"""Roofline HLO analyzer: trip-count weighting, collective accounting."""
import textwrap

import pytest

from repro.roofline import hlo as H
from repro.roofline.report import RooflineRow

SYNTH = textwrap.dedent("""\
    HloModule jit_step, is_scheduled=true

    %body (p: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
      %p = (s32[], f32[8,16]{1,0}) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[8,16]{1,0} get-tuple-element(%p), index=1
      %w = f32[16,16]{1,0} constant({...})
      %dot.1 = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[8,16]{1,0} all-reduce(%dot.1), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add
      %one = s32[] constant(1)
      %i2 = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[8,16]{1,0}) tuple(%i2, %ar)
    }

    %cond (p: (s32[], f32[8,16])) -> pred[] {
      %p = (s32[], f32[8,16]{1,0}) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %n = s32[] constant(5)
      ROOT %lt = pred[] compare(%i, %n), direction=LT
    }

    %add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(%a, %b)
    }

    ENTRY %main (arg: f32[8,16]) -> (s32[], f32[8,16]) {
      %arg = f32[8,16]{1,0} parameter(0)
      %zero = s32[] constant(0)
      %init = (s32[], f32[8,16]{1,0}) tuple(%zero, %arg)
      %w2 = f32[16,4]{1,0} constant({...})
      %dot.2 = f32[8,4]{1,0} dot(%arg, %w2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ag = f32[32,4]{1,0} all-gather(%dot.2), channel_id=2, replica_groups=[256,2]<=[2,256]T(1,0), dimensions={0}
      ROOT %wh = (s32[], f32[8,16]{1,0}) while(%init), condition=%cond, body=%while_body_alias
    }
    """).replace("%while_body_alias", "%body")


def test_split_computations():
    comps = H._split_computations(SYNTH)
    assert set(comps) == {"body", "cond", "add", "main"}
    assert any("dot.1" in l for l in comps["body"])


def test_trip_count_weighting():
    comps = H._split_computations(SYNTH)
    weights, _ = H._call_weights(SYNTH, comps)
    assert weights["main"] == 1.0
    assert weights["body"] == 5.0          # constant(5) in the condition


def test_dot_flops_with_trip_counts():
    pc = H.program_costs(SYNTH)
    # dot.1: 2*8*16*16 = 4096 flops x 5 trips; dot.2: 2*8*4*16 = 1024
    assert pc.flops == 5 * 4096 + 1024
    assert pc.dot_count == 2


def test_collective_stats_and_pod_classification():
    cs = H.collective_stats(SYNTH, pod_size=256)
    # all-reduce in the loop: result 8*16*4B=512B; n=4 -> wire 2*512*3/4
    ar_once = 2 * 512 * 3 // 4
    assert cs.bytes_by_type["all-reduce"] == 5 * ar_once
    # all-gather groups of 256 devices spanning 512 => cross-pod (DCN)
    assert cs.dcn_bytes > 0
    assert cs.ici_bytes == 5 * ar_once


ASYNC = textwrap.dedent("""\
    HloModule jit_step, is_scheduled=true

    %scatter (p1: f32[64]) -> f32[16] {
      %p1 = f32[64]{0} parameter(0)
      ROOT %rs = f32[16]{0} reduce-scatter(%p1), channel_id=5, replica_groups=[1,4]<=[4], dimensions={0}, to_apply=%add
    }

    %add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(%a, %b)
    }

    %gather_fusion (p0: bf16[2,64]) -> bf16[8,64] {
      %p0 = bf16[2,64]{1,0} parameter(0)
      ROOT %ag = bf16[8,64]{1,0} all-gather(%p0), channel_id=3, replica_groups=[1,4]<=[4], dimensions={0}
    }

    ENTRY %main (arg: bf16[2,64]) -> (bf16[8,64], f32[16], f32[16]) {
      %arg = bf16[2,64]{1,0} parameter(0)
      %f = bf16[8,64]{1,0} fusion(%arg), kind=kCustom, calls=%gather_fusion
      %x = f32[16]{0} constant({...})
      %cps = (f32[16]{0}, f32[16]{0}, u32[], u32[]) collective-permute-start(%x), channel_id=4, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
      %cpd = f32[16]{0} collective-permute-done(%cps)
      %y = f32[64]{0} constant({...})
      %rss = ((f32[64]{0}), f32[16]{0}) async-start(%y), calls=%scatter
      %rsd = f32[16]{0} async-done(%rss), calls=%scatter
      ROOT %t = (bf16[8,64]{1,0}, f32[16]{0}, f32[16]{0}) tuple(%f, %cpd, %rsd)
    }
    """)


def test_exchange_bytes_counts_collective_results_per_trip():
    # the loop's all-reduce f32[8,16] (512 B) 5 times, the all-gather's
    # f32[32,4] (512 B) once
    assert H.exchange_bytes(SYNTH) == 5 * 512 + 512
    # inside a fusion, and each asynchronous pair counted once, by its
    # result: bf16[8,64] (1024 B), f32[16] (64 B) and the wrapped
    # reduce-scatter's f32[16] (64 B)
    assert H.exchange_bytes(ASYNC) == 1024 + 64 + 64
    assert H.exchange_bytes(SYNTH.replace("all-reduce(", "add(")
                            .replace("all-gather(", "add(")) == 0


TWICE = textwrap.dedent("""\
    HloModule jit_step, is_scheduled=true

    %add (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(%a, %b)
    }

    %shared (p: f32[8,16]) -> f32[8,16] {
      %p = f32[8,16]{1,0} parameter(0)
      %w = f32[16,16]{1,0} constant({...})
      %d = f32[8,16]{1,0} dot(%p, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      ROOT %ar = f32[8,16]{1,0} all-reduce(%d), channel_id=1, replica_groups={{0,1,2,3}}, to_apply=%add
    }

    ENTRY %main (arg: f32[8,16]) -> f32[8,16] {
      %arg = f32[8,16]{1,0} parameter(0)
      %c1 = f32[8,16]{1,0} call(%arg), to_apply=%shared
      ROOT %c2 = f32[8,16]{1,0} call(%c1), to_apply=%shared
    }
    """)


def test_call_weights_sum_over_call_sites():
    # one computation called from two sites runs twice: its dot, its
    # all-reduce and its exchange count twice, in every counter
    comps = H._split_computations(TWICE)
    weights, _ = H._call_weights(TWICE, comps)
    assert (weights["main"], weights["shared"], weights["add"]) == (1, 2, 0)
    assert H.program_costs(TWICE).flops == 2 * 4096
    assert H.collective_stats(TWICE).bytes_by_type["all-reduce"] == \
        2 * (2 * 512 * 3 // 4)
    assert H.exchange_bytes(TWICE) == 2 * 512
    # an asynchronous pair names its computation twice and runs it once
    async_w, _ = H._call_weights(ASYNC, H._split_computations(ASYNC))
    assert async_w["scatter"] == async_w["gather_fusion"] == 1


POOL = textwrap.dedent("""\
    HloModule jit_decode, is_scheduled=true

    %body (p: (s32[], f32[4,8,16])) -> (s32[], f32[4,8,16]) {
      %p = (s32[], f32[4,8,16]{2,1,0}) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %pool = f32[4,8,16]{2,1,0} get-tuple-element(%p), index=1
      %z = s32[] constant(0)
      %slab = f32[1,8,16]{2,1,0} dynamic-slice(%pool, %i, %z, %z), dynamic_slice_sizes={1,8,16}
      %weights = f32[3,16,8]{2,1,0} constant({...})
      %layer_w = f32[1,16,8]{2,1,0} dynamic-slice(%weights, %i, %z, %z), dynamic_slice_sizes={1,16,8}
      %one = s32[] constant(1)
      %i2 = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[4,8,16]{2,1,0}) tuple(%i2, %pool)
    }

    %cond (p: (s32[], f32[4,8,16])) -> pred[] {
      %p = (s32[], f32[4,8,16]{2,1,0}) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %n = s32[] constant(3)
      ROOT %lt = pred[] compare(%i, %n), direction=LT
    }

    %dus_fusion (p0: f32[4,8,16], p1: f32[8,16], p2: s32[]) -> f32[4,8,16] {
      %p0 = f32[4,8,16]{2,1,0} parameter(0)
      %p1 = f32[8,16]{1,0} parameter(1)
      %p2 = s32[] parameter(2)
      %up = f32[1,8,16]{2,1,0} bitcast(%p1)
      %z = s32[] constant(0)
      ROOT %dus = f32[4,8,16]{2,1,0} dynamic-update-slice(%p0, %up, %p2, %z, %z)
    }

    %set (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      ROOT %b = f32[] parameter(1)
    }

    ENTRY %main (arg: f32[4,8,16], row: f32[8,16], tok: f32[2,16], at: s32[2,3]) -> f32[4,8,16] {
      %arg = f32[4,8,16]{2,1,0} parameter(0)
      %row = f32[8,16]{1,0} parameter(1)
      %tok = f32[2,16]{1,0} parameter(2)
      %at = s32[2,3]{1,0} parameter(3)
      %moved = f32[4,8,16]{2,1,0} copy(%arg)
      %small = f32[2,16]{1,0} copy(%tok)
      %zero = s32[] constant(0)
      %init = (s32[], f32[4,8,16]{2,1,0}) tuple(%zero, %moved)
      %wh = (s32[], f32[4,8,16]{2,1,0}) while(%init), condition=%cond, body=%body
      %after = f32[4,8,16]{2,1,0} get-tuple-element(%wh), index=1
      %upd = f32[4,8,16]{2,1,0} fusion(%after, %row, %zero), kind=kLoop, calls=%dus_fusion
      ROOT %tokens = f32[4,8,16]{2,1,0} scatter(%upd, %at, %small), update_window_dims={1}, inserted_window_dims={0,1}, scatter_dims_to_operand_dims={0,1,2}, index_vector_dim=1, to_apply=%set
    }
    """)


def test_pool_move_bytes_counts_slab_moves_per_trip():
    # a layer's slab of the pool is f32[8,16] (512 B): the whole pool's
    # copy (2048 B) once, the loop's slab slice 3 times, the fused
    # dynamic-update-slice's slab update once; the small copy, the
    # two-token scatter (f32[2,16] updates) and the loop's weight slice
    # (512 B too, but not a slab of the pool) do not count
    assert H.pool_move_bytes(POOL, [(8, 16)]) == 2048 + 3 * 512 + 512
    # a (4, 8, 16) slab is held by the pool's copy alone, a (2, 16) one
    # by the small copy and the scatter's updates
    assert H.pool_move_bytes(POOL, [(4, 8, 16)]) == 2048
    assert H.pool_move_bytes(POOL, [(2, 16)]) == 2 * 128
    # nothing holds a slab when the pool stays in place
    assert H.pool_move_bytes(POOL, [(5, 8, 16)]) == 0


def test_shape_bytes():
    assert H._shape_bytes("f32[8,16]") == 512
    assert H._shape_bytes("bf16[2,3] whatever pred[7]") == 12 + 7
    assert H._shape_bytes("(f32[4], s32[2])") == 16 + 8


def test_roofline_row_terms():
    r = RooflineRow(arch="x", shape="train_4k", mesh="single", chips=256,
                    hlo_flops=197e12 * 256, hlo_bytes=819e9 * 256,
                    ici_bytes=200e9, dcn_bytes=0.0,
                    model_flops=0.75 * 197e12 * 256)
    assert r.t_compute == pytest.approx(1.0)
    assert r.t_memory == pytest.approx(1.0)
    assert r.t_collective == pytest.approx(1.0)
    assert r.useful_flops_frac == pytest.approx(0.75)
    assert r.roofline_frac == pytest.approx(0.75)
    assert r.dominant in ("compute", "memory", "collective")


def test_roofline_dominant_term():
    r = RooflineRow(arch="x", shape="s", mesh="single", chips=1,
                    hlo_flops=1e12, hlo_bytes=1e12, ici_bytes=0,
                    dcn_bytes=0, model_flops=1e12)
    # 1e12 bytes / 819e9 = 1.22 s >> 1e12/197e12 flops
    assert r.dominant == "memory"
