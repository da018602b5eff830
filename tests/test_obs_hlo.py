"""``repro.obs.hlo.collectives`` on handwritten HLO in the two forms the
compilers print: known trip counts (CPU) and loop conditions against a
constant, asynchronous collective fusions under one channel and the
reduce-scatter fusion (TPU)."""
from __future__ import annotations

from repro.obs.hlo import collectives

HLO = """HloModule jit_step, entry_computation_layout={()}

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %sum = f32[] add(%x, %y)
}

%all-reduce-scatter.clone (input.2: f32[32,256]) -> f32[32,128] {
  %input.2 = f32[32,256]{1,0:T(8,128)} parameter(0)
  %all-reduce.8 = f32[32,256]{1,0:T(8,128)} all-reduce(%input.2), channel_id=19, to_apply=%add
  ROOT %dynamic-slice.46 = f32[32,128]{1,0} dynamic-slice(%all-reduce.8, %c, %d)
}

%start_part (param_0.58: bf16[1,128,128]) -> bf16[1,256,128] {
  %param_0.58 = bf16[1,128,128]{2,1,0} parameter(0)
  ROOT %all-gather.38 = bf16[1,256,128]{2,1,0} all-gather(%param_0.58), channel_id=18, dimensions={1}
}

%loop_part (param_0.62: bf16[1,128,128]) -> bf16[1,256,128] {
  %param_0.62 = bf16[1,128,128]{2,1,0} parameter(0)
  ROOT %all-gather.40 = bf16[1,256,128]{2,1,0} all-gather(%param_0.62), channel_id=18, dimensions={1}
}

%body (wide.param: (s32[], f32[32,256])) -> (s32[], f32[32,256]) {
  %wide.param = (s32[], f32[32,256]{1,0}) parameter(0)
  %h = f32[32,256]{1,0} get-tuple-element(%wide.param), index=1
  %fusion.28 = f32[32,128]{1,0} fusion(%h), kind=kCustom, calls=%all-reduce-scatter.clone
  %w = bf16[1,128,128]{2,1,0} constant({...})
  %fusion.30 = bf16[1,256,128]{2,1,0} fusion(%w), kind=kCustom, calls=%loop_part
  %all-reduce-start.1 = f32[32,256]{1,0} all-reduce-start(%h), channel_id=7, to_apply=%add
  %all-reduce-done.1 = f32[32,256]{1,0} all-reduce-done(%all-reduce-start.1)
  ROOT %t = (s32[], f32[32,256]{1,0}) tuple(%i, %all-reduce-done.1)
}

%cond (wide.param.1: (s32[], f32[32,256])) -> pred[] {
  %constant.132 = s32[]{:T(128)} constant(4)
  %wide.param.1 = (s32[], f32[32,256]{1,0}) parameter(0)
  %gte = s32[]{:T(128)} get-tuple-element(%wide.param.1), index=0
  ROOT %lt.10 = pred[]{:T(512)} compare(%gte, %constant.132), direction=LT
}

%body2 (p: (s32[], bf16[8,16])) -> (s32[], bf16[8,16]) {
  %p = (s32[], bf16[8,16]{1,0}) parameter(0)
  %v = bf16[8,16]{1,0} get-tuple-element(%p), index=1
  %all-to-all.1 = bf16[8,16]{1,0} all-to-all(%v), channel_id=30, dimensions={0}
  ROOT %t2 = (s32[], bf16[8,16]{1,0}) tuple(%i2, %all-to-all.1)
}

%cond2 (p2: (s32[], bf16[8,16])) -> pred[] {
  %p2 = (s32[], bf16[8,16]{1,0}) parameter(0)
  ROOT %lt = pred[] compare(%g, %k), direction=LT
}

ENTRY %main (param.5: bf16[1,128,128], param.4: f32[32,256]) -> f32[] {
  %param.5 = bf16[1,128,128]{2,1,0} parameter(0)
  %param.4 = f32[32,256]{1,0} parameter(1)
  %fusion.21 = bf16[1,256,128]{2,1,0} fusion(%param.5), kind=kCustom, calls=%start_part
  %while.30 = (s32[], f32[32,256]{1,0}) while(%tuple.32), condition=%cond, body=%body
  %while.31 = (s32[], bf16[8,16]{1,0}) while(%tuple.33), condition=%cond2, body=%body2, backend_config={"known_trip_count":{"n":"3"},"known_init_step":{"init":"0","step":"1"}}
  %s = f32[]{:T(128)} constant(1)
  %collective-permute.2 = f32[]{:T(128)} collective-permute(%s), channel_id=40, source_target_pairs={{0,1},{1,0}}
  ROOT %all-reduce.4 = f32[] all-reduce(%s), channel_id=16, to_apply=%add
}
"""


def test_counts_and_bytes_per_kind():
    got = collectives(HLO)
    # the loop body runs 4 times (its condition's constant)
    assert got["reduce-scatter"] == {"count": 4, "bytes": 4 * 32 * 256 * 4}
    # one all-gather under channel 18, printed in the start part (once)
    # and the loop part (4 times): counted as the loop part
    assert got["all-gather"] == {"count": 4, "bytes": 4 * 128 * 128 * 2}
    # the asynchronous pair once per trip, and the entry's scalar
    assert got["all-reduce"] == {"count": 5, "bytes": 4 * 32 * 256 * 4 + 4}
    # known_trip_count wins over the condition
    assert got["all-to-all"] == {"count": 3, "bytes": 3 * 8 * 16 * 2}
    assert got["collective-permute"] == {"count": 1, "bytes": 4}


def test_a_program_without_collectives_reads_zero():
    text = ("HloModule m\n\nENTRY %main (p: f32[4]) -> f32[4] {\n"
            "  %p = f32[4]{0} parameter(0)\n"
            "  ROOT %n = f32[4]{0} negate(%p)\n}\n")
    assert all(v == {"count": 0, "bytes": 0}
               for v in collectives(text).values())
