"""HLO cost-analysis tests: analytic cross-checks of the
call-graph-weighted FLOP/byte/collective accounting."""
import textwrap

import pytest

from repro.launch import hlo_analysis as HA


SIMPLE_HLO = textwrap.dedent("""\
    HloModule test

    %body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
      %p = (s32[], f32[8,8]{1,0}) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
      %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}, to_apply=%add.red
      %one = s32[] constant(1)
      %ni = s32[] add(%i, %one)
      ROOT %t = (s32[], f32[8,8]{1,0}) tuple(%ni, %ar)
    }

    %cond.1 (p2: (s32[], f32[8,8])) -> pred[] {
      %p2 = (s32[], f32[8,8]{1,0}) parameter(0)
      %i2 = s32[] get-tuple-element(%p2), index=0
      %n = s32[] constant(10)
      ROOT %lt = pred[] compare(%i2, %n), direction=LT
    }

    %add.red (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %s = f32[] add(%a, %b)
    }

    ENTRY %main (arg: f32[8,8]) -> f32[8,8] {
      %arg = f32[8,8]{1,0} parameter(0)
      %zero = s32[] constant(0)
      %init = (s32[], f32[8,8]{1,0}) tuple(%zero, %arg)
      %w = (s32[], f32[8,8]{1,0}) while(%init), condition=%cond.1, body=%body.1
      %big = f32[128,64]{1,0} dot(%arg, %arg), lhs_contracting_dims={1}, rhs_contracting_dims={0}
      ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
    }
""")


class TestHloAnalysis:
    def test_loop_weighted_flops(self):
        hc = HA.analyze_hlo_text(SIMPLE_HLO)
        # loop body dot: 2*8*8*8 = 1024 flops x 10 trips; entry dot:
        # 2*128*64*8 = 131072 x 1
        assert hc.loop_trips == {"body.1": 10}
        assert hc.flops == pytest.approx(1024 * 10 + 2 * 128 * 64 * 8)

    def test_loop_weighted_collectives(self):
        hc = HA.analyze_hlo_text(SIMPLE_HLO)
        # all-reduce of f32[8,8] = 256B x 10 trips
        assert hc.collective_bytes["all-reduce"] == 256 * 10
        assert hc.collective_counts["all-reduce"] == 10

    def test_traffic_counts_dots_and_entry_io(self):
        hc = HA.analyze_hlo_text(SIMPLE_HLO)
        # per-trip dot traffic: result 256 + 2x operand 256 = 768
        # entry dot: 32768 + 2*256 = 33280 ; entry param io = 2*256
        assert hc.hbm_bytes == pytest.approx(768 * 10 + 33280 + 2 * 256)
