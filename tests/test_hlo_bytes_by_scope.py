"""``scripts/hlo_bytes_by_scope.py`` on a hand-written program: what counts
as traffic, which branch of a conditional, and which scope an op goes to."""
import collections

import pytest

from scripts import hlo_bytes_by_scope as hlo_bytes

PROGRAM = '''HloModule jit_step

%fused_computation.1 (p: bf16[8,128]) -> bf16[8,128] {
  %p = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %huge = bf16[8,128]{1,0:T(8,128)(2,1)} copy(%p)
}

%usual (a: bf16[8,128]) -> bf16[8,128] {
  %a = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %fusion.5 = bf16[4,8,128]{2,1,0:T(8,128)(2,1)} fusion(%a), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(step)/layer1/moe/experts/cond/branch_0_fun/combine/gather"}
  %copy.9 = bf16[4,8,128]{2,1,0:T(8,128)(2,1)} copy(%fusion.5)
  ROOT %sum.1 = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%copy.9), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/layer1/moe/experts/cond/branch_0_fun/combine/reduce_sum"}
}

%fallback (b: bf16[8,128]) -> bf16[8,128] {
  %b = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %all = f32[64,8,128]{2,1,0:T(8,128)} fusion(%b), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/layer1/moe/experts/cond/branch_1_fun/mul"}
}

ENTRY %main (x: bf16[8,128], w: f32[16,128]) -> bf16[8,128] {
  %x = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(0)
  %w = f32[16,128]{1,0:T(8,128)} parameter(1)
  %flag = pred[]{:T(512)} constant(true)
  %view = bf16[1024]{0:T(1024)(128)(2,1)} bitcast(%x)
  %slice-start = ((f32[16,128]{1,0:T(8,128)}), f32[8,128]{1,0:T(8,128)}, u32[]{:S(2)}) slice-start(%w), slice={[0:8], [0:128]}
  %slice-done = f32[8,128]{1,0:T(8,128)} slice-done(%slice-start)
  %mix = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%x, %slice-done), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/transpose(jvp(online_forward))/layer1/mhc/add"}
  %conditional.3 = bf16[8,128]{1,0:T(8,128)(2,1)} conditional(%flag, %mix, %mix), branch_computations={%usual, %fallback}
  ROOT %step = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%conditional.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/update/add"}
}
'''
ROW = 8 * 128 * 2                     # one bf16[8,128]


@pytest.mark.parametrize("text,want", [
    ("bf16[16384,3584]{1,0:T(8,128)(2,1)}", 16384 * 3584 * 2),
    ("(s32[65536]{0:T(1024)}, pred[4,16]{1,0}, f32[])", 65536 * 4 + 64 + 4),
    ("token[]", 0),
])
def test_type_bytes(text, want):
    assert hlo_bytes.type_bytes(text) == want


def test_traffic_goes_to_scopes_and_the_cheaper_branch():
    comps = hlo_bytes.parse(PROGRAM)
    into, ops = collections.Counter(), []
    total = hlo_bytes.count(comps, comps[None], None, into, ops)
    assert into["(no scope)"] == 8 * 128 * 4          # the slice: its result
    assert into["mhc"] == 2 * ROW + 8 * 128 * 4       # operands + result
    assert into["update"] == 2 * ROW
    # the usual branch (18 rows) and not the fallback (257): gather 1 + 4,
    # reduce 4 + 1 under ``combine``; the pathless copy between them (4 + 4)
    # inherits the enclosing ``moe/experts``, not ``combine``
    assert into["combine"] == 10 * ROW
    assert into["moe/experts"] == 8 * ROW
    assert total == sum(into.values())
    # a fused computation's inside, a bitcast and a parameter move nothing
    assert {op[3] for op in ops} == {"%slice-done", "%mix", "%step"}


LOOP = '''HloModule jit_scan

%fused_slice (p0: bf16[64,8,128], p1: s32[]) -> bf16[8,128] {
  %p0 = bf16[64,8,128]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = s32[]{:T(128)} parameter(1)
  %zero = s32[]{:T(128)} constant(0)
  %ds = bf16[1,8,128]{2,1,0:T(8,128)(2,1)} dynamic-slice(%p0, %p1, %zero, %zero), dynamic_slice_sizes={1,8,128}
  ROOT %row = bf16[8,128]{1,0:T(8,128)(2,1)} bitcast(%ds)
}

%fused_update (p0: bf16[64,8,128], p1: bf16[8,128], p2: s32[]) -> bf16[64,8,128] {
  %p0 = bf16[64,8,128]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[8,128]{1,0:T(8,128)(2,1)} parameter(1)
  %p2 = s32[]{:T(128)} parameter(2)
  %zero = s32[]{:T(128)} constant(0)
  %one = bf16[1,8,128]{2,1,0:T(8,128)(2,1)} bitcast(%p1)
  ROOT %dus = bf16[64,8,128]{2,1,0:T(8,128)(2,1)} dynamic-update-slice(%p0, %one, %p2, %zero, %zero)
}

%body (t: (s32[], bf16[64,8,128], bf16[64,8,128])) -> (s32[], bf16[64,8,128], bf16[64,8,128]) {
  %t = (s32[]{:T(128)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%t), index=0
  %xs = bf16[64,8,128]{2,1,0:T(8,128)(2,1)} get-tuple-element(%t), index=1
  %ys = bf16[64,8,128]{2,1,0:T(8,128)(2,1)} get-tuple-element(%t), index=2
  %x = bf16[8,128]{1,0:T(8,128)(2,1)} fusion(%xs, %i), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(step)/layer0/gdn/core/while/body/dynamic_slice"}
  %new = bf16[64,8,128]{2,1,0:T(8,128)(2,1)} fusion(%ys, %x, %i), kind=kLoop, calls=%fused_update, metadata={op_name="jit(step)/layer0/gdn/core/while/body/dynamic_update_slice"}
  ROOT %out = (s32[]{:T(128)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}) tuple(%i, %xs, %new)
}

%cond (t: (s32[], bf16[64,8,128], bf16[64,8,128])) -> pred[] {
  %t = (s32[]{:T(128)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%t), index=0
  %n = s32[]{:T(128)} constant(64)
  ROOT %lt = pred[]{:T(512)} compare(%i, %n), direction=LT
}

ENTRY %main (a: (s32[], bf16[64,8,128], bf16[64,8,128])) -> (s32[], bf16[64,8,128], bf16[64,8,128]) {
  %a = (s32[]{:T(128)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}) parameter(0)
  ROOT %while.1 = (s32[]{:T(128)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}, bf16[64,8,128]{2,1,0:T(8,128)(2,1)}) while(%a), condition=%cond, body=%body, metadata={op_name="jit(step)/layer0/gdn/core/while"}
}
'''


def test_a_scan_counts_its_trips_and_its_slices_not_its_buffers():
    """64 trips; each reads one row of the stacked input (and the index)
    and writes one row of the stacked output in place: 64 x (row + 4 + row
    read, row + row written), not 64 x the two 64-row buffers."""
    comps = hlo_bytes.parse(LOOP)
    into = collections.Counter()
    total = hlo_bytes.count(comps, comps[None], None, into, [])
    a_slice = ROW + 4 + ROW              # row and index read, row written
    an_update = ROW + 4 + ROW            # row and index read, row written
    assert total == into["gdn/core"] == 64 * (a_slice + an_update)
