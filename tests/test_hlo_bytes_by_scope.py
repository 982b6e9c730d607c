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
