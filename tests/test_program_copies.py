"""``benchmarks/program_copies.py``: what a compiled program copies,
read off its optimized HLO text (no JAX, no chip)."""

import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# a program shaped like the latent decode program at the parent: a
# stack of weights transposed once a program, a layer of the copy
# sliced out inside the layer loop, multiplied by a second fusion
HLO = r"""HloModule jit_decode_multi, is_scheduled=true, entry_computation_layout={(bf16[4,512,1024]{2,1,0}, f32[8,512]{1,0})->f32[8,1024]{1,0}}

FileNames
1 "/root/repo/kaito_tpu/engine/model.py"

%fused_slice (param_0.1: bf16[4,512,1024], param_1.2: s32[]) -> bf16[1,512,1024] {
  %param_0.1 = bf16[4,512,1024]{1,2,0:T(8,128)(2,1)} parameter(0)
  %param_1.2 = s32[]{:T(128)} parameter(1)
  %constant.7 = s32[]{:T(128)} constant(0)
  ROOT %dynamic_slice.3 = bf16[1,512,1024]{1,2,0:T(8,128)(2,1)S(1)} dynamic-slice(%param_0.1, %param_1.2, %constant.7, %constant.7), dynamic_slice_sizes={1,512,1024}
}

%fused_dot (param_0.3: bf16[1,512,1024], param_1.4: f32[8,512]) -> f32[8,1024] {
  %param_0.3 = bf16[1,512,1024]{1,2,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.4 = f32[8,512]{1,0:T(8,128)} parameter(1)
  %bitcast.9 = bf16[512,1024]{0,1:T(8,128)(2,1)S(1)} bitcast(%param_0.3)
  %convert.2 = f32[512,1024]{0,1:T(8,128)S(1)} convert(%bitcast.9)
  ROOT %dot.5 = f32[8,1024]{1,0:T(8,128)} dot(%param_1.4, %convert.2), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%fused_big (param_0.8: f32[8,512]) -> f32[1024,512] {
  %param_0.8 = f32[8,512]{1,0:T(8,128)} parameter(0)
  %slice.1 = f32[1,512]{1,0:T(8,128)} slice(%param_0.8), slice={[0:1], [0:512]}
  ROOT %broadcast.1 = f32[1024,512]{1,0:T(8,128)} broadcast(%slice.1), dimensions={0,1}
}

%body (arg: (s32[], bf16[4,512,1024], f32[8,512], f32[8,1024])) -> (s32[], bf16[4,512,1024], f32[8,512], f32[8,1024]) {
  %arg = (s32[]{:T(128)}, bf16[4,512,1024]{1,2,0:T(8,128)(2,1)}, f32[8,512]{1,0:T(8,128)}, f32[8,1024]{1,0:T(8,128)}) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  %w = bf16[4,512,1024]{1,2,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %x = f32[8,512]{1,0:T(8,128)} get-tuple-element(%arg), index=2
  %constant_dynamic-slice_fusion.9 = bf16[1,512,1024]{1,2,0:T(8,128)(2,1)S(1)} fusion(%w, %i), kind=kLoop, calls=%fused_slice, metadata={op_name="jit(decode_multi)/while/body/dynamic_slice" source_line=731}
  %fusion.5 = f32[8,1024]{1,0:T(8,128)} fusion(%constant_dynamic-slice_fusion.9, %x), kind=kOutput, calls=%fused_dot, metadata={op_name="jit(decode_multi)/while/body/dot_general" source_line=733}
  %fusion.6 = f32[1024,512]{1,0:T(8,128)} fusion(%x), kind=kLoop, calls=%fused_big
  %one = s32[]{:T(128)} constant(1)
  %next = s32[]{:T(128)} add(%i, %one)
  ROOT %tuple.2 = (s32[]{:T(128)}, bf16[4,512,1024]{1,2,0:T(8,128)(2,1)}, f32[8,512]{1,0:T(8,128)}, f32[8,1024]{1,0:T(8,128)}) tuple(%next, %w, %x, %fusion.5)
}

%cond (arg.1: (s32[], bf16[4,512,1024], f32[8,512], f32[8,1024])) -> pred[] {
  %arg.1 = (s32[]{:T(128)}, bf16[4,512,1024]{1,2,0:T(8,128)(2,1)}, f32[8,512]{1,0:T(8,128)}, f32[8,1024]{1,0:T(8,128)}) parameter(0)
  %i.1 = s32[]{:T(128)} get-tuple-element(%arg.1), index=0
  %constant.4 = s32[]{:T(128)} constant(4)
  ROOT %lt = pred[]{:T(512)} compare(%i.1, %constant.4), direction=LT
}

ENTRY %main (params__moe____q_b__.1: bf16[4,512,1024], x.1: f32[8,512]) -> f32[8,1024] {
  %params__moe____q_b__.1 = bf16[4,512,1024]{2,1,0:T(8,128)(2,1)} parameter(0), metadata={op_name="params[\'moe\'][\'q_b\']"}
  %x.1 = f32[8,512]{1,0:T(8,128)} parameter(1), metadata={op_name="x"}
  %copy.179 = bf16[4,512,1024]{1,2,0:T(8,128)(2,1)} copy(%params__moe____q_b__.1)
  %zero = s32[]{:T(128)} constant(0)
  %init = f32[8,1024]{1,0:T(8,128)} broadcast(%zero), dimensions={}
  %tuple.1 = (s32[]{:T(128)}, bf16[4,512,1024]{1,2,0:T(8,128)(2,1)}, f32[8,512]{1,0:T(8,128)}, f32[8,1024]{1,0:T(8,128)}) tuple(%zero, %copy.179, %x.1, %init)
  %while.1 = (s32[]{:T(128)}, bf16[4,512,1024]{1,2,0:T(8,128)(2,1)}, f32[8,512]{1,0:T(8,128)}, f32[8,1024]{1,0:T(8,128)}) while(%tuple.1), condition=%cond, body=%body
  ROOT %out = f32[8,1024]{1,0:T(8,128)} get-tuple-element(%while.1), index=3
}
"""


@pytest.fixture(scope="module")
def pc():
    spec = importlib.util.spec_from_file_location(
        "program_copies",
        os.path.join(ROOT, "benchmarks", "program_copies.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_stacks_copy_and_its_slice_are_listed_with_their_parameter(pc):
    rows = {r["op"]: r for r in pc.large_ops(HLO)}
    assert set(rows) == {"copy.179", "constant_dynamic-slice_fusion.9",
                         "fusion.6"}
    copy = rows["copy.179"]
    assert copy["where"] == "program" and copy["kind"] == "copy"
    assert copy["mib"] == 4.0 and copy["reads"] == ["params['moe']['q_b']"]
    # the slice is inside the loop, four trips, and is still that
    # parameter: through the loop's carry, the tuple and the copy
    layer = rows["constant_dynamic-slice_fusion.9"]
    assert layer["where"] == "x4" and layer["mib"] == 1.0
    assert layer["reads"] == ["params['moe']['q_b']"]
    assert layer["op_name"] == "dynamic_slice"
    # a large result that reads no large parameter names none
    assert rows["fusion.6"]["reads"] == []


def test_a_product_reading_a_slice_only_fusion_reads_the_stack(pc):
    """Under the size asked for, the product shows too, traced through
    the fusion that only moves a layer's matrix."""
    rows = {r["op"]: r for r in pc.large_ops(HLO, min_bytes=1 << 15)}
    assert rows["fusion.5"]["reads"] == ["params['moe']['q_b']"]
    assert rows["fusion.5"]["where"] == "x4"


@pytest.mark.parametrize("shape,want", [
    ("bf16[39,1536,6144]{1,2,0:T(8,128)(2,1)}", 39 * 1536 * 6144 * 2),
    ("(f32[4096]{0:T(1024)S(1)}, bf16[4096,2048]{0,1})",
     4096 * 4 + 4096 * 2048 * 2),
    ("s32[]{:T(128)}", 4),
    ("bf16[40,1423,64,0]{3,2,1,0}", 0),
    ("pred[24]{0:T(512)(128)(4,1)S(1)}", 24),
])
def test_bytes_of_a_printed_type(pc, shape, want):
    assert pc.shape_bytes(shape) == want


def test_the_fingerprint_moves_with_the_program_and_not_with_its_lines(pc):
    moved = HLO.replace("source_line=731", "source_line=745")
    assert moved != HLO and pc.fingerprint(moved) == pc.fingerprint(HLO)
    other = HLO.replace("direction=LT", "direction=LE")
    assert pc.fingerprint(other) != pc.fingerprint(HLO)
    assert pc.program_name(HLO) == "jit_decode_multi"


def test_a_constant_cut_short_is_read_past(pc):
    cut = HLO.replace(
        "  %zero = s32[]{:T(128)} constant(0)\n",
        "  %zero = s32[]{:T(128)} constant(0)\n"
        "  %constant.511 = bf16[40,1423,64,0]{3,2,1,0} constant({ { /*i0=0*/ "
        "{ /*i1=0*/ {}, {}, {}, ...}\n")
    assert cut != HLO
    assert [r["op"] for r in pc.large_ops(cut)] == \
        [r["op"] for r in pc.large_ops(HLO)]


def test_the_command_prints_a_table_and_filters_by_parameter(pc, tmp_path,
                                                             capsys):
    path = tmp_path / "decode.txt"
    path.write_text(HLO)
    assert pc.main([str(path), "--reads", "q_b"]) == 0
    out = capsys.readouterr().out
    assert "`copy.179`" in out and "`fusion.6`" not in out
    assert "| program | `copy.179` | copy |" in out
