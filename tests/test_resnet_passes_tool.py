"""Parser tests for tools/resnet_passes.py.  The tool's compile of the
ResNet-50 step for ``v5e`` takes 45 s and libtpu's lock file, so no test
makes it; what it reads off the compiled text is pinned here on a
hand-written entry computation in the chip's print style (operands bare,
layouts with tiles and memory spaces)."""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

from resnet_passes import entry_fusions, passes, reduce_only  # noqa: E402

ACT = "bf16[128,8,8,64]{3,0,2,1:T(8,128)(2,1)}"
VEC = "f32[64]{0:T(128)S(1)}"
ACT_BYTES, W_BYTES, VEC_BYTES = 128 * 8 * 8 * 64 * 2, 64 * 64 * 4, 64 * 4

HLO = f"""
HloModule m
%fused_computation.1 (p: bf16[128,8,8,64]) -> f32[64] {{
  %p = {ACT} parameter(0)
  ROOT %nested = {VEC} fusion(%p), kind=kLoop, calls=%inner
}}
ENTRY %main (a: bf16[128,8,8,64], w: f32[1,1,64,64]) -> bf16[128,8,8,64] {{
  %a = {ACT} parameter(0)
  %w = f32[1,1,64,64]{{3,2,1,0:T(8,128)S(1)}} parameter(1)
  %convert_reduce_fusion.1 = ({VEC}, {ACT}) fusion(%a, %w), kind=kOutput, calls=%fused_computation.2, metadata={{op_name="jit(body)/jvp()/conv_general_dilated" stack_frame_id=1}}
  %get-tuple-element.1 = {ACT} get-tuple-element(%convert_reduce_fusion.1), index=1
  %get-tuple-element.2 = {VEC} get-tuple-element(%convert_reduce_fusion.1), index=0
  %fusion.35 = {VEC} fusion(%get-tuple-element.1, %get-tuple-element.2), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(body)/jvp(jit(_var))/reduce_sum"}}
  %multiply_reduce_fusion.3 = (bf16[64]{{0}}, bf16[64]{{0}}) fusion(%get-tuple-element.1, /*index=1*/%a), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="jit(body)/transpose(jvp())/reduce_sum"}}
  %fusion.36 = {VEC} fusion(%w), kind=kLoop, calls=%fused_computation.4, metadata={{op_name="jit(body)/reduce_sum"}}
  ROOT %multiply_add_fusion.7 = {ACT} fusion(%get-tuple-element.1, %fusion.35), kind=kLoop, calls=%fused_computation.5
}}
"""


def test_entry_fusions_reads_operand_shapes_by_name():
    by_name = {f.name: f for f in entry_fusions(HLO)}
    assert list(by_name) == [
        "convert_reduce_fusion.1", "fusion.35", "multiply_reduce_fusion.3",
        "fusion.36", "multiply_add_fusion.7"]  # not the nested one
    conv = by_name["convert_reduce_fusion.1"]
    assert conv.operands == [[128, 8, 8, 64], [1, 1, 64, 64]]
    assert conv.results == [[64], [128, 8, 8, 64]]
    assert conv.read_bytes == ACT_BYTES + W_BYTES
    assert conv.write_bytes == VEC_BYTES + ACT_BYTES
    # the weight and the statistics lie in the fast memory space (S(1))
    assert conv.hbm_bytes == 2 * ACT_BYTES
    var = by_name["fusion.35"]  # operands are two get-tuple-elements
    assert (var.read_bytes, var.write_bytes, var.hbm_bytes) == (
        ACT_BYTES + VEC_BYTES, VEC_BYTES, ACT_BYTES)


@pytest.mark.parametrize("name,is_pass,backward", [
    ("convert_reduce_fusion.1", False, False),   # writes an activation
    ("fusion.35", True, False),                  # the variance's own pass
    ("multiply_reduce_fusion.3", True, True),    # a gradient's two sums
    ("fusion.36", False, False),                 # a sum over a weight
    ("multiply_add_fusion.7", False, False),     # the apply
])
def test_reduce_only_fusions_over_an_activation(name, is_pass, backward):
    f = {f.name: f for f in entry_fusions(HLO)}[name]
    assert reduce_only(f, batch=128) is is_pass
    assert f.backward is backward
    assert not reduce_only(f, batch=64)  # another batch's activation


def test_passes_sums_sides_families_and_bytes():
    got = passes(HLO, batch=128)
    assert got["fusions"] == 5
    assert got["reduce_only"] == {
        "forward": {"count": 1, "read_bytes": ACT_BYTES + VEC_BYTES,
                    "hbm_bytes": ACT_BYTES},
        "backward": {"count": 1, "read_bytes": 2 * ACT_BYTES,
                     "hbm_bytes": 2 * ACT_BYTES + 2 * 64 * 2}}
    assert got["fusion_bytes"] == (
        7 * ACT_BYTES + 2 * W_BYTES + 5 * VEC_BYTES + 2 * 64 * 2)
    assert got["fusion_hbm_bytes"] == 7 * ACT_BYTES + 2 * 64 * 2
    fam = {f["name"]: f for f in got["families"]}
    assert fam["fusion"] == {
        "name": "fusion", "count": 2,
        "bytes": ACT_BYTES + W_BYTES + 3 * VEC_BYTES,
        "hbm_bytes": ACT_BYTES}
    assert got["families"][0]["name"] == "convert_reduce_fusion"
