"""Operations and bytes of each kernel, read from the call's instruction
text as the trace holds it, against counts made by hand; and the share of
the roofline a synthetic trace gives."""
import pytest

from chipbench import found, roofline
from chipbench.peaks import Peaks

SEG = ("%seg_mean_kernel.3 = f32[10,3]{1,0:T(8,128)} custom-call("
       "f32[8,3]{1,0:T(8,128)} %p, s32[8]{0} %l, pred[8]{0} %k), "
       "custom_call_target=\"tpu_custom_call\", operand_layout_constraints="
       "{f32[8,3]{1,0}, s32[8]{0}, pred[8]{0}}")
PD = ("%pairwise_dist_kernel.22 = f32[5,2]{1,0:T(8,128)S(1)} custom-call("
      "f32[5,3]{1,0:T(8,128)} %pad.96, f32[2,3]{1,0:T(8,128)S(1)} %pad.97), "
      "custom_call_target=\"tpu_custom_call\"")


def test_seg_mean_hand_count():
    # 8 rows of width 3 into 10 label slots: 8 * 3 adds; reads 8 * 3
    # floats, 8 labels and 8 one-byte flags, writes 10 * 3 means
    result, operands = roofline.call_shapes(SEG)
    assert result == [("f32", (10, 3))]
    assert operands == [("f32", (8, 3)), ("s32", (8,)), ("pred", (8,))]
    assert found.module("kernel_costs", "seg_mean").ops(result, operands) == 24
    assert roofline.io_bytes(result, operands) == 4 * 24 + 4 * 8 + 8 + 4 * 30


def test_pairwise_dist_hand_count():
    # 5 rows, 2 centroids, 3 dims: a multiply and an add per (row,
    # centroid, dim); reads 15 + 6 floats, writes 10
    result, operands = roofline.call_shapes(PD)
    cost = found.module("kernel_costs", "pairwise_dist")
    assert cost.ops(result, operands) == 60
    assert roofline.io_bytes(result, operands) == 4 * (15 + 6 + 10)


def test_share_of_the_roofline_from_a_trace():
    # two calls of 2 ns each: 62 * 4 bytes each at 31 B/s is 8 s; the
    # operations (60 each at 1000/s) take less, so memory bounds it
    obs = {"trace": {"op_texts": {PD: (4.0 * 16, 2), "%other = f32[1] x": (1.0, 1)}},
           "peaks": Peaks(flops=1000.0, hbm_bw=31.0), "root": found.HERE}
    assert roofline.kernel_share(obs, "pairwise_dist") == pytest.approx(
        100.0 * 2 * 124 / 31.0 / 64.0)
    assert roofline.kernel_share(obs, "seg_mean") is None
    assert roofline.kernel_share(dict(obs, trace=None), "seg_mean") is None
