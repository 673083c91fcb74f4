"""``counts/flash.py``: the work of one flash-attention call, by hand at
small shapes and at the cell's level-0 shape."""
import pytest

import harness

flash = harness.load_module("counts", "flash.py")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_products_by_hand():
    # 4 queries over 4 keys: causal pairs (q, k) with k <= q are 1+2+3+4
    assert flash.products(4, 4, True) == 10
    assert flash.products(4, 6, False) == 24
    with pytest.raises(ValueError):
        flash.products(4, 6, True)


def test_forward_and_backward_by_hand():
    # 2 heads, 4 causal queries, width 8: 10 useful pairs a head
    flops, nbytes = flash.forward(bh=2, s=4, t=4, d=8, causal=True)
    assert flops == 2 * (2 * 10 * 8 * 2)            # QK^T and PV, 2 FLOPs a multiply-add
    assert nbytes == 2 * (4 * 4 * 8 * 2 + 4 * 4)    # q, k, v, o bf16; lse f32 a row
    flops, nbytes = flash.backward(bh=2, s=4, t=4, d=8, causal=True)
    assert flops == 2 * (5 * 10 * 8 * 2)            # QK^T, dO V^T, dq, dk, dv
    # q, k, v, do read and dq, dk, dv written (bf16); lse and delta read (f32)
    assert nbytes == 2 * (7 * 4 * 8 * 2 + 2 * 4 * 4)
    # non-causal: every pair, keys counted at their own length
    flops, nbytes = flash.forward(bh=1, s=4, t=6, d=8, causal=False)
    assert flops == 2 * 24 * 8 * 2
    assert nbytes == (4 + 6 + 6 + 4) * 8 * 2 + 4 * 4


def test_level0_step_of_the_cell():
    """GPT-Base level 0 (16 rows x 12 heads, 1024 causal tokens, width 64):
    FLOPs bind both calls; with remat a layer runs two forwards and one
    backward, about 7.07 ms of attention work a step over 12 layers."""
    shape = dict(bh=16 * 12, s=1024, t=1024, d=64, causal=True)
    fwd, bwd = flash.forward(**shape), flash.backward(**shape)
    assert fwd[0] == pytest.approx(25.79e9, rel=1e-3)
    assert fwd[1] == pytest.approx(101.4e6, rel=1e-3)
    assert bwd[0] == pytest.approx(64.49e9, rel=1e-3)
    assert bwd[1] == pytest.approx(177.7e6, rel=1e-3)
    assert flash.bound_s(fwd, PEAKS) == fwd[0] / 197e12
    assert flash.bound_s(bwd, PEAKS) == bwd[0] / 197e12
    step = 12 * (2 * flash.bound_s(fwd, PEAKS) + flash.bound_s(bwd, PEAKS))
    assert step == pytest.approx(7.07e-3, rel=1e-3)
    # bytes bind a call whose keys are few
    short = flash.forward(bh=1, s=1024, t=16, d=64, causal=False)
    assert flash.bound_s(short, PEAKS) == short[1] / 819e9
