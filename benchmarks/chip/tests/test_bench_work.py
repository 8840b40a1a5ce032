"""The work functions of the roofline readers against hand counts."""

import pytest

from _bench_helpers import CHIP
from benchlib import spec


@pytest.fixture(scope="module")
def mttkrp():
    return spec.load_module(CHIP, "metrics", "mttkrp_roofline_share")


@pytest.fixture(scope="module")
def multi_ttm():
    return spec.load_module(CHIP, "metrics", "multi_ttm_roofline_share")


def test_mttkrp_work_by_hand(mttkrp):
    # 4x5x6 tensor, rank 3, mode 0: 2*3*120 flops; the tensor (120), the
    # other factors (5*3 + 6*3) and the output (4*3), in fp32
    assert mttkrp.work((4, 5, 6), 3, 0) == (720, 4 * (120 + 33 + 12))
    assert mttkrp.work((4, 5, 6), 3, 2) == (720, 4 * (120 + 27 + 18))


def test_multi_ttm_work_by_hand(multi_ttm):
    # keep mode 0 of 4x5x6 at ranks (2, 3, 2): mode 2 first costs
    # 2*120*2 + 2*(4*5*2)*3 = 720; mode 1 first 2*120*3 + 2*(4*3*6)*2 =
    # 1008; the matrices 5*3 + 6*2, the output 4*3*2
    assert multi_ttm.work((4, 5, 6), (2, 3, 2), 0) == (720, 4 * (120 + 27 + 24))


def test_least_time_picks_the_binding_side(mttkrp, multi_ttm):
    peaks = {"f32_highest_flops": 1e12, "hbm_bytes_per_s": 1e9}
    call = {"shape": [1024, 1024, 1024], "rank": 64, "mode": 1}
    flops, nbytes = mttkrp.work((1024,) * 3, 64, 1)
    assert mttkrp.least_time(call, peaks) == max(flops / 1e12, nbytes / 1e9)
    # at the v5e's peaks a rank-64 fp32 MTTKRP is bound by its bytes
    v5e = spec.peaks(CHIP, "TPU v5 lite")
    assert nbytes / v5e["hbm_bytes_per_s"] > flops / v5e["f32_highest_flops"]
    assert v5e["f32_highest_flops"] == pytest.approx(v5e["bf16_flops"] / 6)
    tcall = {"shape": [1024] * 3, "ranks": [32] * 3, "keep": 0}
    f, b = multi_ttm.work((1024,) * 3, (32,) * 3, 0)
    assert multi_ttm.least_time(tcall, v5e) == pytest.approx(
        max(f / v5e["f32_highest_flops"], b / v5e["hbm_bytes_per_s"]))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks(CHIP, "TPU v9 imaginary")
