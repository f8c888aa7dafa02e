"""Operations and bytes of each kernel against hand counts at small
shapes, the roofline share's arithmetic, and the peaks table."""
from __future__ import annotations

import pytest

from bench import registry, roofline

GAUSS = registry.load_module("work", "gaussian")
MANDEL = registry.load_module("work", "mandelbrot")


def test_gaussian_hand_count():
    # 2x3 output, 3 taps: the vertical pass makes 2 x (3 + 2) = 10
    # intermediates of 3 multiply-adds, the horizontal 6 outputs of 3
    ops, nbytes = GAUSS.work(2, 3, {"ksize": 3})
    assert ops == 2 * 3 * 10 + 2 * 3 * 6 == 96
    # reads the 4 x 5 padded tile, writes 2 x 3, float32
    assert nbytes == 4 * (4 * 5 + 2 * 3) == 104


def test_gaussian_per_pixel_at_the_paper_size():
    ops, nbytes = GAUSS.work(8192, 8192, {"ksize": 31})
    px = 8192 * 8192
    assert ops / px == pytest.approx(2 * 31 * 2, rel=5e-3)
    assert nbytes / px == pytest.approx(8, rel=1e-2)


def test_mandelbrot_counts_the_iterations_the_output_holds():
    ops, nbytes = MANDEL.work(2, 3, {}, row_sum=10)
    assert ops == 8 * 10
    assert nbytes == 4 * 6 + 4 * (2 + 3)
    with pytest.raises(ValueError):
        MANDEL.work(2, 3, {})


def test_roofline_share_names_its_bound():
    peak = {"flops_per_s": 100.0, "bytes_per_s": 10.0}
    assert roofline.share(50, 1, 1.0, peak) == (50.0, "compute")
    assert roofline.share(1, 5, 1.0, peak) == (50.0, "memory")
    with pytest.raises(ValueError):
        roofline.share(1, 1, 0.0, peak)


def test_v5e_peaks_are_the_published_ones():
    p = roofline.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["bytes_per_s"] == 819e9
    assert p["source"]


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_kernel_share_adds_the_chips_packets_against_kernel_time():
    """Two gaussian packets on the chip (group 0), one on the host
    (group 1, not counted), 1 ms of matching kernel events."""
    from types import SimpleNamespace

    import numpy as np

    from bench import drive
    from bench import trace as T

    pk = [SimpleNamespace(offset=0, size=2, device=0),
          SimpleNamespace(offset=2, size=1, device=0),
          SimpleNamespace(offset=3, size=1, device=1)]
    sub = drive.Submit(wall_s=1.0, result=SimpleNamespace(packets=pk),
                       row0=0, n_rows=512, col0=0, n_cols=256,
                       unit_rows=128, sample_rows=np.zeros(0, int),
                       sample=np.zeros((0, 256)), input_seed=0)
    name = ('%_run_tile.1 = f32[256,256]{1,0} custom-call(...), '
            'custom_call_target="tpu_custom_call"')
    summary = T.Summary(window_s=1.0, busy_s=0.5, n_devices=1,
                        op_s={name: 1e-3, "%pad.0 = f32[1]{0} pad()": 1.0},
                        idle_gaps=[])
    run = drive.RunRecord(setup_s=1.0, submits=[sub], compiles=0,
                          chip_groups=[0], config={"ksize": 31},
                          device_kind="TPU v5 lite", trace=summary)
    ops = sum(GAUSS.work(r, 256, {"ksize": 31})[0] for r in (256, 128))
    nbytes = sum(GAUSS.work(r, 256, {"ksize": 31})[1] for r in (256, 128))
    pct, bound = roofline.kernel_share(run, "gaussian")
    assert bound == "memory"
    assert pct == pytest.approx(100 * nbytes / 819e9 / 1e-3)
    assert ops / 197e12 < nbytes / 819e9
    assert roofline.kernel_share(
        drive.RunRecord(1.0, [sub], 0, [0], {"ksize": 31},
                        "TPU v5 lite"), "gaussian") is None
