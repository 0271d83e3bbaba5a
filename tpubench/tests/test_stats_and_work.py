"""Metric arithmetic and the families' work counts against hand-worked
figures. The counts live in the family module a configuration names
(``reference``); these pin them where they were before they moved."""

import math

import pytest

from tpubench.harness import cells, peaks, stats

MEDIUM = cells.load_json(cells.BENCH_DIR / "configs" / "gpt2-medium.json")
LARGE = cells.load_json(cells.BENCH_DIR / "configs" / "gpt2-large.json")
work = cells.load_family(cells.ROOT / MEDIUM["reference"])


def test_percentile_interpolates_between_order_statistics():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(1, 101), 95) == pytest.approx(95.05)
    assert stats.percentile([7.0], 95) == 7.0


def test_percentile_of_unanswered_requests_is_infinite():
    values = [10.0] * 18 + [math.inf] * 2
    assert math.isinf(stats.percentile(values, 95))
    assert stats.percentile([10.0] * 99 + [math.inf], 95) == 10.0


def test_ttft_counts_from_when_the_request_was_due():
    # Due at 1.0 s, submitted late at 1.4 s, first token at 1.5 s: the
    # caller waited 500 ms, not 100.
    assert stats.ttfts_ms([1.0], [1.5]) == [pytest.approx(500.0)]
    assert stats.lateness_ms([1.0], [1.4]) == [pytest.approx(400.0)]
    assert math.isinf(stats.ttfts_ms([1.0], [None])[0])


def test_iqr_share_is_the_contracts_spread():
    # statistics.quantiles(n=4) of 1..6: q1 = 1.75, q3 = 5.25, median 3.5.
    assert stats.iqr_share([1, 2, 3, 4, 5, 6]) == pytest.approx(1.0)


def test_gpt2_medium_parameters_and_flops_by_hand():
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) + 1024 x 50257
    assert work.matmul_params(MEDIUM) == 24 * 12_582_912 + 51_463_168
    # 2 x 51,463,168 + 1,048,576 + 24 x 12,596,224 + 2,048 + 50,257.
    assert work.param_count(MEDIUM) == 406_336_593
    # forward per token: 2 x 353,453,056 + 24 x 2 x 1025 x 1024
    assert work.forward_flops_per_token(MEDIUM, 1024) == pytest.approx(
        706_906_112 + 50_380_800)
    assert work.train_flops_per_token(MEDIUM, 1024) == pytest.approx(
        2.27186e9, rel=1e-5)
    # 2.272 GFLOP a token, to the last digit as it stood in harness/work.py.
    assert work.train_flops_per_token(MEDIUM, 1024) == 2271860736.0
    assert cells.load_family(cells.ROOT / LARGE["reference"]) is work
    assert work.sizes(LARGE) == {"n_vocab": 50257, "n_ctx": 1024}


def test_gpt2_large_parameters_and_decode_step_by_hand():
    assert work.param_count(LARGE) == 838_409_297
    mm = 36 * (4 * 1280 * 1280 + 2 * 1280 * 5120) + 1280 * 50257
    assert work.matmul_params(LARGE) == mm
    # An int8 position: 2 x 36 x 1280 payload bytes + 2 x 36 x 20 scales of 4.
    assert work.kv_bytes_per_token(LARGE, "int8") == 92_160 + 5_760
    assert 16 * work.kv_bytes_per_token(LARGE, "int8") == 1_566_720
    # Two slots at contexts 100 and 300.
    flops = work.decode_step_flops(LARGE, [100, 300])
    assert flops == 2 * 2 * mm + 2 * 2 * 1280 * 36 * 400
    assert work.decode_step_bytes(LARGE, 400, "int8") == 2 * mm + 400 * 97_920


def test_flash_work_and_which_bound_applies():
    flops, bytes_ = work.flash_fwd_work(MEDIUM, 8, 1024)
    assert flops == 8 * 4 * (1024 * 1025 / 2) * 1024
    assert bytes_ == 4 * 8 * 1024 * 1024 * 2
    bwd = work.flash_bwd_work(MEDIUM, 8, 1024)
    assert bwd == (2 * flops, 2 * bytes_)
    least, bound = peaks.roofline_seconds(flops, bytes_,
                                          peaks.peaks_for("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(flops / 197e12)
    # A step's table: every layer's call, what kernel_roofline divides.
    table = work.train_kernels(MEDIUM, 8, 1024)
    assert table == {"flash_fwd": (24 * flops, 24 * bytes_),
                     "flash_bwd": (48 * flops, 48 * bytes_)}


def test_an_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks known"):
        peaks.peaks_for("TPU v9 imaginary")
