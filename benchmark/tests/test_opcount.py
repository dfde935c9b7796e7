"""opcount against numbers worked by hand."""

import pytest

import opcount


def test_als_on_a_3_by_2_rating_matrix():
    # 3 users x 2 items, 4 ratings, rank 2, 1 iteration.
    # per half-step: 4 ratings x (2*2^2 outer + 2*2 rhs) = 4 x 12 = 48 FLOPs;
    # two half-steps: 96. Solves: 5 rows x (2^3/3 + 2*2^2) = 5 x 10.6667.
    flops, bytes_ = opcount.als_train(4, 3, 2, 2, 1, gather_bytes=2)
    assert flops == pytest.approx(96 + 5 * (8 / 3 + 8))
    # per half-step 4 ratings x (2 values x 2 B + 4 B index + 4 B rating)
    # = 48 B; two: 96. Solutions written: 5 rows x 2 x 4 B = 40.
    assert bytes_ == 96 + 40
    # iterations multiply both
    f3, b3 = opcount.als_train(4, 3, 2, 2, 3, gather_bytes=2)
    assert f3 == pytest.approx(3 * flops) and b3 == 3 * bytes_


def test_topk_on_a_4_item_catalog():
    # 3 query rows, rank 2, 4 items: 2*3*2*4 = 48 FLOPs; the factors are
    # read once: 4 items x 2 x 4 B = 32 B whatever the batch
    assert opcount.topk_call(4, 2, 3) == (48.0, 32.0)
    assert opcount.topk_call(4, 2, 64)[1] == 32.0
    assert opcount.topk_query_flops(4, 2) == 16.0


def test_roofline_takes_the_larger_bound_and_says_which():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert opcount.roofline_seconds(200.0, 10.0, peaks) == (2.0, "flops")
    assert opcount.roofline_seconds(200.0, 50.0, peaks) == (5.0, "bytes")


def test_a_device_without_peaks_is_an_error():
    assert opcount.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        opcount.peaks_for("cpu")
