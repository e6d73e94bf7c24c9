"""The yardstick's arithmetic on fixed inputs."""

import math

import pytest

from yardstick import stats


def test_percentile_interpolates_between_order_statistics():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 4.0
    assert stats.median(xs) == 2.5
    assert stats.percentile(xs, 25) == pytest.approx(1.75)
    assert stats.percentile(list(range(101)), 99) == pytest.approx(99.0)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile(xs, 101)


def test_quartiles_and_spread():
    q = stats.quartiles([10.0, 20.0, 30.0, 40.0, 50.0])
    assert (q["n"], q["q1"], q["median"], q["q3"]) == (5, 20.0, 30.0, 40.0)
    assert q["spread"] == pytest.approx(20.0 / 30.0)


def test_a_block_sample_is_reduced_by_its_median_not_its_best():
    per_op = [t / 10 for t in (0.20, 0.10, 0.40, 0.30, 0.90)]  # blocks of 10
    assert stats.quartiles(per_op)["median"] == pytest.approx(0.030)
    assert min(per_op) == pytest.approx(0.010)


def test_algbw_and_fold_bytes():
    payload = (1 << 26) * 4
    assert stats.coll_algbw_gbps(payload, 1.948e-3) == pytest.approx(137.8, abs=0.05)
    assert stats.fold_bytes(4, payload) == 5 * payload


def test_geomean():
    assert stats.geomean([1.0, 100.0]) == pytest.approx(10.0)
    assert stats.geomean([7.0]) == pytest.approx(7.0)
    with pytest.raises(ValueError):
        stats.geomean([1.0, 0.0])


def test_flagship_flops_match_flagship_probe():
    cfg = {"d_model": 1024, "n_layers": 8, "d_ff": 4096, "vocab": 32768}
    b, t, d, f, v = 8, 1024, 1024, 4096, 32768
    per_layer = 2*b*t*d*3*d + 4*b*t*t*d + 2*b*t*d*d + 4*b*t*d*f
    want = 3.0 * (8 * per_layer + 2*b*t*d*v)
    assert stats.transformer_flops_per_step(cfg, 8, 1024) == want
    assert want == 7421703487488.0


def test_chain_bound_keeps_sums_exact():
    k = stats.chain_ops_bound("float32", 4)
    assert 1 + k * 3 <= 2 ** 24 < 1 + (k + 1) * 3 + 3
    assert stats.chain_ops_bound("bfloat16", 4) == 85
    assert math.isfinite(stats.chain_ops_bound("int32", 2))
