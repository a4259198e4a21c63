import pytest

from harness import runtime, shapes, spec, stats


def test_percentile_interpolates_between_order_statistics():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(xs, 0) == 10.0
    assert stats.percentile(xs, 50) == 30.0
    assert stats.percentile(xs, 100) == 50.0
    assert stats.percentile(xs, 90) == pytest.approx(46.0)  # position 3.6
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0  # sorts first


def test_p99_of_a_thousand_rests_on_ten_samples_beyond_it():
    xs = list(range(1000))
    assert stats.percentile(xs, 99) == pytest.approx(989.01)


def test_spread_is_quartile_distance_over_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx((4.0 - 2.0) / 3.0)
    assert stats.spread([7.0] * 6) == 0.0


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_peaks_table_knows_the_v5e_and_refuses_the_rest():
    peaks = spec.load_peaks("TPU v5 lite")
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert peaks["bf16_flops_per_s"] == 197e12
    assert "source" in peaks
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v9 imaginary")


def test_epoch_need_by_hand():
    # V=10, E=40, widths 8-4-2, bf16. Layer 1: one aggregation pass at 8,
    # two products. Layer 2: two passes at 4, three products.
    need = shapes.gcn_epoch_need(10, 40, [8, 4, 2], 2)
    agg1 = 40 * (8 + 8 * 2) + 10 * 8 * 2
    agg2 = 40 * (8 + 4 * 2) + 10 * 4 * 2
    dense1 = 2 * 10 * (8 + 4) * 2
    dense2 = 3 * 10 * (4 + 2) * 2
    assert need["bytes"] == agg1 + 2 * agg2 + dense1 + dense2
    flops = 2 * 40 * 8 + 2 * (2 * 40 * 4) + 2 * (2 * 10 * 8 * 4) + 3 * (2 * 10 * 4 * 2)
    assert need["flops"] == flops


def test_least_time_names_its_bound():
    peaks = {"hbm_bytes_per_s": 100.0, "bf16_flops_per_s": 1000.0}
    assert shapes.least_time({"bytes": 500.0, "flops": 1000.0}, peaks) == {"seconds": 5.0, "bound": "hbm"}
    assert shapes.least_time({"bytes": 50.0, "flops": 4000.0}, peaks, chips=2) == {"seconds": 2.0, "bound": "flops"}


def test_wire_rows():
    assert shapes.exchange_rows_per_device(1, 100) == 0
    assert shapes.exchange_rows_per_device(4, 100) == 300
    # three layers: 3 exchanges forward, 2 backward
    assert shapes.epoch_wire_rows_per_device(4, 100, 3) == 5 * 300


def test_cpu_steal_is_a_running_total_or_nothing():
    first, second = runtime.cpu_steal_s(), runtime.cpu_steal_s()
    assert first is None or (first >= 0.0 and second >= first)
