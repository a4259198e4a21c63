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


GCN_NEED = spec.named_module("needs", "gcn")
REDDIT = {"vertices": 232965, "edges": 114615892, "layers": [602, 128, 41], "itemsize": 2}
PRODUCTS = {"vertices": 2449029, "edges": 126167309, "layers": [100, 256, 256, 47],
            "itemsize": 2, "partitions": 4, "vp": 615456}


def test_epoch_need_by_hand():
    # V=10, E=40, widths 8-4-2, bf16. Layer 1: no aggregation pass (the
    # features' aggregate is set-up), two products. Layer 2: two passes at
    # 4, three products.
    need = GCN_NEED.epoch_need({"vertices": 10, "edges": 40, "layers": [8, 4, 2], "itemsize": 2})
    agg2 = 40 * (8 + 4 * 2) + 10 * 4 * 2
    dense1 = 2 * 10 * (8 + 4) * 2
    dense2 = 3 * 10 * (4 + 2) * 2
    assert need["bytes"] == 2 * agg2 + dense1 + dense2
    flops = 2 * (2 * 40 * 4) + 2 * (2 * 10 * 8 * 4) + 3 * (2 * 10 * 4 * 2)
    assert need["flops"] == flops


@pytest.mark.parametrize("shape, gigabytes, was", [(REDDIT, 61.6, 200.7), (PRODUCTS, 282.9, 309.6)])
def test_epoch_need_of_the_cells_prices_no_pass_at_the_input_width(shape, gigabytes, was):
    need = GCN_NEED.epoch_need(shape)
    assert need["bytes"] / 1e9 == pytest.approx(gigabytes, abs=0.05)
    # what the count held until PR 27: one pass at the input width more
    hoisted = GCN_NEED.aggregation_pass(shape["vertices"], shape["edges"], shape["layers"][0],
                                        shape["itemsize"])
    assert (need["bytes"] + hoisted["bytes"]) / 1e9 == pytest.approx(was, abs=0.05)
    peaks = spec.load_peaks("TPU v5 lite")
    assert shapes.least_time(need, peaks, shape.get("partitions", 1))["bound"] == "hbm"


def test_least_time_names_its_bound():
    peaks = {"hbm_bytes_per_s": 100.0, "bf16_flops_per_s": 1000.0}
    assert shapes.least_time({"bytes": 500.0, "flops": 1000.0}, peaks) == {"seconds": 5.0, "bound": "hbm"}
    assert shapes.least_time({"bytes": 50.0, "flops": 4000.0}, peaks, chips=2) == {"seconds": 2.0, "bound": "flops"}


def test_wire_rows():
    assert GCN_NEED.exchange_rows_per_device(1, 100) == 0
    assert GCN_NEED.exchange_rows_per_device(4, 100) == 300
    # three layers: 2 exchanges forward, 2 backward; the features' is set-up
    assert GCN_NEED.wire_rows_per_device(dict(PRODUCTS, vp=100)) == 4 * 300
    assert GCN_NEED.wire_rows_per_device(PRODUCTS) == 7385472  # 9,231,840 until PR 27
    assert GCN_NEED.wire_rows_per_device(REDDIT) is None


def test_cpu_steal_is_a_running_total_or_nothing():
    first, second = runtime.cpu_steal_s(), runtime.cpu_steal_s()
    assert first is None or (first >= 0.0 and second >= first)
