"""BENCHMARK.json against the files it names: what a later PR relies on
when it adds a cell, a mix or a metric as data and entries only."""

import os

from harness import e2e, spec


def test_every_cell_finds_its_config_and_traffic():
    bench = spec.load_benchmark()
    kinds = {"train_epochs", "open_loop"}
    for w in bench["workloads"]:
        cell = spec.load_cell(bench, w["name"])
        assert cell["config_data"]["name"] == w["config"]
        assert cell["traffic_data"]["kind"] in kinds
        assert cell["chips"] in (1, 4)


def test_every_metric_has_its_reader():
    bench = spec.load_benchmark()
    for m in bench["end_to_end"]:
        assert m["name"] in e2e.READERS
        assert m["source"] in ("host_clock", "device_trace")
    e2e_names = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        path = os.path.join(spec.BENCH_DIR, "layer_metrics", m["name"] + ".py")
        assert os.path.exists(path), path
        assert m["moves"] in e2e_names


def test_a_layer_metric_is_reported_only_where_what_it_moves_is():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        have = {m["name"] for m in spec.metrics_for(bench, "end_to_end", w["name"])}
        assert "setup_s" in have and len(have) >= 2
        layer = spec.metrics_for(bench, "per_layer", w["name"])
        assert layer
        for m in layer:
            assert m["moves"] in have, (w["name"], m["name"])
