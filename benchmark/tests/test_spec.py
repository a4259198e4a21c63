"""BENCHMARK.json against the files it names: what a later PR relies on
when it adds a cell, a mix or a metric as data and entries only."""

import os

import pytest

from harness import e2e, spec


def test_every_cell_finds_its_config_and_traffic():
    bench = spec.load_benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(bench, w["name"])
        assert cell["config_data"]["name"] == w["config"]
        assert callable(spec.traffic_kind(cell["traffic_data"]["kind"]))
        assert cell["chips"] in (1, 4)


@pytest.mark.parametrize("mix", sorted(os.listdir(os.path.join(spec.BENCH_DIR, "traffic"))))
def test_every_mix_finds_its_kind_by_name(mix):
    import json

    with open(os.path.join(spec.BENCH_DIR, "traffic", mix)) as fh:
        kind = json.load(fh)["kind"]
    assert spec.traffic_kind(kind).__module__ == f"kinds.{kind}"
    with pytest.raises(spec.SpecError, match="kinds/ has no module named"):
        spec.traffic_kind(kind + "_of_another_kind")


@pytest.mark.parametrize("name", sorted(os.listdir(os.path.join(spec.BENCH_DIR, "configs"))))
def test_a_configuration_without_the_keys_gets_the_graph_modules(name):
    import json

    with open(os.path.join(spec.BENCH_DIR, "configs", name)) as fh:
        config = json.load(fh)
    assert not {"inputs", "check", "need"} & set(config)  # the files are as they were
    inputs, check, need = (spec.config_module(config, k) for k in ("inputs", "check", "need"))
    assert (inputs.__name__, check.__name__, need.__name__) == (
        "inputs.vertex_graph", "checks.vertex_graph", "needs.gcn")
    assert callable(inputs.build) and callable(inputs.shape) and callable(check.check)
    assert callable(need.epoch_need) and callable(need.wire_rows_per_device)
    named = dict(config, inputs="token_sequences")
    with pytest.raises(spec.SpecError, match="inputs/ has no module named 'token_sequences'"):
        spec.config_module(named, "inputs")


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
