"""Configurations, traffic mixes, limits and per-layer metrics are found
by the names BENCHMARK.json gives them."""

import os

import pytest

import registry

BENCH = registry.benchmark()


def test_every_cell_has_its_configuration_traffic_and_limits():
    configs = {c["name"] for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert w["config"] in configs
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        cfg = registry.config(w["config"])
        assert cfg["name"] == w["config"]
        assert registry.traffic(w["traffic"])["name"] == w["traffic"]
        lim = registry.limits(w["name"])["limits"]
        assert {"loss", "bits"} <= set(lim) <= {"loss", "dir1", "xnorm", "bits"}


def test_every_configuration_file_is_its_own_and_lies_under_paths():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith("bench/") and os.path.exists(os.path.join(registry.ROOT, f))


@pytest.mark.parametrize("entry", BENCH["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_module_declares_what_the_benchmark_says(entry):
    mod = registry.metric(entry["name"])
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == (entry["unit"], entry["layer"], entry["moves"])
    assert callable(mod.read)


def test_per_layer_metrics_of_a_cell_follow_their_workloads_lists():
    for w in BENCH["workloads"]:
        names = {m["name"] for m in registry.per_layer_for(w["name"])}
        for m in BENCH["per_layer"]:
            assert (m["name"] in names) == (w["name"] in m.get("workloads", [w["name"]]))


def test_unknown_names_are_errors():
    with pytest.raises(KeyError):
        registry.cell("no-such.cell")
    with pytest.raises(KeyError):
        registry.config("no-such-config")
