"""Every cell, configuration, mix and metric in BENCHMARK.json resolves to
its own file by name, and the files agree with the entries."""

import os

import chipbench_tiny  # noqa: F401
from chipbench import spec

import pytest

BENCH = spec.benchmark()


@pytest.mark.parametrize("w", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves(w):
    r = spec.resolve(BENCH, w)
    entry = r["workload"]
    assert entry["name"] == f"{entry['config']}.{entry['traffic']}"
    assert r["config"]["name"] == entry["config"]
    assert r["traffic"]["name"] == entry["traffic"]
    # an adaptive cell's crossing falls among the compared set-up steps
    assert r["traffic"]["test_interval"] < r["traffic"]["setup_steps"] or \
        r["traffic"]["schedule"] != "adaptive"
    # a cell compares the numbers that separate its sound runs from its
    # control and faults; every cell holds the norm test's statistics, the
    # weights' change and the rung decisions
    assert {"grad_sqnorm_gap", "var_l1_gap", "change_gap",
            "rung_mismatches"} <= set(r["cell"]["limits"]) <= {
        "loss_gap", "grad_sqnorm_gap", "var_l1_gap", "first_grad_gap",
        "change_gap", "rung_mismatches"}
    names = {m["name"] for m in r["metrics"]["end_to_end"]}
    assert {"setup_s", "train_tokens_per_s"} <= names
    assert r["metrics"]["per_layer"]


CONFIG_FILES = sorted(f[:-5] for f in os.listdir(os.path.dirname(
    spec.config_path("x"))) if f.endswith(".json"))
METRIC_FILES = sorted(f[:-3] for f in os.listdir(os.path.dirname(
    spec.metric_path("x"))) if f.endswith(".py"))


def test_every_entry_has_its_file():
    assert {c["name"] for c in BENCH["configs"]} <= set(CONFIG_FILES)
    assert {m["name"] for m in BENCH["per_layer"]} <= set(METRIC_FILES)


@pytest.mark.parametrize("c", CONFIG_FILES)
def test_config_file_names_what_it_changed(c):
    """Every configuration file, also one whose cell is not in
    BENCHMARK.json yet, states its cut; an entry agrees with its file."""
    cfg = spec.load_json(spec.config_path(c))
    assert cfg["name"] == c
    assert sorted(cfg["published"]) == sorted(cfg["reduced"])
    for key in cfg["reduced"]:
        assert cfg["model"][key] != cfg["published"][key]
    assert cfg["program"]["overrides"]["num_layers"] == \
        cfg["model"]["num_hidden_layers"]
    entry = next((x for x in BENCH["configs"] if x["name"] == c), None)
    if entry is not None:
        assert entry["file"] == os.path.relpath(spec.config_path(c),
                                                spec.ROOT)
        assert cfg["source"] == entry["source"]
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])


@pytest.mark.parametrize("m", METRIC_FILES)
def test_metric_reader_resolves(m):
    assert callable(spec.metric_reader(m))
    entry = next((x for x in BENCH["per_layer"] if x["name"] == m), None)
    if entry is not None:
        assert entry["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        for w in entry.get("workloads", []):
            spec.workload(BENCH, w)


def test_paths_hold_only_the_benchmark():
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(spec.ROOT, p))
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
