"""`BENCHMARK.json` and the files it names, found by name.

A cell `<config>.<traffic>` reads `configs/<config>.json`,
`traffic/<traffic>.json` and `cells/<cell>.json`; the configuration's
`"reference"` names its plain reference, `chipbench/reference/<name>.py`;
a per-layer metric `<name>` is read by `metrics/<name>.py`.  Adding a
configuration, a mix, a cell or a metric adds files and edits none.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def config_path(name: str) -> str:
    return os.path.join(HERE, "configs", f"{name}.json")


def traffic_path(name: str) -> str:
    return os.path.join(HERE, "traffic", f"{name}.json")


def cell_path(name: str) -> str:
    return os.path.join(HERE, "cells", f"{name}.json")


def metric_path(name: str) -> str:
    return os.path.join(HERE, "metrics", f"{name}.py")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def resolve(bench: dict, name: str) -> dict:
    """Everything one cell runs from: its entry, configuration, traffic,
    cell file, and the metric entries that apply to it."""
    w = workload(bench, name)
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in bench[kind]:
            if "workloads" not in m or name in m["workloads"]:
                metrics[kind].append(m)
    return {"workload": w,
            "config": load_json(config_path(w["config"])),
            "traffic": load_json(traffic_path(w["traffic"])),
            "cell": load_json(cell_path(name)),
            "metrics": metrics}


def reference_module(name: str):
    """The plain reference module a configuration names (see
    `chipbench/reference/__init__.py` for what it provides)."""
    full = f"chipbench.reference.{name}"
    if not re.fullmatch(r"[a-z_][a-z0-9_]*", name) or name in (
            "train", "markov"):
        raise ValueError(f"{name!r} is no reference module name")
    try:
        return importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:
            raise
        raise ValueError(f"the configuration names reference {name!r}: "
                         f"there is no chipbench/reference/{name}.py") from e


def metric_reader(name: str):
    """The `read(ctx)` function of a per-layer metric's own module."""
    path = metric_path(name)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
