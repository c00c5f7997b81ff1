"""BENCHMARK.json -> the files of one cell, found by name. No JAX.

A cell ``<config>.<mix>`` names its configuration and traffic mix; the
configuration's entry names its file; a mix is ``traffic/<mix>.json``; a
metric is ``metrics/<name>.py`` (end to end) or ``layer_metrics/<name>.py``
(per layer); a configuration's plain reference is ``reference/<config>.py``.
Adding a cell, a configuration, a mix or a metric is new files plus new
entries: nothing here knows a name.
"""

from __future__ import annotations

import importlib.util
import json
import os


GROUPS = ("configs", "workloads", "end_to_end", "per_layer")


def load_bench(root: str, candidate: str = "") -> dict:
    """BENCHMARK.json; with ``candidate``, plus the entries of
    ``candidates/<candidate>.json``: cells that were built and run but are
    not listed (yet), kept runnable."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if candidate:
        with open(os.path.join(bench_dir(root, bench), "candidates", candidate + ".json")) as f:
            extra = json.load(f)
        for group in GROUPS:
            bench[group] = bench[group] + extra.get(group, [])
    return bench


def bench_dir(root: str, bench: dict) -> str:
    return os.path.join(root, bench["paths"][0])


def load_module(root: str, bench: dict, kind: str, name: str):
    path = os.path.join(bench_dir(root, bench), kind, name + ".py")
    safe = "".join(c if c.isalnum() else "_" for c in f"bench_{kind}_{name}")
    spec = importlib.util.spec_from_file_location(safe, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: dict, group: str) -> list:
    """The metrics of ``group`` that this cell reports: those with no
    ``workloads`` key, and those that list the cell."""
    return [m for m in bench[group] if "workloads" not in m or cell["name"] in m["workloads"]]


def resolve(root: str, workload: str, candidate: str = "") -> dict:
    """{"bench", "cell", "config", "traffic"} for a workload's name."""
    bench = load_bench(root, candidate)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(bench_dir(root, bench), "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return {"bench": bench, "cell": cell, "config": config, "traffic": traffic}


def readers(root: str, bench: dict, cell: dict, traced: bool) -> list:
    """(metric entry, reader module) for the run's kind."""
    group, kind = ("per_layer", "layer_metrics") if traced else ("end_to_end", "metrics")
    return [(m, load_module(root, bench, kind, m["name"])) for m in cell_metrics(bench, cell, group)]
