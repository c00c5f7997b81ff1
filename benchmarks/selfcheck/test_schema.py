"""BENCHMARK.json against the contract's limits, and the harness's promise
that a configuration, a traffic mix and a metric are added as files plus
entries with no edit to a file that is there."""

import json
import os
import re
import shutil

import pytest
from conftest import BENCH, ROOT

from harness import cells, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


CANDIDATES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "candidates")))


@pytest.fixture(scope="module", params=[""] + CANDIDATES, ids=["listed"] + CANDIDATES)
def bench(request):
    """BENCHMARK.json as it is, and as each kept candidate would make it."""
    return cells.load_bench(ROOT, request.param)


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(bench, indent=1)) <= 64 * 1024
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    cells_full = 24
    assert (2 + 14 * cells_full) * (bench["run_seconds"] + 60) + cells_full * 180 + 1200 <= 43200
    assert 1 <= len(bench["paths"]) <= 16 and len(bench["command"]) <= 32
    for word in bench["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_units_and_entry_keys(bench):
    seen = set()
    for group, extra in (("end_to_end", {"bound"}), ("per_layer", {"layer", "moves"})):
        for m in bench[group]:
            assert set(m) - {"workloads"} == {"name", "unit", "better", "source"} | extra, m
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
            assert m["name"] not in seen
            seen.add(m["name"])
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert c["file"].startswith(bench["paths"][0] + "/") and os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic")) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    assert len({(w["config"], w["traffic"]) for w in bench["workloads"]}) == len(bench["workloads"])
    assert {c["name"] for c in bench["configs"]} == {w["config"] for w in bench["workloads"]}


def test_every_cell_reports_what_the_contract_asks(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "workloads" not in e2e["setup_s"] and e2e["setup_s"]["bound"] <= 0.1
    for w in bench["workloads"]:
        mine = {m["name"] for m in cells.cell_metrics(bench, w, "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layers = cells.cell_metrics(bench, w, "per_layer")
        assert layers
        for m in layers:  # the metric it should move is reported where it is
            assert m["moves"] in mine, (w["name"], m["name"])
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_a_metric_is_listed_once_and_read_from_its_own_group(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert not e2e & {m["name"] for m in bench["per_layer"]}  # promoted or not, listed once
    for name in e2e:  # an end-to-end metric is the benchmark's own reading: metrics/<name>.py
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py")), name
        assert not os.path.exists(os.path.join(BENCH, "layer_metrics", name + ".py")), name
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
        listed = [w for w in bench["workloads"] if "workloads" not in m or w["name"] in m["workloads"]]
        assert listed, m["name"]
        for w in listed:  # what it should move is reported by each cell that lists it
            assert m["moves"] in {x["name"] for x in cells.cell_metrics(bench, w, "end_to_end")}, (m["name"], w["name"])


def test_every_name_has_its_files(bench, request):
    candidate = request.node.callspec.params["bench"]
    for w in bench["workloads"]:
        found = cells.resolve(ROOT, w["name"], candidate)
        assert found["config"]["name"] == w["config"]
        assert os.path.exists(os.path.join(BENCH, "reference", w["config"] + ".py"))
        for traced in (False, True):
            for m, reader in cells.readers(ROOT, bench, w, traced):
                assert callable(reader.read), m["name"]
    for root, _dirs, files in os.walk(BENCH):
        for f in files:
            if "__pycache__" not in root:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


def test_configuration_files_say_what_they_run():
    for name in os.listdir(os.path.join(BENCH, "configs")):
        with open(os.path.join(BENCH, "configs", name)) as f:
            c = json.load(f)
        for key in ("source", "reduced", "assumed", "stands_for", "deployment", "tier", "reference"):
            assert key in c, (name, key)
    with open(os.path.join(BENCH, "configs", "gpt2-large.json")) as f:
        c = json.load(f)
    unit = {p["name"]: p["value"] for p in c["deployment"]["spec"]["predictors"][0]["graph"]["parameters"]}
    assert (int(unit["hidden"]), int(unit["layers"]), int(unit["ffn"]), int(unit["vocab"]), int(unit["max_len"])) == (
        c["n_embd"], c["n_layer"], c["n_inner"], c["vocab_size"], c["n_positions"])
    assert c["n_embd"] // 64 == c["n_head"] == c["reference"]["n_head"]
    assert int(unit["seq"]) + int(unit["max_new_tokens"]) <= c["n_positions"]
    tpu = c["deployment"]["spec"]["predictors"][0]["tpu"]
    per_slot = -(-(int(unit["seq"]) + int(unit["max_new_tokens"])) // tpu["decode_kv_page_size"])
    assert tpu["decode_kv_pages"] >= tpu["decode_slots"] * per_slot + 1  # every slot can be admitted


def test_a_config_a_mix_and_a_metric_are_added_without_an_edit(tmp_path):
    """In a temporary copy: new files and new entries, nothing else touched;
    the harness finds them by name."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "benchmarks", ignore=shutil.ignore_patterns("__pycache__"))
    before = {
        os.path.relpath(os.path.join(r, f), tmp_path): open(os.path.join(r, f), "rb").read()
        for r, _d, fs in os.walk(tmp_path / "benchmarks") for f in fs
    }
    b = tmp_path / "benchmarks"
    cfg = json.loads((b / "configs" / "bert-base-dag.json").read_text())
    cfg["name"] = "dummy-dag"
    (b / "configs" / "dummy-dag.json").write_text(json.dumps(cfg))
    shutil.copy(b / "reference" / "bert-base-dag.py", b / "reference" / "dummy-dag.py")
    (b / "traffic" / "closed-8.json").write_text(json.dumps(
        {"generator": "closed", "protocol": "json", "clients": 8, "rows": 2, "ramp_s": 1.0}))
    (b / "layer_metrics" / "dummy_ratio.py").write_text(
        'def read(o):\n    return o["after"]["rows"] / o["after"]["batches"]\n')
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "dummy-dag", "source": cfg["source"], "file": "benchmarks/configs/dummy-dag.json",
                             "reduced": ["mesh"], "why": "dummy"})
    bench["workloads"].append({"name": "dummy-dag.closed-8", "config": "dummy-dag", "traffic": "closed-8",
                               "chips": 1, "why": "dummy"})
    bench["per_layer"].append({"name": "dummy_ratio", "unit": "rows", "better": "higher", "source": "program_counter",
                               "layer": "batcher + executor", "moves": "preds_per_s", "workloads": ["dummy-dag.closed-8"]})
    # the graph tier's end-to-end metric: its reader is kept, its entry comes with its first cell
    bench["end_to_end"].append({"name": "preds_per_s", "unit": "preds/s", "better": "higher", "bound": 0.03,
                                "source": "host_clock", "workloads": ["dummy-dag.closed-8"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    found = cells.resolve(str(tmp_path), "dummy-dag.closed-8")
    assert found["config"]["name"] == "dummy-dag" and found["traffic"]["clients"] == 8
    plan = traffic.build_plan(found["traffic"], seed=1, seconds=5)
    assert len(plan["clients"]) == 8 and plan["clients"][0][0]["rows"] == 2
    assert callable(cells.load_module(str(tmp_path), found["bench"], "reference", "dummy-dag").probabilities)
    layer = dict((m["name"], r) for m, r in cells.readers(str(tmp_path), found["bench"], found["cell"], True))
    assert layer["dummy_ratio"].read({"after": {"rows": 64, "batches": 2}}) == 32
    e2e = [m["name"] for m, _ in cells.readers(str(tmp_path), found["bench"], found["cell"], False)]
    assert sorted(e2e) == ["preds_per_s", "setup_s"]
    for rel, data in before.items():  # no file that was there changed
        assert open(os.path.join(tmp_path, rel), "rb").read() == data, rel
