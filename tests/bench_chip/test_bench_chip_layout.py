"""BENCHMARK.json against the benchmark's contract, every name resolving
to its file, and a cell added by files alone."""
from __future__ import annotations

import json
import re
import shutil

import pytest

from benchmarks.chip import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SPEC = harness.load_spec()


def test_top_level_keys_and_command():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len(SPEC["command"]) <= 32
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word
    for path in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", path)
        assert (harness.ROOT / path).is_dir()
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_units_and_keys():
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    names = [e["name"] for e in entries]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    assert len(json.dumps(SPEC)) <= 64 * 1024


def test_every_cell_resolves_and_reports_what_it_must():
    used = set()
    for w in SPEC["workloads"]:
        res = harness.resolve(SPEC, w["name"])
        used.add(w["config"])
        e2e = {m["name"] for m in res["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert res["per_layer"], w["name"]
        assert res["limits"], f"no limits file for {w['name']}"
        harness.driver_class(res["traffic"]["kind"])
        for m in res["per_layer"]:
            assert m["moves"] in e2e
    assert used == {c["name"] for c in SPEC["configs"]}


def test_metric_files_state_their_unit_and_moves():
    # BENCHMARK.json states each metric's unit and moves; each name finds
    # a reader, which reads nothing where there is nothing to read
    for m in SPEC["per_layer"]:
        assert m["unit"] and m["moves"]
        mod = harness.metric_reader(m["name"])
        assert mod.read({"driver": None, "trace": None, "peaks": None,
                         "config": {}}) is None
    assert harness.metric_reader("idle_share.any_cell").__file__.endswith(
        "idle_share.py")


def test_limits_sit_between_their_readings():
    for w in SPEC["workloads"]:
        for name, lim in harness.resolve(SPEC, w["name"])["limits"].items():
            assert lim["lower"] <= lim["limit"], (w["name"], name)
            assert lim["limit"] > lim["lower"] or lim["limit"] == 0
            if lim.get("upper") is not None:
                assert lim["limit"] < lim["upper"], (w["name"], name)


def test_check_schedule_fits_the_time_limit():
    runs = 2 + 14 * 24
    total = runs * (SPEC["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


@pytest.fixture
def tree_with_a_new_cell(tmp_path):
    """A copy of the benchmark with one configuration, mix, metric and
    cell added as files and entries only."""
    root = tmp_path / "repo"
    shutil.copytree(harness.HERE, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    here = root / "benchmarks" / "chip"
    spec = json.loads(json.dumps(SPEC))
    cfg = json.loads((here / "configs" / "mlp_mnist_h200.json").read_text())
    cfg["name"] = "mlp_dummy"
    (here / "configs" / "mlp_dummy.json").write_text(json.dumps(cfg))
    (here / "traffic" / "dummy_train.json").write_text(json.dumps(
        {"kind": "mlp_train", "metric": "mlp_train_tuples_per_s",
         "engine": "dense", "iters_per_query": 2, "batches": 1}))
    (here / "metrics" / "dummy.queries.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    spec["configs"].append({"name": "mlp_dummy", "source": "x",
                            "file": "benchmarks/chip/configs/mlp_dummy.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "mlp_dummy.dummy_train",
                              "config": "mlp_dummy",
                              "traffic": "dummy_train", "chips": 1,
                              "why": "x"})
    for m in spec["end_to_end"]:
        if m["name"] == "mlp_train_tuples_per_s":
            m["workloads"].append("mlp_dummy.dummy_train")
    spec["per_layer"].append({"name": "dummy.queries", "unit": "queries",
                              "better": "higher", "source": "host_clock",
                              "layer": "IR engines",
                              "moves": "mlp_train_tuples_per_s",
                              "workloads": ["mlp_dummy.dummy_train"]})
    return spec, here


def test_a_cell_is_added_by_files_alone(tree_with_a_new_cell):
    spec, here = tree_with_a_new_cell
    res = harness.resolve(spec, "mlp_dummy.dummy_train", here=here)
    assert res["config"]["name"] == "mlp_dummy"
    assert res["traffic"]["iters_per_query"] == 2
    assert [m["name"] for m in res["per_layer"]][-1] == "dummy.queries"
    assert harness.metric_reader("dummy.queries", here=here).read({}) == 1.0
    assert harness.driver_class(res["traffic"]["kind"]).SPANS
