"""BENCHMARK.json against the benchmark's contract, and the lookup by
name that lets a later change add a cell's parts as files alone."""
import json
import pathlib
import re

import pytest

from bench import lookup
from bench.spec import Spec
from bench.tests import tiny

BM = lookup.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_top_level_keys_and_paths():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024


def test_names_units_and_entries():
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in BM["end_to_end"])
    e2e = {m["name"] for m in BM["end_to_end"]}
    for m in BM["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
    for w in BM["workloads"]:
        assert w["chips"] == 1 and len(w["why"]) <= 200


@pytest.mark.parametrize("w", [w["name"] for w in BM["workloads"]])
def test_every_cell_resolves_by_name(w):
    """Configuration, traffic, cell parameters and metric readers of each
    cell load through the harness's lookup; each cell reports set-up,
    another end-to-end metric and a per-layer metric."""
    cell = lookup.cell(w)
    Spec.from_config(cell["config"])
    assert cell["config"]["name"] == cell["workload"]["config"]
    assert {"slots", "sample_tokens", "trace_s", "limits"} <= set(
        cell["params"])
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
    for m in cell["per_layer"]:
        assert callable(lookup.metric_reader(m["name"]))
        assert m["moves"] in e2e


@pytest.mark.parametrize("c", BM["configs"], ids=lambda c: c["name"])
def test_config_files_state_their_cut(c):
    conf = lookup.config(c["name"])
    assert c["file"] == f"bench/configs/{c['name']}.json"
    assert conf["source"] == c["source"]
    assert sorted(conf["reduced"]) == sorted(c["reduced"])
    for key in ("assumed", "departures", "deployment"):
        assert conf[key]
    widths = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|"
                        r"heads|per_tok)")
    assert not any(widths.search(k) for k in c["reduced"])


def test_a_cell_added_as_files_alone(tmp_path):
    """A new configuration, mix and cell in a copied root resolve with no
    code changed."""
    root = tiny.make_root(tmp_path)
    cell = lookup.cell("tiny.open", root)
    assert cell["config"]["name"] == "tiny"
    assert cell["traffic"]["max_seq"] == tiny.TRAFFIC["max_seq"]
    assert cell["params"]["slots"] == tiny.CELL["slots"]
    assert {m["name"] for m in cell["per_layer"]} == {
        m["name"] for m in BM["per_layer"]}


def test_a_metric_added_as_a_file(tmp_path):
    root = tiny.make_root(tmp_path)
    (root / "bench" / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return ctx['x'] * 2\n")
    assert lookup.metric_reader("new.metric", root)({"x": 21}) == 42


def test_traffic_and_configs_on_disk_all_load():
    bench = pathlib.Path(lookup.__file__).parent
    for p in sorted((bench / "configs").glob("*.json")):
        Spec.from_config(lookup.config(p.stem))
    for p in sorted((bench / "traffic").glob("*.json")):
        assert lookup.traffic(p.stem)["arrivals"] in ("poisson", "batch")
