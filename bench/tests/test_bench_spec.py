"""BENCHMARK.json against the benchmark's contract, and the files it names:
every cell's configuration, traffic, driver and readers exist, and a cell,
a configuration or a metric added as new files and entries is found without
an edit."""
import json
import re

import pytest

from benchlib import BENCH, ROOT, SPEC, harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
E2E = {m["name"]: m for m in SPEC["end_to_end"]}
PER_LAYER = [m["name"] for m in SPEC["per_layer"]]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_exist_and_load(cell):
    c = harness.load_cell(cell)
    assert c.chips in (1, 4)
    assert c.driver_path.is_file()
    assert c.traffic["rate_metric"] in E2E
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert any(m["name"] == c.traffic["rate_metric"] for m in c.end_to_end)
    assert c.per_layer, "every cell reports a per-layer metric"


@pytest.mark.parametrize("cell", CELLS)
def test_cell_entry_follows_the_contract(cell):
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_configuration_entries(name):
    c = next(c for c in SPEC["configs"] if c["name"] == name)
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
    assert any(w["config"] == name for w in SPEC["workloads"])
    assert all(NAME.match(k) for k in c["reduced"])
    for k in c["reduced"]:
        assert not k.endswith(("_dim", "_rank", "_size")) and "heads" not in k


@pytest.mark.parametrize("name", list(E2E))
def test_end_to_end_metrics(name):
    m = E2E[name]
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["source"] in ("host_clock", "device_trace")
    assert m["better"] in ("lower", "higher")
    assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("name", PER_LAYER)
def test_per_layer_metric_has_a_reader_and_moves_a_reported_metric(name):
    m = next(m for m in SPEC["per_layer"] if m["name"] == name)
    assert NAME.match(name) and UNIT.match(m["unit"])
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert (BENCH / "metrics" / f"{name}.py").is_file()
    reader = harness.load_module(BENCH / "metrics" / f"{name}.py")
    assert callable(reader.read)
    moved = E2E[m["moves"]]
    for cell in m["workloads"]:
        assert cell in CELLS
        assert _reports(moved, cell), f"{cell} does not report {m['moves']}"


def test_a_cell_added_as_files_and_entries_is_found(tmp_path):
    """A new traffic mix for an existing driver, a new configuration file
    and a new reader: only new files and new entries."""
    import os

    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "metrics").mkdir(parents=True)
    os.symlink(BENCH / "drivers", tmp_path / "bench" / "drivers")
    os.symlink(BENCH / "configs", tmp_path / "bench" / "configs")
    for f in (BENCH / "traffic").iterdir():
        (tmp_path / "bench" / "traffic" / f.name).write_text(f.read_text())
    for f in (BENCH / "metrics").iterdir():
        if f.suffix == ".py":
            (tmp_path / "bench" / "metrics" / f.name).write_text(f.read_text())
    first = SPEC["workloads"][0]
    traffic = json.loads((BENCH / "traffic" / f"{first['traffic']}.json").read_text())
    traffic["overrides"] = {**traffic["overrides"], "n_nodes": 64}
    (tmp_path / "bench" / "traffic" / "new-mix.json").write_text(json.dumps(traffic))
    (tmp_path / "bench" / "new-config.json").write_text(json.dumps(
        json.loads((ROOT / next(c["file"] for c in SPEC["configs"]
                               if c["name"] == first["config"])).read_text())))
    (tmp_path / "bench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "new-config", "source": "https://example.org",
                            "file": "bench/new-config.json", "reduced": [],
                            "why": "added by a test"})
    spec["workloads"].append({"name": "new-cell", "config": "new-config",
                              "traffic": "new-mix", "chips": 1,
                              "why": "added by a test"})
    spec["per_layer"].append({"name": "new_metric", "unit": "ms", "better": "lower",
                              "source": "host_clock", "layer": "planner",
                              "moves": traffic["rate_metric"],
                              "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = harness.load_cell("new-cell", tmp_path)
    assert cell.traffic["overrides"]["n_nodes"] == 64
    assert cell.driver_path.name == f"{traffic['driver']}.py"
    assert [m["name"] for m in cell.per_layer] == ["new_metric"]
    ctx = harness.RunContext(cell=cell, seed=1, devices=[], spans=harness.Spans())
    assert harness.read_per_layer(ctx) == {"new_metric": {"value": 1.0, "unit": "ms"}}
    # the cells that were there are unchanged
    assert harness.load_cell(first["name"], tmp_path).traffic == json.loads(
        (BENCH / "traffic" / f"{first['traffic']}.json").read_text())


def test_unknown_cell_or_missing_file_is_a_setup_error(tmp_path):
    with pytest.raises(harness.SetupError):
        harness.load_cell("no-such-cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(SPEC))
    with pytest.raises(harness.SetupError):
        harness.load_cell(CELLS[0], tmp_path)
