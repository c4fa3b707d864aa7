"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files the harness finds by name."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import loadgen  # noqa: E402
import manifest  # noqa: E402

SPEC = manifest.load(ROOT)
END_TO_END = {m["name"]: m for m in SPEC["end_to_end"]}
ALL_METRICS = SPEC["end_to_end"] + SPEC["per_layer"]
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/")
        assert ".." not in Path(p).parts
    assert len(SPEC["command"]) <= 32
    for word in SPEC["command"][1:]:
        assert any(Path(word).parts[0] == p for p in SPEC["paths"])
        assert (ROOT / word).is_file()
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("entry", SPEC["configs"] + SPEC["workloads"]
                         + ALL_METRICS, ids=lambda e: e["name"])
def test_names_and_units_use_allowed_characters(entry):
    assert manifest.NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert manifest.NAME.match(entry[key])
    if "unit" in entry:
        assert manifest.UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")
    for key in ("why", "layer", "source"):
        if key in entry:
            text = entry[key]
            assert 1 <= len(text) <= 200
            assert "\n" not in text and "\t" not in text


def test_names_are_unique():
    for group in (SPEC["configs"], SPEC["workloads"], ALL_METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_entries_have_only_their_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_cells_report_what_it_moves(metric):
    moved = END_TO_END[metric["moves"]]
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert manifest.reports(SPEC, moved, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    c = manifest.cell(SPEC, cell, ROOT)
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer


@pytest.mark.parametrize("cell", CELLS)
def test_cell_config_and_traffic_resolve_by_name(cell):
    c = manifest.cell(SPEC, cell, ROOT)
    conf = next(x for x in SPEC["configs"] if x["name"] == c.config_name)
    assert any(Path(conf["file"]).parts[0] == p for p in SPEC["paths"])
    assert c.config["name"] == conf["name"]
    assert c.config["reduced"] == conf["reduced"]
    assert (BENCH / c.config["reference"]).is_file()
    loadgen.Traffic.from_dict(c.traffic)     # parses, or raises


def test_config_files_are_distinct():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    path = manifest.reader_path(metric["name"])
    assert path.is_file(), path
    assert "def read(" in path.read_text()


def test_split_metric_falls_back_to_the_shared_reader():
    assert manifest.reader_path("batch_size_mean.closed").name == \
        "batch_size_mean.py"
    assert manifest.reader_path("setup_s").name == "setup_s.py"


def test_unknown_cell_is_refused():
    with pytest.raises(KeyError):
        manifest.cell(SPEC, "no.such.cell", ROOT)


def test_traffic_files_are_parameters_only():
    for f in (BENCH / "traffic").iterdir():
        assert f.suffix == ".json"
        loadgen.Traffic.from_dict(json.loads(f.read_text()))
