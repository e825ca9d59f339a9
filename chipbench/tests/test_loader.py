"""Every name in BENCHMARK.json finds its files, and every name and unit
keeps to the characters the benchmark's contract allows."""
import json
import pathlib
import re

import pytest

import loader

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
            for k in ("config", "traffic"):
                if k in e:
                    assert NAME.match(e[k]), e[k]
    for c in BENCH["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k)
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    c = loader.cell(cell)
    assert c["traffic_file"].is_file()
    assert loader.runner(c["config"]).run_cell
    for m in c["end_to_end"] + c["per_layer"]:
        assert callable(loader.reader(m["name"]))
    names = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert c["per_layer"], "every cell reports a per-layer metric"
    for m in c["per_layer"]:
        assert m["moves"] in names


def test_config_files_under_paths_and_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert any(f.startswith(p + "/") for p in BENCH["paths"])
        cfg = json.loads((ROOT / f).read_text())
        assert cfg["reduced"] == next(
            c["reduced"] for c in BENCH["configs"] if c["file"] == f)


def test_peaks_table_names_its_source():
    assert "TPU v5 lite" in json.loads(
        (ROOT / "chipbench" / "peaks.json").read_text())["devices"]
    with pytest.raises(KeyError):
        loader.peaks("a device nobody has")


KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.mark.parametrize("group", list(KEYS))
def test_entry_keys_and_text_fields(group):
    for e in BENCH[group]:
        extra = set(e) - KEYS[group]
        assert extra <= {"workloads"} and group in ("end_to_end",
                                                    "per_layer") \
            or not extra, (e["name"], extra)
        assert KEYS[group] <= set(e), e["name"]
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], (e["name"], k)
        if "better" in e:
            assert e["better"] in ("lower", "higher")
        if "bound" in e:
            assert 0.01 <= e["bound"] <= 0.25
        if group in ("end_to_end", "per_layer"):
            assert e["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")


def test_four_chip_cells_within_share():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
