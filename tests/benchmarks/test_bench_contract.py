"""``BENCHMARK.json`` keeps to the contract's limits, and every name in it
resolves to files that exist."""

import os
import re

import pytest

from zkbench import cells

ROOT = cells.ROOT
BENCH = cells.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head_dim", "n_embd", "n_inner")


def metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p for p in BENCH["paths"])
    assert 1 <= len(BENCH["command"]) <= 32
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    s = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"] + metrics(), ids=lambda e: e["name"])
def test_names_and_lines(entry):
    assert NAME.match(entry["name"])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]


def test_names_are_unique():
    for group in (BENCH["configs"], BENCH["workloads"], metrics()):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("metric", metrics(), ids=lambda m: m["name"])
def test_metric_entries(metric):
    end_to_end = metric in BENCH["end_to_end"]
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if end_to_end else {"layer", "moves"}
    assert set(metric) <= allowed
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells_known = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells_known
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
        assert len(moved) == 1
        # every cell that reads it reports the metric it moves
        for cell in metric.get("workloads", []):
            assert cells.metric_applies(moved[0], cell, [])


def test_setup_s_is_everywhere():
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    assert setup[0]["bound"] <= 0.1


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entries(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert os.path.isfile(os.path.join(ROOT, config["file"]))
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key)
        assert not key.endswith(("_dim", "_rank"))
        assert not any(word in key for word in WIDTH_WORDS)
    used = {w["config"] for w in BENCH["workloads"]}
    assert config["name"] in used
    files = [c["file"] for c in BENCH["configs"]]
    assert files.count(config["file"]) == 1


def test_four_chip_quota():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("workload", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_resolves_to_files(workload):
    assert set(workload) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(workload["config"]) and NAME.match(workload["traffic"])
    cell = cells.Cell(workload["name"])
    assert cell.config["entry"] and cell.traffic["kind"]
    assert hasattr(cell.entry_module(), "run")
    assert cell.reference_module() is not None
    names = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for metric in cell.per_layer:
        spec, reader = cell.layer_metric(metric["name"])
        assert callable(reader.read)
        # the reader's file says the same as BENCHMARK.json
        assert spec["unit"] == metric["unit"]
        assert spec["layer"] == metric["layer"]
        assert spec["moves"] == metric["moves"]
        assert spec["source"] == metric["source"]
        shapes = spec.get("params", {}).get("shapes")
        if shapes:
            assert cell.shapes_module(shapes) is not None


def test_files_under_paths_are_named_from_name_characters():
    for base in BENCH["paths"]:
        for dirpath, dirnames, filenames in os.walk(os.path.join(ROOT, base)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                if f.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert PATH.match(rel), rel


def test_roofline_and_mfu_naming():
    names = [m["name"] for m in BENCH["per_layer"]]
    for moved in {m["moves"] for m in BENCH["per_layer"] if m["name"].endswith("_roofline")}:
        beside = [
            m["name"] for m in BENCH["per_layer"]
            if m["moves"] == moved and "mfu" in re.split(r"[._]", m["name"])
        ]
        assert beside, f"no whole-step mfu beside the rooflines that move {moved}"
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert len(names) <= 128


def test_peaks_table_has_the_v5e_and_refuses_others():
    cell = cells.Cell(BENCH["workloads"][0]["name"])
    row = cell.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["int8_ops_per_s"] == 393e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(cells.CellError):
        cell.peaks("some other accelerator")
