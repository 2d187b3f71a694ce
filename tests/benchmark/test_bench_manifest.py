"""BENCHMARK.json against the files it names and the contract's limits."""

import json
import os
import re

import pytest

from benchmarks.harness import manifest
from benchmarks.harness.result import NAME, UNIT

MAN = manifest.manifest()
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def under_paths(rel: str) -> bool:
    return any(rel == p or rel.startswith(p + "/") for p in MAN["paths"])


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(manifest.ROOT, p))
    assert len(MAN["command"]) <= 32
    for word in MAN["command"][1:]:
        if os.path.exists(os.path.join(manifest.ROOT, word)):
            assert under_paths(word)
    assert isinstance(MAN["run_seconds"], int)
    assert 1 <= MAN["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(manifest.ROOT,
                                        "BENCHMARK.json")) <= 64 << 10


@pytest.mark.parametrize("entry", MAN["configs"], ids=lambda c: c["name"])
def test_config_resolves_to_its_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert under_paths(entry["file"])
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] and len(
        entry["reduced"]) <= 16
    for text in (entry["why"], entry["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    for key in ("sql", "stream", "view", "keys", "frame_rows",
                "frames_per_call", "events_per_advance", "generator",
                "reference", "limits", "guarantees", "assumed", "dry",
                "aggregates"):
        assert key in config, key
    assert any(w["config"] == entry["name"] for w in MAN["workloads"])
    ref = manifest.reference_of(config)
    assert callable(ref.compare) and callable(ref.answers)
    gen = manifest.generator_of(config)
    for duty in ("streams", "warm_frames", "frame", "closers", "pulls",
                 "reader_pull"):
        assert callable(getattr(gen, duty)), duty
    # the server block, where there is one, holds options serve() takes
    from hstream_tpu.server.main import serve

    manifest.server_options(config, serve)
    # a dry block never changes a shape, only the scale
    assert set(config["dry"]) <= {"keys", "events_per_advance",
                                  "frame_rows", "frames_per_call"}


def test_config_files_are_distinct():
    files = [c["file"] for c in MAN["configs"]]
    assert len(set(files)) == len(files)
    assert len({c["name"] for c in MAN["configs"]}) == len(files)


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_config_and_traffic(name):
    cell = manifest.cell(name)
    w = next(w for w in MAN["workloads"] if w["name"] == name)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(w["traffic"])
    assert name == f"{w['config']}.{w['traffic']}"
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert cell["traffic"]["loop"] == "closed"
    assert cell["traffic"]["producers"] == 1
    assert os.path.exists(os.path.join(manifest.BENCH_DIR, "traffic",
                                       w["traffic"] + ".json"))
    e2e = [m["name"] for m in manifest.metrics_of(name, MAN, "end_to_end")]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert manifest.metrics_of(name, MAN, "per_layer")


def test_cells_are_unique_and_few_take_four_chips():
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(set(pairs)) == len(pairs) and len(set(CELLS)) == len(CELLS)
    four = sum(1 for w in MAN["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_resolves_to_its_file_and_reader(metric):
    end_to_end = metric in MAN["end_to_end"]
    allowed = ({"name", "unit", "better", "bound", "source", "workloads"}
               if end_to_end else
               {"name", "unit", "better", "source", "layer", "moves",
                "workloads"})
    assert set(metric) <= allowed
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    spec, read = manifest.reader_of(metric["name"])
    assert callable(read)
    for key in ("unit", "better", "source"):
        assert spec[key] == metric[key], key
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert spec["layer"] == metric["layer"]
        assert 1 <= len(metric["layer"]) <= 200
        moved = next(m for m in MAN["end_to_end"]
                     if m["name"] == metric["moves"])
        cells = [c for c in CELLS if manifest.reports(metric, c, MAN)]
        assert cells
        for c in cells:  # every cell that reads it reports what it moves
            assert manifest.reports(moved, c, MAN), (metric["name"], c)
    for c in metric.get("workloads", []):
        assert c in CELLS


def test_metric_names_are_unique_and_setup_is_bounded():
    names = [m["name"] for m in METRICS]
    assert len(set(names)) == len(names)
    setup = next(m for m in MAN["end_to_end"] if m["name"] == "setup_s")
    assert setup["bound"] <= 0.25
    assert 1 <= len(MAN["end_to_end"]) <= 16
    assert 1 <= len(MAN["per_layer"]) <= 128
    # a share of a roofline is named <kernel>_roofline and counted in %
    for m in METRICS:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_every_benchmark_file_has_an_allowed_name():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in MAN["paths"]:
        for base, dirs, files in os.walk(os.path.join(manifest.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), manifest.ROOT)
                assert ok.match(rel), rel
