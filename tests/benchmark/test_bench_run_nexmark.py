"""`nexmark_q11.replay` through `run.py` and `harness/result.py` at toy
size on the CPU, over a fixture manifest that lists the one cell
(`tests/benchmark/nexmark/BENCHMARK.json`: its entries are the real
manifest's, letter for letter); the cell's metric files against what
the program declares; the session step's roofline from the
configuration alone."""

import json
import re

import pytest

from bench_drive import drive

from benchmarks.harness import manifest, rooflines, session_rooflines

CELL = "nexmark_q11.replay"
FIXTURE = "tests/benchmark/nexmark/BENCHMARK.json"
MAN = manifest.manifest()
STAGE_METRICS = {"session_mirror_pct": "session_mirror",
                 "session_key_codes_pct": "session_key_codes",
                 "task_decode_pct": "decode",
                 "session_close_cycle_p50_ms": "session_close"}
NEW = [*STAGE_METRICS, "session_step_roofline"]


@pytest.fixture(scope="module")
def toy():
    rc, line, err = drive(CELL, 2**31 + 29, manifest=FIXTURE, seconds=2.0)
    assert rc == 0, err[-3000:]
    info = next(ln for ln in err.splitlines() if ln.startswith("# info "))
    return line, json.loads(info[len("# info "):]), err


def test_fixture_manifest_is_the_real_manifests_entries():
    with open(FIXTURE) as f:
        fixture = json.load(f)
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in fixture[kind]:
            assert entry in MAN[kind], (kind, entry["name"])
    assert [w["name"] for w in fixture["workloads"]] == [CELL]
    assert {m["name"] for m in fixture["per_layer"]} == set(NEW)


def test_toy_run_is_correct_and_reports_the_cells_metrics(toy):
    line, info, err = toy
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"events_per_s", "setup_s"} == {
        m["name"] for m in manifest.metrics_of(CELL, MAN, "end_to_end")}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["compared"]) == {
        "rows_missing", "rows_extra", "bids_mismatch", "bounds_mismatch",
        "acked_not_stored", "device_fallbacks", "executor_wrong",
        "late_drops", "query_not_running", "compiles_in_window"}
    assert all(p["value"] == 0 == p["limit"]
               for p in line["compared"].values())
    assert err.strip().splitlines()[-1] == "correct: true"


def test_events_per_s_counts_the_bids_the_session_step_took(toy):
    line, info, _err = toy
    # one frame a call, a call's bids a step: what was consumed in the
    # window is whole frames, and no more than were acknowledged (the
    # lead of 2^22 events never holds the toy producer back)
    rows = manifest.size_of(manifest.cell(CELL)["config"], True)["frame_rows"]
    consumed = line["metrics"]["events_per_s"]["value"] * info["window_s"]
    assert round(consumed) % rows == 0
    assert 0 < consumed <= rows * (info["calls"] + 2)


def test_toy_run_shows_every_label_the_cells_readers_difference(toy):
    _line, info, _err = toy
    stages = info["stage_ms_and_count"]
    for name, label in STAGE_METRICS.items():
        assert stages[label][1] >= 1, (name, label)
    for label in ("session_close_fetch", "session_close_decode", "step",
                  "read_wait", "state_wait", "emit"):
        assert stages[label][1] >= 1, label
    # the ingest pipeline's and the key table's stages are not this
    # path's: their metrics stay out of the cell's list
    assert not set(stages) & {"key_encode", "stage_wait", "ring_wait",
                              "encode", "close"}
    top = ("read_wait", "decode", "state_wait", "step", "emit", "snapshot")
    total_s = sum(stages[s][0] for s in top if s in stages) / 1e3
    assert 0.8 * info["window_s"] <= total_s <= 1.1 * info["window_s"]


def test_the_cell_reports_its_own_per_layer_metrics_and_no_others():
    mine = [m["name"] for m in manifest.metrics_of(CELL, MAN, "per_layer")]
    assert mine == NEW
    # and no accepted metric's list names the new cell
    for m in MAN["per_layer"]:
        if m["name"] not in NEW:
            assert CELL not in m["workloads"], m["name"]
    assert MAN["workloads"][-1]["name"] == CELL
    assert MAN["configs"][-1]["name"] == "nexmark_q11"
    assert MAN["configs"][-1]["reduced"] == []


@pytest.mark.parametrize("name", NEW)
def test_metric_file_names_what_the_program_declares(name):
    from hstream_tpu.common.tracing import TRACE_STAGES
    from hstream_tpu.engine import lattice

    spec, read = manifest.reader_of(name)
    assert callable(read) and spec["name"] == name
    assert spec["kind"] == "per_layer" and spec["moves"] == "events_per_s"
    if name in STAGE_METRICS:
        assert spec["source"] == "program_span"
        assert spec["histogram"] == "stage_latency_ms"
        assert spec["label"] == STAGE_METRICS[name] in TRACE_STAGES
    else:
        assert spec["source"] == "device_trace"
        assert any(re.match(p, lattice.SESSION_STEP_PROGRAM)
                   for p in spec["programs"])
        assert not any(re.match(p, other) for p in spec["programs"]
                       for other in (lattice.STEP_PROGRAM,
                                     lattice.SESSION_EXTRACT_PROGRAM,
                                     lattice.SESSION_REMAP_PROGRAM))
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == spec[key], key
    assert entry["workloads"] == [CELL]


def _run_with(programs: dict, runs: dict) -> dict:
    cell = manifest.cell(CELL)
    return {"trace": {"programs": programs, "program_runs": runs},
            "size": manifest.size_of(cell["config"], False),
            "config": cell["config"],
            "device": {"kind": "TPU v5 lite"}}


def test_session_step_roofline_from_the_configuration_alone():
    cell = manifest.cell(CELL)
    # key code + time read once; two bounds and a count read and written
    assert session_rooflines.step_bytes_per_event(cell["config"]) \
        == 8 + 16 + 8
    peak = rooflines.peaks("TPU v5 lite")
    spec, read = manifest.reader_of("session_step_roofline")
    # 40 steps of 65 536 bids in 1.2 s of device time
    got = read(_run_with({"jit_session_step": 1.2, "jit_session_extract":
                          0.3}, {"jit_session_step": 40.0}), spec)
    least = 40 * 65536 * 32 / peak["hbm_bytes_per_s"]
    assert got == pytest.approx(100.0 * least / 1.2)
    assert 0 < got < 100
    # the least time itself reads 100: no step can read above it
    assert read(_run_with({"jit_session_step": least},
                          {"jit_session_step": 40.0}), spec) \
        == pytest.approx(100.0)


def test_session_step_roofline_reads_nothing_where_no_such_program_ran():
    """The parent's step is `jit_step`: the reader returns None there
    and the line leaves the metric out; never 0."""
    spec, read = manifest.reader_of("session_step_roofline")
    assert read(_run_with({"jit_step": 1.0}, {"jit_step": 9.0}),
                spec) is None
    assert read({"trace": None}, spec) is None


def test_stage_readers_read_a_session_span_and_nothing_on_the_parent():
    hist = {"bounds": [1.0, 10.0, 100.0], "cum": [0, 10, 10],
            "sum_ms": 500.0, "count": 10}
    zero = {"bounds": [1.0, 10.0, 100.0], "cum": [0, 0, 0],
            "sum_ms": 0.0, "count": 0}
    for name, label in STAGE_METRICS.items():
        spec, read = manifest.reader_of(name)
        run = {"start": {"histograms": {"stage_latency_ms": {label: zero}}},
               "end": {"histograms": {"stage_latency_ms": {label: hist}}},
               "window_s": 2.0}
        got = read(run, spec)
        if name.endswith("_pct"):
            assert got == pytest.approx(25.0), name
        else:
            assert 1.0 < got <= 10.0, name
        none = {"start": {"histograms": {"stage_latency_ms": {}}},
                "end": {"histograms": {"stage_latency_ms": {}}},
                "window_s": 2.0}
        assert read(none, spec) is None, name


# ---- what the sensor-only yardstick tests hold, for this configuration ------
# (their own `nexmark_q11` cases fail until a `benchmark` PR narrows them:
# PERF.md section 7)


def test_config_resolves_to_its_file_and_names_its_modules():
    import os

    from hstream_tpu.server.main import serve

    entry = next(c for c in MAN["configs"] if c["name"] == "nexmark_q11")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    with open(os.path.join(manifest.ROOT, entry["file"])) as f:
        config = json.load(f)
    assert config["name"] == entry["name"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"] == []
    for text in (entry["why"], entry["source"]):
        assert 1 <= len(text) <= 200 and "\n" not in text
    for key in ("sql", "streams", "view", "frame_rows", "frames_per_call",
                "events_per_gap", "gap_ms", "generator", "reference",
                "limits", "guarantees", "assumed", "dry", "aggregates",
                "nexmark", "executor", "view_rows_kept"):
        assert key in config, key
    assert config["generator"] == "nexmark"
    ref = manifest.reference_of(config)
    assert callable(ref.compare) and callable(ref.sessions)
    gen = manifest.generator_of(config)
    for duty in ("streams", "warm_frames", "frame", "closers", "pulls",
                 "reader_pull"):
        assert callable(getattr(gen, duty)), duty
    assert manifest.server_options(config, serve) == {}  # server defaults
    # a dry block never changes a shape, only the scale
    assert set(config["dry"]) <= {"events_per_gap", "frame_rows",
                                  "warm_gaps"}
    assert set(config["limits"].values()) == {0}
    with pytest.raises(SystemExit, match="names no generator"):
        manifest.generator_of({"name": "x"})


def test_a_session_roofline_share_over_100_is_refused():
    from benchmarks.harness import result

    due = manifest.metrics_of(CELL, MAN, "per_layer")
    line = result.build(
        correct=True, attempted=5, failed=0,
        metrics={m["name"]: {"value": 1.0, "unit": m["unit"]} for m in due},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1, "busy_s": 1.0, "window_s": 4.0},
        compared={})
    for share, ok in ((0.0106, True), (100.0, True), (101.0, False),
                      (0.0, False)):
        line["metrics"]["session_step_roofline"]["value"] = share
        assert (result.validate(line, due, traced=True, chips=1) == []) is ok
    del line["metrics"]["session_step_roofline"]  # due, so never left out
    assert result.validate(line, due, traced=True, chips=1)
