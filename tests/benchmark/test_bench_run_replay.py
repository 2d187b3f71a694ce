"""`run.py` end to end at toy size on the CPU: the tumbling / HLL
configuration without a reader (`sensor_hll_100k.replay`), and each
fault the cell can have read as not correct."""

import pytest

from bench_drive import drive

from benchmarks.harness import manifest

CELL = "sensor_hll_100k.replay"
MAN = manifest.manifest()


def test_toy_run_is_correct_and_reports_no_pull_metric():
    rc, line, err = drive(CELL, 2**31 + 28)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    due = {m["name"] for m in manifest.metrics_of(CELL, MAN, "end_to_end")}
    # the ack's p95 sits on the knee of this cell's distribution (PERF.md
    # section 2): it is not reported here, the median ack is, per layer
    assert due == {"events_per_s", "setup_s"}
    assert set(line["metrics"]) == due
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert err.strip().splitlines()[-1] == "correct: true"


def test_the_cell_reports_what_cell_2_reports_but_the_acks_tail():
    mine = {m["name"] for m in manifest.metrics_of(CELL, MAN, "per_layer")}
    hop = {m["name"] for m in manifest.metrics_of(
        "sensor_hop_1k.replay", MAN, "per_layer")}
    # `append_store_p50_ms` moves the p95 this cell does not report; the
    # median ack moves its rate (the producer is ack-bound here)
    assert mine == hop - {"append_store_p50_ms"} | {"append_ack_p50_ms"}
    assert not any(n.startswith(("pull_", "peek_", "read_")) for n in mine)


def test_median_ack_of_the_windows_calls():
    spec, read = manifest.reader_of("append_ack_p50_ms")
    calls = [[0, 2, 1.0, 1.0 + ms / 1e3, 0, 1, True, "s", 10]
             for ms in (30.0, 40.0, 50.0, 400.0)]
    assert read({"calls": calls}, spec) == pytest.approx(45.0)
    assert read({"calls": []}, spec) is None
    assert spec["moves"] == "events_per_s"


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_a_fault_under_the_timed_path_reads_incorrect(fault):
    rc, line, err = drive(CELL, 29, fault=fault)
    assert rc == 0, err[-3000:]
    assert line["correct"] is False, line["compared"]
    over = {k for k, p in line["compared"].items()
            if p["value"] > p["limit"]}
    assert over & {"cnt_mismatch", "rows_missing", "sum_rel_err"}, over
