"""`run.py` end to end at toy size on the CPU: the tumbling / HLL
configuration with a reader. A dry run says `platform: cpu` and is never
a result."""

from bench_drive import drive

from benchmarks.harness import manifest

CELL = "sensor_hll_100k.replay_pull"
MAN = manifest.manifest()


def test_toy_run_is_correct_and_prints_the_contract_line():
    rc, line, err = drive(CELL, 2**31 + 5)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert line["device"]["platform"] == "cpu"
    assert line["failed"] == 0 and line["attempted"] > 0
    due = {m["name"] for m in manifest.metrics_of(CELL, MAN, "end_to_end")}
    assert set(line["metrics"]) == due
    assert {"events_per_s", "append_ack_p95_ms", "pull_p50_ms",
            "setup_s"} == due
    assert list(line)[-1] == "compared"
    for pair in line["compared"].values():
        assert pair["value"] <= pair["limit"]
    assert "compiles_in_window" in line["compared"]
    assert err.strip().splitlines()[-1] == "correct: true"


def test_corrupted_reference_reads_incorrect():
    rc, line, err = drive(CELL, 12, fault="reference_corrupted",
                          reference="tumbling_hll")
    assert rc == 0, err[-3000:]
    assert line["correct"] is False
    over = {k for k, p in line["compared"].items()
            if p["value"] > p["limit"]}
    assert "cnt_mismatch" in over


def test_an_altered_answer_reads_incorrect():
    rc, line, err = drive(CELL, 13, fault="answer_altered")
    assert rc == 0, err[-3000:]
    assert line["correct"] is False, line["compared"]


def test_traced_toy_run_off_a_tpu_prints_no_result():
    """No TPU plane in a CPU trace: the run exits non-zero rather than
    print `busy_s: 0`."""
    rc, line, err = drive(CELL, 14, trace=1)
    assert rc != 0 and line is None
    assert "no /device:TPU plane" in err
