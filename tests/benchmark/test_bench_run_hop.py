"""`run.py` end to end at toy size on the CPU: the hopping
configuration, and the harness's refusal to run off a TPU."""

import os
import subprocess
import sys

from bench_drive import ROOT, drive

CELL = "sensor_hop_1k.replay"


def test_toy_run_is_correct():
    rc, line, err = drive(CELL, 21)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"events_per_s", "append_ack_p95_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())


def test_half_of_each_batch_left_out_reads_incorrect():
    rc, line, err = drive(CELL, 22, fault="half_batch")
    assert rc == 0, err[-3000:]
    assert line["correct"] is False, line["compared"]


def test_corrupted_reference_reads_incorrect():
    rc, line, err = drive(CELL, 23, fault="reference_corrupted",
                          reference="hop_minmax")
    assert rc == 0, err[-3000:]
    assert line["correct"] is False
    assert line["compared"]["avg_rel_err"]["value"] \
        > line["compared"]["avg_rel_err"]["limit"]


def test_a_run_off_a_tpu_exits_nonzero_and_prints_no_result():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_an_unknown_workload_exits_nonzero():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "nothing.here", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--dry", "1"], cwd=ROOT, capture_output=True,
        text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
