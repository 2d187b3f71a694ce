"""The per-layer metrics that read the program's host timeline (PR 25):
`stage_share_pct` and `program_ms_per_run` on hand-made runs and on
the recorded slice, every metric file of the seven against its reader
and the program's declared names, and the toy run of each cell against
the stage labels the readers difference. A traced run off a TPU prints
no result by design, so the readers are held to hand-made dicts and
the labels to the untraced toy run.
"""

import json
import os
import re

import pytest

from bench_drive import drive
from benchmarks.harness import manifest, trace
from benchmarks.readers import program_ms_per_run, stage_share_pct

MAN = manifest.manifest()
BOUNDS = [0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0]

# metric file -> the cells its label is observed in. All are entries
# of BENCHMARK.json since PR 28 (every parent since PR 25 has the
# spans), each with the cells listed where it reads a number.
STAGE_METRICS = {
    "task_key_encode_pct": "both",
    "task_state_wait_pct": "both",
    "pipe_stage_wait_pct": "both",
    "close_cycle_p50_ms": "both",
    "pull_hold_p50_ms": "pull",
    "pull_state_wait_p50_ms": "pull",
}
CELL_OF = {"pull": "sensor_hll_100k.replay_pull",
           "hop": "sensor_hop_1k.replay",
           "replay": "sensor_hll_100k.replay"}
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_slice_hll_100k.json")


def series(observations: list) -> dict:
    """One labelled histogram series, as `served.counters` snapshots
    it, from a list of observations in ms."""
    counts = [0] * (len(BOUNDS) + 1)
    for v in observations:
        counts[next((i for i, b in enumerate(BOUNDS) if v <= b),
                    len(BOUNDS))] += 1
    cum, seen = [], 0
    for c in counts:
        seen += c
        cum.append(seen)
    return {"bounds": BOUNDS, "cum": cum, "sum_ms": float(sum(observations)),
            "count": len(observations)}


def run_of(start: dict, end: dict, window_s: float = 10.0) -> dict:
    return {"start": {"histograms": {"stage_latency_ms": start}},
            "end": {"histograms": {"stage_latency_ms": end}},
            "window_s": window_s}


SPEC = {"histogram": "stage_latency_ms", "label": "state_wait"}


def test_share_of_the_window_spent_in_a_stage():
    run = run_of({"state_wait": series([40.0])},
                 {"state_wait": series([40.0] + [90.0] * 20 + [200.0])})
    # 2 000 ms of 10 s: what came before the window does not count
    assert stage_share_pct.read(run, SPEC) == pytest.approx(20.0)


def test_an_absent_label_reads_none_not_zero():
    run = run_of({}, {"key_encode": series([5.0])})
    assert stage_share_pct.read(run, SPEC) is None


def test_a_label_new_in_the_window_counts_from_zero():
    run = run_of({}, {"state_wait": series([500.0])})
    assert stage_share_pct.read(run, SPEC) == pytest.approx(5.0)


def test_waits_of_no_length_read_zero():
    """Cell 2 has no reader: every `state_wait` is observed and none
    lasts. 0.0 is a value, and the line keeps it."""
    run = run_of({"state_wait": series([0.0] * 3)},
                 {"state_wait": series([0.0] * 400)})
    assert stage_share_pct.read(run, SPEC) == 0.0


def traced(programs: dict, runs: dict) -> dict:
    return {"trace": {"programs": programs, "program_runs": runs}}


PEEK = {"programs": ["jit_peek_slots$", "jit_extract$"]}


def test_device_ms_per_run_of_a_program():
    run = traced({"jit_step": 0.98, "jit_peek_slots": 0.036,
                  "jit_extract_and_reset": 0.5},
                 {"jit_step": 99.0, "jit_peek_slots": 12.0,
                  "jit_extract_and_reset": 1.0})
    assert program_ms_per_run.read(run, PEEK) == pytest.approx(3.0)


def test_a_run_cut_by_the_slices_edge_counts_for_its_share():
    """`trace.reduce` gives a program cut by the edge a fraction of a
    run and that fraction of its time: the quotient does not move."""
    lo = 1_000_000
    events = [
        ["/host:CPU", "python3", trace.SLICE_NAME, lo, 10_000_000],
        # one whole run of 2 ms, one of which only a quarter lies inside
        ["/device:TPU:0", trace.MODULES_LINE, "jit_peek_slots(1)",
         lo + 1_000_000, 2_000_000],
        ["/device:TPU:0", trace.OPS_LINE, "%fusion", lo + 1_000_000,
         2_000_000],
        ["/device:TPU:0", trace.MODULES_LINE, "jit_peek_slots(1)",
         lo + 9_500_000, 2_000_000],
        ["/device:TPU:0", trace.OPS_LINE, "%fusion", lo + 9_500_000,
         2_000_000],
    ]
    red = trace.reduce(events)
    assert red["program_runs"]["jit_peek_slots"] == pytest.approx(1.25)
    assert program_ms_per_run.read({"trace": red}, PEEK) \
        == pytest.approx(2.0)


def test_the_closes_program_is_not_the_peeks():
    run = traced({"jit_extract_and_reset": 0.5},
                 {"jit_extract_and_reset": 1.0})
    assert program_ms_per_run.read(run, PEEK) is None
    assert program_ms_per_run.read({"trace": None}, PEEK) is None


def test_peek_on_the_slice_recorded_before_the_name_was_pinned():
    """PR 24's slice of cell 1 calls the peek's program `jit_extract`:
    the metric reads the parent's trace and the change's alike."""
    with open(FIXTURE) as f:
        red = trace.reduce(trace.unpack(json.load(f)))
    spec, read = manifest.reader_of("peek_device_ms")
    got = read({"trace": red}, spec)
    assert got == pytest.approx(
        1e3 * red["programs"]["jit_extract"]
        / red["program_runs"]["jit_extract"])
    assert 0 < got < 50


@pytest.mark.parametrize("name", [*STAGE_METRICS, "peek_device_ms"])
def test_metric_file_names_what_the_program_declares(name):
    """Each of the seven files resolves to a reader, moves an accepted
    end-to-end metric, and reads a name the program declares: a stage
    of `TRACE_STAGES`, or the pinned program of the peek."""
    from hstream_tpu.common.tracing import TRACE_STAGES
    from hstream_tpu.engine import lattice

    spec, read = manifest.reader_of(name)
    assert callable(read) and spec["name"] == name
    assert spec["kind"] == "per_layer"
    assert spec["moves"] in {m["name"] for m in MAN["end_to_end"]}
    assert spec["layer"] in {m["layer"] for m in MAN["per_layer"]}
    if name == "peek_device_ms":
        assert spec["source"] == "device_trace"
        assert any(re.match(p, lattice.PEEK_PROGRAM)
                   for p in spec["programs"])
        assert not any(re.match(p, lattice.CLOSE_PROGRAM)
                       or re.match(p, lattice.STEP_PROGRAM)
                       for p in spec["programs"])
    else:
        assert spec["source"] == "program_span"
        assert spec["histogram"] == "stage_latency_ms"
        assert spec["label"] in TRACE_STAGES
    entry = next((m for m in MAN["per_layer"] if m["name"] == name), None)
    if entry is not None:
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == spec[key], key


@pytest.mark.parametrize("name", sorted(STAGE_METRICS))
def test_manifest_lists_a_stage_metric_where_its_label_is_observed(name):
    entry = next(m for m in MAN["per_layer"] if m["name"] == name)
    want = (set(CELL_OF.values()) if STAGE_METRICS[name] == "both"
            else {CELL_OF[STAGE_METRICS[name]]})
    assert set(entry["workloads"]) == want


@pytest.fixture(scope="module", params=sorted(CELL_OF))
def toy(request):
    """The untraced toy run of one cell: (kind, stage_ms_and_count of
    its `# info` line)."""
    rc, line, err = drive(CELL_OF[request.param], 25)
    assert rc == 0 and line["correct"] is True, err[-3000:]
    info = next(ln for ln in err.splitlines() if ln.startswith("# info "))
    return request.param, json.loads(info[len("# info "):])


def test_toy_run_shows_every_label_the_readers_difference(toy):
    kind, info = toy
    stages = info["stage_ms_and_count"]
    for name, where in STAGE_METRICS.items():
        label = manifest.load_json("metrics", name + ".json")["label"]
        if where == "both" or where == kind:
            assert stages[label][1] >= 1, (name, label)
        else:
            assert label not in stages, (name, label)
    # the rest of the table: observed whether or not anything waited
    for label in ("read_wait", "ring_wait", "encode", "store_read",
                  "close_fetch", "close_decode"):
        assert stages[label][1] >= 1, label
    if kind == "pull":
        # the window's end may cut the last pull between two spans
        assert info["pulls"] >= 2
        assert stages["pull_serve"][1] >= info["pulls"] - 1
        assert abs(stages["pull_hold"][1]
                   - stages["pull_state_wait"][1]) <= 1


def test_toy_runs_task_thread_is_accounted_for(toy):
    """The task thread's top-level stages, summed over the window, stay
    under its length and take most of it: the sums the `*_pct` metrics
    divide are shares of one thread's wall."""
    from hstream_tpu.common.tracing import TRACE_PARENT

    _kind, info = toy
    stages = info["stage_ms_and_count"]
    task = ("read_wait", "decode", "state_wait", "key_encode", "step",
            "emit", "snapshot")
    assert not set(task) & set(TRACE_PARENT)
    total_s = sum(stages[s][0] for s in task if s in stages) / 1e3
    # a span that straddles the window's start counts whole: one poll
    assert 0.8 * info["window_s"] <= total_s <= 1.1 * info["window_s"]
