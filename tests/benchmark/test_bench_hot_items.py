"""NEXmark Q5, hot items, as a cell: the generator's duties
(`generators/nexmark_q5.py`), the plain reference
(`references/hot_items.py`) held to a record-at-a-time loop and its
comparison held to catch what it must (its two controls, a row left
out, made up or altered), the program held to it at toy size (with the
QUALIFY and without, on one device and key-sharded over the 8 virtual
devices, on a seed and on one never used while writing the change), the
cell's metric files against what the program declares, and a `--dry 1`
run of `nexmark_q5.replay` through `run.py`."""

import json
import os

import numpy as np
import pytest

from bench_drive import drive

from benchmarks.generators import nexmark as bid_gen
from benchmarks.generators import nexmark_q5 as gen
from benchmarks.harness import manifest, rooflines, top_close_rooflines
from benchmarks.references import hot_items as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "nexmark_q5.replay"
MAN = manifest.manifest()
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nexmark_q5.json")) as _f:
    CONFIG = json.load(_f)
DRY = manifest.size_of(CONFIG, True)
N_FRAMES = gen.warm_frames(DRY) + 24
SEEDS = [2**31 + 13, 31]      # the second: a seed with ties (below)
NEW = ["key_retire_pct", "hot_items_close_cycle_p50_ms",
       "hot_items_step_roofline", "top_close_roofline",
       "hot_items_read_wait_pct", "hot_items_key_encode_pct",
       "hot_items_decode_pct"]


# ---- the configuration and the manifest's entries -------------------------


def test_the_configuration_is_the_sources():
    entry = next(c for c in MAN["configs"] if c["name"] == "nexmark_q5")
    assert entry["reduced"] == [] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nexmark_q11.json")) as f:
        q11 = json.load(f)
    # the stream is Q11's: the same rules, numbering, density, frames
    assert CONFIG["nexmark"] == q11["nexmark"]
    assert CONFIG["first_event"] == q11["first_event"]
    assert CONFIG["density"] == {"events": q11["events_per_gap"],
                                 "per_ms": q11["gap_ms"]}
    assert (CONFIG["frame_rows"], CONFIG["frames_per_call"]) == (65536, 1)
    assert (CONFIG["size_ms"], CONFIG["advance_ms"]) == (10_000, 2_000)
    assert CONFIG["warm_windows"] >= 1.3
    assert set(CONFIG["limits"]) == {
        "rows_missing", "rows_extra", "num_mismatch", "window_mismatch",
        "acked_not_stored", "device_fallbacks", "executor_wrong",
        "late_drops", "query_not_running", "compiles_in_window"}
    assert set(CONFIG["limits"].values()) == {0}
    assert "server" not in CONFIG        # server defaults
    cell = next(w for w in MAN["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nexmark_q5", "replay", 1)
    assert len(cell["why"]) <= 200


def test_the_cells_metrics_are_the_manifests():
    due = {m["name"] for m in manifest.metrics_of(CELL, MAN, "per_layer")}
    assert due == set(NEW)
    for name in NEW:
        entry = next(m for m in MAN["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]
        assert entry["moves"] == "events_per_s"
        spec, read = manifest.reader_of(name)
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[k] == entry[k], (name, k)
        assert callable(read)
    assert {m["name"] for m in manifest.metrics_of(CELL, MAN,
                                                   "end_to_end")} == {
        "events_per_s", "setup_s"}


def test_the_metric_files_name_what_the_program_declares():
    from hstream_tpu.common.tracing import TRACE_PARENT, TRACE_STAGES
    from hstream_tpu.engine import lattice

    assert manifest.reader_of("key_retire_pct")[0]["label"] \
        == "key_retire" in TRACE_STAGES
    assert TRACE_PARENT["key_retire"] == "key_encode"
    assert manifest.reader_of("hot_items_close_cycle_p50_ms")[0][
        "label"] == "close" in TRACE_STAGES
    for stage in ("read_wait", "key_encode", "decode"):
        spec = manifest.reader_of(f"hot_items_{stage}_pct")[0]
        assert spec["label"] == stage in TRACE_STAGES
        assert spec["reader"] == "stage_share_pct"
        assert stage not in TRACE_PARENT      # the task thread's own
    assert manifest.reader_of("top_close_roofline")[0]["programs"] == [
        lattice.TOP_CLOSE_PROGRAM]
    assert manifest.reader_of("hot_items_step_roofline")[0][
        "programs"] == [lattice.STEP_PROGRAM]


def test_the_rooflines_from_the_configuration_alone():
    peak = rooflines.peaks("TPU v5 lite")
    assert rooflines.step_bytes_per_event(CONFIG) == 8
    assert top_close_rooflines.close_bytes_per_group(CONFIG) == 8
    t = top_close_rooflines.least_close_seconds(CONFIG, 500_000, peak)
    assert t == pytest.approx(500_000 * 8 / peak["hbm_bytes_per_s"])


def _read_top(runs, seconds, groups, cycles, **end):
    spec, read = manifest.reader_of("top_close_roofline")
    name = "jit_extract_top_and_reset(123)"
    run = {"trace": {"programs": {name: seconds} if runs else {},
                     "program_runs": {name: runs} if runs else {}},
           "start": {"close_stats": {"close_cycles": 2, "close_groups": 9}},
           "end": {"close_stats": {"close_cycles": 2 + cycles,
                                   "close_groups": 9 + groups, **end}},
           "config": CONFIG, "device": {"kind": "TPU v5 lite"}}
    return read(run, spec)


def test_top_close_roofline_reads_none_where_there_is_nothing():
    peak = rooflines.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    got = _read_top(2, 0.004, 7_000_000, 14)
    assert got == pytest.approx(100 * (2 * 500_000 * 8 / peak) / 0.004)
    assert 0 < got < 100
    assert _read_top(0, 0.0, 7_000_000, 14) is None   # no run in the slice
    assert _read_top(2, 0.004, 0, 0) is None          # no close counted
    spec, read = manifest.reader_of("top_close_roofline")
    run = {"trace": None}
    assert read(run, spec) is None
    # a program without the counter (the parent): nothing, never 0
    run = {"trace": {"programs": {"jit_extract_top_and_reset(1)": 0.1},
                     "program_runs": {"jit_extract_top_and_reset(1)": 1}},
           "start": {"close_stats": {"close_cycles": 0}},
           "end": {"close_stats": {"close_cycles": 3}},
           "config": CONFIG, "device": {"kind": "TPU v5 lite"}}
    assert read(run, spec) is None


# ---- the generator's duties -----------------------------------------------


def test_streams_frames_and_the_clock():
    assert gen.streams(DRY) == [{"name": "bid",
                                 "schema": DRY["schemas"]["bid"]}]
    stream, ts, cols, events = gen.frame(DRY, 7, 3)
    assert stream == "bid" and events == DRY["frame_rows"] == len(ts)
    assert tuple(cols) == gen.COLUMNS
    d = bid_gen.draw(gen.clock(DRY), 7, 3)
    assert (ts == d["ts"]).all()
    for c in gen.COLUMNS:
        assert (cols[c] == d[c]).all()
    assert (np.diff(ts) >= 0).all()
    # the density: events per ms of event time, bids 46 of 50
    full = gen.clock(CONFIG)
    assert (full["events_per_gap"], full["gap_ms"]) == (1 << 23, 10_000)


@pytest.mark.parametrize("seed", [5, 2**31 + 99])
def test_same_seed_same_frames_other_seed_other_frames(seed):
    a, b = gen.frame(DRY, seed, 11), gen.frame(DRY, seed, 11)
    assert (a[1] == b[1]).all()
    assert all((a[2][c] == b[2][c]).all() for c in gen.COLUMNS)
    other = gen.frame(DRY, seed + 1, 11)
    assert (a[1] == other[1]).all()                  # time is the log's
    assert (a[2]["auction"] != other[2]["auction"]).any()


def test_auction_ids_lie_past_two_to_the_24():
    a = gen.bids(CONFIG, 3, 0, columns=("bidder", "auction"))["auction"]
    assert a.min() > 1 << 24 and a.dtype == np.int64
    assert (a.astype(np.float32).astype(np.int64) != a).any()


@pytest.mark.parametrize("size", [DRY, CONFIG], ids=["dry", "full"])
def test_the_warm_phase_spans_its_window_sizes(size):
    n = gen.warm_frames(size)
    assert n % size["frames_per_call"] == 0
    span = gen.last_time(size, n) - gen.last_time(size, 0)
    want = size["warm_windows"] * size["size_ms"]
    per_frame = span / n
    assert want - 1 <= span <= want + per_frame + 1


def test_the_closer_and_the_pulls():
    last = gen.last_time(DRY, N_FRAMES)
    (stream, ts, cols, events), = gen.closers(DRY, N_FRAMES)
    assert stream == "bid" and events == 1
    assert ts.tolist() == [last + DRY["size_ms"] + DRY["advance_ms"]]
    assert cols["auction"].tolist() == [gen.CLOSER_AUCTION]
    assert set(cols) == set(gen.COLUMNS)
    plan = gen.pulls(DRY, N_FRAMES)
    assert plan["before"] == [{
        "sql": f"SELECT * FROM hot_items WHERE winEnd <= {last};",
        "complete": ["before_closer"]}]
    assert plan["after"] == [{
        "sql": f"SELECT * FROM hot_items WHERE winEnd <= {ts[0]};",
        "complete": ["after_closer"]}]
    assert plan["horizon"] == ts[0]
    # every window a bid of the frames is in ends before the closer
    assert (last - last % DRY["advance_ms"]) + DRY["size_ms"] < ts[0]
    pull = gen.reader_pull(DRY, np.random.default_rng(1))
    assert pull["sql"].startswith("SELECT * FROM hot_items WHERE winStart")
    assert pull["winStart"] % DRY["advance_ms"] == 0


# ---- the reference against itself -----------------------------------------


def loop_hot_items(size: dict, seed: int, n_frames: int) -> dict:
    """Record at a time, as the semantics are stated: a bid counts in
    the five windows that start in (t - size, t]; a window's answer is
    every auction that reaches its maximum."""
    counts: dict = {}
    adv, per = size["advance_ms"], size["size_ms"] // size["advance_ms"]
    for i in range(n_frames):
        d = gen.bids(size, seed, i, columns=("bidder", "auction"))
        for a, t in zip(d["auction"].tolist(), d["ts"].tolist()):
            for back in range(per):
                ws = t - t % adv - back * adv
                w = counts.setdefault(ws, {})
                w[a] = w.get(a, 0) + 1
    out = {}
    for ws, w in counts.items():
        best = max(w.values())
        out[ws] = {a: n for a, n in w.items() if n == best}
    return out


@pytest.fixture(scope="module", params=SEEDS)
def case(request):
    seed = request.param
    return seed, ref.hot_items(DRY, seed, N_FRAMES)


def served_of(rows: list, complete=("before_closer", "after_closer")):
    return {"final": rows, "complete": list(complete), "pulls": [],
            "horizon": gen.pulls(DRY, N_FRAMES)["horizon"]}


def test_the_reference_is_the_record_at_a_time_loop(case):
    seed, hot = case
    assert hot == loop_hot_items(DRY, seed, N_FRAMES)
    per = DRY["size_ms"] // DRY["advance_ms"]
    starts = sorted(hot)
    assert all(ws % DRY["advance_ms"] == 0 for ws in starts)
    # a window for every slide from size - slide before the first bid
    assert len(starts) == (starts[-1] - starts[0]) // DRY["advance_ms"] + 1
    first = int(gen.bids(DRY, seed, 0, columns=())["ts"][0])
    assert starts[0] == first - first % DRY["advance_ms"] \
        - (per - 1) * DRY["advance_ms"]


def test_the_reference_agrees_with_itself(case):
    seed, hot = case
    rows = ref.rows_from(DRY, hot)
    assert len(rows) >= len(hot)
    got = ref.compare(DRY, seed, N_FRAMES, served_of(rows), hot)
    assert got == {"rows_missing": 0, "rows_extra": 0, "num_mismatch": 0,
                   "window_mismatch": 0}


def test_the_second_seed_has_a_tie():
    hot = ref.hot_items(DRY, SEEDS[1], N_FRAMES)
    assert any(len(w) > 1 for w in hot.values())


def test_the_control_in_float32_fails(case):
    seed, _hot = case
    got = ref.control(DRY, seed, N_FRAMES, "float32_ids")
    assert got["num_mismatch"] > 0 and got["rows_missing"] > 0
    assert any(v > DRY["limits"][k] for k, v in got.items())


def test_the_strict_comparison_fails_on_a_seed_with_a_tie():
    got = ref.control(DRY, SEEDS[1], N_FRAMES, "strict_gt")
    assert got["rows_missing"] > 0
    assert got["rows_extra"] == got["num_mismatch"] == 0
    with pytest.raises(ValueError):
        ref.control(DRY, SEEDS[1], N_FRAMES, "no such control")


@pytest.mark.parametrize("fault,number", [
    ("drop", "rows_missing"), ("other_auction", "rows_extra"),
    ("twice", "rows_extra"), ("count", "num_mismatch"),
    ("end", "window_mismatch"), ("start", "window_mismatch"),
])
def test_the_comparison_catches_each_fault(case, fault, number):
    seed, hot = case
    rows = ref.rows_from(DRY, hot)
    r = dict(rows[5])
    if fault == "drop":
        rows.pop(5)
    elif fault == "other_auction":
        rows[5] = {**r, "auction": r["auction"] + 1}
    elif fault == "twice":
        rows.append({**r, "num": r["num"] + 1})
    elif fault == "count":
        rows[5] = {**r, "num": r["num"] - 1}
    elif fault == "end":
        rows[5] = {**r, "winEnd": r["winEnd"] + 1}
    else:
        rows[5] = {**r, "winStart": r["winStart"] + 1,
                   "winEnd": r["winEnd"] + 1}
    got = ref.compare(DRY, seed, N_FRAMES, served_of(rows), hot)
    assert got[number] >= 1, got


@pytest.mark.parametrize("fault,number", [
    (None, None), ("not_a_leader", "rows_extra"),
    ("leader_so_far", "num_mismatch"), ("no_window", "window_mismatch"),
])
def test_a_readers_pull_is_held_to_the_closed_rows(case, fault, number):
    """An open window has no row yet: a pull's row is a final winner at
    its final count, or it is counted."""
    seed, hot = case
    rows = ref.rows_from(DRY, hot)
    r = dict(rows[7])
    pulled = rows[3:9]
    if fault == "not_a_leader":
        pulled.append({**r, "auction": r["auction"] + 1, "num": 1})
    elif fault == "leader_so_far":
        pulled.append({**r, "num": r["num"] - 1})
    elif fault == "no_window":
        pulled.append({**r, "winStart": r["winStart"] + 1,
                       "winEnd": r["winEnd"] + 1})
    srv = {**served_of(rows), "pulls": [{"rows": pulled}]}
    got = ref.compare(DRY, seed, N_FRAMES, srv, hot)
    assert got == {k: int(k == number) for k in got}


def test_before_the_closer_only_closed_windows_are_due(case):
    seed, hot = case
    last = gen.last_time(DRY, N_FRAMES)
    closed = [r for r in ref.rows_from(DRY, hot) if r["winEnd"] <= last]
    assert 0 < len(closed) < len(ref.rows_from(DRY, hot))
    got = ref.compare(DRY, seed, N_FRAMES,
                      served_of(closed, ("before_closer",)), hot)
    assert got["rows_missing"] == 0
    got = ref.compare(DRY, seed, N_FRAMES, served_of(closed), hot)
    assert got["rows_missing"] > 0       # after it, every window is
    closer = {"auction": gen.CLOSER_AUCTION, "num": 1,
              "winStart": last + DRY["size_ms"],
              "winEnd": last + 2 * DRY["size_ms"]}
    got = ref.compare(DRY, seed, N_FRAMES,
                      served_of(ref.rows_from(DRY, hot) + [closer]), hot)
    assert set(got.values()) == {0}      # its own windows: not compared


# ---- the program held to the reference ------------------------------------


def _program_rows(seed: int, *, qualify: bool, mesh=None) -> tuple:
    from hstream_tpu.server.tasks import _columnar_key_ids
    from hstream_tpu.sql.codegen import make_executor, stream_codegen

    sql = DRY["sql"] if qualify else DRY["sql"].split(" QUALIFY ")[0] + ";"
    ex = make_executor(stream_codegen(sql).select,
                       sample_rows=[{"auction": 1}], mesh=mesh,
                       initial_keys=1024, batch_capacity=4096)
    rows = []
    frames = [gen.frame(DRY, seed, i) for i in range(N_FRAMES)]
    frames.extend(gen.closers(DRY, N_FRAMES))
    for _stream, ts, cols, n in frames:
        ent = {"auction": ("i64", np.asarray(cols["auction"], np.int64),
                           None)}
        kids = _columnar_key_ids(ex, ent, n, ts_hi=int(ts.max()))
        rows.extend(ex.process_columnar(kids, ts, {}))
    return ex, rows


@pytest.mark.parametrize("seed", [2**31 + 13, 31, 2**31 + 20261003])
def test_the_program_gives_the_references_rows(seed):
    ex, rows = _program_rows(seed, qualify=True)
    got = ref.compare(DRY, seed, N_FRAMES, served_of(rows))
    assert set(got.values()) == {0}, got
    assert len(rows) > 0 and ex.key_stats["key_ids_reused"] > 0
    assert ex.late_drops == 0 and ex.device_fallbacks == 0
    assert ex.close_stats["close_tie_refetches"] == 0


@pytest.mark.parametrize("mesh", [None, "1x8"])
@pytest.mark.parametrize("seed", [2**31 + 13, 2**31 + 20261003])
def test_without_the_filter_every_groups_count_is_the_references(seed,
                                                                 mesh):
    if mesh is not None:
        from hstream_tpu.parallel import make_mesh

        mesh = make_mesh(n_data=1, n_key=8)
    ex, rows = _program_rows(seed, qualify=False, mesh=mesh)
    if mesh is not None:
        assert type(ex).__name__ == "ShardedQueryExecutor"
    panes = ref.pane_counts(DRY, seed, N_FRAMES)
    got: dict = {}
    for r in rows:
        if r["auction"] != gen.CLOSER_AUCTION:
            got.setdefault(r["winStart"], {})[r["auction"]] = r["num"]
    per = DRY["size_ms"] // DRY["advance_ms"]
    want = {}
    for p in range(min(panes) - per + 1, max(panes) + 1):
        auctions, total = ref.window_counts(DRY, panes, p)
        want[p * DRY["advance_ms"]] = dict(zip(auctions.tolist(),
                                               total.tolist()))
    assert got == want
    assert ex.key_stats["key_ids_reused"] > 0   # under retirement
    assert ex.spec.n_keys <= 4096


def test_on_a_mesh_the_filtered_statement_is_refused_by_name():
    from hstream_tpu.sql.codegen import mesh_exclusion_reason, stream_codegen

    reason = mesh_exclusion_reason(stream_codegen(DRY["sql"]))
    assert reason is not None and "QUALIFY" in reason and \
        "single-chip" in reason


# ---- the cell through run.py ----------------------------------------------


@pytest.fixture(scope="module")
def toy():
    rc, line, err = drive(CELL, 2**31 + 33, seconds=2.0)
    assert rc == 0, err[-3000:]
    info = next(ln for ln in err.splitlines() if ln.startswith("# info "))
    return line, json.loads(info[len("# info "):]), err


def test_toy_run_is_correct_and_reports_the_cells_metrics(toy):
    line, info, err = toy
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"events_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert set(line["compared"]) == set(CONFIG["limits"])
    assert all(p["value"] == 0 == p["limit"]
               for p in line["compared"].values())
    assert err.strip().splitlines()[-1] == "correct: true"


def test_toy_run_shows_every_label_the_cells_readers_difference(toy):
    _line, info, _err = toy
    stages = info["stage_ms_and_count"]
    for label in ("key_retire", "close", "close_fetch", "close_decode",
                  "key_encode", "step", "decode", "read_wait", "emit"):
        assert stages[label][1] >= 1, label
    assert not any(label.startswith("session_") for label in stages)
    # a frame a call, a call's bids a step
    consumed = _line["metrics"]["events_per_s"]["value"] * info["window_s"]
    assert round(consumed) % DRY["frame_rows"] == 0
