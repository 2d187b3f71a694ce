"""NEXmark Q8, new users, as a cell: the configuration against its
sources, the generator's duties (`generators/nexmark_q8.py`: determinism,
frame order, widths), the plain reference (`references/new_users.py`)
held to a record-at-a-time loop and its comparison held to catch what it
must (a row left out, made up or altered, and its two controls at dry
AND full sizes, without a chip), the cell's metric files against what
the program declares, and a `--dry 1` run of `nexmark_q8.replay` through
`run.py`. Every entry is found BY NAME: nothing here rests on the cell
being the manifest's last."""

import json
import os

import numpy as np
import pytest

from bench_drive import drive

from benchmarks.generators import nexmark_q8 as gen
from benchmarks.harness import manifest, rooflines, window_join_rooflines
from benchmarks.references import new_users as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "nexmark_q8.replay"
MAN = manifest.manifest()
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nexmark_q8.json")) as _f:
    CONFIG = json.load(_f)
FULL = manifest.size_of(CONFIG, False)
DRY = manifest.size_of(CONFIG, True)
N_DRY = gen.warm_frames(DRY) + 60
N_FULL = 141                    # 70 spans: two boundaries, 2.1 windows
SEEDS = [2**31 + 41, 41]
STAGES = {"new_users_read_wait_pct": "read_wait",
          "new_users_join_key_codes_pct": "join_key_codes",
          "new_users_join_shadow_pct": "join_shadow",
          "new_users_join_fetch_pct": "join_fetch",
          "new_users_emit_pct": "emit"}
NEW = ["window_join_step_roofline", "window_join_evict_roofline",
       "join_device_idle_pct", *STAGES]


# ---- the configuration and the manifest's entries -------------------------


def test_the_configuration_is_the_sources():
    entry = next(c for c in MAN["configs"] if c["name"] == "nexmark_q8")
    assert entry["reduced"] == [] == CONFIG["reduced"]
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmarks/configs/nexmark_q8.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nexmark_q5.json")) as f:
        q5 = json.load(f)
    # the log is Q5's and Q11's: the same rules, numbering and density
    assert {k: v for k, v in CONFIG["nexmark"].items()
            if k in q5["nexmark"]} == q5["nexmark"]
    assert {k: CONFIG["nexmark"][k] for k in (
        "hot_sellers_ratio", "hot_seller_rounding",
        "avg_person_byte_size", "avg_auction_byte_size")} == {
        "hot_sellers_ratio": 4, "hot_seller_rounding": 100,
        "avg_person_byte_size": 200, "avg_auction_byte_size": 500}
    assert CONFIG["first_event"] == q5["first_event"] == 10**9
    assert CONFIG["density"] == q5["density"] == {"events": 2**23,
                                                  "per_ms": 10_000}
    assert CONFIG["streams"] == ["person", "auction"]
    assert (CONFIG["span_epochs"], CONFIG["frames_per_call"]) == (5000, 1)
    assert CONFIG["size_ms"] == 10_000 and "GRACE BY INTERVAL 0" in \
        CONFIG["sql"]
    assert "WITHIN WINDOW" in CONFIG["sql"]
    assert CONFIG["warm_windows"] >= 1.2
    assert CONFIG["executor"] == "JoinExecutor"
    assert CONFIG["view_rows_kept"] == 100_000
    assert set(CONFIG["limits"]) == {
        "rows_missing", "rows_extra", "name_mismatch",
        "auctions_mismatch", "window_mismatch", "acked_not_stored",
        "device_fallbacks", "executor_wrong", "late_drops",
        "join_rows_past_retention", "query_not_running",
        "compiles_in_window"}
    assert set(CONFIG["limits"].values()) == {0}
    assert "server" not in CONFIG        # server defaults
    assert any("MINIMUM over" in g for g in CONFIG["guarantees"])
    for key in ("density", "first_event", "streams", "frames", "draws",
                "person fields", "auction fields", "statement",
                "auctions", "edge", "view_rows_kept", "name", "extra"):
        assert key in CONFIG["assumed"], key
    cell = next(w for w in MAN["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nexmark_q8", "replay", 1)
    assert len(cell["why"]) <= 200
    assert manifest.load_json("traffic", "replay.json")[
        "max_lead_events"] == 2**22


def test_the_cells_metrics_are_the_manifests():
    due = {m["name"] for m in manifest.metrics_of(CELL, MAN, "per_layer")}
    assert due == set(NEW) and len(NEW) <= 8
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
    # no accepted cell gained a metric of this one's
    for m in MAN["per_layer"]:
        if m["name"] not in NEW:
            assert CELL not in m.get("workloads", [CELL]), m["name"]


def test_the_metric_files_name_what_the_program_declares():
    from hstream_tpu.common.tracing import TRACE_PARENT, TRACE_STAGES
    from hstream_tpu.engine import lattice

    for name, stage in STAGES.items():
        spec = manifest.reader_of(name)[0]
        assert spec["label"] == stage in TRACE_STAGES
        assert spec["reader"] == "stage_share_pct"
        assert TRACE_PARENT.get(stage) == (
            "step" if stage.startswith("join_") else None)
    assert manifest.reader_of("window_join_step_roofline")[0][
        "programs"] == [lattice.WINDOW_JOIN_STEP_PROGRAM]
    assert manifest.reader_of("window_join_evict_roofline")[0][
        "programs"] == [lattice.WINDOW_JOIN_EVICT_PROGRAM]
    assert manifest.reader_of("join_device_idle_pct")[0][
        "reader"] == "device_idle_pct"


def test_the_rooflines_from_the_configuration_alone():
    peak = rooflines.peaks("TPU v5 lite")
    assert window_join_rooflines.rows_per_step(CONFIG) == 10_000
    # a row: 8 B read + 12 B written; three rows of four pair once:
    # 8 B of the stored row + 8 B of the COUNT cell
    assert window_join_rooflines.step_bytes_per_row(CONFIG) == 32
    t = window_join_rooflines.least_step_seconds(CONFIG, 3, peak)
    assert t == pytest.approx(3 * 10_000 * 32 / peak["hbm_bytes_per_s"])
    t = window_join_rooflines.least_evict_seconds(CONFIG, 2, peak)
    assert t == pytest.approx(2 * 20_000 * 24 / peak["hbm_bytes_per_s"])


def _read(name, program, runs, seconds, config=CONFIG):
    spec, read = manifest.reader_of(name)
    run = {"trace": {"programs": {program: seconds} if runs else {},
                     "program_runs": {program: runs} if runs else {}},
           "config": config, "size": config,
           "device": {"kind": "TPU v5 lite"}}
    return read(run, spec)


@pytest.mark.parametrize("name,program", [
    ("window_join_step_roofline", "jit_window_join_step(77)"),
    ("window_join_evict_roofline", "jit_window_join_evict(3)")])
def test_a_roofline_reads_none_where_there_is_nothing(name, program):
    got = _read(name, program, 40, 1.6)
    assert 0 < got < 0.1                 # far under 1%: a sort a batch
    assert _read(name, program, 0, 0.0) is None  # no run in the slice
    # the interval join's programs are not this metric's
    assert _read(name, "jit_probe_insert_step(1)", 40, 1.6) is None
    assert _read(name, "jit_evict(1)", 40, 1.6) is None
    spec, read = manifest.reader_of(name)
    assert read({"trace": None}, spec) is None
    # another deployment's configuration: nothing, never a raise
    assert _read(name, program, 40, 1.6,
                 {"aggregates": ["COUNT"]}) is None


# ---- the generator ----------------------------------------------------------


def test_frames_are_a_pure_function_and_alternate_streams():
    for size in (DRY, FULL):
        per = size["span_epochs"]
        for i in (0, 1, 6, 7):
            a = gen.frame(size, 7, i)
            b = gen.frame(size, 7, i)
            assert a[0] == b[0] == ("person", "auction")[i % 2]
            assert a[3] == (per, 3 * per)[i % 2] == len(a[1])
            assert np.array_equal(a[1], b[1])
            assert all(np.array_equal(a[2][c], b[2][c]) for c in a[2])
            assert set(a[2]) == set(size["schemas"][a[0]])
            other = gen.frame(size, 8, i)
            assert np.array_equal(a[1], other[1])    # times: the log's
            assert not np.array_equal(a[2]["extra"], other[2]["extra"])
        p, a = gen.frame(size, 7, 4), gen.frame(size, 7, 5)
        assert np.all(np.diff(p[1]) >= 0) and np.all(np.diff(a[1]) >= 0)
        # a span's two frames cover the same event time, the next the next
        assert p[1][0] <= a[1][0] <= a[1][-1] <= gen.frame(size, 7, 6)[1][0]
    assert gen.warm_frames(FULL) % 2 == 0
    assert gen.warm_frames(FULL) * 125_000 >= 1.2 * 2**23


def test_ids_sellers_and_widths_are_beams():
    p = gen.persons(FULL, 11, 3)
    a = gen.auctions(FULL, 11, 3)
    epoch0 = 10**9 // 50 + 3 * 5000
    assert p["id"][0] == epoch0 + 1000 and np.all(np.diff(p["id"]) == 1)
    assert a["id"][0] == 3 * epoch0 + 1000
    assert p["id"].min() > 2**24
    seller = a["seller"] - 1000
    epoch = np.repeat(np.arange(epoch0, epoch0 + 5000), 3)
    hot = seller == epoch // 100 * 100
    assert 0.73 < hot.mean() < 0.77      # 3 draws of 4
    cold = seller[~hot]
    assert np.all(cold > epoch[~hot] - 1000)
    assert np.all(cold <= epoch[~hot] + 10)
    assert (cold > epoch[~hot]).any()    # named before its Person event
    width = lambda d: sum(  # noqa: E731
        np.char.str_len(v).mean() if v.dtype.kind == "S" else 8
        for k, v in d.items() if k != "event")
    assert 195 < width(p) < 205
    assert 495 < width(a) < 515
    # a reader that needs the first columns sees a full frame's values
    assert np.array_equal(
        gen.auctions(FULL, 11, 3, columns=("seller",))["seller"],
        a["seller"])
    assert np.array_equal(
        gen.persons(FULL, 11, 3, columns=("name",))["name"], p["name"])


def test_closers_lie_on_both_streams_past_every_window():
    got = gen.closers(DRY, N_DRY)
    assert [c[0] for c in got] == ["person", "auction"]
    t = gen.closer_time(DRY, N_DRY)
    assert all(int(c[1][0]) == t and c[3] == 1 for c in got)
    assert t >= gen.newest_time(DRY, N_DRY) + 2 * DRY["size_ms"]
    assert gen.last_time(DRY, N_DRY) <= gen.newest_time(DRY, N_DRY)
    assert gen.last_time(DRY, 1) == -1   # one source has said nothing
    plan = gen.pulls(DRY, N_DRY)
    assert str(gen.last_time(DRY, N_DRY)) in plan["before"][0]["sql"]
    assert plan["horizon"] == t


# ---- the reference ----------------------------------------------------------


def brute(size, seed, n_frames):
    w = size["size_ms"]
    people = {}
    n_p, n_a = gen.spans_of(n_frames)
    for k in range(n_p):
        f = gen.persons(size, seed, k, columns=("name",))
        for i, t, n in zip(f["id"].tolist(), f["ts"].tolist(),
                           f["name"].tolist()):
            people[i] = (t, n.decode())
    out = {}
    for k in range(n_a):
        f = gen.auctions(size, seed, k, columns=("seller",))
        for s, t in zip(f["seller"].tolist(), f["ts"].tolist()):
            p = people.get(s)
            if p is not None and p[0] // w == t // w:
                key = (s, p[1], t // w * w)
                out[key] = out.get(key, 0) + 1
    return out


def served_of(size, rows, n_frames):
    return {"final": rows, "pulls": [],
            "complete": ["before_closer", "after_closer"],
            "horizon": gen.pulls(size, n_frames)["horizon"]}


@pytest.mark.parametrize("seed", SEEDS)
def test_the_reference_agrees_with_a_loop_a_record(seed):
    ans = ref.answers(DRY, seed, N_DRY)
    rows = ref.rows_from(DRY, ans)
    assert {(r["person.id"], r["person.name"], r["winStart"]):
            r["auctions"] for r in rows} == brute(DRY, seed, N_DRY)
    assert len(ans) >= 4 and len(rows) > 400
    assert ref.compare(DRY, seed, N_DRY, served_of(DRY, rows, N_DRY)) \
        == {"rows_missing": 0, "rows_extra": 0, "name_mismatch": 0,
            "auctions_mismatch": 0, "window_mismatch": 0}


def test_the_comparison_catches_a_row_left_out_made_up_or_altered():
    seed = SEEDS[0]
    rows = ref.rows_from(DRY, ref.answers(DRY, seed, N_DRY))
    zero = dict.fromkeys(("rows_missing", "rows_extra", "name_mismatch",
                          "auctions_mismatch", "window_mismatch"), 0)

    def numbers(changed):
        return ref.compare(DRY, seed, N_DRY,
                           served_of(DRY, changed, N_DRY))

    assert numbers(rows[1:]) == {**zero, "rows_missing": 1}
    assert numbers(rows + [rows[0]]) == {**zero, "rows_extra": 1}
    made_up = {**rows[0], "person.id": rows[0]["person.id"] + 10**6}
    assert numbers(rows + [made_up]) == {**zero, "rows_extra": 1}
    assert numbers([{**rows[0], "person.name": "no one"}] + rows[1:]) \
        == {**zero, "name_mismatch": 1}
    assert numbers([{**rows[0], "auctions": rows[0]["auctions"] + 1}]
                   + rows[1:]) == {**zero, "auctions_mismatch": 1}
    shifted = {**rows[0], "winStart": rows[0]["winStart"] + 1,
               "winEnd": rows[0]["winEnd"] + 1}
    assert numbers([shifted] + rows[1:]) == {
        **zero, "window_mismatch": 1, "rows_missing": 1}
    # a row past the horizon is the closers' own window: not compared
    late = {**rows[0], "winStart": gen.closer_time(DRY, N_DRY),
            "winEnd": gen.closer_time(DRY, N_DRY) + 10_000}
    assert numbers(rows + [late]) == zero


def test_what_the_view_must_hold_is_its_newest_windows_that_fit():
    seed = SEEDS[1]
    ans = ref.answers(DRY, seed, N_DRY)
    rows = ref.rows_from(DRY, ans)
    sizes = {ws: len(v[0]) for ws, v in ans.items()}
    last = max(sizes)
    closed = max(ws for ws in sizes
                 if ws + 10_000 <= gen.last_time(DRY, N_DRY))
    assert closed < last and sizes[last] < sizes[closed]
    small = {**DRY, "view_rows_kept": sizes[closed] + 3}
    newest = [r for r in rows if r["winStart"] == last]
    # the newest window alone: whole, and the one before it does not fit
    # beside it, so nothing is missing after the closers; the cut before
    # them asks for the newest window CLOSED by then, which does fit
    got = ref.compare(small, seed, N_DRY,
                      {**served_of(small, newest, N_DRY),
                       "complete": ["after_closer"]})
    assert got["rows_missing"] == 0
    got = ref.compare(small, seed, N_DRY, served_of(small, newest, N_DRY))
    assert closed < last and got["rows_missing"] == sizes[closed]


@pytest.mark.parametrize("size_name,n_frames", [("dry", N_DRY),
                                                ("full", N_FULL)])
@pytest.mark.parametrize("seed", SEEDS + [7])
def test_both_controls_fail(size_name, n_frames, seed):
    """The reference computed wrongly in the program's place comes out
    over a limit, at dry and full sizes, with no chip. The interval
    join in the window join's place differs by the pairs astride a
    boundary alone: whole rows too many (a person of one window with an
    auction in the next). Seller ids through float32 fail by whole
    rows: ids near 2 x 10^7 fall on even numbers."""
    size = DRY if size_name == "dry" else FULL
    got = ref.control(size, seed, n_frames, "interval_join")
    assert got["rows_extra"] > 0, got
    assert got["rows_missing"] == got["name_mismatch"] == 0
    boundaries = len(ref.answers(size, seed, n_frames)) - 1
    assert boundaries >= 2
    if size_name == "full":              # a few hundred a boundary
        assert 100 * boundaries < got["rows_extra"] < 600 * boundaries
    got = ref.control(size, seed, n_frames, "float32_ids")
    assert min(got["rows_missing"], got["rows_extra"],
               got["auctions_mismatch"]) > (20 if size_name == "dry"
                                            else 20_000), got
    assert got["name_mismatch"] == got["window_mismatch"] == 0


def test_a_full_window_gives_the_rows_the_source_says():
    """~89 000 rows a window: 53% of a window's 167 772 persons are
    named by an auction of their window."""
    ans = ref.answers(FULL, 3, N_FULL)
    first = min(ans)
    ids, names, counts = ans[first]
    assert 88_000 < len(ids) < 90_000
    assert counts.sum() > 495_000        # ~503 000 matched pairs
    assert counts.max() > 150            # a hot seller's
    assert np.all(np.diff(ids) > 0) and len(names) == len(ids)


# ---- a dry run of the cell --------------------------------------------------


@pytest.fixture(scope="module")
def toy():
    rc, line, err = drive(CELL, 2**31 + 43, seconds=2.0)
    assert rc == 0, err[-3000:]
    info = next(ln for ln in err.splitlines() if ln.startswith("# info "))
    return line, json.loads(info[len("# info "):]), err


def test_toy_run_is_correct_and_reports_the_cells_metrics(toy):
    line, info, err = toy
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"events_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # every number the comparison gives has a limit, all 0; the join's
    # rows past retention are held through late_drops (the harness reads
    # no counter of its own name: CHANGES.md, PR 35)
    assert set(line["compared"]) == set(CONFIG["limits"]) - {
        "join_rows_past_retention"}
    assert all(p["value"] == 0 == p["limit"]
               for p in line["compared"].values())
    assert err.strip().splitlines()[-1] == "correct: true"


def test_toy_run_shows_every_label_the_cells_readers_difference(toy):
    _line, info, _err = toy
    stages = info["stage_ms_and_count"]
    for label in ("join_key_codes", "join_shadow", "join_pack",
                  "join_fetch", "step", "decode", "read_wait"):
        assert stages[label][1] >= 1, label
    # (a toy window may hold no close: `join_evict` and `emit` are held
    # by tests/test_new_users_served.py)
    assert not any(label.startswith("session_") for label in stages)
    assert "key_encode" not in stages
    # both streams count: a span's persons and its auctions a call each
    assert info["calls"] > 10
