"""The plain reference of NEXmark Q11 (`references/user_sessions.py`):
held to a record-at-a-time loop, and its comparison held to catch what
it must: another gap, ids in a lower precision, a row left out, a row
made up, a count or a bound altered."""

import json
import os

import numpy as np
import pytest

from benchmarks.generators import nexmark as gen
from benchmarks.harness import manifest
from benchmarks.references import user_sessions as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nexmark_q11.json")) as _f:
    CONFIG = json.load(_f)
DRY = manifest.size_of(CONFIG, True)
LIMITS = DRY["limits"]
N_FRAMES = gen.warm_frames(DRY) + 24
SEEDS = [2**31 + 11, 29]


def loop_sessions(size: dict, seed: int, n_frames: int) -> dict:
    """Record at a time, as the semantics are stated: a bid joins its
    bidder's open session if it lies within the gap of the session's
    last bid, else it opens a new one."""
    done: dict = {}
    open_: dict = {}
    for i in range(n_frames):
        d = gen.draw(size, seed, i, columns=("bidder",))
        for b, t in zip(d["bidder"].tolist(), d["ts"].tolist()):
            s = open_.get(b)
            if s is not None and t - s[1] <= size["gap_ms"]:
                s[1] = t
                s[2] += 1
            else:
                if s is not None:
                    done[(b, s[0])] = (s[1], s[2])
                open_[b] = [t, t, 1]
    for b, s in open_.items():
        done[(b, s[0])] = (s[1], s[2])
    return done


@pytest.fixture(scope="module", params=SEEDS)
def case(request):
    seed = request.param
    return seed, ref.sessions(DRY, seed, N_FRAMES)


def served_of(ses: dict, rows: list) -> dict:
    return {"final": rows, "complete": ["before_closer", "after_closer"],
            "pulls": [], "horizon": gen.pulls(DRY, N_FRAMES)["horizon"]}


# a stream four times as sparse: a person's dozen bids spread over a
# minute, so most bidders have several sessions
SPARSE = {**DRY, "events_per_gap": DRY["events_per_gap"] // 4}


@pytest.mark.parametrize("size", [DRY, SPARSE], ids=["dry", "sparse"])
@pytest.mark.parametrize("seed", SEEDS)
def test_reference_equals_the_record_at_a_time_loop(size, seed):
    n_frames = 40
    ses = ref.sessions(size, seed, n_frames)
    want = loop_sessions(size, seed, n_frames)
    got = {(int(b), int(a)): (int(z), int(n)) for b, a, z, n in zip(
        ses["bidder"], ses["t0"], ses["t1"], ses["bids"])}
    assert got == want
    assert int(ses["bids"].sum()) == n_frames * size["frame_rows"]
    if size is SPARSE:  # not one session a bidder: many split at the gap
        assert len(got) > 1.2 * len({b for b, _ in got})


def test_a_session_closes_with_the_frame_that_passes_it_by_two_gaps(case):
    _seed, ses = case
    marks = [gen.last_time(DRY, f + 1) for f in range(N_FRAMES)]
    for t1, c in zip(ses["t1"].tolist()[::37], ses["cycle"].tolist()[::37]):
        due_at = t1 + 2 * DRY["gap_ms"]
        if c < N_FRAMES:
            assert marks[c] >= due_at and (c == 0 or marks[c - 1] < due_at)
        else:
            assert marks[-1] < due_at
    assert (ses["cycle"] == N_FRAMES).any()   # left for the closer
    assert (ses["cycle"] < N_FRAMES).any()    # closed inside the run


def test_the_reference_passes_its_own_comparison(case):
    seed, ses = case
    numbers = ref.compare(DRY, seed, N_FRAMES,
                          served_of(ses, ref.rows_from(DRY, ses)), ses=ses)
    assert set(numbers) == {"rows_missing", "rows_extra", "bids_mismatch",
                            "bounds_mismatch"}
    assert all(v == 0 for v in numbers.values()), numbers
    assert all(LIMITS[k] == 0 for k in numbers)
    # the dry view holds every session: all of them are due
    assert ref.due(DRY, ses, N_FRAMES).all()


@pytest.mark.parametrize("how,over", [
    ("gap_plus_1", "bounds_mismatch"), ("float32_ids", "rows_extra")])
def test_the_control_comes_out_as_not_correct(case, how, over):
    """The reference computed wrongly in the program's place (a gap of
    10 001 ms; bidder ids through float32, which holds no odd id past
    2^24) fails the cell's limits."""
    seed, _ses = case
    numbers = ref.control(DRY, seed, N_FRAMES, how)
    failed = {k for k, v in numbers.items() if v > LIMITS[k]}
    assert over in failed, numbers
    if how == "float32_ids":
        assert numbers["rows_missing"] > 0


def test_each_fault_of_a_row_has_its_number(case):
    seed, ses = case
    rows = ref.rows_from(DRY, ses)

    def numbers(changed):
        return ref.compare(DRY, seed, N_FRAMES, served_of(ses, changed),
                           ses=ses)

    assert numbers(rows[1:])["rows_missing"] == 1
    assert numbers(rows + [dict(rows[0])])["rows_extra"] == 1
    ghost = {**rows[0], "bidder": rows[0]["bidder"] + 10**6}
    assert numbers(rows + [ghost])["rows_extra"] == 1
    more = [{**rows[0], "bids": rows[0]["bids"] + 1}] + rows[1:]
    assert numbers(more) == {"rows_missing": 0, "rows_extra": 0,
                             "bids_mismatch": 1, "bounds_mismatch": 0}
    late = [{**rows[0], "winEnd": rows[0]["winEnd"] + 1}] + rows[1:]
    assert numbers(late)["bounds_mismatch"] == 1
    # the closer's own session, and rows past the horizon, are open
    closer = {"bidder": gen.CLOSER_BIDDER, "bids": 1,
              "winStart": gen.closer_time(DRY, N_FRAMES),
              "winEnd": gen.closer_time(DRY, N_FRAMES) + DRY["gap_ms"]}
    assert all(v == 0 for v in numbers(rows + [closer]).values())
    # floats as a pull gives them compare exactly
    floats = [{k: float(v) for k, v in r.items()} for r in rows]
    assert all(v == 0 for v in numbers(floats).values())


def test_only_whole_close_cycles_inside_the_kept_rows_are_due(case):
    """A view that keeps fewer rows than the run closed: the newest
    cycles that fit whole must be there, the cycle on the cut need not,
    and a row of it that is there is still held to the reference."""
    seed, ses = case
    per_cycle = np.bincount(ses["cycle"], minlength=N_FRAMES + 1)
    assert per_cycle[-2] and per_cycle[-3] and per_cycle[-4]
    size = {**DRY, "view_rows_kept": int(per_cycle[-2] + per_cycle[-3]
                                         + per_cycle[-4] // 2)}
    must = ref.due(size, ses, N_FRAMES)
    # the two newest cycles before the closer fit whole, the third does
    # not; what the closer closes alone is more than the view keeps
    assert set(np.unique(ses["cycle"][must]).tolist()) == {
        N_FRAMES - 1, N_FRAMES - 2}
    assert must.sum() == per_cycle[-2] + per_cycle[-3]
    rows = ref.rows_from(size, ses)
    kept = [r for r, m in zip(rows, must) if m]
    assert all(v == 0 for v in ref.compare(
        size, seed, N_FRAMES, served_of(ses, kept), ses=ses).values())
    assert ref.compare(size, seed, N_FRAMES, served_of(ses, kept[1:]),
                       ses=ses)["rows_missing"] == 1


def test_a_readers_pull_of_a_growing_session_is_inside_what_it_becomes(
        case):
    seed, ses = case
    i = int(np.argmax(ses["bids"]))
    row = ref.rows_from(DRY, ses)[i]
    grown = {**row, "bids": row["bids"] - 1, "winEnd": row["winEnd"] - 1}
    served = served_of(ses, [])
    served["complete"] = []
    served["pulls"] = [{"rows": [row, grown]}]
    assert all(v == 0 for v in ref.compare(
        DRY, seed, N_FRAMES, served, ses=ses).values())
    served["pulls"] = [{"rows": [{**grown, "bids": row["bids"]}]}]
    assert ref.compare(DRY, seed, N_FRAMES, served,
                       ses=ses)["bids_mismatch"] == 1
