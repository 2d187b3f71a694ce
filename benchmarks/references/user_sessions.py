"""Plain numpy reference for NEXmark Q11, user sessions: COUNT(*) per
bidder over session windows. Independent of `hstream_tpu`: the frames
come from the benchmark's own generator (bidder and time alone, int64
throughout), are sorted by (bidder, time) and cut where the distance
between neighbours exceeds the gap; a session is one run, its count the
run's length.

The semantics are those `engine/session.py`'s docstring cites from
`SessionWindowedStream.hs`, and they are Flink's `SESSION(dateTime,
INTERVAL '10' SECOND)` of nexmark-flink's `q11.sql` but for the edge:
  * a bid EXACTLY `gap_ms` after its bidder's last one joins the session
    (the program's rule: within the gap of the session's edge); Flink
    merges `[t, t + gap)` windows only where they intersect, so it
    would start a new session there;
  * `winStart` is the first bid's time, `winEnd` the last bid's time
    plus the gap: Flink's `window_start` / `window_end` alike;
  * q11.sql has no closing rule; here a session reaches the view once
    the watermark (the newest event time consumed) has passed its end by
    `close_after_gaps` gaps. That decides when a row appears, never
    what it holds.

What a run can compare: the view keeps its newest `view_rows_kept`
closed rows, in the order they closed. Every frame is one batch and one
close cycle, so the reference knows each session's cycle (the first
frame whose last event time reaches `end + close_after_gaps * gap`)
and, counting back from the newest cycle, which cycles still fit the
view whole: every session of those must be there (`rows_missing`). The
cycle on the cut is there in part, in an order that is the program's;
its rows, like all rows, are held to the reference one by one.

Numbers compared (limits in the configuration's file, all 0, exact):
  rows_missing     sessions of a whole kept cycle the view did not give
  rows_extra       rows given that are no closed session of the
                   reference (by bidder and winStart), or given twice
  bids_mismatch    rows whose count is not the reference's
  bounds_mismatch  rows whose winEnd is not the reference's
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import nexmark as gen


def sessions(size: dict, seed: int, n_frames: int, *,
             gap_ms: int | None = None, lower=None) -> dict:
    """Every session of the first `n_frames` frames, as int64 columns
    sorted by (bidder, t0): `bidder`, `t0`, `t1`, `bids`, and `cycle`,
    the frame at whose end it closes (`n_frames`: only the closer
    closes it). `gap_ms` and `lower` are the control's: another gap,
    and a function the bidder ids pass through (a lower precision)."""
    gap = size["gap_ms"] if gap_ms is None else gap_ms
    frames = [gen.draw(size, seed, i, columns=("bidder",))
              for i in range(n_frames)]
    bidder = np.concatenate([f["bidder"] for f in frames])
    ts = np.concatenate([f["ts"] for f in frames])
    marks = np.maximum.accumulate(
        np.array([f["ts"].max() for f in frames], np.int64))
    del frames
    if lower is not None:
        bidder = lower(bidder)
    order = np.lexsort((ts, bidder))
    bidder, ts = bidder[order], ts[order]
    del order
    cut = np.empty(len(ts), np.bool_)
    cut[0] = True
    cut[1:] = (bidder[1:] != bidder[:-1]) | (ts[1:] - ts[:-1] > gap)
    starts = np.flatnonzero(cut)
    ends = np.append(starts[1:], len(ts)) - 1
    t1 = ts[ends]
    return {"bidder": bidder[starts], "t0": ts[starts], "t1": t1,
            "bids": ends - starts + 1,
            "cycle": np.searchsorted(
                marks, t1 + size["close_after_gaps"] * gap, "left"),
            "gap": gap}


def rows_from(size: dict, ses: dict) -> list[dict]:
    """The reference's sessions as the view would give them."""
    key, cnt = size["key_column"], size["count_column"]
    return [{key: int(b), cnt: int(n), "winStart": int(a),
             "winEnd": int(z) + ses["gap"]}
            for b, n, a, z in zip(ses["bidder"], ses["bids"], ses["t0"],
                                  ses["t1"])]


def due(size: dict, ses: dict, n_frames: int) -> np.ndarray:
    """Which sessions the view must hold, before the closer or after
    it: those of the newest close cycles that fit `view_rows_kept`
    whole. The closer's cycle (`n_frames`) closes whatever was left."""
    kept = size["view_rows_kept"]
    per_cycle = np.bincount(ses["cycle"], minlength=n_frames + 1)
    must = np.zeros(len(ses["cycle"]), np.bool_)
    for newest in (n_frames - 1, n_frames):
        room = kept
        for c in range(newest, -1, -1):
            room -= per_cycle[c]
            if room < 0:
                break
            if per_cycle[c]:
                must |= ses["cycle"] == c
    return must


def compare(size: dict, seed: int, n_frames: int, served: dict,
            ses: dict | None = None) -> dict:
    key, cnt = size["key_column"], size["count_column"]
    if ses is None:
        ses = sessions(size, seed, n_frames)
    gap = size["gap_ms"]
    at = {key_: i for i, key_ in enumerate(
        zip(ses["bidder"].tolist(), ses["t0"].tolist()))}
    numbers = {"rows_missing": 0, "rows_extra": 0, "bids_mismatch": 0,
               "bounds_mismatch": 0}
    got = np.zeros(len(ses["bidder"]), np.bool_)
    for r in served["final"]:
        if r["winEnd"] > served["horizon"] \
                or r[key] == gen.CLOSER_BIDDER:
            continue  # still open past the closer: not compared
        i = at.get((int(r[key]), int(r["winStart"])))
        if i is None or got[i] or r[key] != int(r[key]):
            numbers["rows_extra"] += 1
            continue
        got[i] = True
        numbers["bids_mismatch"] += int(r[cnt] != ses["bids"][i])
        numbers["bounds_mismatch"] += int(
            r["winEnd"] != ses["t1"][i] + gap)
    if served["complete"]:
        numbers["rows_missing"] = int(
            (due(size, ses, n_frames) & ~got).sum())
    # a reader's pull sees open sessions too: a row of one still growing
    # lies inside the session it will be, with fewer bids than it
    for r in (r for p in served["pulls"] for r in p["rows"]):
        i = at.get((int(r[key]), int(r["winStart"])))
        end = None if i is None else ses["t1"][i] + gap
        if end is None or r["winEnd"] > end:
            numbers["rows_extra"] += 1
        elif r["winEnd"] == end:
            numbers["bids_mismatch"] += int(r[cnt] > ses["bids"][i])
        else:
            numbers["bids_mismatch"] += int(r[cnt] >= ses["bids"][i])
    return numbers


def control(size: dict, seed: int, n_frames: int, how: str) -> dict:
    """The control of `correct`: the reference computed wrongly in the
    program's place, through the same comparison; it has to come out
    over a limit. `gap_plus_1`: sessions cut at `gap_ms + 1`;
    `float32_ids`: bidder ids passed through float32, the nearest
    precision below the int64 the configuration states."""
    if how == "gap_plus_1":
        wrong = sessions(size, seed, n_frames, gap_ms=size["gap_ms"] + 1)
    elif how == "float32_ids":
        wrong = sessions(
            size, seed, n_frames,
            lower=lambda b: b.astype(np.float32).astype(np.int64))
    else:
        raise ValueError(f"unknown control {how!r}")
    served = {"final": rows_from(size, wrong),
              "complete": ["before_closer", "after_closer"], "pulls": [],
              "horizon": gen.pulls(size, n_frames)["horizon"]}
    return compare(size, seed, n_frames, served)
