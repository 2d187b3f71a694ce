"""Plain numpy reference for NEXmark Q5, hot items: for every hopping
window, the auction or auctions with the most bids, and that count.
Independent of `hstream_tpu`: the frames come from the benchmark's own
generator (auction and time alone, int64 throughout); bids are counted
per (pane of one slide, auction), a window's count of an auction is the
sum over its `size_ms / advance_ms` panes, its answer the maximum and
every auction that reaches it.

The semantics are nexmark-flink's `q5.sql`: `HOP(dateTime, INTERVAL '2'
SECOND, INTERVAL '10' SECOND)`, `num >= maxn`:
  * windows start at multiples of the slide in absolute event time and
    hold `[start, start + size)`;
  * a row (auction, num, winStart, winEnd) is in the view once the
    window has closed iff `num` is the maximum over all auctions with a
    bid in that window; ties are all there; a window without a bid
    gives no row;
  * q5.sql has no closing rule; here a window closes once the watermark
    (the newest event time consumed) reaches its end, GRACE being 0.
    That decides when a row appears, never what it holds.

What a run can compare: the view keeps its newest `view_rows_kept`
closed rows and a window gives about one, so every window of the run is
held to the reference, those of the warm phase too: each the argmax of
all bids of ten seconds over every auction bid on in them. A miscount
in a group that never leads is not seen by this comparison: the tests
hold every group's count of the statement without its QUALIFY to
`window_counts`.

Numbers compared (limits in the configuration's file, all 0, exact):
  rows_missing     rows of the reference, of a window closed by the
                   cut, that the view did not give
  rows_extra       rows given, of a window of the reference, whose
                   auction is not among its winners, or given twice;
                   a reader's pull is held to this too: an open window
                   has no row, so a group of one is a row too many
  num_mismatch     rows of a winner whose count is not the reference's
  window_mismatch  rows whose two bounds are no window of the reference
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import nexmark_q5 as gen


def pane_counts(size: dict, seed: int, n_frames: int, *,
                lower=None) -> dict:
    """Bids per (pane, auction): {pane index: (auctions ascending,
    counts)}, a pane being one slide of absolute event time. `lower`
    is the control's: a function the auction ids pass through."""
    adv = size["advance_ms"]
    parts: dict[int, list] = {}
    done: dict[int, tuple] = {}

    def settle(below: int | None) -> None:
        for p in [p for p in parts if below is None or p < below]:
            done[p] = np.unique(np.concatenate(parts.pop(p)),
                                return_counts=True)

    for i in range(n_frames):
        f = gen.bids(size, seed, i, columns=("bidder", "auction"))
        auction = f["auction"] if lower is None else lower(f["auction"])
        pane = f["ts"] // adv
        for p in np.unique(pane).tolist():
            parts.setdefault(p, []).append(auction[pane == p])
        settle(int(pane.min()))  # frames are in time order
    settle(None)
    return done


def window_counts(size: dict, panes: dict, start_pane: int) -> tuple:
    """(auctions ascending, bids) of the window that starts with pane
    `start_pane`: the sum over its panes."""
    per = size["size_ms"] // size["advance_ms"]
    have = [panes[p] for p in range(start_pane, start_pane + per)
            if p in panes]
    if not have:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    auctions, inv = np.unique(np.concatenate([a for a, _n in have]),
                              return_inverse=True)
    total = np.zeros(len(auctions), np.int64)
    np.add.at(total, inv, np.concatenate([n for _a, n in have]))
    return auctions, total


def hot_items(size: dict, seed: int, n_frames: int, *, lower=None,
              first_only: bool = False) -> dict:
    """Every window's answer: {winStart: {auction: num}} over the
    windows with a bid. `first_only` is the control's: of the auctions
    that tie, the first alone (a scan that replaces its best on a
    strict `>`)."""
    panes = pane_counts(size, seed, n_frames, lower=lower)
    per = size["size_ms"] // size["advance_ms"]
    out = {}
    for p in range(min(panes) - per + 1, max(panes) + 1):
        auctions, total = window_counts(size, panes, p)
        if len(total) == 0:
            continue
        best = int(total.max())
        winners = auctions[total == best]
        if first_only:
            winners = winners[:1]
        out[p * size["advance_ms"]] = {int(a): best for a in winners}
    return out


def rows_from(size: dict, hot: dict) -> list[dict]:
    """The reference's answers as the view would give them."""
    key, cnt = size["key_column"], size["count_column"]
    return [{key: a, cnt: n, "winStart": ws,
             "winEnd": ws + size["size_ms"]}
            for ws, winners in sorted(hot.items())
            for a, n in sorted(winners.items())]


def compare(size: dict, seed: int, n_frames: int, served: dict,
            hot: dict | None = None) -> dict:
    key, cnt = size["key_column"], size["count_column"]
    if hot is None:
        hot = hot_items(size, seed, n_frames)
    numbers = {"rows_missing": 0, "rows_extra": 0, "num_mismatch": 0,
               "window_mismatch": 0}
    got: set = set()
    for r in served["final"]:
        if r["winEnd"] > served["horizon"] \
                or r[key] == gen.CLOSER_AUCTION:
            continue  # the closer's own windows: still open
        ws = int(r["winStart"])
        winners = hot.get(ws)
        if winners is None or r["winEnd"] != ws + size["size_ms"]:
            numbers["window_mismatch"] += 1
            continue
        a = r[key]
        if a != int(a) or int(a) not in winners or (ws, int(a)) in got:
            numbers["rows_extra"] += 1
            continue
        got.add((ws, int(a)))
        numbers["num_mismatch"] += int(r[cnt] != winners[int(a)])
    # which windows had closed by the cut: all after the closer, those
    # that end at or before the last bid's time before it
    if "after_closer" in served["complete"]:
        cut = served["horizon"]
    elif "before_closer" in served["complete"]:
        cut = gen.last_time(size, n_frames)
    else:
        cut = None
    if cut is not None:
        numbers["rows_missing"] = sum(
            1 for ws, winners in hot.items()
            if ws + size["size_ms"] <= cut
            for a in winners if (ws, a) not in got)
    # an open window has no row yet (its extreme is known when it
    # closes), so whatever a reader's pull gives is a closed window's:
    # a winner of the reference at its final count. A group of an open
    # window, the leader so far included, is a row too many
    for r in (r for p in served["pulls"] for r in p["rows"]):
        ws = int(r["winStart"])
        if r[key] == gen.CLOSER_AUCTION:
            continue
        winners = hot.get(ws)
        if winners is None or r["winEnd"] != ws + size["size_ms"]:
            numbers["window_mismatch"] += 1
        elif r[key] != int(r[key]) or int(r[key]) not in winners:
            numbers["rows_extra"] += 1
        else:
            numbers["num_mismatch"] += int(
                r[cnt] != winners[int(r[key])])
    return numbers


def control(size: dict, seed: int, n_frames: int, how: str) -> dict:
    """The control of `correct`: the reference computed wrongly in the
    program's place, through the same comparison; it has to come out
    over a limit. `float32_ids`: auction ids passed through float32,
    the nearest precision below the int64 the configuration states
    (ids near 6 * 10^7 then fall on multiples of 4); `strict_gt`: of
    the auctions that tie for a window's maximum the first alone, which
    fails on a seed with a tie."""
    if how == "float32_ids":
        wrong = hot_items(
            size, seed, n_frames,
            lower=lambda a: a.astype(np.float32).astype(np.int64))
    elif how == "strict_gt":
        wrong = hot_items(size, seed, n_frames, first_only=True)
    else:
        raise ValueError(f"unknown control {how!r}")
    served = {"final": rows_from(size, wrong),
              "complete": ["before_closer", "after_closer"], "pulls": [],
              "horizon": gen.pulls(size, n_frames)["horizon"]}
    return compare(size, seed, n_frames, served)
