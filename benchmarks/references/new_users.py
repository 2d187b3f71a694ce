"""Plain numpy reference for NEXmark Q8, monitor new users: for every
tumbling window, the persons who registered in it and were named as
seller by at least one auction of the same window, each with its name
and the number of those auctions. Independent of `hstream_tpu`: both
streams are regenerated from the seed by the benchmark's own generator
(person id, name and time; auction seller and time; int64 throughout),
persons are sorted by id, every auction finds its seller's Person by one
`searchsorted`, and a pair counts where both times fall in the same
window.

The semantics are nexmark-flink's `q8.sql`: `person` grouped by `(id,
name, TUMBLE 10 s)` joined with `auction` grouped by `(seller, TUMBLE
10 s)` on `P.id = A.seller` and equal window bounds:
  * windows start at multiples of the size in absolute event time and
    hold `[start, start + size)`;
  * a row (id, name, auctions, winStart, winEnd) is in the view once the
    window has closed iff the person's Person event AND at least one
    auction naming it as seller lie in that window; `auctions` counts
    those auctions (more than q8.sql projects: it holds every pair);
  * a pair astride a boundary joins nowhere, however close in time; an
    auction may come before its seller's Person event;
  * q8.sql has no closing rule; here a window closes once event time
    (the MINIMUM over both sources' newest record) reaches its end,
    GRACE being 0. That decides when a row appears, never what it holds.

What a run can compare: the view keeps its newest `view_rows_kept`
closed rows and a window gives some 89 000, so the rows the view holds
before the closers and after them are compared (every one of them must
be right), and the newest windows that fit the view whole must be there
whole. Tier-1 holds every window of a dry run.

Numbers compared (limits in the configuration's file, all 0, exact):
  rows_missing       rows of the reference, of a window the view must
                     hold whole, that it did not give
  rows_extra         rows given, of a window of the reference, whose
                     person is not among its new users, or given twice
  name_mismatch      rows of a new user whose name is not the person's
  auctions_mismatch  rows of a new user whose count is not the
                     reference's
  window_mismatch    rows whose two bounds are no window of the
                     reference
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import nexmark_q8 as gen


def events(size: dict, seed: int, n_frames: int, *, lower=None) -> dict:
    """Both streams of the first `n_frames` frames as arrays: persons
    (`pid` ascending, `pts`, `pname`) and auctions (`seller`, `ats`).
    `lower` is the control's: a function the seller ids pass through."""
    n_p, n_a = gen.spans_of(n_frames)
    ps = [gen.persons(size, seed, k, columns=("name",))
          for k in range(n_p)]
    aus = [gen.auctions(size, seed, k, columns=("seller",))
           for k in range(n_a)]

    def cat(frames, name, dtype=np.int64):
        return (np.concatenate([f[name] for f in frames]) if frames
                else np.zeros(0, dtype))

    width = max((f["name"].dtype.itemsize for f in ps), default=1)
    seller = cat(aus, "seller")
    return {"pid": cat(ps, "id"), "pts": cat(ps, "ts"),
            "pname": (np.concatenate([f["name"].astype(f"S{width}")
                                      for f in ps]) if ps
                      else np.zeros(0, "S1")),
            "seller": seller if lower is None else lower(seller),
            "ats": cat(aus, "ts")}


def pairs(ev: dict) -> tuple:
    """(index of its seller's Person, or -1) of every auction: person
    ids ascend (a Person event a new id), so one `searchsorted`."""
    pid = ev["pid"]
    if len(pid) == 0:
        return np.full(len(ev["seller"]), -1, np.int64)
    at = np.searchsorted(pid, ev["seller"])
    at = np.minimum(at, len(pid) - 1)
    return np.where(pid[at] == ev["seller"], at, -1)


def answers(size: dict, seed: int, n_frames: int, *, lower=None,
            interval: bool = False) -> dict:
    """Every window's answer: {winStart: (ids ascending, names,
    auctions)} over the windows with a new user. `interval` is the
    control's: the interval join in the window join's place, pairs with
    `|p.ts - a.ts| <= size_ms` grouped by the window of the later
    record."""
    w = size["size_ms"]
    ev = events(size, seed, n_frames, lower=lower)
    at = pairs(ev)
    ok = at >= 0
    at, ats = at[ok], ev["ats"][ok]
    pts = ev["pts"][at]
    if interval:
        ok = np.abs(pts - ats) <= w
        win = np.maximum(pts, ats) // w
    else:
        ok = pts // w == ats // w
        win = ats // w
    at, win = at[ok], win[ok]
    # one row a (window, person): count the pairs of each
    order = np.lexsort((at, win))
    at, win = at[order], win[order]
    first = np.ones(len(at), np.bool_)
    first[1:] = (at[1:] != at[:-1]) | (win[1:] != win[:-1])
    starts = np.flatnonzero(first)
    counts = np.diff(np.append(starts, len(at)))
    at, win = at[starts], win[starts]
    out = {}
    for ws in np.unique(win).tolist():
        m = win == ws
        out[ws * w] = (ev["pid"][at[m]], ev["pname"][at[m]], counts[m])
    return out


def rows_from(size: dict, ans: dict) -> list[dict]:
    """The reference's answers as the view would give them."""
    key, name, cnt = (size["key_column"], size["name_column"],
                      size["count_column"])
    return [{key: int(i), name: n.decode(), cnt: int(c), "winStart": ws,
             "winEnd": ws + size["size_ms"]}
            for ws, (ids, names, counts) in sorted(ans.items())
            for i, n, c in zip(ids.tolist(), names.tolist(),
                               counts.tolist())]


def due(size: dict, ans: dict, cuts: list) -> set:
    """The windows the view must hold whole: for each cut (an event
    time by which windows had closed), the newest closed windows that
    fit `view_rows_kept` together."""
    must: set = set()
    for cut in cuts:
        room = size["view_rows_kept"]
        for ws in sorted((ws for ws in ans
                          if ws + size["size_ms"] <= cut), reverse=True):
            room -= len(ans[ws][0])
            if room < 0:
                break
            must.add(ws)
    return must


def compare(size: dict, seed: int, n_frames: int, served: dict,
            ans: dict | None = None) -> dict:
    key, name, cnt = (size["key_column"], size["name_column"],
                      size["count_column"])
    if ans is None:
        ans = answers(size, seed, n_frames)
    numbers = {"rows_missing": 0, "rows_extra": 0, "name_mismatch": 0,
               "auctions_mismatch": 0, "window_mismatch": 0}
    got: dict[int, int] = {}

    def hold(rows: list, final: bool) -> None:
        """Rows given against the reference's; `final`: the view's
        answers, where a row given twice is one too many and the rows
        found count towards their window's being whole (a reader's
        pulls repeat rows by nature)."""
        by_window: dict[int, list] = {}
        for r in rows:
            if r["winEnd"] > served["horizon"] \
                    or r[key] == gen.CLOSER_PERSON:
                continue  # the closers' own window: still open
            ws = int(r["winStart"])
            if ws not in ans or r["winEnd"] != ws + size["size_ms"]:
                numbers["window_mismatch"] += 1
                continue
            by_window.setdefault(ws, []).append(r)
        for ws, given_rows in by_window.items():
            ids, names, counts = ans[ws]
            given = np.array([r[key] for r in given_rows])
            whole = given == given.astype(np.int64)
            given = given.astype(np.int64)
            at = np.minimum(np.searchsorted(ids, given), len(ids) - 1)
            good = whole & (ids[at] == given)
            numbers["rows_extra"] += int((~good).sum())
            if final:
                _, first = np.unique(given, return_index=True)
                once = np.zeros(len(given), np.bool_)
                once[first] = True
                numbers["rows_extra"] += int((good & ~once).sum())
                good &= once
                got[ws] = int(good.sum())
            idx = np.flatnonzero(good)
            if len(idx) == 0:
                continue
            numbers["name_mismatch"] += sum(
                1 for i, n in zip(idx.tolist(), names[at[idx]].tolist())
                if given_rows[i][name] != n.decode())
            numbers["auctions_mismatch"] += int(
                (np.array([given_rows[i][cnt] for i in idx.tolist()])
                 != counts[at[idx]]).sum())

    hold(served["final"], True)
    hold([r for p in served["pulls"] for r in p["rows"]], False)
    # which windows had closed by each cut: all after the closers, those
    # that end at or before the query's event time before them
    cuts = []
    if "before_closer" in served["complete"]:
        cuts.append(gen.last_time(size, n_frames))
    if "after_closer" in served["complete"]:
        cuts.append(served["horizon"])
    for ws in due(size, ans, cuts):
        numbers["rows_missing"] += len(ans[ws][0]) - got.get(ws, 0)
    return numbers


def control(size: dict, seed: int, n_frames: int, how: str) -> dict:
    """The control of `correct`: the reference computed wrongly in the
    program's place, through the same comparison; it has to come out
    over a limit. `interval_join`: the interval join in the window
    join's place (pairs within `size_ms` of each other, grouped by the
    window of the later record), which differs by the pairs astride a
    boundary alone; `float32_ids`: seller ids passed through float32,
    the nearest precision below the int64 the configuration states
    (ids near 2 * 10^7 then fall on even numbers)."""
    if how == "interval_join":
        wrong = answers(size, seed, n_frames, interval=True)
    elif how == "float32_ids":
        wrong = answers(
            size, seed, n_frames,
            lower=lambda a: a.astype(np.float32).astype(np.int64))
    else:
        raise ValueError(f"unknown control {how!r}")
    right = answers(size, seed, n_frames)
    horizon = gen.pulls(size, n_frames)["horizon"]
    served = {"final": rows_from(size, wrong), "pulls": [],
              "complete": ["before_closer", "after_closer"],
              "horizon": horizon}
    # every window of the run, not only the view's newest: the control
    # has no view in its way
    numbers = compare({**size, "view_rows_kept": 1 << 62}, seed,
                      n_frames, served, ans=right)
    return numbers
