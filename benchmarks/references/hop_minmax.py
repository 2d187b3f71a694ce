"""Plain numpy reference for AVG / MIN / MAX per key over hopping
windows. Independent of `hstream_tpu`: frames come from the benchmark's
own generator. Values are decimals of a small integer range, so one
histogram per pane, [key, value-in-tenths] -> count, carries everything:
a window's count, exact f64 sum, minimum and maximum are read off the
sum of its panes' histograms.

Numbers compared (limits in the configuration's file):
  rows_missing     expected (key, window) rows absent, duplicated, unknown
  minmax_mismatch  rows whose MIN or MAX is not the reference's f32 value
  avg_rel_err      max |avg - f64 mean| / mean|x| over rows
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import sensor as gen
from benchmarks.references._rows import lower, match


def value_range(size: dict) -> tuple[int, int]:
    """(lowest value, number of values) the histogram covers, in units
    of the last decimal: ten standard deviations either side."""
    val = size["value"]
    scale = 10 ** int(val["decimals"])
    lo = int((val["mean"] - 10 * val["std"]) * scale)
    return lo, int(20 * val["std"] * scale) + 1


def pane_histograms(size: dict, seed: int, n_frames: int) -> dict:
    n_keys = size["keys"]
    lo0, span = value_range(size)
    hists: dict[int, np.ndarray] = {}
    for i in range(n_frames):
        kids, tenths, _temps, _ts = gen.draw(size, seed, i)
        if tenths.min() < lo0 or tenths.max() >= lo0 + span:
            raise ValueError("value outside the reference's histogram")
        flat = kids.astype(np.int64) * span + (tenths - lo0)
        h = np.bincount(flat, minlength=n_keys * span)
        pane = gen.pane_of(size, i)
        if pane in hists:
            hists[pane] += h
        else:
            hists[pane] = h
    return {p: h.reshape(n_keys, span) for p, h in hists.items()}


def answers(size: dict, seed: int, n_frames: int,
            precision: str = "f32") -> dict[int, dict]:
    """Per hopping window (indexed by its first pane, which may be
    negative): per-key cnt, avg, mean|x|, lo, hi."""
    hists = pane_histograms(size, seed, n_frames)
    scale = 10 ** int(size["value"]["decimals"])
    lo0, span = value_range(size)
    f32 = (np.arange(lo0, lo0 + span).astype(np.float32)
           * np.float32(1.0 / scale))
    f32 = lower(f32, precision)
    vals = f32.astype(np.float64)
    width = size["size_ms"] // size["advance_ms"]
    out = {}
    for m in range(min(hists) - width + 1, max(hists) + 1):
        parts = [hists[p] for p in range(m, m + width) if p in hists]
        if not parts:
            continue
        h = np.sum(parts, axis=0)
        cnt = h.sum(axis=1)
        has = h > 0
        first = has.argmax(axis=1)
        last = span - 1 - has[:, ::-1].argmax(axis=1)
        safe = np.maximum(cnt, 1)
        if precision == "f32":
            lo, hi = f32[first], f32[last]
        else:  # rounding can reorder neighbours: take the extremes
            big = np.where(has, f32[None, :], np.float32(np.inf))
            lo = big.min(axis=1)
            hi = np.where(has, f32[None, :], np.float32(-np.inf)).max(axis=1)
        out[m] = {"cnt": cnt, "avg": (h @ vals) / safe,
                  "mean_abs": (h @ np.abs(vals)) / safe, "lo": lo, "hi": hi}
    return out


def rows_from(size: dict, names: np.ndarray, ref: dict[int, dict]) -> list:
    cols = size["result_columns"]
    rows = []
    for m, acc in ref.items():
        start = gen.BASE + m * size["advance_ms"]
        for k in np.flatnonzero(acc["cnt"]):
            rows.append({size["columns"][0]: str(names[k]),
                         "winStart": start,
                         "winEnd": start + size["size_ms"],
                         cols["avg"]: float(acc["avg"][k]),
                         cols["lo"]: float(acc["lo"][k]),
                         cols["hi"]: float(acc["hi"][k])})
    return rows


def compare(size: dict, seed: int, n_frames: int, served: dict) -> dict:
    cols = size["result_columns"]
    key_col = size["columns"][0]
    adv = size["advance_ms"]
    names = gen.key_names(size)
    lookup = {n: i for i, n in enumerate(names.tolist())}
    numbers = {"rows_missing": 0, "minmax_mismatch": 0, "avg_rel_err": 0.0}
    ref = answers(size, seed, n_frames)
    closed = [r for r in served["final"] if r["winEnd"] <= served["horizon"]]
    by_win: dict[int, list] = {}
    for r in closed:
        by_win.setdefault(int((r["winStart"] - gen.BASE) // adv),
                          []).append(r)
    seen: dict[int, set] = {}
    for m, rows in by_win.items():
        acc = ref.get(m)
        if acc is None:
            numbers["rows_missing"] += len(rows)
            continue
        idx, rows, bad = match(rows, lookup, key_col)
        numbers["rows_missing"] += bad
        numbers["rows_missing"] += int((acc["cnt"][idx] == 0).sum())
        seen[m] = set(idx.tolist())
        lo = np.array([r[cols["lo"]] for r in rows], np.float64)
        hi = np.array([r[cols["hi"]] for r in rows], np.float64)
        avg = np.array([r[cols["avg"]] for r in rows], np.float64)
        numbers["minmax_mismatch"] += int(
            ((lo != acc["lo"][idx].astype(np.float64))
             | (hi != acc["hi"][idx].astype(np.float64))).sum())
        numbers["minmax_mismatch"] += int(sum(
            r["winEnd"] != r["winStart"] + size["size_ms"] for r in rows))
        if len(idx):
            rel = (np.abs(avg - acc["avg"][idx])
                   / np.maximum(acc["mean_abs"][idx], 1e-30))
            numbers["avg_rel_err"] = max(numbers["avg_rel_err"],
                                         float(rel.max()))
    for m in served["complete"]:
        want = set(np.flatnonzero(ref[m]["cnt"]).tolist()) \
            if m in ref else set()
        numbers["rows_missing"] += len(want - seen.get(m, set()))
    return numbers
