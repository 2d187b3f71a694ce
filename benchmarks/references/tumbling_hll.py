"""Plain numpy reference for COUNT / SUM / APPROX_COUNT_DISTINCT per key
over tumbling windows. Independent of `hstream_tpu`: frames come from the
benchmark's own generator, the HyperLogLog is written out here (copied
from `chip_smoke.py` `hll_reference`, p = 10, murmur3 finalizer over the
f32 bits).

`answers()` gives, for whole panes, what a correct system returns;
`compare()` holds served rows to it. A served row of an OPEN window is
held to a prefix of that pane's frames: frames are consumed in order and
one at a time, so its count must be the count after some whole number of
frames, and its sum and sketch those of the same prefix.

Numbers compared (limits in the configuration's file):
  rows_missing   expected (key, window) rows absent, duplicated or unknown
  cnt_mismatch   rows whose count equals no prefix / differs from the pane
  sum_rel_err    max |total - f64 sum| / sum|x| over rows
  uniq_abs_err   max |uniq - reference HLL estimate| over rows
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import sensor as gen
from benchmarks.references._rows import lower, match

P = 10


def hll_registers(kids: np.ndarray, temps: np.ndarray, n_keys: int,
                  regs: np.ndarray | None = None) -> np.ndarray:
    m = 1 << P
    h = np.where(temps == 0.0, np.float32(0.0), temps).view(np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    reg = (h >> np.uint32(32 - P)).astype(np.int64)
    rest = h << np.uint32(P)
    clz = 32 - np.frexp(rest.astype(np.float64))[1]
    rank = np.minimum(clz + 1, 32 - P + 1).astype(np.int8)
    if regs is None:
        regs = np.zeros(n_keys * m, np.int8)
    np.maximum.at(regs, kids.astype(np.int64) * m + reg, rank)
    return regs


def hll_estimate(regs: np.ndarray, n_keys: int) -> np.ndarray:
    m = 1 << P
    regs = regs.reshape(n_keys, m)
    inv = np.exp2(-np.arange(64, dtype=np.float64))
    est = np.empty(n_keys, np.float64)
    alpha = 0.7213 / (1 + 1.079 / m)
    for lo in range(0, n_keys, 8192):
        r = regs[lo:lo + 8192]
        raw = alpha * m * m / inv[r].sum(axis=1)
        zeros = (r == 0).sum(axis=1)
        linear = m * np.log(m / np.maximum(zeros, 1))
        est[lo:lo + 8192] = np.where((raw <= 2.5 * m) & (zeros > 0),
                                     linear, raw)
    return est


def _frames(size: dict, seed: int, n_frames: int):
    for i in range(n_frames):
        yield i, gen.pane_of(size, i), gen.draw(size, seed, i)


def answers(size: dict, seed: int, n_frames: int, panes: set[int],
            precision: str = "f32") -> dict[int, dict]:
    """Per pane in `panes`: per-key cnt, f64 total, sum|x| and the HLL
    estimate, over every frame of the stream that lies in that pane."""
    n_keys = size["keys"]
    out = {p: {"cnt": np.zeros(n_keys, np.int64),
               "total": np.zeros(n_keys), "abs": np.zeros(n_keys),
               "regs": None} for p in panes}
    for _i, pane, (kids, _tenths, temps, _ts) in _frames(size, seed,
                                                        n_frames):
        if pane not in out:
            continue
        acc = out[pane]
        t64 = lower(temps, precision).astype(np.float64)
        acc["cnt"] += np.bincount(kids, minlength=n_keys)
        acc["total"] += np.bincount(kids, weights=t64, minlength=n_keys)
        acc["abs"] += np.bincount(kids, weights=np.abs(t64),
                                  minlength=n_keys)
        acc["regs"] = hll_registers(kids, lower(temps, precision),
                                    n_keys, acc["regs"])
    for acc in out.values():
        regs = acc.pop("regs")
        acc["uniq"] = (hll_estimate(regs, n_keys) if regs is not None
                       else np.zeros(n_keys))
    return out


def rows_from(size: dict, names: np.ndarray, ref: dict[int, dict]) -> list:
    """The reference's answers as served rows (the control's way in)."""
    cols = size["result_columns"]
    rows = []
    for pane, acc in ref.items():
        start = gen.BASE + pane * size["advance_ms"]
        for k in np.flatnonzero(acc["cnt"]):
            rows.append({size["columns"][0]: str(names[k]),
                         "winStart": start,
                         "winEnd": start + size["size_ms"],
                         cols["cnt"]: int(acc["cnt"][k]),
                         cols["total"]: float(acc["total"][k]),
                         cols["uniq"]: float(np.rint(acc["uniq"][k]))})
    return rows


def _prefixes(size: dict, seed: int, n_frames: int, keys: set[int]):
    """For each pulled key: (pane, frame index, value) of every one of
    its events, in frame order."""
    sel = np.zeros(size["keys"], bool)
    sel[list(keys)] = True
    got: dict[int, list] = {k: [] for k in keys}
    for i, pane, (kids, _tenths, temps, _ts) in _frames(size, seed,
                                                       n_frames):
        hit = np.flatnonzero(sel[kids])
        for j in hit:
            got[int(kids[j])].append((pane, i, float(temps[j])))
    return got


def compare(size: dict, seed: int, n_frames: int, served: dict) -> dict:
    """`served`: {"final": rows of whole-view pulls, "complete": panes
    whose every key must be there, "pulls": [{"key": id, "rows": [...]}],
    "horizon": event time at and past which a window is still open}."""
    cols = size["result_columns"]
    key_col = size["columns"][0]
    adv = size["advance_ms"]
    names = gen.key_names(size)
    lookup = {n: i for i, n in enumerate(names.tolist())}
    numbers = {"rows_missing": 0, "cnt_mismatch": 0, "sum_rel_err": 0.0,
               "uniq_abs_err": 0.0}

    closed = [r for r in served["final"] if r["winEnd"] <= served["horizon"]]
    panes = {int((r["winStart"] - gen.BASE) // adv) for r in closed}
    panes |= set(served["complete"])
    ref = answers(size, seed, n_frames, panes)
    seen: dict[int, set] = {p: set() for p in panes}
    for p in panes:
        rows = [r for r in closed
                if (r["winStart"] - gen.BASE) // adv == p]
        if not rows:
            continue
        idx, rows, bad = match(rows, lookup, key_col)
        numbers["rows_missing"] += bad
        seen[p] |= set(idx.tolist())
        acc = ref[p]
        cnt = np.array([r[cols["cnt"]] for r in rows], np.int64)
        total = np.array([r[cols["total"]] for r in rows], np.float64)
        uniq = np.array([r[cols["uniq"]] for r in rows], np.float64)
        numbers["cnt_mismatch"] += int((cnt != acc["cnt"][idx]).sum())
        numbers["cnt_mismatch"] += int(sum(
            r["winEnd"] != r["winStart"] + size["size_ms"] for r in rows))
        if len(idx):
            rel = (np.abs(total - acc["total"][idx])
                   / np.maximum(acc["abs"][idx], 1e-30))
            numbers["sum_rel_err"] = max(numbers["sum_rel_err"],
                                         float(rel.max()))
            numbers["uniq_abs_err"] = max(
                numbers["uniq_abs_err"],
                float(np.abs(uniq - acc["uniq"][idx]).max()))
    for p in served["complete"]:
        want = set(np.flatnonzero(ref[p]["cnt"]).tolist())
        numbers["rows_missing"] += len(want - seen.get(p, set()))

    pulls = served.get("pulls") or []
    keys = {p["key"] for p in pulls}
    events = _prefixes(size, seed, n_frames, keys) if keys else {}
    for pull in pulls:
        ev = events[pull["key"]]
        for r in pull["rows"]:
            if r[key_col] != names[pull["key"]]:
                numbers["rows_missing"] += 1
                continue
            pane = int((r["winStart"] - gen.BASE) // adv)
            mine = [(i, v) for p, i, v in ev if p == pane]
            # the count after each whole frame of this pane
            n = int(r[cols["cnt"]])
            take = None
            for j in range(len(mine)):
                last = j + 1 == len(mine) or mine[j + 1][0] != mine[j][0]
                if last and j + 1 == n:
                    take = j + 1
                    break
            if take is None:
                numbers["cnt_mismatch"] += 1
                continue
            vals = np.array([v for _i, v in mine[:take]], np.float32)
            v64 = vals.astype(np.float64)
            rel = abs(r[cols["total"]] - v64.sum()) / max(
                np.abs(v64).sum(), 1e-30)
            numbers["sum_rel_err"] = max(numbers["sum_rel_err"],
                                         float(rel))
            est = hll_estimate(hll_registers(
                np.zeros(len(vals), np.int64), vals, 1), 1)[0]
            numbers["uniq_abs_err"] = max(
                numbers["uniq_abs_err"], float(abs(r[cols["uniq"]] - est)))
    return numbers
