"""The toy generator's own reference: a loop over every event, one
dict entry per (site, window). Numbers compared (limits in the toy
configuration's file):
  rows_missing    expected (site, window) rows absent, doubled, unknown
  hits_mismatch   rows whose COUNT is not the loop's
  bytes_mismatch  rows whose SUM or MAX is not the loop's
  pull_mismatch   pulled rows of another site than drawn, or holding
                  more than the window's final answer
"""

from __future__ import annotations

from benchmarks.harness import manifest


def answers(size: dict, seed: int, n_frames: int) -> dict:
    gen = manifest.generator_of(size)
    names = gen.site_names(size).tolist()
    adv = size["advance_ms"]
    out: dict = {}
    for i in range(n_frames):
        kids, nbytes, ts = gen.draw(size, seed, i)
        for k, b, t in zip(kids.tolist(), nbytes.tolist(), ts.tolist()):
            key = (names[k], (t - gen.T0) // adv)
            hits, total, top = out.get(key, (0, 0, -1))
            out[key] = (hits + 1, total + b, max(top, b))
    return out


def compare(size: dict, seed: int, n_frames: int, served: dict) -> dict:
    gen = manifest.generator_of(size)
    adv = size["advance_ms"]
    ref = answers(size, seed, n_frames)
    numbers = {"rows_missing": 0, "hits_mismatch": 0, "bytes_mismatch": 0,
               "pull_mismatch": 0}
    seen = set()
    for r in served["final"]:
        if r["winEnd"] > served["horizon"]:
            continue
        key = (r["site"], (r["winStart"] - gen.T0) // adv)
        if key not in ref or key in seen:
            numbers["rows_missing"] += 1
            continue
        seen.add(key)
        hits, total, top = ref[key]
        numbers["hits_mismatch"] += int(r["hits"] != hits)
        numbers["bytes_mismatch"] += int(r["total"] != total
                                         or r["top"] != top)
    due = {key for key in ref if key[1] in set(served["complete"])}
    numbers["rows_missing"] += len(due - seen)
    for pull in served.get("pulls") or []:
        for r in pull["rows"]:
            key = (r["site"], (r["winStart"] - gen.T0) // adv)
            hits, total, top = ref.get(key, (0, 0, -1))
            if (r["site"] != pull["site"] or r["hits"] > hits
                    or r["total"] > total or r["top"] > top):
                numbers["pull_mismatch"] += 1
    return numbers
