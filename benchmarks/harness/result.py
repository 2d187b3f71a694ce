"""The last line of a run: built here, and held to the driver's contract
BEFORE it is printed. A line that would be refused is never printed: the
run exits non-zero with the reasons on stderr.

The contract: the last stdout line is one JSON object with the keys
`correct`, `attempted`, `failed`, `metrics` and `device`; `metrics`
gives each metric due in this cell and mode as `{"value", "unit"}`
(`--trace 0`: the cell's end-to-end metrics, `--trace 1`: its per-layer
metrics); `device` gives `platform`, `kind`, `count`,
`memory_peak_bytes` and, traced, `window_s` and `busy_s` with
`0 < busy_s <= window_s`. Other keys are ignored by the driver; this
harness adds `breakdown` (traced) and, last, `compared`: each number the
comparison held, beside its limit.
"""

from __future__ import annotations

import json
import math
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SHARE_CAP = 100.0  # a share of a roofline or a peak cannot pass it


def build(*, correct: bool, attempted: int, failed: int, metrics: dict,
          device: dict, compared: dict, breakdown: dict | None = None
          ) -> dict:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return line


def _number(x) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def validate(line: dict, due: list[dict], *, traced: bool, chips: int,
             platform: str = "tpu") -> list[str]:
    """Every way `line` breaks the contract, for the metrics `due` (the
    manifest's entries for this cell and mode)."""
    bad = []
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        if key not in line:
            bad.append(f"key {key!r} is missing")
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not a boolean")
    for key in ("attempted", "failed"):
        v = line[key]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            bad.append(f"{key} is not a whole number >= 0")
    if not bad and line["failed"] > line["attempted"]:
        bad.append("failed exceeds attempted")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        return bad + ["metrics is not an object"]
    want = {m["name"]: m for m in due}
    for name in want:
        if name not in metrics:
            bad.append(f"metric {name!r} is due in this cell and missing")
    for name, got in metrics.items():
        if not NAME.match(name):
            bad.append(f"metric name {name!r} has a character or length "
                       "the contract does not allow")
        if name not in want:
            bad.append(f"metric {name!r} is not due in this cell and mode")
            continue
        if not isinstance(got, dict) or set(got) != {"value", "unit"}:
            bad.append(f"metric {name!r} is not {{value, unit}}")
            continue
        if not isinstance(got["unit"], str) or not UNIT.match(got["unit"]):
            bad.append(f"metric {name!r}: unit {got['unit']!r} not allowed")
        elif got["unit"] != want[name]["unit"]:
            bad.append(f"metric {name!r}: unit {got['unit']!r}, the "
                       f"manifest says {want[name]['unit']!r}")
        if not _number(got["value"]):
            bad.append(f"metric {name!r}: value {got['value']!r} is not a "
                       "finite number")
        elif (("roofline" in name or "mfu" in name)
              and not 0 < got["value"] <= SHARE_CAP):
            bad.append(f"metric {name!r}: a share of a roofline reads "
                       f"{got['value']}, outside (0, {SHARE_CAP}]")
        elif not traced and got["value"] <= 0:
            bad.append(f"end-to-end metric {name!r} reads {got['value']}")
    dev = line["device"]
    if not isinstance(dev, dict):
        return bad + ["device is not an object"]
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        if key not in dev:
            bad.append(f"device.{key} is missing")
    if not bad:
        if dev["platform"] != platform:
            bad.append(f"device.platform is {dev['platform']!r}, not "
                       f"{platform!r}")
        if not isinstance(dev["kind"], str) or not dev["kind"]:
            bad.append("device.kind is empty")
        if dev["count"] != chips:
            bad.append(f"device.count is {dev['count']}, the cell asks "
                       f"for {chips}")
        peak = dev["memory_peak_bytes"]
        if (not isinstance(peak, int) or isinstance(peak, bool) or peak < 0
                or (platform == "tpu" and peak == 0)):
            bad.append(f"device.memory_peak_bytes is {peak!r}")
    if traced:
        w, b = dev.get("window_s"), dev.get("busy_s")
        if not _number(w) or not _number(b):
            bad.append("a traced run needs device.window_s and "
                       "device.busy_s")
        elif not 0 < b <= w:
            bad.append(f"device.busy_s {b} is not above 0 and at most "
                       f"device.window_s {w}")
    if "breakdown" in line:
        br = line["breakdown"]
        for key in ("device_ops", "idle_gaps"):
            rows = br.get(key) if isinstance(br, dict) else None
            if not isinstance(rows, list) or len(rows) > 10 or any(
                    not (isinstance(r, list) and len(r) == 2
                         and isinstance(r[0], str) and _number(r[1]))
                    for r in rows):
                bad.append(f"breakdown.{key} is not a list of at most 10 "
                           "[name, seconds]")
    if list(line)[-1] != "compared" and "compared" in line:
        bad.append("compared is not the last key")
    return bad


def emit(line: dict, due: list[dict], *, traced: bool, chips: int,
         platform: str = "tpu", out=None, err=None) -> int:
    """Print the compared numbers on stderr, then the line on stdout as
    its last line: only if it passes. Returns the exit code."""
    out = sys.stdout if out is None else out
    err = sys.stderr if err is None else err
    bad = validate(line, due, traced=traced, chips=chips,
                   platform=platform)
    if bad:
        print("result line refused by benchmarks/harness/result.py:",
              file=err)
        for b in bad:
            print(f"  - {b}", file=err)
        err.flush()
        return 3
    for name, pair in (line.get("compared") or {}).items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})",
              file=err)
    print(f"correct: {str(line['correct']).lower()}", file=err)
    err.flush()
    print(json.dumps(line), file=out)
    out.flush()
    return 0
