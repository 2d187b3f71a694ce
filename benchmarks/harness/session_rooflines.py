"""Work a session step has to do, from the configuration alone: the
bytes one event makes the device move, whatever implements the step
(never the program's buffers, its sort or its arena's padding), and the
least time the chip could take for them. `harness/rooflines.py` is the
window lattice's; the peaks are its `peaks`.

Per event: its packed record read once, a key code and a time relative
to the epoch, an i32 each (4 + 4); and the read and write of the session
cell it lands in: the two bounds of the session, i32 each (2 x (4 + 4)),
and per aggregate the state `rooflines.STATE_BYTES` gives (COUNT an
i32: 4 + 4). A session that a batch touches many times is counted once
an event, so a step that folds a batch's events before it touches the
arena cannot read over 100%. Sorting does a few comparisons an event
and no arithmetic worth a roof: the bound is memory.
"""

from __future__ import annotations

from benchmarks.harness import rooflines

RECORD_BYTES = 8   # key code i32 + relative time i32, read once
BOUNDS_BYTES = 16  # t0 and t1, i32 each, read and written


def step_bytes_per_event(config: dict) -> int:
    return RECORD_BYTES + BOUNDS_BYTES + sum(
        rooflines.STATE_BYTES[a] for a in config["aggregates"])


def least_step_seconds(config: dict, events: float, peak: dict) -> float:
    """The least time the chip could take to step `events` events of
    this configuration: memory-bound."""
    return events * step_bytes_per_event(config) / peak["hbm_bytes_per_s"]
