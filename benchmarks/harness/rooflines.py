"""Work a lattice step has to do, from the configuration alone: the
bytes of aggregate state one event makes the device read and write,
whatever implements the step (never the program's buffers or codec), and
the least time the chip could take for them.

Per event and aggregate, read + write of the state cell it lands in:
COUNT an i32 (4 + 4); SUM an f32 (4 + 4); APPROX_COUNT_DISTINCT one HLL
register, an i8 (1 + 1); AVG a sum and a count (16); MIN, MAX an f32
each (8). A hopping window counts the ONE pane an event falls in, not
every window over it, so a pane-based step cannot read over 100%.
The step is a scatter: a few integer operations per event, so the bound
is memory, and the flop term is kept only to say which bound binds.
"""

from __future__ import annotations

import json
import os

STATE_BYTES = {"COUNT": 8, "SUM": 8, "APPROX_COUNT_DISTINCT": 2,
               "AVG": 16, "MIN": 8, "MAX": 8}
OPS_PER_EVENT = {"COUNT": 1, "SUM": 1, "APPROX_COUNT_DISTINCT": 12,
                 "AVG": 2, "MIN": 1, "MAX": 1}


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}")
    return table[device_kind]


def step_bytes_per_event(config: dict) -> int:
    return sum(STATE_BYTES[a] for a in config["aggregates"])


def step_ops_per_event(config: dict) -> int:
    return sum(OPS_PER_EVENT[a] for a in config["aggregates"])


def least_step_seconds(config: dict, events: int, peak: dict) -> dict:
    """The least time the chip could take to step `events` events, and
    which roof gives it."""
    mem = events * step_bytes_per_event(config) / peak["hbm_bytes_per_s"]
    ops = events * step_ops_per_event(config) / peak["int8_ops_per_s"]
    return {"seconds": max(mem, ops),
            "bound": "memory" if mem >= ops else "compute"}
