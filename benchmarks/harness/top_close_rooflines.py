"""Work a top close has to do, from the configuration and the windows
it closed: the bytes one closing window makes the device move, whatever
implements the close (never the program's buffers, the table's
capacity or a plane's padding), and the least time the chip could take
for them. `harness/rooflines.py` is the step's; the state bytes and the
peaks are its.

Per group that held a count in the closing slot (the device's own
reduce counts them: `close_stats["close_groups"]`), each aggregate's
cell is read once, to finalize it and to take the window's extreme, and
its reset is written: `rooflines.STATE_BYTES` gives both together
(COUNT an i32: 4 + 4). The rows that survive are a handful beside that.
A reduce does a compare a group and no arithmetic worth a roof: the
bound is memory.
"""

from __future__ import annotations

from benchmarks.harness import rooflines


def close_bytes_per_group(config: dict) -> int:
    return sum(rooflines.STATE_BYTES[a] for a in config["aggregates"])


def least_close_seconds(config: dict, groups: float, peak: dict) -> float:
    """The least time the chip could take to close windows that held
    `groups` groups between them: memory-bound."""
    return groups * close_bytes_per_group(config) / peak["hbm_bytes_per_s"]
