"""Work a window join has to do, from the configuration alone: the bytes
one batch's rows make the device move, whatever implements the join
(never the program's sorts, its stores' capacity or its buffers'
padding), and the least time the chip could take for them.
`harness/rooflines.py` is the window lattice's; the state bytes and the
peaks are its.

A step, per row of the batch: its join-key code and its time relative
to the epoch read once, an i32 each (4 + 4), and the row written once
into its side's store (code, time and the flags word, 4 + 4 + 4; the
plan stores no column of either side: the query counts). Per matched
pair: the stored row's code and time read once (4 + 4) and the read and
write of the inner aggregate's cell (`rooflines.STATE_BYTES`: COUNT an
i32, 4 + 4). How many pairs a row makes is the source's: every auction
names one seller, who registered in the auction's window in all but the
few hundred cases a window that lie astride its start, and a person
matches as a stored row, so a batch's pairs are its auctions at most:
`auction_proportion` of every `person_proportion + auction_proportion`
rows. A step runs one frame: a span's persons or its auctions, the
mean of the two a run.

An eviction reads and writes each surviving row once (12 + 12 B): the
rows of the open windows, which at a close are what the sources' skew
has put past the boundary, one frame of each stream at most. A sort
does a few comparisons a row and no arithmetic worth a roof: both bounds
are memory.
"""

from __future__ import annotations

from benchmarks.harness import rooflines

KEY_BYTES = 8     # join-key code i32 + relative time i32
ROW_BYTES = 12    # code, time, flags word: a stored row


def _sent(config: dict) -> tuple[int, int]:
    r = config["nexmark"]
    return r["person_proportion"], r["auction_proportion"]


def rows_per_step(config: dict) -> float:
    """Rows of one run of the step program: a span's persons or its
    auctions, each a frame, the mean of the two."""
    p, a = _sent(config)
    return config["span_epochs"] * (p + a) / 2.0


def step_bytes_per_row(config: dict) -> float:
    p, a = _sent(config)
    pairs = a / (p + a)
    state = sum(rooflines.STATE_BYTES[x] for x in config["aggregates"])
    return KEY_BYTES + ROW_BYTES + pairs * (KEY_BYTES + state)


def least_step_seconds(config: dict, runs: float, peak: dict) -> float:
    """The least time the chip could take for `runs` runs of the step
    program of this configuration: memory-bound."""
    return (runs * rows_per_step(config) * step_bytes_per_row(config)
            / peak["hbm_bytes_per_s"])


def least_evict_seconds(config: dict, runs: float, peak: dict) -> float:
    """The least time the chip could take for `runs` evictions: each
    reads and writes the rows that survive it."""
    p, a = _sent(config)
    surviving = config["span_epochs"] * (p + a)
    return runs * surviving * 2 * ROW_BYTES / peak["hbm_bytes_per_s"]
