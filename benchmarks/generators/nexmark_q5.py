"""The seeded NEXmark Bid stream for query 5, hot items: the frames are
`generators/nexmark.py`'s (`draw`, its rules and its departures from
Beam, all stated there and under `assumed` in the configuration), one
function of (size, seed, frame index), so the producer's encoder
processes and the reference regenerate the same frames without sharing
a byte. A configuration names it with `"generator": "nexmark_q5"`; the
duties are listed in `benchmarks/README.md`.

What is this deployment's own: the clock is stated as a density
(`density.events` events of all kinds per `density.per_ms` ms), the
query's window is `size_ms` every `advance_ms`, the warm phase is a
number of window sizes, and the closer and the pulls are those of a
hopping window: a window is closed once the watermark (the newest event
time consumed) reaches its end, GRACE being 0.
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators.nexmark import BASE, draw, events_of, times_of

KINDS = ("bid",)
COLUMNS = ("bidder", "auction", "price", "extra")
CLOSER_AUCTION = 0  # under FIRST_AUCTION_ID: no auction's id


def clock(size: dict) -> dict:
    """The sizes as `generators/nexmark.py` reads them: its clock is
    `events_per_gap` events per `gap_ms`."""
    return {**size, "gap_ms": size["density"]["per_ms"],
            "events_per_gap": size["density"]["events"]}


def streams(size: dict) -> list[dict]:
    """The streams to create, each with the schema its frames carry."""
    kinds = list(size["streams"])
    if any(k not in KINDS for k in kinds):
        raise ValueError(f"streams {kinds}: this generator sends "
                         f"{list(KINDS)} only")
    return [{"name": k, "schema": dict(size["schemas"][k])}
            for k in kinds]


def bids(size: dict, seed: int, index: int,
         columns: tuple = COLUMNS) -> dict:
    """Frame `index` as int64 arrays: `event`, `ts` and `columns`."""
    return draw(clock(size), seed, index, columns=columns)


def warm_frames(size: dict) -> int:
    """Frames of the warm phase (whole calls): the bids of the first
    `warm_windows` window sizes of event time. Past one size the first
    group keys have died, so set-up sees a full live set, several
    closes, the key table's first retirement and its final capacity."""
    r = size["nexmark"]
    d = size["density"]
    events = int(np.ceil(size["warm_windows"] * size["size_ms"]
                         * d["events"] / d["per_ms"]))
    n_bids = -(-events * r["bid_proportion"]
               // r["proportion_denominator"])
    per_call = size["frames_per_call"]
    n = -(-n_bids // size["frame_rows"])
    return -(-n // per_call) * per_call


def frame(size: dict, seed: int, index: int) -> tuple:
    """Frame `index` as it is sent: (the stream it goes to, ts, cols as
    the client library's `encode_batch` takes them, the events it
    carries): the four Bid columns, 99.5 B a bid."""
    d = bids(size, seed, index)
    return ("bid", d["ts"], {c: d[c] for c in COLUMNS},
            size["frame_rows"])


def last_time(size: dict, n_frames: int) -> int:
    """Event time of the last bid of the first `n_frames` frames: the
    query's watermark once it has consumed them."""
    c = clock(size)
    last = np.array([n_frames * size["frame_rows"] - 1], np.int64)
    return int(times_of(c, events_of(c, last))[0])


def closer_time(size: dict, n_frames: int) -> int:
    """Where the closer lies: a window size plus a slide past the last
    bid, past the end of every window a bid of the frames is in."""
    return last_time(size, n_frames) + size["size_ms"] \
        + size["advance_ms"]


def closers(size: dict, n_frames: int) -> list[tuple]:
    """One bid of an auction no auction has, in a frame's form, far
    enough past the last bid to close every window the frames left
    open; its own windows stay open and are not compared."""
    one = np.array([0], np.int64)
    return [("bid", np.array([closer_time(size, n_frames)], np.int64),
             {"bidder": one, "auction": one + CLOSER_AUCTION,
              "price": one, "extra": np.array([b""], "S1")}, 1)]


def pulls(size: dict, n_frames: int) -> dict:
    """The answers to pull once `n_frames` frames are consumed: every
    row of a closed window, before the closer and after it. With GRACE
    0 a window is closed once the watermark reaches its end, so the
    bound `winEnd <= watermark` keeps the pull to the view's closed
    rows; `complete` names the cut, and the reference says which
    windows had closed by then (all of them, after the closer)."""
    sql = "SELECT * FROM {} WHERE winEnd <= {};"
    return {
        "before": [{"sql": sql.format(size["view"],
                                      last_time(size, n_frames)),
                    "complete": ["before_closer"]}],
        "after": [{"sql": sql.format(size["view"],
                                     closer_time(size, n_frames)),
                   "complete": ["after_closer"]}],
        "horizon": closer_time(size, n_frames),
    }


def reader_pull(size: dict, rng: np.random.Generator) -> dict:
    """One draw of a reader: the hot items of one window of the warm
    phase's first window size. No accepted cell has a reader over this
    deployment."""
    adv = size["advance_ms"]
    start = BASE - BASE % adv + int(rng.integers(
        0, size["size_ms"] // adv)) * adv
    return {"sql": f"SELECT * FROM {size['view']} WHERE winStart = "
                   f"{start};", "winStart": start}
