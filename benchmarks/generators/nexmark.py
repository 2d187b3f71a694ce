"""The seeded NEXmark event log, Bid stream: one function of (size,
seed, frame index), so the producer's encoder processes and the
reference after the window regenerate the same frames without sharing a
byte. A configuration names it with `"generator": "nexmark"`; the duties
(`streams`, `warm_frames`, `frame`, `closers`, `pulls`, `reader_pull`)
are listed in `benchmarks/README.md`. The reference reads `draw`.

The rules are those of Apache Beam's NEXmark generator
(`sdks/java/testing/nexmark`, `sources/generator/`) at
`NexmarkConfiguration`'s defaults, stated as sizes in the
configuration's `nexmark` block:

  numbering   event `e` is a Person where `e % 50 < 1`, an Auction where
              `e % 50 < 4`, a Bid otherwise (1 : 3 : 46 of 50)
  time        event `e` at `BASE + (e - first_event) * gap_ms //
              events_per_gap` ms, in order (`outOfOrderGroupSize` 1)
  persons     the last person before `e` is `e // 50` (`lastBase0PersonId`)
  bidder      3 draws of 4 (`hotBiddersRatio` 4: `nextInt(4) > 0`) the
              hot bidder, `(last person // 100) * 100 + 1`; else uniform
              over the last `numActivePeople` = 1 000 persons plus a
              lead of 10 (`nextBase0PersonId`); `+ FIRST_PERSON_ID`
  auction     1 draw of 2 (`hotAuctionRatio` 2) the hot auction,
              `(last auction // 100) * 100`, the last auction being
              `(e // 50) * 3 + 2`; else uniform over the last
              `numInFlightAuctions` = 100 plus a lead of 10;
              `+ FIRST_AUCTION_ID`
  price       `round(10 ** (6 u) * 100)`, u uniform in [0, 1)
  extra       lower-case letters, Beam's `nextExtra`: the record's four
              longs take 32 B of `avgBidByteSize` = 100, the rest is 68,
              so a length uniform in [54, 82): 99.5 B a bid on average

Departures, all under `assumed` in the configuration: numpy's PCG64
draws stand where Java's `Random` stands in Beam, one generator a frame,
seeded with (seed, 1, frame index); `first_event` is not 0 (a log that
has run for a while, so person ids lie past 2^24); the rate is the
configuration's `events_per_gap`, which NEXmark leaves free.

Event numbers run over all three kinds, so a bid's ids and its time are
those of the whole log; the configuration's `streams` names the kinds
that are sent. Only `bid` is made here: Q11 reads nothing else, and a
Person or an Auction keeps its place in the numbering and is not sent.
Frame `i` holds the bids of ordinal `i * frame_rows` up to
`(i + 1) * frame_rows`: the event time a frame spans is the same for
every seed, only the draws inside it differ.
"""

from __future__ import annotations

import numpy as np

BASE = 1_700_000_000_000  # absolute epoch ms of event `first_event`
KINDS = ("bid",)          # the kinds this module makes frames of
CLOSER_BIDDER = 0         # under FIRST_PERSON_ID: no person's id
# the order of the draws inside a frame: a reader that needs the first
# columns stops early and still sees the values a full frame carries
COLUMNS = ("bidder", "auction", "price", "extra")


def _rules(size: dict) -> dict:
    r = size["nexmark"]
    if r["person_proportion"] + r["auction_proportion"] \
            + r["bid_proportion"] != r["proportion_denominator"]:
        raise ValueError("the proportions do not add up to the "
                         "denominator")
    if size["first_event"] % r["proportion_denominator"]:
        raise ValueError("first_event must start an epoch of the "
                         "numbering")
    return r


def streams(size: dict) -> list[dict]:
    """The streams to create, each with the schema its frames carry."""
    kinds = list(size["streams"])
    if any(k not in KINDS for k in kinds):
        raise ValueError(f"streams {kinds}: this generator sends "
                         f"{list(KINDS)} only")
    return [{"name": k, "schema": dict(size["schemas"][k])}
            for k in kinds]


def events_of(size: dict, ordinals: np.ndarray) -> np.ndarray:
    """Event number (over all kinds) of the bids of these ordinals."""
    r = _rules(size)
    first_bid = r["person_proportion"] + r["auction_proportion"]
    epoch, off = np.divmod(np.asarray(ordinals, np.int64),
                           r["bid_proportion"])
    return (size["first_event"] + epoch * r["proportion_denominator"]
            + first_bid + off)


def times_of(size: dict, events: np.ndarray) -> np.ndarray:
    """Event time of these event numbers, absolute epoch ms."""
    return BASE + ((events - size["first_event"]) * size["gap_ms"]
                   // size["events_per_gap"])


def warm_frames(size: dict) -> int:
    """Frames of the warm phase (whole calls): the bids of the first
    `warm_gaps` gaps of event time. Past two gaps the first sessions
    have closed, so the arena, the close cycle and the code dictionary
    are at the sizes the window will see."""
    r = _rules(size)
    events = int(np.ceil(size["warm_gaps"] * size["events_per_gap"]))
    bids = -(-events * r["bid_proportion"] // r["proportion_denominator"])
    per_call = size["frames_per_call"]
    n = -(-bids // size["frame_rows"])
    return -(-n // per_call) * per_call


def draw(size: dict, seed: int, index: int,
         columns: tuple = COLUMNS) -> dict:
    """Frame `index` of the Bid stream for `seed` as int64 arrays (and
    `extra` as fixed-width bytes): `event`, `ts` and, drawn in the order
    of `COLUMNS`, those of `columns` (a prefix of it in effect: a later
    column never changes an earlier one)."""
    r = _rules(size)
    n = size["frame_rows"]
    event = events_of(size, np.arange(index * n, (index + 1) * n,
                                      dtype=np.int64))
    out = {"event": event, "ts": times_of(size, event)}
    rng = np.random.default_rng([int(seed), 1, int(index)])
    epoch = event // r["proportion_denominator"]
    stop = max((COLUMNS.index(c) + 1 for c in columns), default=0)
    for name in COLUMNS[:stop]:
        if name == "bidder":
            last_person = epoch * r["person_proportion"] \
                + r["person_proportion"] - 1
            people = last_person + 1
            active = np.minimum(people, r["num_active_people"])
            hot = rng.integers(0, r["hot_bidders_ratio"], n) > 0
            cold = people - active + rng.integers(
                0, active + r["person_id_lead"])
            hot_id = (last_person // r["hot_bidder_rounding"]
                      * r["hot_bidder_rounding"] + 1)
            col = np.where(hot, hot_id, cold) + r["first_person_id"]
        elif name == "auction":
            last_auction = epoch * r["auction_proportion"] \
                + r["auction_proportion"] - 1
            lo = np.maximum(last_auction - r["num_in_flight_auctions"], 0)
            hot = rng.integers(0, r["hot_auction_ratio"], n) > 0
            cold = lo + rng.integers(
                0, last_auction - lo + 1 + r["auction_id_lead"])
            hot_id = (last_auction // r["hot_auction_rounding"]
                      * r["hot_auction_rounding"])
            col = np.where(hot, hot_id, cold) + r["first_auction_id"]
        elif name == "price":
            col = np.floor(10.0 ** (rng.random(n) * 6.0) * 100.0
                           + 0.5).astype(np.int64)
        else:
            col = _extra(rng, n, r["avg_bid_byte_size"] - 32)
        out[name] = col
    return {k: v for k, v in out.items()
            if k in ("event", "ts") or k in columns}


def _extra(rng: np.random.Generator, n: int, mean: int) -> np.ndarray:
    """Beam's `nextExtra`: `n` strings of lower-case letters, lengths
    uniform in [mean - delta, mean + delta), delta = round(0.2 mean);
    one draw for every letter of the frame, as bytes `S<width>` (numpy
    strips the zero padding)."""
    delta = int(mean * 0.2 + 0.5)
    lo = mean - delta
    lengths = lo + rng.integers(0, max(2 * delta, 1), n)
    width = lo + max(2 * delta - 1, 0)
    letters = rng.integers(ord("a"), ord("z") + 1, (n, width),
                           dtype=np.uint8)
    letters[np.arange(width)[None, :] >= lengths[:, None]] = 0
    return letters.view(f"S{width}").reshape(n)


def frame(size: dict, seed: int, index: int) -> tuple:
    """Frame `index` as it is sent: (the stream it goes to, ts, cols as
    the client library's `encode_batch` takes them, the events it
    carries). Indices are in the order the one ordered producer sends."""
    d = draw(size, seed, index)
    return ("bid", d["ts"], {c: d[c] for c in COLUMNS},
            size["frame_rows"])


def last_time(size: dict, n_frames: int) -> int:
    """Event time of the last bid of the first `n_frames` frames: the
    query's watermark once it has consumed them."""
    last = np.array([n_frames * size["frame_rows"] - 1], np.int64)
    return int(times_of(size, events_of(size, last))[0])


def closer_time(size: dict, n_frames: int) -> int:
    """Where the closer lies: `close_after_gaps` gaps past the last bid,
    so every session of the frames closes."""
    return last_time(size, n_frames) \
        + size["close_after_gaps"] * size["gap_ms"]


def closers(size: dict, n_frames: int) -> list[tuple]:
    """One bid of a bidder no person has, in a frame's form, far enough
    past the last bid to close every session the frames left open; its
    own session stays open and is not compared."""
    one = np.array([0], np.int64)
    return [("bid", np.array([closer_time(size, n_frames)], np.int64),
             {"bidder": one + CLOSER_BIDDER, "auction": one,
              "price": one, "extra": np.array([b""], "S1")}, 1)]


def pulls(size: dict, n_frames: int) -> dict:
    """The answers to pull once `n_frames` frames are consumed: every
    closed session the view holds, before the closer and after it. A
    session is closed once the watermark has passed its end by
    `close_after_gaps` gaps, so its `winEnd` (end + gap) lies at least
    `close_after_gaps - 1` gaps behind the watermark and an open one's
    does not: the bound keeps the pull to the view's closed rows (the
    open sessions, a few hundred thousand, stay on the device). Which
    close cycles must be whole is the reference's to say (`complete`
    names the two cuts): the view keeps its newest `view_rows_kept`
    rows, and the reference knows how many each cycle closed."""
    back = (size["close_after_gaps"] - 1) * size["gap_ms"]
    sql = "SELECT * FROM {} WHERE winEnd <= {};"
    return {
        "before": [{"sql": sql.format(size["view"],
                                      last_time(size, n_frames) - back),
                    "complete": ["before_closer"]}],
        "after": [{"sql": sql.format(size["view"],
                                     closer_time(size, n_frames) - back),
                   "complete": ["after_closer"]}],
        "horizon": closer_time(size, n_frames) - back,
    }


def reader_pull(size: dict, rng: np.random.Generator) -> dict:
    """One draw of a reader: the sessions of one person of the warm
    phase's first gap. No accepted cell has a reader over this
    deployment: such a pull extracts every open session."""
    r = _rules(size)
    people = size["events_per_gap"] // r["proportion_denominator"]
    bidder = (size["first_event"] // r["proportion_denominator"]
              + int(rng.integers(0, people)) + r["first_person_id"])
    return {"sql": f"SELECT * FROM {size['view']} WHERE bidder = "
                   f"{bidder};", "bidder": bidder}
