"""The seeded NEXmark Person and Auction streams for query 8, monitor new
users: the numbering, the rules' sizes and the times are
`generators/nexmark.py`'s (its rules and its departures from Beam are
stated there and under `assumed` in the configuration; its `_extra` is
Beam's padding for a record of fixed fields, and is taken here record by
record, `_padding`), one function of (size, seed, frame
index), so the producer's encoder processes and the reference regenerate
the same frames without sharing a byte. A configuration names it with
`"generator": "nexmark_q8"`; the duties are listed in
`benchmarks/README.md`. No bid is made: a Bid keeps its number and its
time in the log and is not sent, as Q11 and Q5 send no Person and no
Auction.

The rules are those of Apache Beam's NEXmark generator
(`sdks/java/testing/nexmark`, `sources/generator/model/PersonGenerator`,
`AuctionGenerator`) at `NexmarkConfiguration`'s defaults, as sizes in the
configuration's `nexmark` block:

  numbering   epoch `e` (50 events) holds one Person, event `50 e`, and
              three Auctions, events `50 e + 1 .. 50 e + 3`; person id
              `e + FIRST_PERSON_ID`, auction id `3 e + k +
              FIRST_AUCTION_ID`
  seller      3 draws of 4 (`hotSellersRatio` 4: `nextInt(4) > 0`) the
              hot seller, `(last person // 100) * 100`, the last person
              of an auction of epoch `e` being `e`; else uniform over
              the last `numActivePeople` = 1 000 persons plus a lead of
              10 (`nextBase0PersonId`): an auction may name a person
              whose Person event comes a moment later; `+
              FIRST_PERSON_ID`
  person      name `<first> <last>` from Beam's two lists; emailAddress
              `nextString(7) @ nextString(5) .com`; creditCard four
              groups of four digits; city and state from Beam's lists
  auction     itemName `nextString(20)`, description `nextString(100)`,
              initialBid `nextPrice`, reserve initialBid + `nextPrice`,
              expires the event's time + up to a few minutes, category
              10 + `nextInt(5)`
  extra       Beam's `nextExtra(currentSize, avg)`: each record is
              padded to its kind's average width (`avgPersonByteSize`
              200, `avgAuctionByteSize` 500) from what its other fields
              take, the length uniform within a fifth of what is left

Frames follow spans of `span_epochs` epochs: frame `2 k` is span `k`'s
Persons, frame `2 k + 1` its Auctions, so calls alternate streams and
each source trails the other by at most one span at the door. The event
time a frame spans is the same for every seed; the draws inside differ.
Draws are numpy's PCG64, one generator a frame, seeded with (seed, 8,
frame index), in the order of `PERSON_COLUMNS` / `AUCTION_COLUMNS`: a
reader that needs the first columns stops early and still sees the
values a full frame carries.
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators.nexmark import BASE, _rules, times_of
from benchmarks.generators.nexmark_q5 import clock

KINDS = ("person", "auction")
# drawn in this order (id and the times are not drawn)
PERSON_COLUMNS = ("name", "emailAddress", "creditCard", "city", "state",
                  "extra")
AUCTION_COLUMNS = ("seller", "itemName", "description", "initialBid",
                   "reserve", "expires", "category", "extra")
CLOSER_PERSON = 0  # under FIRST_PERSON_ID: no person's id
CLOSER_SELLER = 1  # nor this one: the closers do not join

FIRST_NAMES = np.array([b"Peter", b"Paul", b"Luke", b"John", b"Saul",
                        b"Vicky", b"Kate", b"Julie", b"Sarah", b"Deiter",
                        b"Walter"])
LAST_NAMES = np.array([b"Shultz", b"Abrams", b"Spencer", b"White",
                       b"Bartels", b"Walton", b"Smith", b"Jones",
                       b"Noris"])
US_STATES = np.array([b"AZ", b"CA", b"ID", b"OR", b"WA", b"WY"])
US_CITIES = np.array([b"Phoenix", b"Los Angeles", b"San Francisco",
                      b"Boise", b"Portland", b"Bend", b"Redmond",
                      b"Seattle", b"Kent", b"Cheyenne"])
MIN_STRING_LENGTH = 3
FIRST_CATEGORY_ID, NUM_CATEGORIES = 10, 5
MAX_AUCTION_LENGTH_MS = 600_000


def streams(size: dict) -> list[dict]:
    """The streams to create, each with the schema its frames carry."""
    kinds = list(size["streams"])
    if any(k not in KINDS for k in kinds):
        raise ValueError(f"streams {kinds}: this generator sends "
                         f"{list(KINDS)} only")
    return [{"name": k, "schema": dict(size["schemas"][k])}
            for k in kinds]


def _epochs(size: dict, span: int) -> np.ndarray:
    r = _rules(size)
    n = size["span_epochs"]
    first = size["first_event"] // r["proportion_denominator"]
    return first + np.arange(span * n, (span + 1) * n, dtype=np.int64)


def _draws(rng, n: int, width: int) -> np.ndarray:
    """`n` x `width` uniform bytes in one draw (a frame's strings are
    some 8 MB of letters: a byte a letter is what keeps an encoder
    process ahead of the sender)."""
    return np.frombuffer(rng.bytes(n * width), np.uint8).reshape(
        n, width)


def _letters(rng, n: int, width: int) -> np.ndarray:
    """Lower-case letters, a byte's draw modulo 26 (the first 22 letters
    a 64th likelier than the last four: only widths matter here)."""
    return _draws(rng, n, width) % 26 + ord("a")


def _next_string(rng, n: int, max_len: int) -> np.ndarray:
    """Beam's `nextString`: `n` strings of 3 up to `max_len` - 1
    lower-case letters, one draw of 13 a space; as bytes `S<width>`."""
    lengths = MIN_STRING_LENGTH + rng.integers(
        0, max_len - MIN_STRING_LENGTH, n)
    width = max_len - 1
    letters = _letters(rng, n, width)
    letters[_draws(rng, n, width) % 13 == 0] = ord(" ")
    letters[np.arange(width)[None, :] >= lengths[:, None]] = 0
    return letters.view(f"S{width}").reshape(n)


def _next_price(rng, n: int) -> np.ndarray:
    return np.floor(10.0 ** (rng.random(n) * 6.0) * 100.0
                    + 0.5).astype(np.int64)


def _padding(rng, taken: np.ndarray, avg: int) -> np.ndarray:
    """Beam's `nextExtra(currentSize, desiredAverageSize)`, the rule of
    `nexmark.py`'s `_extra` taken record by record: for records that
    already take `taken` bytes, `avg - taken` lower-case letters give
    or take a fifth (none where a record is over its average); one
    draw for every letter of the frame, as bytes `S<width>`."""
    want = np.maximum(avg - taken, 0)
    delta = (want * 0.2 + 0.5).astype(np.int64)
    lengths = want - delta + (rng.random(len(taken))
                              * 2 * delta).astype(np.int64)
    width = max(int((want + delta).max()), 1)
    letters = _letters(rng, len(taken), width)
    letters[np.arange(width)[None, :] >= lengths[:, None]] = 0
    return letters.view(f"S{width}").reshape(len(taken))


def persons(size: dict, seed: int, span: int,
            columns: tuple = PERSON_COLUMNS) -> dict:
    """Span `span`'s Person events as arrays: `event`, `ts`, `id` and,
    drawn in the order of `PERSON_COLUMNS`, those of `columns`."""
    r = _rules(size)
    epoch = _epochs(size, span)
    n = len(epoch)
    event = epoch * r["proportion_denominator"]
    out = {"event": event, "ts": times_of(clock(size), event),
           "id": epoch * r["person_proportion"] + r["first_person_id"]}
    rng = np.random.default_rng([int(seed), 8, 2 * int(span)])
    stop = max((PERSON_COLUMNS.index(c) + 1 for c in columns), default=0)
    taken = np.full(n, 8 + 8, np.int64)  # id and dateTime
    for name in PERSON_COLUMNS[:stop]:
        if name == "name":
            col = np.char.add(np.char.add(
                FIRST_NAMES[rng.integers(0, len(FIRST_NAMES), n)], b" "),
                LAST_NAMES[rng.integers(0, len(LAST_NAMES), n)])
        elif name == "emailAddress":
            col = np.char.add(np.char.add(np.char.add(
                _next_string(rng, n, 7), b"@"),
                _next_string(rng, n, 5)), b".com")
        elif name == "creditCard":
            digits = rng.integers(0, 10_000, (n, 4))
            col = np.array([b"%04d %04d %04d %04d" % tuple(d)
                            for d in digits.tolist()])
        elif name == "city":
            col = US_CITIES[rng.integers(0, len(US_CITIES), n)]
        elif name == "state":
            col = US_STATES[rng.integers(0, len(US_STATES), n)]
        else:
            col = _padding(rng, taken, r["avg_person_byte_size"])
        taken = taken + np.char.str_len(col)
        out[name] = col
    return {k: v for k, v in out.items()
            if k in ("event", "ts", "id") or k in columns}


def auctions(size: dict, seed: int, span: int,
             columns: tuple = AUCTION_COLUMNS) -> dict:
    """Span `span`'s Auction events as arrays: `event`, `ts`, `id` and,
    drawn in the order of `AUCTION_COLUMNS`, those of `columns`."""
    r = _rules(size)
    k = r["auction_proportion"]
    epoch = np.repeat(_epochs(size, span), k)
    n = len(epoch)
    off = np.tile(np.arange(k, dtype=np.int64), n // k)
    event = (epoch * r["proportion_denominator"]
             + r["person_proportion"] + off)
    ts = times_of(clock(size), event)
    out = {"event": event, "ts": ts,
           "id": epoch * k + off + r["first_auction_id"]}
    rng = np.random.default_rng([int(seed), 8, 2 * int(span) + 1])
    stop = max((AUCTION_COLUMNS.index(c) + 1 for c in columns),
               default=0)
    taken = np.full(n, 8 * 6, np.int64)  # the six longs and the time
    for name in AUCTION_COLUMNS[:stop]:
        if name == "seller":
            people = epoch * r["person_proportion"] \
                + r["person_proportion"]  # the last person is epoch's
            active = np.minimum(people, r["num_active_people"])
            hot = rng.integers(0, r["hot_sellers_ratio"], n) > 0
            cold = people - active + rng.integers(
                0, active + r["person_id_lead"])
            hot_id = ((people - 1) // r["hot_seller_rounding"]
                      * r["hot_seller_rounding"])
            col = np.where(hot, hot_id, cold) + r["first_person_id"]
        elif name == "itemName":
            col = _next_string(rng, n, 20)
        elif name == "description":
            col = _next_string(rng, n, 100)
        elif name == "initialBid":
            col = _next_price(rng, n)
        elif name == "reserve":
            col = out["initialBid"] + _next_price(rng, n)
        elif name == "expires":
            col = ts + 1 + rng.integers(0, MAX_AUCTION_LENGTH_MS, n)
        elif name == "category":
            col = FIRST_CATEGORY_ID + rng.integers(0, NUM_CATEGORIES, n)
        else:
            col = _padding(rng, taken, r["avg_auction_byte_size"])
        if col.dtype.kind == "S":
            taken = taken + np.char.str_len(col)
        out[name] = col
    return {k: v for k, v in out.items()
            if k in ("event", "ts", "id") or k in columns}


def spans_of(n_frames: int) -> tuple[int, int]:
    """(person frames, auction frames) among the first `n_frames`."""
    return (n_frames + 1) // 2, n_frames // 2


def warm_frames(size: dict) -> int:
    """Frames of the warm phase: both frames of every span of the first
    `warm_windows` window sizes of event time. Past one size the first
    window has closed (an eviction, a code reclamation, ~a window's
    rows to the view); by 1.6 the inner key table has met its first
    retirement at its final capacity."""
    r = _rules(size)
    d = size["density"]
    events = int(np.ceil(size["warm_windows"] * size["size_ms"]
                         * d["events"] / d["per_ms"]))
    per_span = size["span_epochs"] * r["proportion_denominator"]
    return 2 * -(-events // per_span)


def frame(size: dict, seed: int, index: int) -> tuple:
    """Frame `index` as it is sent: (the stream it goes to, ts, cols as
    the client library's `encode_batch` takes them, the events it
    carries). Even indices are a span's Persons, odd its Auctions."""
    span, odd = divmod(int(index), 2)
    if odd:
        d = auctions(size, seed, span)
        names = ("id",) + AUCTION_COLUMNS
    else:
        d = persons(size, seed, span)
        names = ("id",) + PERSON_COLUMNS
    return (KINDS[odd], d["ts"], {c: d[c] for c in names}, len(d["ts"]))


def last_time(size: dict, n_frames: int) -> int:
    """The query's event time once it has consumed the first `n_frames`
    frames: the minimum over both sources of their newest record's
    time (-1 while a source has sent nothing)."""
    r = _rules(size)
    n_p, n_a = spans_of(n_frames)
    if not n_p or not n_a:
        return -1
    first = size["first_event"] // r["proportion_denominator"]
    last_epoch = first + np.array([n_p, n_a], np.int64) \
        * size["span_epochs"] - 1
    event = last_epoch * r["proportion_denominator"] + np.array(
        [0, r["person_proportion"] + r["auction_proportion"] - 1])
    return int(times_of(clock(size), event).min())


def newest_time(size: dict, n_frames: int) -> int:
    """The newest record's time over both sources."""
    r = _rules(size)
    n_p, n_a = spans_of(n_frames)
    first = size["first_event"] // r["proportion_denominator"]
    spans = max(n_p, n_a, 1)
    event = ((first + spans * size["span_epochs"] - 1)
             * r["proportion_denominator"]
             + r["person_proportion"] + r["auction_proportion"] - 1)
    return int(times_of(clock(size), np.array([event], np.int64))[0])


def closer_time(size: dict, n_frames: int) -> int:
    """Where the closers lie: two window sizes past the newest record,
    past the end of every window a record of the frames is in."""
    return newest_time(size, n_frames) + 2 * size["size_ms"]


def closers(size: dict, n_frames: int) -> list[tuple]:
    """One record on EACH stream (event time is the minimum over both),
    in a frame's form, far enough past the newest record to close every
    window the frames left open: a person no auction names and an
    auction of a seller no person is; their own window stays open and
    is not compared."""
    ts = np.array([closer_time(size, n_frames)], np.int64)
    one = np.array([0], np.int64)
    empty = np.array([b""], "S1")
    person = {"id": one + CLOSER_PERSON,
              **{c: empty for c in PERSON_COLUMNS}}
    auction = {"id": one, **{c: one for c in AUCTION_COLUMNS}}
    auction.update(seller=one + CLOSER_SELLER, itemName=empty,
                   description=empty, extra=empty)
    return [("person", ts, person, 1), ("auction", ts, auction, 1)]


def pulls(size: dict, n_frames: int) -> dict:
    """The answers to pull once `n_frames` frames are consumed: every
    row of a closed window the view holds, before the closers and after
    them. With GRACE 0 a window is closed once event time (the minimum
    over both sources) reaches its end, so the bound `winEnd <= event
    time` keeps the pull to closed rows; `complete` names the cut, and
    the reference says which windows had closed by then and which of
    them the view (its newest `view_rows_kept` rows) must hold whole."""
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
    """One draw of a reader: the new users of the first window of the
    warm phase. No accepted cell has a reader over this deployment."""
    del rng
    w = size["size_ms"]
    start = BASE - BASE % w
    return {"sql": f"SELECT * FROM {size['view']} WHERE winStart = "
                   f"{start};", "winStart": start}
