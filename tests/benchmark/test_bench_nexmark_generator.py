"""The NEXmark generator (`benchmarks/generators/nexmark.py`): frames
are a pure function of (sizes, seed, index) and carry what Apache Beam's
generator would put on the wire at `NexmarkConfiguration`'s defaults."""

import json
import os

import numpy as np
import pytest

from benchmarks.generators import nexmark as gen
from benchmarks.harness import manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nexmark_q11.json")) as _f:
    CONFIG = json.load(_f)
FULL = manifest.size_of(CONFIG, False)
DRY = manifest.size_of(CONFIG, True)
RULES = FULL["nexmark"]


def _same(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(
        np.array_equal(a[k], b[k]) for k in a)


@pytest.mark.parametrize("size", [FULL, DRY], ids=["full", "dry"])
def test_frame_is_a_pure_function_of_sizes_seed_and_index(size):
    seed = 2**31 + 29
    first = gen.draw(size, seed, 7)
    gen.draw(size, seed, 3)  # another frame in between changes nothing
    gen.draw(size, seed + 1, 7)
    assert _same(first, gen.draw(size, seed, 7))
    assert not _same(first, gen.draw(size, seed + 1, 7))
    assert not _same(first, gen.draw(size, seed, 8))
    stream, ts, cols, events = gen.frame(size, seed, 7)
    assert stream == "bid" and events == size["frame_rows"] == len(ts)
    assert list(cols) == ["bidder", "auction", "price", "extra"]
    assert np.array_equal(ts, first["ts"])
    assert all(np.array_equal(cols[c], first[c]) for c in cols)


def test_a_reader_of_the_first_columns_sees_the_full_frames_values():
    """The reference draws bidders alone: the same values, without the
    letters of `extra`."""
    full = gen.draw(FULL, 29, 5)
    some = gen.draw(FULL, 29, 5, columns=("bidder",))
    assert sorted(some) == ["bidder", "event", "ts"]
    assert np.array_equal(some["bidder"], full["bidder"])
    upto = gen.draw(FULL, 29, 5, columns=("price",))
    assert np.array_equal(upto["price"], full["price"])


def test_46_bids_of_50_events_in_order():
    d = gen.draw(FULL, 29, 0)
    nxt = gen.draw(FULL, 29, 1)
    event = np.concatenate([d["event"], nxt["event"]])
    assert event[0] == FULL["first_event"] + 4  # after 1 person, 3 auctions
    assert (event % 50 >= 4).all()              # a bid's place in an epoch
    assert (np.diff(event) > 0).all()
    epochs, per_epoch = np.unique(event // 50, return_counts=True)
    assert RULES["bid_proportion"] == 46
    assert (per_epoch[1:-1] == 46).all()
    assert (np.diff(epochs) == 1).all()         # no epoch left out
    ts = np.concatenate([d["ts"], nxt["ts"]])
    assert (np.diff(ts) >= 0).all()
    # 2^23 events of all kinds span one gap of event time
    span = gen.times_of(FULL, np.array(
        [FULL["first_event"], FULL["first_event"] + FULL["events_per_gap"]]))
    assert span[1] - span[0] == FULL["gap_ms"] and span[0] == gen.BASE


def test_three_bids_of_four_go_to_the_hot_bidder():
    frames = [gen.draw(FULL, 2**31 + 5, i, columns=("bidder",))
              for i in range(4)]
    bidder = np.concatenate([f["bidder"] for f in frames])
    event = np.concatenate([f["event"] for f in frames])
    hot_id = (event // 50 // 100) * 100 + 1 + RULES["first_person_id"]
    share = (bidder == hot_id).mean()
    n = len(bidder)
    # the cold draw hits the hot id once in 1 010: inside the error
    assert abs(share - 0.75) < 4 * np.sqrt(0.75 * 0.25 / n) + 0.001, share
    # the hot bidder moves on every 5 000 events
    assert len(np.unique(hot_id)) == pytest.approx(
        (event[-1] - event[0]) / 5000, abs=2)


def test_ids_follow_beams_formulas_at_first_event():
    d = gen.draw(FULL, 31, 0)
    last_person = d["event"] // 50           # lastBase0PersonId of a bid
    people = last_person + 1
    hot_id = last_person // 100 * 100 + 1 + 1000
    cold = d["bidder"] != hot_id
    lo = people - RULES["num_active_people"] + 1000
    assert (d["bidder"][cold] >= lo[cold]).all()
    assert (d["bidder"][cold]
            < (people + RULES["person_id_lead"] + 1000)[cold]).all()
    # over the whole active set, not a corner of it
    assert np.ptp(d["bidder"][cold] - lo[cold]) > 1000
    assert d["bidder"].min() > 2**24         # float32 cannot hold them
    last_auction = last_person * 3 + 2       # lastBase0AuctionId of a bid
    hot_auction = last_auction // 100 * 100 + 1000
    assert abs((d["auction"] == hot_auction).mean() - 0.5) < 0.02
    other = d["auction"] != hot_auction
    assert (d["auction"][other]
            >= (last_auction - RULES["num_in_flight_auctions"]
                + 1000)[other]).all()
    assert (d["auction"][other]
            < (last_auction + 1 + RULES["auction_id_lead"]
               + 1000)[other]).all()
    assert d["price"].min() >= 100 and d["price"].max() <= 100_000_000
    # log-uniform: half of the prices under 10^3 * 100 cents
    assert abs((d["price"] < 100_000).mean() - 0.5) < 0.02


def test_a_bid_is_100_bytes_on_average():
    d = gen.draw(FULL, 2**31 + 7, 2)
    lengths = np.char.str_len(d["extra"])
    assert lengths.min() >= 54 and lengths.max() <= 81
    record = 8 * 4 + lengths.mean()  # auction, bidder, price, dateTime
    assert abs(record - RULES["avg_bid_byte_size"]) < 5, record
    letters = np.frombuffer(b"".join(d["extra"][:1000].tolist()), np.uint8)
    assert letters.min() >= ord("a") and letters.max() <= ord("z")
    assert len(set(d["extra"][:1000].tolist())) == 1000  # none shared


def test_the_frame_on_the_wire_decodes_to_the_same_columns():
    """Through the producer's own encoding: int64 columns stay exact,
    `extra` rides as a dictionary of one entry a row."""
    from benchmarks.harness.producer import encode_frame
    from hstream_tpu.common import colframe, columnar

    _stream, ts, cols, n = gen.frame(DRY, 29, 3)
    payload, rows, last_ts = colframe.open_block(encode_frame(ts, cols))
    assert rows == n and last_ts == ts[-1]
    got_ts, got, nulls = columnar.decode_columnar_nulls(payload)
    assert nulls is None and np.array_equal(got_ts, ts)
    for name in ("bidder", "auction", "price"):
        kind, arr, _d = got[name]
        assert kind == "i64" and np.array_equal(arr, cols[name])
    kind, ids, words = got["extra"]
    assert kind == "str" and len(words) == n
    assert np.array_equal(np.array(words)[ids], cols["extra"].astype(str))
    # about 100 B a bid on the wire too (dictionary framing on top)
    assert 95 < len(payload) / n < 115


@pytest.mark.parametrize("size", [FULL, DRY], ids=["full", "dry"])
def test_warm_phase_passes_the_first_close(size):
    n_warm = gen.warm_frames(size)
    assert n_warm % size["frames_per_call"] == 0
    span = gen.last_time(size, n_warm) - gen.BASE
    assert span >= size["close_after_gaps"] * size["gap_ms"]
    assert span <= (size["warm_gaps"] + 0.1) * size["gap_ms"]
    assert gen.last_time(size, n_warm) == int(
        gen.draw(size, 1, n_warm - 1, columns=())["ts"][-1])


def test_closer_and_pulls_bound_the_closed_sessions():
    n = gen.warm_frames(DRY) + 10
    (stream, ts, cols, events), = gen.closers(DRY, n)
    assert stream == "bid" and events == 1
    assert ts[0] == gen.last_time(DRY, n) + 2 * DRY["gap_ms"]
    assert cols["bidder"][0] == gen.CLOSER_BIDDER < \
        RULES["first_person_id"]
    plan = gen.pulls(DRY, n)
    # a closed session's winEnd lies one gap behind the watermark
    assert plan["before"][0]["sql"].endswith(
        f"winEnd <= {gen.last_time(DRY, n) - DRY['gap_ms']};")
    assert plan["after"][0]["sql"].endswith(
        f"winEnd <= {int(ts[0]) - DRY['gap_ms']};")
    assert plan["horizon"] == int(ts[0]) - DRY["gap_ms"]


def test_streams_are_the_kinds_the_configuration_sends():
    assert [s["name"] for s in gen.streams(FULL)] == ["bid"]
    with pytest.raises(ValueError):
        gen.streams({**FULL, "streams": ["bid", "person"]})
    pull = gen.reader_pull(FULL, np.random.default_rng(3))
    assert pull["sql"] == (f"SELECT * FROM user_sessions WHERE bidder = "
                           f"{pull['bidder']};")


def test_the_generator_knows_nothing_of_the_program():
    """Frames are a function of the sizes: the encoder processes start
    without JAX, and no duty looks at the program under test."""
    import ast

    with open(gen.__file__) as f:
        tree = ast.parse(f.read())
    imported = {a.name.split(".")[0] for n in ast.walk(tree)
                if isinstance(n, ast.Import) for a in n.names}
    imported |= {n.module.split(".")[0] for n in ast.walk(tree)
                 if isinstance(n, ast.ImportFrom) and n.module}
    assert imported <= {"__future__", "numpy"}, imported
