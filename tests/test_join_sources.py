"""Two sources under one query. Event time of a window join is the
minimum over its sources' progress, so a batch is never lost because
the other log was read further: one source fed two windows ahead of the
other, in both orders, gives every row the reference has, with
`rows_past_retention` 0 and no late drop. And the skew is bounded where
it is made: a reader of several logs under a backlog hands out their
batches in turn, where it used to drain one log before it looked at the
next; a reader of one log reads exactly as before."""

import numpy as np
import pytest

from test_window_join import (
    W,
    answers,
    brute,
    closers,
    make_join,
    rows_of,
    streams,
)

from hstream_tpu.store import DataBatch, MemLogStore


def fed(ex, order):
    out = []
    for stream, ts, cols in order:
        out += rows_of(ex.process_columnar(ts, cols, None, stream=stream))
    return out


@pytest.mark.parametrize("device", [True, False])
@pytest.mark.parametrize("ahead", ["person", "auction"])
def test_one_source_two_windows_ahead_loses_nothing(ahead, device):
    batches = streams(53, n_spans=60)          # six windows
    want = brute(batches)
    lead = [b for b in batches if b[0] == ahead]
    lag = [b for b in batches if b[0] != ahead]
    per_window = W // 1_000
    order = lead[:2 * per_window]              # two whole windows ahead
    for i, b in enumerate(lag):
        order.append(b)
        if 2 * per_window + i < len(lead):
            order.append(lead[2 * per_window + i])
    order += closers(batches)
    ex = make_join(use_device_join=device)
    hi = {"person": -1, "auction": -1}
    for stream, ts, cols in order[:2 * per_window + 1]:
        hi[stream] = max(hi[stream], int(ts.max()))
    assert hi[ahead] - hi["auction" if ahead == "person" else "person"] \
        > 2 * W - 2_000
    got = answers(fed(ex, order))
    assert got == want
    assert ex.join_stats["rows_past_retention"] == 0
    assert ex._inner.late_drops == 0
    assert (ex._dev is not None) == device


def test_event_time_is_the_minimum_over_the_sources():
    ex = make_join(use_device_join=False)
    batches = streams(59, n_spans=30)
    persons = [b for b in batches if b[0] == "person"]
    auctions = [b for b in batches if b[0] == "auction"]
    fed(ex, persons)                       # three windows of persons
    assert ex.watermark == -1              # the other source is silent
    assert ex._src_hi["l"] > persons[0][1][0] + 2 * W
    assert len(ex._stores["l"]) == 30 * 50  # nothing evicted
    fed(ex, auctions[:5])
    assert ex.watermark == int(auctions[4][1].max())
    assert ex._open_from == ex.watermark - ex.watermark % W


def test_a_row_behind_the_eviction_bound_is_counted_not_joined():
    """What the guarantee counts: a source that falls behind its own
    past (a record older than a window both sources have closed)."""
    batches = streams(61, n_spans=30)
    for device in (True, False):
        ex = make_join(use_device_join=device)
        fed(ex, batches)
        stale = batches[2]                 # a person batch of window 0
        assert stale[0] == "person"
        out = rows_of(ex.process_columnar(stale[1], stale[2], None,
                                          stream="person"))
        assert out == []
        assert ex.join_stats["rows_past_retention"] == len(stale[1])
        assert ex.join_gauges()["store_rows_left"] <= 10 * 50 + 50


# ---- the reader ------------------------------------------------------------


@pytest.fixture(params=["mem", "native"])
def store(request, tmp_path):
    if request.param == "mem":
        yield MemLogStore()
    else:
        from hstream_tpu.store.native import NativeLogStore

        st = NativeLogStore(str(tmp_path / "nstore"))
        yield st
        st.close()


def read_all(reader, chunk):
    out = []
    while True:
        got = [r for r in reader.read(chunk) if isinstance(r, DataBatch)]
        if not got:
            return out
        out += got


@pytest.mark.parametrize("chunk", [1, 3, 2048])
def test_a_reader_of_two_logs_hands_out_batches_in_turn(store, chunk):
    for logid in (11, 12):
        store.create_log(logid)
    for i in range(20):                    # a backlog on both logs
        store.append(11, b"p%d" % i)
    for i in range(20):
        store.append(12, b"a%d" % i)
    reader = store.new_reader(max_logs=2) if hasattr(
        store, "new_reader") else None
    reader.set_timeout(0)
    reader.start_reading(11)
    reader.start_reading(12)
    got = read_all(reader, chunk)
    assert len(got) == 40
    seq = [(b.logid, b.payloads[0]) for b in got]
    # each log in its own order, and neither more than one item ahead
    assert [p for l, p in seq if l == 11] == [b"p%d" % i
                                             for i in range(20)]
    assert [p for l, p in seq if l == 12] == [b"a%d" % i
                                             for i in range(20)]
    ahead = 0
    for logid, _p in seq:
        ahead += 1 if logid == 11 else -1
        assert abs(ahead) <= 1, seq


def test_an_uneven_backlog_is_read_to_its_end(store):
    for logid in (21, 22):
        store.create_log(logid)
    for i in range(3):
        store.append(21, b"x%d" % i)
    for i in range(9):
        store.append(22, b"y%d" % i)
    reader = store.new_reader(max_logs=2)
    reader.set_timeout(0)
    reader.start_reading(21)
    reader.start_reading(22)
    got = [(b.logid, b.payloads[0]) for b in read_all(reader, 4)]
    assert sorted(got) == sorted(
        [(21, b"x%d" % i) for i in range(3)]
        + [(22, b"y%d" % i) for i in range(9)])
    assert [p for l, p in got if l == 22] == [b"y%d" % i
                                             for i in range(9)]
    assert [l for l, _p in got[:6]] == [21, 22, 21, 22, 21, 22]


@pytest.mark.parametrize("chunk", [1, 4, 2048])
def test_a_reader_of_one_log_reads_as_before(store, chunk):
    store.create_log(31)
    lsns = [store.append_batch(31, [b"r%d" % i, b"s%d" % i])
            for i in range(10)]
    reader = store.new_reader()
    reader.set_timeout(0)
    reader.start_reading(31)
    first = [r for r in reader.read(chunk) if isinstance(r, DataBatch)]
    assert [b.lsn for b in first] == lsns[:min(chunk, 10)]
    rest = read_all(reader, chunk)
    assert [b.lsn for b in first + rest] == lsns
    assert [b.payloads for b in first + rest] == [
        (b"r%d" % i, b"s%d" % i) for i in range(10)]
    assert reader.read(chunk) == []


def test_the_two_stores_interleave_alike(tmp_path):
    from hstream_tpu.store.native import NativeLogStore

    seqs = []
    native = NativeLogStore(str(tmp_path / "n"))
    try:
        for st in (MemLogStore(), native):
            for logid in (5, 3):               # created out of order
                st.create_log(logid)
            for i in range(6):
                st.append(5, b"five%d" % i)
                st.append(3, b"three%d" % i)
            reader = st.new_reader(max_logs=2)
            reader.set_timeout(0)
            reader.start_reading(5)
            reader.start_reading(3)
            seqs.append([(b.logid, b.payloads[0])
                         for b in read_all(reader, 5)])
    finally:
        native.close()
    assert seqs[0] == seqs[1]
    assert np.all(np.diff([l for l, _p in seqs[0]]) != 0)
