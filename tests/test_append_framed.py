"""Framed columnar append path (ISSUE 12): wire-format codec, the
sharded append front, server equivalence against the protobuf Append
path (same rows, same record ids), the streaming variant, and the
malformed/torn/overlong-frame refusal contract (typed INVALID_ARGUMENT,
never a partial ingest)."""

import contextlib
import time

import grpc
import numpy as np
import pytest

from hstream_tpu.common import colframe, columnar
from hstream_tpu.common import records as rec
from hstream_tpu.common.errors import InvalidFrame
from hstream_tpu.common.faultinject import FAULTS
from hstream_tpu.client.producer import ColumnarProducer, encode_batch
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.server.appendfront import AppendFront
from hstream_tpu.server.main import serve
from hstream_tpu.store.memstore import MemLogStore

from helpers import (columnar_block, header_cases, wait_attached,
                     wait_watermark, whole_header_outcome)

BASE = 1_700_000_000_000


# ---- frame codec ----------------------------------------------------------

def test_frame_roundtrip():
    payload = columnar.encode_columnar(
        BASE + np.arange(4, dtype=np.int64),
        {"k": ["a", "b", "a", "c"], "v": np.arange(4, dtype=np.float32)})
    frame = colframe.encode_frame(payload)
    assert len(frame) == colframe.FRAME_HEADER_LEN + len(payload)
    body = colframe.open_frame(frame)
    assert bytes(body) == payload
    # open_block validates the embedded columnar bounds too
    body2, n, last_ts = colframe.open_block(frame)
    assert (n, last_ts) == (4, BASE + 3)


@pytest.mark.parametrize("mutate, msg", [
    (lambda f: f[:8], "shorter than"),                      # short header
    (lambda f: b"XXXX" + f[4:], "bad frame magic"),         # magic
    (lambda f: f[:4] + bytes([99]) + f[5:], "version"),     # version
    (lambda f: f[:-3], "truncated"),                        # truncated
    (lambda f: f + b"xx", "overlong"),                      # overlong
    (lambda f: f[:-1] + bytes([f[-1] ^ 0xFF]), "CRC"),      # corrupt
])
def test_frame_refusals(mutate, msg):
    frame = colframe.encode_frame(columnar.encode_columnar(
        np.array([BASE], np.int64), {"k": ["a"]}))
    with pytest.raises(InvalidFrame, match=msg):
        colframe.open_frame(mutate(frame))


def test_frame_torn_bytes_refused_deterministically():
    """The faultinject torn machinery (seeded mid-payload truncation)
    against the frame door: every torn shape is a typed refusal."""
    payload = columnar.encode_columnar(
        BASE + np.arange(64, dtype=np.int64),
        {"k": [f"k{i % 5}" for i in range(64)],
         "v": np.arange(64, dtype=np.float32)})
    frame = colframe.encode_frame(payload)
    for seed in range(8):
        FAULTS.arm("test.frame.torn", f"torn:1:{seed}")
        try:
            torn = FAULTS.mutate("test.frame.torn", frame)
        finally:
            FAULTS.disarm("test.frame.torn")
        assert len(torn) < len(frame)
        with pytest.raises(InvalidFrame):
            colframe.open_frame(torn)


def test_forged_inner_block_refused():
    """A well-framed block whose columnar header lies about its sizes
    must be refused at the door (open_block), not deep in a task."""
    good = columnar.encode_columnar(
        BASE + np.arange(8, dtype=np.int64), {"v": np.arange(8)})
    # truncate the block body but reframe with a VALID frame header:
    # only the inner columnar bounds check can catch this
    forged = colframe.encode_frame(good[:-8])
    with pytest.raises(InvalidFrame, match="columnar"):
        colframe.open_block(forged)
    # an empty block (n=0) is refused too — nothing to append
    empty = colframe.encode_frame(columnar.encode_columnar(
        np.array([], np.int64), {}))
    with pytest.raises(InvalidFrame, match="empty"):
        colframe.open_block(empty)


# ---- null-mask wire extension ---------------------------------------------

def test_columnar_nulls_roundtrip():
    ts = BASE + np.arange(6, dtype=np.int64)
    cols = {"k": ["a", "b", "a", "b", "a", "b"],
            "v": np.arange(6, dtype=np.float32)}
    nulls = {"v": np.array([0, 1, 0, 0, 1, 0], np.bool_)}
    blob = columnar.encode_columnar(ts, cols, nulls=nulls)
    ts2, cols2, nulls2 = columnar.decode_columnar_nulls(blob)
    np.testing.assert_array_equal(ts2, ts)
    np.testing.assert_array_equal(nulls2["v"], nulls["v"])
    # legacy payloads (no masks) decode with nulls=None
    legacy = columnar.encode_columnar(ts, cols)
    _, _, n3 = columnar.decode_columnar_nulls(legacy)
    assert n3 is None
    # the 2-tuple decode stays stable for old callers
    ts4, cols4 = columnar.decode_columnar(blob)
    np.testing.assert_array_equal(ts4, ts)
    assert set(cols4) == {"k", "v"}
    # rows: masked cells are ABSENT like the per-record decode shape
    rows = columnar.to_rows(ts2, cols2, nulls2, drop_null=True)
    assert "v" not in rows[1] and rows[0]["v"] == 0.0


def test_columnar_nulls_bounds_checked():
    ts = BASE + np.arange(4, dtype=np.int64)
    blob = columnar.encode_columnar(
        ts, {"v": np.arange(4)},
        nulls={"v": np.array([1, 0, 0, 1], np.bool_)})
    # cut into the mask region: declared sizes no longer fit
    with pytest.raises(ValueError):
        columnar.decode_columnar_nulls(blob[:-2])
    with pytest.raises(ValueError):
        columnar.encode_columnar(
            ts, {"v": np.arange(4)},
            nulls={"missing": np.zeros(4, np.bool_)})
    with pytest.raises(ValueError):
        columnar.encode_columnar(
            ts, {"v": np.arange(4)},
            nulls={"v": np.zeros(3, np.bool_)})


# ---- record splice --------------------------------------------------------

def test_wrap_raw_record_parses_identically():
    payload = columnar.encode_columnar(
        BASE + np.arange(3, dtype=np.int64), {"v": np.arange(3)})
    spliced = rec.wrap_raw_record(payload, BASE + 2)
    reference = rec.build_record(payload, publish_time_ms=BASE + 2)
    got = rec.parse_record(spliced)
    assert got == reference
    assert got.header.flag == pb.RECORD_FLAG_RAW
    assert got.header.publish_time_ms == BASE + 2
    assert got.payload == payload


def test_record_bytes_stamps_batch_default_once():
    """The Append satellite: a record already carrying a timestamp is
    never mutated; one missing it gets the batch default — and both
    parse identically to the full SerializeToString path."""
    stamped = rec.build_record({"k": "a"}, publish_time_ms=BASE)
    unstamped = rec.build_record({"k": "b"})
    unstamped.header.publish_time_ms = 0
    assert rec.parse_record(rec.record_bytes(stamped, default_ts=123)) \
        == stamped
    got = rec.parse_record(rec.record_bytes(unstamped, default_ts=456))
    assert got.header.publish_time_ms == 456
    assert rec.record_to_dict(got) == {"k": "b"}
    # big payloads take the splice path: equivalence there too
    big = rec.build_record(b"\x00" * 100_000, key="kk",
                           attributes={"a": "1"}, publish_time_ms=BASE)
    assert rec.parse_record(rec.record_bytes(big, default_ts=1)) == big


def test_peek_columnar_payload():
    """The zero-copy read-side peek: columnar records yield their
    payload view with no protobuf parse; everything else returns None
    (full-parse fallback)."""
    payload = columnar.encode_columnar(
        BASE + np.arange(4, dtype=np.int64), {"v": np.arange(4)})
    for data in (rec.wrap_raw_record(payload, BASE),
                 rec.build_columnar_record(
                     BASE + np.arange(4, dtype=np.int64),
                     {"v": np.arange(4)}).SerializeToString(),
                 rec.build_record(payload, key="k",
                                  attributes={"a": "b"},
                                  publish_time_ms=BASE)
                 .SerializeToString()):
        v = rec.peek_columnar_payload(data)
        assert v is not None
        assert columnar.is_columnar(v)
    # JSON records, raw non-columnar records, garbage: None
    assert rec.peek_columnar_payload(
        rec.build_record({"k": "a"}).SerializeToString()) is None
    assert rec.peek_columnar_payload(
        rec.build_record(b"opaque").SerializeToString()) is None
    assert rec.peek_columnar_payload(b"\x99garbage") is None
    # a JSON-flagged record whose payload bytes open with the magic
    # must NOT masquerade as a column batch (flag check)
    forged = rec.build_record({"k": "a"})
    forged.payload = columnar.MAGIC + forged.payload
    assert rec.peek_columnar_payload(forged.SerializeToString()) is None


# ---- append front ---------------------------------------------------------

def test_append_front_per_log_fifo_and_errors():
    store = MemLogStore()
    store.create_log(1)
    store.create_log(2)
    front = AppendFront(store, lanes=2)
    try:
        futs = [front.submit(1 + (i % 2), [b"p%d" % i]) for i in range(20)]
        lsns = [f.result(timeout=5) for f in futs]
        # per-log order: each log's lsns are strictly increasing
        assert lsns[0::2] == sorted(lsns[0::2])
        assert lsns[1::2] == sorted(lsns[1::2])
        # an unknown log resolves to the store's exception, the lane
        # survives for the next submission
        bad = front.submit(999, [b"x"])
        with pytest.raises(Exception):
            bad.result(timeout=5)
        ok = front.submit(1, [b"tail"])
        assert ok.result(timeout=5) > 0
        st = front.stats()
        assert st["submitted"] == 22 and st["in_flight"] == 0
    finally:
        front.close()


# ---- server: equivalence + streaming + refusals ---------------------------

@contextlib.contextmanager
def _served(**options):
    server, ctx = serve("127.0.0.1", 0, "mem://", **options)
    channel = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    try:
        yield HStreamApiStub(channel), ctx
    finally:
        channel.close()
        server.stop(grace=1)
        ctx.shutdown()


@pytest.fixture(scope="module")
def server_stub():
    with _served() as pair:
        yield pair


def _mk_batches(n_batches, n, seed=3):
    rng = np.random.default_rng(seed)
    out = []
    for b in range(n_batches):
        ts = BASE + b * 1000 + np.sort(rng.integers(0, 1000, n)) \
            .astype(np.int64)
        cols = {"device": [f"d{i}" for i in
                           rng.integers(0, 7, n).tolist()],
                "temp": rng.normal(20, 5, n).astype(np.float32)}
        out.append((ts, cols))
    return out


def _view_rows(stub, view, pred, timeout=30):
    rows = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        resp = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text=f"SELECT * FROM {view};"))
        rows = [rec.struct_to_dict(s) for s in resp.result_set]
        if pred(rows):
            break
        time.sleep(0.2)
    return rows


def _mk_view(stub, ctx, view, src, metadata=None):
    stub.CreateStream(pb.Stream(stream_name=src))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text=f"CREATE VIEW {view} AS SELECT device, COUNT(*) AS c, "
                  f"SUM(temp) AS s FROM {src} "
                  f"GROUP BY device, TUMBLING (INTERVAL 10 SECOND) "
                  f"GRACE BY INTERVAL 0 SECOND;"), metadata=metadata)
    return wait_attached(ctx, f"view-{view}")


def test_framed_equals_protobuf_append(server_stub):
    """THE equivalence contract: the same micro-batches through the
    protobuf Append path and the framed AppendColumnar path land the
    same rows (byte-identical view results) under the same record ids
    (fresh streams -> same LSN sequence)."""
    stub, ctx = server_stub
    _mk_view(stub, ctx, "eqpb", "eqsrc_pb")
    _mk_view(stub, ctx, "eqfr", "eqsrc_fr")
    batches = _mk_batches(5, 512)
    closer = (np.array([BASE + 60_000], np.int64),
              {"device": ["zz"], "temp": np.array([1.0], np.float32)})
    pb_ids, fr_ids = [], []
    for ts, cols in batches + [closer]:
        req = pb.AppendRequest(stream_name="eqsrc_pb")
        req.records.append(rec.build_columnar_record(ts, cols))
        r = stub.Append(req)
        pb_ids.extend((i.batch_id, i.batch_index) for i in r.record_ids)
    for ts, cols in batches + [closer]:
        r = stub.AppendColumnar(pb.AppendColumnarRequest(
            stream_name="eqsrc_fr", blocks=[encode_batch(ts, cols)]))
        fr_ids.extend((i.batch_id, i.batch_index) for i in r.record_ids)
        assert r.rows == len(ts)
    assert fr_ids == pb_ids

    def done(rows):
        return sum(r["c"] for r in rows
                   if r.get("winStart", -1) >= 0) >= 5 * 512

    rows_pb = _view_rows(stub, "eqpb", done)
    rows_fr = _view_rows(stub, "eqfr", done)
    key = lambda r: (r.get("winStart"), r.get("device"))  # noqa: E731
    assert sorted(rows_pb, key=key) == sorted(rows_fr, key=key)
    # the 5 data batches, excluding the closer's own window
    assert sum(r["c"] for r in rows_pb
               if r.get("winStart") < BASE + 60_000) == 5 * 512


def test_streaming_append_one_call_many_batches(server_stub):
    stub, ctx = server_stub
    _mk_view(stub, ctx, "stv", "stsrc")
    batches = _mk_batches(8, 256, seed=11)
    prod = ColumnarProducer(f"127.0.0.1:{ctx.port}", "stsrc")
    try:
        resp = prod.append_stream(iter(batches))
        assert resp.rows == 8 * 256
        assert len(resp.record_ids) == 8
        lsns = [i.batch_id for i in resp.record_ids]
        assert lsns == sorted(lsns)  # submission order preserved
        prod.append(np.array([BASE + 60_000], np.int64),
                    {"device": ["zz"], "temp": np.array([1.0], np.float32)})
    finally:
        prod.close()
    rows = _view_rows(
        stub, "stv",
        lambda rs: sum(r["c"] for r in rs if "c" in r) >= 8 * 256)
    assert sum(r["c"] for r in rows
               if r.get("winStart") < BASE + 60_000) == 8 * 256


def test_served_steady_state_compiles_nothing(retrace_guard):
    """The served retrace gate (ISSUE 12): AppendColumnarStream -> frame
    door -> append front -> store -> query task -> staged device step ->
    window close hits only shapes the warm-up compiled, over 50 steady
    batches. Tracing runs at sample rate 1 (every call carries a request
    id, so RPC and task-stage spans record), the stats reporter folds
    and the placer loop sweeps DURING the guarded run, and the guarded
    region scrapes the stats planes: all host-only by construction. The
    lock witness and the device-time sampler stay disarmed throughout,
    and must come out of a served run holding no state at all (the
    one-attribute-read + one-branch disarmed contract)."""
    from hstream_tpu.common.locktrace import LOCKTRACE
    from hstream_tpu.stats.devicecost import DEVICE_TIME
    from hstream_tpu.stats.prometheus import render_metrics

    assert not LOCKTRACE.active and not DEVICE_TIME.active
    warm, steady = 20, 50
    # four batches cycled, each at its own offsets inside its second:
    # stable wire combos -> stable shapes (fresh random data a batch
    # would legitimately grow a new combo mid-run)
    uniq = _mk_batches(4, 512)

    def frame(b):
        ts, cols = uniq[b % 4]
        ts = ts + (b - b % 4) * 1000
        return int(ts[-1]), encode_batch(ts, cols)

    with _served(trace_sample=1.0, load_report_interval_ms=500,
                 placer_interval_ms=200) as (stub, ctx):
        task = _mk_view(stub, ctx, "rtv", "rtsrc",
                        metadata=(("x-request-id", "retrace-create"),))

        def stream_batches(lo, hi):
            frames = [frame(b) for b in range(lo, hi)]
            stub.AppendColumnarStream(
                iter([pb.AppendColumnarRequest(stream_name="rtsrc",
                                               blocks=[f])
                      for _last, f in frames]),
                metadata=(("x-request-id", f"retrace-{lo}"),))
            wait_watermark(task, frames[-1][0])

        for b in range(3):  # slow path first: one batch per poll
            last, f = frame(b)
            stub.AppendColumnar(pb.AppendColumnarRequest(
                stream_name="rtsrc", blocks=[f]))
            wait_watermark(task, last)
        stream_batches(3, warm)  # burst: spans window closes
        with retrace_guard():
            stream_batches(warm, warm + steady)
            render_metrics(ctx)
            for verb, args in (("stats", {"entity": "streams"}),
                               ("placer", {})):
                stub.SendAdminCommand(pb.AdminCommandRequest(
                    command=verb, args=rec.dict_to_struct(args)))
            stub.ClusterStats(pb.ClusterStatsRequest())
        # the planes were armed, not just configured
        assert ctx.tracing.spans("rtsrc") and ctx.tracing.spans("view-rtv")
        assert ctx.placer.armed
    assert LOCKTRACE.edge_count() == 0
    assert not LOCKTRACE.status()["locks"]
    state = DEVICE_TIME.state()
    assert not any(state["counts"].values())
    assert not any(state["samples"].values())


def test_bad_frame_refused_no_partial_ingest(server_stub):
    """A request mixing a good and a bad frame is refused atomically:
    INVALID_ARGUMENT and NOT ONE row of the good frame lands."""
    stub, ctx = server_stub
    _mk_view(stub, ctx, "badv", "badfr")
    (ts, cols), = _mk_batches(1, 64, seed=7)
    good = encode_batch(ts, cols)
    bad = good[:-3]  # torn
    with pytest.raises(grpc.RpcError) as ei:
        stub.AppendColumnar(pb.AppendColumnarRequest(
            stream_name="badfr", blocks=[good, bad]))
    assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    # non-frame garbage and a forged inner header refuse the same way
    for junk in (b"junk", colframe.encode_frame(b"not columnar")):
        with pytest.raises(grpc.RpcError) as ei:
            stub.AppendColumnar(pb.AppendColumnarRequest(
                stream_name="badfr", blocks=[junk]))
        assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    # the stream is untouched: nothing was appended
    logid = ctx.streams.get_logid("badfr")
    from hstream_tpu.store.api import LSN_INVALID

    assert ctx.store.tail_lsn(logid) == LSN_INVALID
    # and a correct append afterwards still works
    r = stub.AppendColumnar(pb.AppendColumnarRequest(
        stream_name="badfr", blocks=[good]))
    assert r.rows == 64


def test_framed_nulls_reach_engine_like_absent_fields(server_stub):
    """Null-masked cells on the framed path behave exactly like fields
    a per-record producer never sent: WHERE temp > 0 sees them NULL."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="nulsrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW nulv AS SELECT device, COUNT(*) AS c "
                  "FROM nulsrc WHERE temp > 0 "
                  "GROUP BY device, TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-nulv")
    n = 40
    ts = BASE + np.arange(n, dtype=np.int64)
    cols = {"device": ["d0"] * n,
            "temp": np.ones(n, np.float32)}
    nulls = {"temp": (np.arange(n) % 4 == 0)}  # 10 masked out
    stub.AppendColumnar(pb.AppendColumnarRequest(
        stream_name="nulsrc", blocks=[encode_batch(ts, cols, nulls)]))
    stub.AppendColumnar(pb.AppendColumnarRequest(
        stream_name="nulsrc",
        blocks=[encode_batch(np.array([BASE + 60_000], np.int64),
                             {"device": ["zz"],
                              "temp": np.array([1.0], np.float32)})]))
    rows = _view_rows(
        stub, "nulv",
        lambda rs: any(r.get("device") == "d0" and r.get("c") == 30
                       for r in rs))
    assert any(r.get("c") == 30 for r in rows), rows


def test_framed_append_admission_and_stats(server_stub):
    """Flow admission gates the framed path (rows+bytes charged), and
    the per-stage append timings land in the stage histograms."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="quotsrc"))
    (ts, cols), = _mk_batches(1, 128, seed=5)
    stub.AppendColumnar(pb.AppendColumnarRequest(
        stream_name="quotsrc", blocks=[encode_batch(ts, cols)]))
    # stage timings observed (decode/admit/handoff/store)
    for stage in ("append_decode", "append_admit", "append_handoff",
                  "append_store"):
        assert ctx.stats.histogram_percentile(
            "stage_latency_ms", stage, 50) is not None, stage
    assert ctx.stats.stream_stat_get(
        "append_columnar_rows", "quotsrc") == 128
    # 1 rec/s quota, burst 1: the second framed append is refused
    from hstream_tpu.flow import Quota

    ctx.flow.set_quota("stream/quotsrc",
                       Quota(records_per_s=1.0, burst_records=1.0))
    try:
        # debt-based bucket: the first append is admitted (driving the
        # bucket into debt), the next refused with retry-after
        stub.AppendColumnar(pb.AppendColumnarRequest(
            stream_name="quotsrc", blocks=[encode_batch(ts, cols)]))
        with pytest.raises(grpc.RpcError) as ei:
            stub.AppendColumnar(pb.AppendColumnarRequest(
                stream_name="quotsrc", blocks=[encode_batch(ts, cols)]))
        assert ei.value.code() == grpc.StatusCode.RESOURCE_EXHAUSTED
    finally:
        ctx.flow.unset_quota("stream/quotsrc")


def test_multi_block_request_is_one_atomic_store_batch(server_stub):
    """All blocks of one request share ONE LSN (like protobuf Append):
    a store failure mid-request can never partially ingest it."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="mbatom"))
    batches = _mk_batches(3, 32, seed=13)
    r = stub.AppendColumnar(pb.AppendColumnarRequest(
        stream_name="mbatom",
        blocks=[encode_batch(ts, cols) for ts, cols in batches]))
    assert r.rows == 3 * 32
    assert len(r.record_ids) == 3
    assert len({i.batch_id for i in r.record_ids}) == 1
    assert [i.batch_index for i in r.record_ids] == [0, 1, 2]
    logid = ctx.streams.get_logid("mbatom")
    reader = ctx.store.new_reader()
    reader.set_timeout(0)
    reader.start_reading(logid, 0)
    (item,) = reader.read(8)
    assert len(item.payloads) == 3
    assert all(rec.peek_columnar_payload(p) is not None
               for p in item.payloads)


def test_append_front_on_replicated_store_honors_compression():
    """ISSUE 12 review: ReplicatedStore.append_async used to reject the
    compression argument, killing the whole framed path on replicated
    deployments."""
    from hstream_tpu.store.api import Compression
    from hstream_tpu.store.replica import ReplicatedStore

    store = ReplicatedStore(MemLogStore(), [], replication_factor=1)
    try:
        store.create_log(7)
        front = AppendFront(store)
        assert front.stats()["async"] is True
        fut = front.submit(7, [b"abc", b"def"], Compression.ZLIB)
        lsn = fut.result(timeout=10)
        assert lsn > 0
        assert front.stats()["in_flight"] == 0
        front.close()
        reader = store.new_reader()
        reader.set_timeout(0)
        reader.start_reading(7, 0)
        (item,) = reader.read(4)
        assert item.payloads == (b"abc", b"def")
    finally:
        store.close()


def test_gateway_append_columnar_route(server_stub):
    """POST /streams/<name>/appendColumnar proxies the raw frame; a bad
    frame comes back 400 (INVALID_ARGUMENT mapping)."""
    from hstream_tpu.http_gateway import Gateway

    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="gwfr"))
    gw = Gateway(f"127.0.0.1:{ctx.port}")
    try:
        ts = BASE + np.arange(5, dtype=np.int64)
        frame = encode_batch(ts, {"k": ["a"] * 5})
        code, out = gw.handle("POST", "/streams/gwfr/appendColumnar",
                              frame)[:2]
        assert code == 200 and out["rows"] == 5
        assert len(out["record_ids"]) == 1
        code, out = gw.handle("POST", "/streams/gwfr/appendColumnar",
                              frame[:-2])[:2]
        assert code == 400
        code, out = gw.handle("POST", "/streams/gwfr/appendColumnar",
                              None)[:2]
        assert code == 400
    finally:
        gw.close()


def test_framed_rows_visible_to_subscriptions(server_stub):
    """The framed path stores a NORMAL columnar record: existing
    consumers (Fetch) read it unchanged."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="subfr"))
    ts = BASE + np.arange(3, dtype=np.int64)
    stub.AppendColumnar(pb.AppendColumnarRequest(
        stream_name="subfr",
        blocks=[encode_batch(ts, {"k": ["a", "b", "c"]})]))
    stub.CreateSubscription(pb.Subscription(
        subscription_id="subfr-s", stream_name="subfr"))
    got = stub.Fetch(pb.FetchRequest(subscription_id="subfr-s",
                                     timeout_ms=2000, max_size=4))
    # the subscription wire expands a columnar record per-row (PR 5's
    # _expand_columnar): consumers see ordinary per-row records with
    # the per-row timestamps
    recs = [rec.parse_record(r.record) for r in got.received_records]
    assert [rec.record_to_dict(r)["k"] for r in recs] == ["a", "b", "c"]
    assert [r.header.publish_time_ms for r in recs] == list(ts)
    # a null-masked cell is absent from the delivered row too
    stub.AppendColumnar(pb.AppendColumnarRequest(
        stream_name="subfr",
        blocks=[encode_batch(
            np.array([BASE + 9], np.int64),
            {"k": ["d"], "v": np.array([7.0], np.float32)},
            {"v": np.array([True])})]))
    got = stub.Fetch(pb.FetchRequest(subscription_id="subfr-s",
                                     timeout_ms=2000, max_size=4))
    (only,) = [rec.record_to_dict(rec.parse_record(r.record))
               for r in got.received_records]
    assert only == {"k": "d"}


# ---- the door reads a header's dictionaries natively (ISSUE 34) -----------

REFUSED = [(name, payload) for name, payload, _ in header_cases()
           if whole_header_outcome(payload)[0] == "raises"]


@pytest.mark.parametrize("name, payload", REFUSED,
                         ids=[c[0] for c in REFUSED])
def test_a_refused_header_stores_nothing(server_stub, name, payload):
    """Validate-all-then-submit, whichever way a header is read: a good
    frame in the same request as a block the whole-header parse refuses
    is refused with it, and the log's tail does not move."""
    stub, ctx = server_stub
    stream = "refused_headers"
    if not ctx.streams.stream_exists(stream):
        stub.CreateStream(pb.Stream(stream_name=stream))
    logid = ctx.streams.get_logid(stream)
    tail = ctx.store.tail_lsn(logid)
    (ts, cols), = _mk_batches(1, 8, seed=9)
    with pytest.raises(grpc.RpcError) as ei:
        stub.AppendColumnar(pb.AppendColumnarRequest(
            stream_name=stream,
            blocks=[encode_batch(ts, cols), colframe.encode_frame(payload)]))
    assert ei.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    assert ctx.store.tail_lsn(logid) == tail
    assert ctx.append_front.stats()["in_flight"] == 0


def _admin_stats(stub, entity: str) -> dict:
    import json

    resp = stub.SendAdminCommand(pb.AdminCommandRequest(
        command="stats", args=rec.dict_to_struct({"entity": entity})))
    return json.loads(resp.result)


def test_the_door_counts_how_each_header_was_read(server_stub):
    """`append_headers_lazy` / `append_headers_eager`, a block: the
    client encoder's headers are scanned natively; one whose strings
    carry an escape is parsed whole, and both land."""
    from hstream_tpu.common import jsondec
    from hstream_tpu.stats.prometheus import render_metrics

    if jsondec.load() is None:
        pytest.skip("no toolchain for the native library")
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="hdrcount"))
    (ts, cols), = _mk_batches(1, 16, seed=2)
    escaped = columnar_block(
        b'{"n":2,"cols":[["device","str"]],'
        b'"dicts":{"device":["tab\\there","b"]}}',
        np.array([0, 1], np.int32))
    r = stub.AppendColumnar(pb.AppendColumnarRequest(
        stream_name="hdrcount",
        blocks=[encode_batch(ts, cols), encode_batch(ts, cols),
                colframe.encode_frame(escaped)]))
    assert r.rows == 34
    assert ctx.stats.stat_ladder(
        "append_headers_lazy", "hdrcount")["total"] == 2
    assert ctx.stats.stat_ladder(
        "append_headers_eager", "hdrcount")["total"] == 1
    row = _admin_stats(stub, "streams")["hdrcount"]
    assert (row["append_headers_lazy_total"],
            row["append_headers_eager_total"]) == (2, 1)
    text = render_metrics(ctx)
    assert 'hstream_append_headers_lazy_rate{stream="hdrcount"}' in text
    assert 'hstream_append_headers_eager_rate{stream="hdrcount"}' in text


@pytest.mark.parametrize("kind, select", [
    ("session", "SELECT bidder, COUNT(*) AS bids FROM {src} "
                "GROUP BY bidder, SESSION (INTERVAL 10 SECOND) "
                "GRACE BY INTERVAL 0 SECOND"),
    ("qualify", "SELECT auction, COUNT(*) AS num FROM {src} "
                "GROUP BY auction, HOPPING (INTERVAL 10 SECOND, "
                "INTERVAL 2 SECOND) GRACE BY INTERVAL 0 SECOND "
                "QUALIFY COUNT(*) >= MAX(COUNT(*)) "
                "OVER (PARTITION BY winStart, winEnd)"),
], ids=["session", "qualify"])
def test_a_string_column_no_plan_reads_is_built_once_for_the_schema(
        server_stub, kind, select):
    """NEXmark's bids carry `extra`, a string a row that neither query
    names: the first batch builds its dictionary (the schema sample
    reads every column), no later batch does, and the door builds none.
    `dictionaries_built` is the count, in `admin stats queries`."""
    from hstream_tpu.common import jsondec

    if jsondec.load() is None:
        pytest.skip("no toolchain for the native library")
    stub, ctx = server_stub
    src, view = f"bids_{kind}", f"unread_{kind}"
    stub.CreateStream(pb.Stream(stream_name=src))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text=f"CREATE VIEW {view} AS {select.format(src=src)};"))
    task = wait_attached(ctx, f"view-{view}")
    qid = task.info.query_id
    rng = np.random.default_rng(5)
    n, sent = 64, 0

    def total(family: str) -> int:
        return int(ctx.stats.stat_ladder(family, qid)["total"])

    def built() -> int:
        return total("dictionaries_built")

    def send(i: int) -> None:
        nonlocal sent
        ts = BASE + i * 1500 + np.sort(rng.integers(0, 1500, n)) \
            .astype(np.int64)
        cols = {"auction": rng.integers(1000, 1010, n),
                "bidder": rng.integers(1, 20, n),
                "extra": [f"x{i}-{j}" for j in range(n)]}
        stub.AppendColumnar(pb.AppendColumnarRequest(
            stream_name=src, blocks=[encode_batch(ts, cols)]))
        sent += n
        deadline = time.time() + 60
        while total("consumed_events") < sent:
            assert time.time() < deadline and task.error is None
            time.sleep(0.01)

    send(0)
    deadline = time.time() + 30
    while built() < 1 and time.time() < deadline:
        time.sleep(0.01)
    assert built() == 1          # `extra`, for the schema sample
    for i in range(1, 6):
        send(i)
    # the task is one thread: with batch 5 counted as consumed, batches
    # 1-4 have been stepped and their dictionaries counted
    assert built() == 1
    assert ctx.stats.stat_ladder(
        "append_headers_lazy", src)["total"] == 6
    assert ctx.stats.stat_ladder(
        "append_headers_eager", src)["total"] == 0
    assert _admin_stats(stub, "queries")[qid][
        "dictionaries_built_total"] == 1
