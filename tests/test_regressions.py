"""Regression tests pinned to reproduced bugs (round-3 ADVICE/VERDICT):

(a) materialized-view row keys built from string-typed values only, so
    distinct numeric group keys collided on (winStart, ()) and silently
    overwrote each other (data loss in pull queries);
(b) subscription dispatch dropped a fetched batch on a full consumer
    queue AFTER it was noted in the AckWindow — never redelivered while
    the server runs, ack lower bound stalled;
(c) executor.peek() called from gRPC threads while the query task
    mutates state concurrently (unsynchronized _open/state access).

(d) — read checkpoints committed before windows close — is covered by
the operator-state checkpoint/resume tests in test_checkpoint_resume.py.
"""

import queue
import threading
import time

import grpc
import pytest

from hstream_tpu.common import records as rec
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.server.main import serve

from helpers import wait_attached
from hstream_tpu.server.views import Materialization

BASE = 1_700_000_000_000


@pytest.fixture()
def server_stub():
    server, ctx = serve("127.0.0.1", 0, "mem://")
    channel = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    stub = HStreamApiStub(channel)
    yield stub, ctx
    channel.close()
    server.stop(grace=1)
    ctx.shutdown()


def append_rows(stub, stream, rows, ts):
    req = pb.AppendRequest(stream_name=stream)
    for row, t in zip(rows, ts):
        req.records.append(rec.build_record(row, publish_time_ms=t))
    return stub.Append(req)


# ---- (a) numeric group keys must not collide in view row keys ---------------


def test_view_rowkey_distinct_numeric_groups():
    mat = Materialization(group_cols=["k"])
    mat.add_closed([
        {"k": 1, "c": 5, "winStart": BASE, "winEnd": BASE + 10},
        {"k": 2, "c": 7, "winStart": BASE, "winEnd": BASE + 10},
    ])
    rows = mat.snapshot()
    assert len(rows) == 2, "distinct numeric group keys must both survive"
    assert {r["k"] for r in rows} == {1, 2}


def test_view_rowkey_updates_same_group():
    mat = Materialization(group_cols=["k"])
    mat.add_closed([{"k": 1, "c": 5, "winStart": BASE}])
    mat.add_closed([{"k": 1, "c": 9, "winStart": BASE}])
    rows = mat.snapshot()
    assert len(rows) == 1 and rows[0]["c"] == 9


def test_view_rowkey_stateless_keeps_every_row():
    mat = Materialization(group_cols=None)
    mat.add_closed([{"a": 1}, {"a": 1}])  # identical rows, no group identity
    assert len(mat.snapshot()) == 2


def test_view_pull_query_numeric_group_key(server_stub):
    """End-to-end: a view grouped on a numeric column serves every
    distinct key (pre-fix: all numeric keys collapsed to one row)."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="numsrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW numview AS SELECT sensor, COUNT(*) AS c "
                  "FROM numsrc GROUP BY sensor, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-numview")
    append_rows(stub, "numsrc",
                [{"sensor": 1, "v": 1.0}, {"sensor": 2, "v": 2.0},
                 {"sensor": 2, "v": 3.0}],
                [BASE, BASE + 1, BASE + 2])
    # window-closer
    append_rows(stub, "numsrc", [{"sensor": 9, "v": 0.0}], [BASE + 30_000])
    deadline = time.time() + 30
    rows = []
    while time.time() < deadline:
        resp = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="SELECT * FROM numview;"))
        rows = [rec.struct_to_dict(s) for s in resp.result_set]
        closed = [r for r in rows if r.get("winStart") == BASE]
        if len({r.get("sensor") for r in closed}) >= 2:
            break
        time.sleep(0.2)
    closed = [r for r in rows if r.get("winStart") == BASE]
    sensors = {r.get("sensor") for r in closed}
    assert {1, 2} <= sensors, rows
    by_sensor = {r["sensor"]: r["c"] for r in closed}
    assert by_sensor[1] == 1 and by_sensor[2] == 2


def test_emitted_group_cols_resolves_aliases():
    """Aliased group keys emit under the alias: the view row key must use
    the emitted name, not the plan column name (else every group's
    row.get('city') is None and all groups collapse again)."""
    from hstream_tpu.sql.codegen import emitted_group_cols, stream_codegen

    plan = stream_codegen(
        "SELECT city AS c, COUNT(*) AS n FROM s GROUP BY city, "
        "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;")
    assert emitted_group_cols(plan.node) == ["c"]
    plain = stream_codegen(
        "SELECT city, COUNT(*) FROM s GROUP BY city, "
        "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;")
    assert emitted_group_cols(plain.node) == ["city"]


def test_view_pull_query_aliased_group_key(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="aliassrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW aliasview AS SELECT city AS c, "
                  "COUNT(*) AS n FROM aliassrc GROUP BY city, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-aliasview")
    append_rows(stub, "aliassrc",
                [{"city": "sf"}, {"city": "la"}, {"city": "la"}],
                [BASE, BASE + 1, BASE + 2])
    append_rows(stub, "aliassrc", [{"city": "zz"}], [BASE + 30_000])
    deadline = time.time() + 30
    rows = []
    while time.time() < deadline:
        resp = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="SELECT * FROM aliasview;"))
        rows = [rec.struct_to_dict(s) for s in resp.result_set]
        closed = [r for r in rows if r.get("winStart") == BASE]
        if len({r.get("c") for r in closed}) >= 2:
            break
        time.sleep(0.2)
    closed = {r["c"]: r["n"] for r in rows if r.get("winStart") == BASE}
    assert closed.get("sf") == 1 and closed.get("la") == 2, rows


# ---- (b) dispatch must never drop a noted batch -----------------------------


def _wait(cond, timeout=10.0, step=0.02):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(step)
    return False


def test_dispatch_reoffers_when_consumer_queue_full(server_stub,
                                                    monkeypatch):
    """A batch that finds the consumer queue full is re-offered, not
    dropped: every appended record is eventually delivered."""
    import hstream_tpu.server.subscriptions as subs

    orig_init = subs.Consumer.__init__

    def tiny_init(self, name, credit_window=0):
        orig_init(self, name, credit_window)
        self.queue = queue.Queue(maxsize=1)  # force queue-full quickly

    monkeypatch.setattr(subs.Consumer, "__init__", tiny_init)

    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="slowsub"))
    off = pb.SubscriptionOffset(special_offset=0)  # EARLIEST
    stub.CreateSubscription(pb.Subscription(
        subscription_id="slow1", stream_name="slowsub", offset=off))
    rt = ctx.subscriptions.get("slow1")

    append_rows(stub, "slowsub", [{"n": 0}], [BASE])
    consumer = rt.register_consumer("c0")
    # wave 1 lands in the 1-slot queue; don't consume it yet
    assert _wait(lambda: not consumer.queue.empty())
    # wave 2: the dispatcher fetches + notes it, finds the queue full,
    # and must keep re-offering instead of dropping
    append_rows(stub, "slowsub", [{"n": 1}], [BASE + 1])
    time.sleep(0.6)  # several put timeouts elapse while the queue is full

    got = []
    deadline = time.time() + 10
    while time.time() < deadline and len(got) < 2:
        try:
            batch = consumer.queue.get(timeout=0.5)
        except queue.Empty:
            continue
        for rid, payload in batch:
            got.append((rid,
                        rec.record_to_dict(rec.parse_record(payload))["n"]))
    assert sorted(n for _, n in got) == [0, 1], got

    # ack everything: the lower bound must advance (no stall)
    rt.ack([rid for rid, _ in got])
    tail = ctx.store.tail_lsn(rt.logid)
    assert rt.committed_lsn >= tail - 1


def test_dead_consumer_batches_are_redelivered(server_stub):
    """Batches sitting in a dead consumer's queue are reclaimed and
    redelivered to the next consumer (pre-fix: lost until restart)."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="dcsub"))
    off = pb.SubscriptionOffset(special_offset=0)
    stub.CreateSubscription(pb.Subscription(
        subscription_id="dc1", stream_name="dcsub", offset=off))
    rt = ctx.subscriptions.get("dc1")

    append_rows(stub, "dcsub", [{"n": 1}, {"n": 2}], [BASE, BASE + 1])
    c1 = rt.register_consumer("c1")
    assert _wait(lambda: not c1.queue.empty())
    rt.unregister_consumer(c1)  # dies with undelivered batches queued

    c2 = rt.register_consumer("c2")
    got = []
    deadline = time.time() + 10
    while time.time() < deadline and len(got) < 2:
        try:
            batch = c2.queue.get(timeout=0.5)
        except queue.Empty:
            continue
        for rid, payload in batch:
            got.append(rec.record_to_dict(rec.parse_record(payload))["n"])
    assert sorted(got) == [1, 2]


# ---- (c) pull queries racing the query task ---------------------------------


def test_view_peek_concurrent_with_ingest(server_stub):
    """Hammer pull queries while the query task is mid-aggregation; no
    request may fail (pre-fix: unlocked iteration over mutating state)."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="racesrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW raceview AS SELECT city, COUNT(*) AS c "
                  "FROM racesrc GROUP BY city, "
                  "TUMBLING (INTERVAL 1 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-raceview")
    errors: list[BaseException] = []
    stop = threading.Event()

    def producer():
        t = BASE
        i = 0
        while not stop.is_set():
            try:
                append_rows(stub, "racesrc",
                            [{"city": f"c{i % 7}", "v": 1.0}
                             for _ in range(16)],
                            [t + j for j in range(16)])
            except grpc.RpcError as e:  # noqa: PERF203
                errors.append(e)
                return
            t += 1500  # advance past window close every other batch
            i += 1

    def puller():
        while not stop.is_set():
            try:
                stub.ExecuteQuery(pb.CommandQuery(
                    stmt_text="SELECT * FROM raceview;"))
            except grpc.RpcError as e:  # noqa: PERF203
                errors.append(e)
                return

    threads = [threading.Thread(target=producer, daemon=True)] + \
        [threading.Thread(target=puller, daemon=True) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(3.0)
    stop.set()
    for t in threads:
        t.join(10)
    assert not errors, [str(e) for e in errors]


# ---- (f) sink columnar records must reach subscribers as JSON rows ----------


def test_subscription_expands_packed_columnar(server_stub):
    """A columnar-packed record (what stream_sink emits for >=32-row
    batches) must be delivered to Fetch consumers as individual JSON
    records, not one opaque RAW blob."""
    import numpy as np

    from hstream_tpu.common import columnar

    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="packed"))
    rows = [{"k": f"x{i}", "c": i} for i in range(40)]
    payload = columnar.rows_to_payload(rows, BASE)
    assert payload is not None
    req = pb.AppendRequest(stream_name="packed")
    req.records.append(rec.build_record(payload, publish_time_ms=BASE))
    stub.Append(req)
    stub.CreateSubscription(pb.Subscription(
        subscription_id="sub-packed", stream_name="packed"))
    got = stub.Fetch(pb.FetchRequest(
        subscription_id="sub-packed", timeout_ms=2000, max_size=100))
    recs = got.received_records
    assert len(recs) == 40, len(recs)
    seen = []
    for rr in recs:
        r = rec.parse_record(rr.record)
        assert r.header.flag == rec.pb.RECORD_FLAG_JSON
        seen.append(rec.record_to_dict(r))
    assert seen == rows
    # ack indices over the expanded space commit cleanly
    stub.Acknowledge(pb.AcknowledgeRequest(
        subscription_id="sub-packed",
        ack_ids=[rr.record_id for rr in recs]))


# ---- (g) batch decode row shape matches per-record decode -------------------


def test_to_rows_drop_null_matches_per_record_shape():
    import numpy as np

    from hstream_tpu.common import columnar

    ts = np.array([BASE, BASE + 1], np.int64)
    cols = {"a": ("f64", np.array([1.0, 0.0]), None),
            "b": ("f64", np.array([0.0, 2.0]), None)}
    nulls = {"a": np.array([False, True]),
             "b": np.array([True, False])}
    rows = columnar.to_rows(ts, cols, nulls, drop_null=True)
    assert rows == [{"a": 1}, {"b": 2}]
    # default keeps explicit Nones (sink/gateway consumers)
    rows = columnar.to_rows(ts, cols, nulls)
    assert rows == [{"a": 1, "b": None}, {"a": None, "b": 2}]


# ---- (h) bool group keys: only present values registered --------------------


def test_bool_group_key_no_phantom_ids():
    import numpy as np

    from hstream_tpu.engine import (
        AggKind, AggSpec, AggregateNode, ColumnType, QueryExecutor,
        Schema, SourceNode, TumblingWindow)
    from hstream_tpu.engine.expr import Col
    from hstream_tpu.server.tasks import _columnar_key_ids

    schema = Schema.of(flag=ColumnType.BOOL, v=ColumnType.FLOAT)
    node = AggregateNode(
        child=SourceNode("s", schema), group_keys=[Col("flag")],
        window=TumblingWindow(10_000, grace_ms=0),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c")])
    ex = QueryExecutor(node, schema, emit_changes=False,
                       initial_keys=4, batch_capacity=64)
    cols = {"flag": ("bool", np.ones(8, np.bool_), None)}
    kids = _columnar_key_ids(ex, cols, 8)
    assert len(set(kids.tolist())) == 1
    assert len(ex._key_rev) == 1  # no phantom False key registered


def test_to_rows_empty_payload_records_preserved():
    import numpy as np

    from hstream_tpu.common import columnar

    ts = np.array([BASE, BASE + 1, BASE + 2], np.int64)
    assert columnar.to_rows(ts, {}, {}) == [{}, {}, {}]


def test_empty_columnar_record_delivered_verbatim(server_stub):
    """A zero-row columnar record must NOT expand to an empty batch
    (which would park the ack window forever) — it is delivered as the
    one opaque record it is, and the checkpoint still advances."""
    import numpy as np

    from hstream_tpu.common import columnar

    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="edgy"))
    empty = columnar.encode_columnar(np.empty(0, np.int64), {})
    req = pb.AppendRequest(stream_name="edgy")
    req.records.append(rec.build_record(empty, publish_time_ms=BASE))
    req.records.append(rec.build_record({"k": "a"}, publish_time_ms=BASE))
    stub.Append(req)
    stub.CreateSubscription(pb.Subscription(
        subscription_id="sub-edgy", stream_name="edgy"))
    got = stub.Fetch(pb.FetchRequest(
        subscription_id="sub-edgy", timeout_ms=2000, max_size=10))
    assert len(got.received_records) == 2
    stub.Acknowledge(pb.AcknowledgeRequest(
        subscription_id="sub-edgy",
        ack_ids=[rr.record_id for rr in got.received_records]))
    rt = ctx.subscriptions.get("sub-edgy")
    assert rt.committed_lsn > 0  # ack window advanced


# ---- ISSUE 4: defects found by hstream-analyze ------------------------------


class _TrackingLock:
    """Duck-typed lock/condition wrapper counting acquisitions, so a
    test can pin 'this read holds the lock' without relying on a race
    the GIL usually masks."""

    def __init__(self, inner):
        self._inner = inner
        self.entered = 0

    def __enter__(self):
        self.entered += 1
        return self._inner.__enter__()

    def __exit__(self, *exc):
        return self._inner.__exit__(*exc)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_subscription_shutdown_joins_dispatcher(server_stub):
    """resource-leak fix: remove() must reap the dispatcher thread —
    pre-fix it was only signalled, so DeleteSubscription could return
    while the loop was still mid-fetch against deleted state."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="reaped"))
    stub.CreateSubscription(pb.Subscription(
        subscription_id="reap1", stream_name="reaped",
        offset=pb.SubscriptionOffset(special_offset=0)))
    rt = ctx.subscriptions.get("reap1")
    rt.register_consumer("c0")
    assert _wait(lambda: rt._dispatcher is not None
                 and rt._dispatcher.is_alive())
    dispatcher = rt._dispatcher
    ctx.subscriptions.remove("reap1")  # -> rt.shutdown()
    assert not dispatcher.is_alive(), \
        "shutdown() returned with the dispatcher still running"


def test_subscription_committed_lsn_reads_under_lock(server_stub):
    """lock-guard fix: committed_lsn is written under rt.lock by the
    fetch/ack paths; the observability read must hold it too."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="lockedsub"))
    stub.CreateSubscription(pb.Subscription(
        subscription_id="ls1", stream_name="lockedsub",
        offset=pb.SubscriptionOffset(special_offset=0)))
    rt = ctx.subscriptions.get("ls1")
    rt.lock = _TrackingLock(rt.lock)
    before = rt.lock.entered
    assert rt.committed_lsn == 0
    assert rt.lock.entered == before + 1


def test_replica_oplog_seq_reads_under_cond():
    """lock-guard fix: ReplicatedStore._seq is written under _cond by
    appender threads; follower_status/oplog_seq must read it locked."""
    from hstream_tpu.store.memstore import MemLogStore
    from hstream_tpu.store.replica import ReplicatedStore

    store = ReplicatedStore(MemLogStore(), [], replication_factor=1)
    try:
        store.create_log(42)
        store.append_batch(42, [b"x"])
        store._cond = _TrackingLock(store._cond)
        before = store._cond.entered
        seq = store.oplog_seq
        assert seq >= 2  # create + append both logged
        assert store._cond.entered == before + 1
    finally:
        store.close()


def test_credit_available_reads_under_cv():
    """lock-guard fix: CreditWindow._avail is mutated under _cv by the
    dispatcher and ack threads; the gauge read must hold it."""
    from hstream_tpu.flow import CreditWindow

    cw = CreditWindow(8)
    assert cw.take_up_to(3) == 3
    cw._cv = _TrackingLock(cw._cv)
    before = cw._cv.entered
    assert cw.available == 5
    assert cw._cv.entered == before + 1


def test_store_dir_bytes_walk_is_ttl_bounded(tmp_path, monkeypatch):
    """blocking-hot fix: the scrape-path store-footprint walk runs at
    most once per TTL — pre-fix every /metrics hit walked the whole
    store directory tree."""
    import os as _os

    from hstream_tpu.stats import prometheus as prom

    (tmp_path / "seg1.dat").write_bytes(b"x" * 10)
    (tmp_path / "wal.log").write_bytes(b"y" * 4)
    prom._dir_bytes_cache.clear()
    calls = {"n": 0}
    real_walk = _os.walk

    def counting_walk(*a, **kw):
        calls["n"] += 1
        return real_walk(*a, **kw)

    monkeypatch.setattr(prom.os, "walk", counting_walk)
    assert prom._store_dir_bytes(str(tmp_path)) == (10, 4)
    assert prom._store_dir_bytes(str(tmp_path)) == (10, 4)
    assert calls["n"] == 1, "second scrape inside the TTL re-walked"
    # expiry: age the cache entry past the TTL -> one more walk
    ts, val = prom._dir_bytes_cache[str(tmp_path)]
    prom._dir_bytes_cache[str(tmp_path)] = (
        ts - prom._DIR_BYTES_TTL_S - 1, val)
    prom._store_dir_bytes(str(tmp_path))
    assert calls["n"] == 2
    prom._dir_bytes_cache.clear()


def test_retry_policy_honors_classification():
    """err-retry-class fix: retryability is an explicit table now.
    Only RESOURCE_EXHAUSTED (a pre-work refusal, duplication-safe)
    retries; NOT_FOUND and a mid-call UNAVAILABLE (which may have
    landed a mutation without a response) fail on the first attempt."""
    from hstream_tpu.client.retry import RetryPolicy, is_retryable

    class FakeErr(grpc.RpcError):
        def __init__(self, code):
            self._code = code

        def code(self):
            return self._code

        def details(self):
            return ""

        def trailing_metadata(self):
            return ()

    assert is_retryable(grpc.StatusCode.RESOURCE_EXHAUSTED)
    assert not is_retryable(grpc.StatusCode.NOT_FOUND)
    assert not is_retryable(grpc.StatusCode.INTERNAL)
    # a mid-call transport drop may have landed a mutation: a blind
    # resend could duplicate it, so it is classified non-retryable
    assert not is_retryable(grpc.StatusCode.UNAVAILABLE)

    attempts = {"n": 0}

    def throttled():
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise FakeErr(grpc.StatusCode.RESOURCE_EXHAUSTED)
        return "ok"

    pol = RetryPolicy(attempts=5, sleep=lambda s: None)
    assert pol.call(throttled) == "ok"
    assert pol.retries == 2

    for code in (grpc.StatusCode.NOT_FOUND, grpc.StatusCode.UNAVAILABLE):
        attempts["n"] = 0

        def hard():
            attempts["n"] += 1
            raise FakeErr(code)

        with pytest.raises(grpc.RpcError):
            pol.call(hard)
        assert attempts["n"] == 1, f"{code} must not retry"


# ---- ISSUE 7: defects found by the kernel-contract passes -------------------


def test_compact_codes_fetches_once_and_preserves_results():
    """dispatch-sync fix: device-mode _compact_codes fetched each
    side's code plane in a per-side loop (two round trips on the
    ingest path); it now stacks both sides into ONE transfer. The
    compaction must still remap codes exactly — results after a manual
    compaction match the host reference run bit-for-bit."""
    from tests.test_join_device import (
        final_changes,
        gen_batches,
        make_join,
        run_batches,
    )

    batches = gen_batches(seed=23, n_batches=10)
    host = make_join(use_device_join=False)
    href = final_changes(run_batches(host, batches))

    dev = make_join()
    out = []
    for i, (rows, ts, side) in enumerate(batches):
        out.extend(dev.process(rows, ts, stream=side))
        if i == 6:
            assert dev._dev is not None, "device path not active yet"
            dev._compact_codes()  # forced mid-stream compaction
    out.extend(dev.flush_changes())
    assert final_changes(out) == href


def test_migrate_store_int32_span_guard():
    """overflow-narrowing fix: device activation migrates host stores
    with `(st.ts - t0).astype(np.int32)` — the host store's 2^41 span
    guard allows ranges int32 cannot hold, so a join whose retention
    spans > 2^31 ms must trip the guard at activation instead of
    silently wrapping every probe bound. Since ISSUE 8 the tripped
    guard degrades the QUERY to the retained host reference path
    (which allows the full 2^41 span exactly) rather than killing it:
    `_activate_device` still raises SQLCodegenError loudly, but
    `_device_ready` catches it, counts device_fallbacks, and the join
    keeps producing correct results on the host path."""
    from hstream_tpu.common.errors import SQLCodegenError
    from tests.test_join_device import BASE, make_join

    # WITHIN 30000000s ~ 3e10 ms: retention exceeds int32 range
    sql = ("SELECT l.k, COUNT(*) AS c, SUM(l.x) AS s FROM l INNER "
           "JOIN r WITHIN (INTERVAL 30000000 SECOND) ON l.k = r.k "
           "GROUP BY l.k, TUMBLING (INTERVAL 10 SECOND) "
           "GRACE BY INTERVAL 0 SECOND EMIT CHANGES;")
    ex = make_join(sql=sql)
    rows = [{"k": "k0", "x": 1.0}]
    # two resident entries > 2^31 ms apart (both within retention)
    ex.process(rows, [BASE], stream="l")
    ex.process(rows, [BASE + (1 << 31) + 500_000], stream="l")
    # first match builds the inner executor and plans the fast path
    ex.process(rows, [BASE + (1 << 31) + 600_000], stream="r")
    # the migration layer fails LOUDLY on the un-narrowable span ...
    fast = ex._fast_info()
    assert fast is not None, "fast path did not plan"
    with pytest.raises(SQLCodegenError, match="int32"):
        ex._activate_device(fast)
    # activation is not exception-atomic; _device_ready's except
    # clause owns the cleanup on the real path — undo the partial
    # activation so the retry below goes through it
    ex._dev = None
    # ... and the query layer degrades to the host path instead of
    # dying: the next batch retries activation through _device_ready,
    # catches the guard, and carries on exactly
    out = ex.process(rows, [BASE + (1 << 31) + 700_000], stream="r")
    assert ex.device_fallbacks == 1
    assert ex.use_device_join is False and ex._dev is None
    assert out is not None
    ex.process(rows, [BASE + (1 << 31) + 800_000], stream="r")
    assert ex.device_fallbacks == 1  # no re-activation attempts


# ---- ISSUE 8: fault injection + self-healing hardening ----------------------


def test_file_checkpoint_store_corrupt_json_recovers_boot(tmp_path):
    """FileCheckpointStore.__init__ did a bare json.load — a truncated
    or torn file raised at construction and prevented server boot. It
    must now recover to an EMPTY store (readers rewind to their trim
    points), preserve the corrupt bytes next to the path, and record
    load_error so the owner can journal checkpoint_corrupt."""
    from hstream_tpu.store import FileCheckpointStore

    path = str(tmp_path / "ckp.json")
    torn = b'{"query-q1": {"7": 123}, "query-q2": {"8"'
    with open(path, "wb") as f:
        f.write(torn)
    st = FileCheckpointStore(path)  # must NOT raise
    assert st.load_error is not None
    assert st.get("query-q1", 7) is None  # empty: rewind, not guess
    with open(path + ".corrupt", "rb") as f:
        assert f.read() == torn  # forensic copy preserved
    # the store works after recovery and persists durably again
    st.update("query-q1", 7, 55)
    assert FileCheckpointStore(path).get("query-q1", 7) == 55


def test_file_checkpoint_store_non_dict_root_recovers(tmp_path):
    """A valid-JSON-but-wrong-shape file (e.g. a list) is corruption
    too: recover empty instead of exploding on the first .items()."""
    from hstream_tpu.store import FileCheckpointStore

    path = str(tmp_path / "ckp.json")
    with open(path, "w") as f:
        f.write("[1, 2, 3]")
    st = FileCheckpointStore(path)
    assert st.load_error is not None
    assert st.get("query-x", 1) is None


def test_follower_reconnect_backoff_grows_jittered_capped():
    """_Follower._run retried a dead peer every fixed 1s — a flapping
    follower now gets jittered exponential backoff: strictly growing
    waits (2x steps beat the 25% jitter), a hard cap, a seeded
    per-address jitter stream (chaos runs replay identical waits), and
    a reset once a connect succeeds."""
    from hstream_tpu.store.replica import (
        _RETRY_CAP_S,
        _RETRY_JITTER,
        _RETRY_S,
        _Follower,
    )

    f = _Follower("127.0.0.1:19999", owner=None)
    waits = [f._backoff() for _ in range(12)]
    lo, hi = 1 - _RETRY_JITTER, 1 + _RETRY_JITTER
    assert _RETRY_S * lo <= waits[0] <= _RETRY_S * hi
    for a, b in zip(waits, waits[1:6]):
        assert b > a  # growth dominates jitter until the cap
    for w in waits[8:]:
        assert _RETRY_CAP_S * lo <= w <= _RETRY_CAP_S * hi
    # seeded per address: a rebuilt follower replays the same waits
    rebuilt = _Follower("127.0.0.1:19999", owner=None)
    assert [rebuilt._backoff() for _ in range(12)] == waits
    # an acked Replicate resets the schedule (what _stream does on
    # progress — a peer that merely ACCEPTS connections but fails
    # every Replicate keeps backing off)
    f.connect_attempts = 0
    assert f._backoff() <= _RETRY_S * hi


def test_try_adopt_race_yields_exactly_one_owner():
    """Two successor contexts racing the meta CAS for the same dead
    owner's query: exactly one may win; the loser must journal an
    adoption_lost event and stand down (return False). The barrier
    holds both racers between their config read and their CAS write,
    so both see the same base version — the true race interleaving."""
    from hstream_tpu.server import scheduler
    from hstream_tpu.server.context import ServerContext
    from hstream_tpu.store import open_store
    from hstream_tpu.store.versioned import VersionedConfigStore

    store = open_store("mem://")
    dead = ServerContext(store)
    scheduler.record_assignment(dead, "q-race")  # the owner that died
    a = ServerContext(store, persistence=dead.persistence)
    b = ServerContext(store, persistence=dead.persistence)
    assert dead.boot_epoch < a.boot_epoch < b.boot_epoch

    barrier = threading.Barrier(2, timeout=10)
    orig_put = VersionedConfigStore.put

    def racing_put(self, *args, **kwargs):
        barrier.wait()  # both racers read before either writes
        return orig_put(self, *args, **kwargs)

    results = {}

    def race(name, ctx):
        results[name] = scheduler.try_adopt(ctx, "q-race")

    VersionedConfigStore.put = racing_put
    try:
        ta = threading.Thread(target=race, args=("a", a))
        tb = threading.Thread(target=race, args=("b", b))
        ta.start(); tb.start(); ta.join(10); tb.join(10)
    finally:
        VersionedConfigStore.put = orig_put
    assert sorted(results.values()) == [False, True], results
    winner, loser = (a, b) if results["a"] else (b, a)
    # the winner's claim stands in the config store
    owner = scheduler.assignment(winner, "q-race")
    assert owner["epoch"] == winner.boot_epoch
    # the loser journaled its stand-down for the operator timeline
    lost = loser.events.query(kind="adoption_lost")
    assert lost and lost[-1]["query"] == "q-race"
    assert not winner.events.query(kind="adoption_lost")


class _SupPersistence:
    """Minimal persistence for QuerySupervisor unit tests: every query
    reads back RUNNING (never terminated while pending)."""

    def get_query(self, qid):
        from hstream_tpu.server.persistence import QueryInfo, TaskStatus

        return QueryInfo(qid, "select 1", 0, status=TaskStatus.RUNNING)

    def set_query_status(self, qid, status):
        pass


class _SupCtx:
    def __init__(self):
        self.running_queries = {}
        self.persistence = _SupPersistence()


def test_supervisor_corpse_teardown_requeues_instead_of_dropping():
    """note_death fires from the dying task's except block, but the
    corpse pops running_queries LAST — its finally joins reader/persist
    threads, which can outlast the ~0.2s first backoff. A restart
    attempt that finds the dead task (``.error`` set) still registered
    must requeue, not mistake the corpse for a live operator-owned task
    and drop the restart forever; a task without ``.error`` really is
    operator-owned and the restart stands down."""
    from hstream_tpu.server.persistence import QueryInfo
    from hstream_tpu.server.scheduler import QuerySupervisor

    class _Corpse:
        error = RuntimeError("died mid-batch")

    class _OperatorTask:
        error = None

    ctx = _SupCtx()
    clock = [100.0]
    sup = QuerySupervisor(ctx, clock=lambda: clock[0])
    resumed = []
    sup.resume_fn = resumed.append
    info = QueryInfo("q-corpse", "select 1", 0)

    ctx.running_queries["q-corpse"] = _Corpse()
    sup._attempt_restart("q-corpse", info, 1)
    assert not resumed
    assert "q-corpse" in sup.status()["pending"]  # requeued, not lost
    # corpse finished tearing down: the requeued attempt lands
    sup._pending.pop("q-corpse")  # what the loop does at dispatch
    del ctx.running_queries["q-corpse"]
    sup._attempt_restart("q-corpse", info, 1)
    assert [i.query_id for i in resumed] == ["q-corpse"]
    assert sup.restarts == 1
    # a LIVE operator-started task (no .error) keeps ownership
    ctx.running_queries["q-corpse"] = _OperatorTask()
    sup._attempt_restart("q-corpse", info, 2)
    assert len(resumed) == 1
    assert "q-corpse" not in sup.status()["pending"]


def test_supervisor_cancel_waits_out_inflight_restart():
    """TerminateQuery racing an executing restart: the restart is
    marked in-flight when it is popped from pending, and cancel()
    blocks until it finishes — so the terminate path always runs AFTER
    any resurrect and the task it pops from running_queries is the
    final one (no deleted query springing back to RUNNING)."""
    from hstream_tpu.server.persistence import QueryInfo
    from hstream_tpu.server.scheduler import QuerySupervisor

    release = threading.Event()
    in_resume = threading.Event()

    def resume(info):
        in_resume.set()
        assert release.wait(5)

    sup = QuerySupervisor(_SupCtx(), resume_fn=resume)
    sup.BACKOFF_BASE_S = 0.01
    sup.BACKOFF_CAP_S = 0.05
    try:
        sup.note_death(QueryInfo("q-term", "select 1", 0))
        assert in_resume.wait(5), sup.status()
        cancel_done = threading.Event()

        def terminate():
            sup.cancel("q-term")
            cancel_done.set()

        t = threading.Thread(target=terminate)
        t.start()
        # the restart is still executing: cancel must not return yet
        assert not cancel_done.wait(0.3)
        release.set()
        assert cancel_done.wait(5)  # returns once the resurrect landed
        t.join(5)
        assert sup.status()["pending"] == {}
        assert sup.restarts == 1
    finally:
        release.set()
        sup.shutdown()


def test_query_labeled_counters_survive_live_stream_filter():
    """/metrics liveness filter vs query-labeled counters: the
    query_restarts / snapshot_fallbacks series are labeled by QUERY id,
    which is never a live stream name — the filter silently dropped
    them from the exposition (found by the PR 8 verify drive: a
    supervised restart bumped the counter but /metrics showed no
    series). They are exempt from the STREAM filter, like "_"-prefixed
    pseudo-streams, but bounded by QUERY existence instead — a deleted
    query's series must not grow the exposition forever."""
    from hstream_tpu.stats import StatsHolder
    from hstream_tpu.stats.prometheus import render_holder

    stats = StatsHolder()
    stats.stream_stat_add("query_restarts", "view-v1")
    stats.stream_stat_add("snapshot_fallbacks", "view-v1")
    stats.stream_stat_add("device_path_fallbacks", "src")   # stream-labeled
    stats.stream_stat_add("device_path_fallbacks", "gone")  # deleted stream
    text = render_holder(stats, live_streams={"src"})
    assert 'hstream_query_restarts_total{stream="view-v1"} 1' in text
    assert 'hstream_snapshot_fallbacks_total{stream="view-v1"} 1' in text
    assert 'hstream_device_path_fallbacks_total{stream="src"} 1' in text
    assert '"gone"' not in text  # liveness filter still applies
    # the query-labeled exemption is bounded by query existence: a
    # still-persisted (even FAILED) query keeps its series, a DELETED
    # query's series are pruned from the scrape
    text = render_holder(stats, live_streams={"src"},
                         live_queries={"view-v1"})
    assert 'hstream_query_restarts_total{stream="view-v1"} 1' in text
    text = render_holder(stats, live_streams={"src"}, live_queries=set())
    assert '"view-v1"' not in text


# ---- ISSUE 9: epoch-fenced failover hardening -------------------------------


def test_supervisor_stands_down_on_leadership_loss():
    """A task that dies of NotLeaderError must NOT be restart-looped:
    this node's store was fenced, every restart would die identically
    and burn the crash-loop breaker. The supervisor stands down
    (journaling the fencing) and leaves the replicated RUNNING record
    for the new leader's boot to adopt; ordinary deaths still
    schedule restarts."""
    from hstream_tpu.common.errors import NotLeaderError
    from hstream_tpu.server.persistence import QueryInfo
    from hstream_tpu.server.scheduler import QuerySupervisor
    from hstream_tpu.stats.events import EventJournal

    ctx = _SupCtx()
    ctx.events = EventJournal()
    sup = QuerySupervisor(ctx)
    info = QueryInfo("q-fenced", "select 1", 0)
    try:
        for _ in range(10):  # repeated fencing never opens the breaker
            sup.note_death(info, NotLeaderError(
                "store leadership lost", leader_hint="new:1"))
        st = sup.status()
        assert st["pending"] == {}
        assert st["breaker_open"] == []
        assert st["restarts"] == 0
        events = ctx.events.query(kind="replica_fenced", limit=20)
        assert events and events[0]["leader_hint"] == "new:1"
        # a plain crash on the same query still schedules a restart
        sup.note_death(info, RuntimeError("boom"))
        assert "q-fenced" in sup.status()["pending"]
    finally:
        sup.shutdown()


def test_supervisor_status_pending_is_sorted():
    """Operator/chaos assertions diff `admin supervisor` output: the
    pending map must come back sorted by query id, not in death
    order."""
    from hstream_tpu.server.persistence import QueryInfo
    from hstream_tpu.server.scheduler import QuerySupervisor

    ctx = _SupCtx()
    clock = [100.0]
    sup = QuerySupervisor(ctx, clock=lambda: clock[0])
    try:
        for qid in ("q-z", "q-a", "q-m"):
            sup.note_death(QueryInfo(qid, "select 1", 0))
        assert list(sup.status()["pending"]) == ["q-a", "q-m", "q-z"]
    finally:
        sup.shutdown()


def test_replica_divergence_checked_before_mutation():
    """_apply must detect an LSN mismatch BEFORE appending: the old
    order landed the batch and then raised, so every sender retry of
    the same entry grew the diverged replica's log further."""
    import pytest

    from hstream_tpu.common.errors import ReplicaDivergence
    from hstream_tpu.store import open_store
    from hstream_tpu.store.replica import _apply

    st = open_store("mem://")
    st.create_log(9)
    st.append(9, b"existing")
    e = pb.LogEntry(op=pb.OP_APPEND, logid=9, payloads=[b"x"],
                    expect_lsn=5)  # tail is 1; 5 expects tail 4
    for _ in range(3):  # retries must not mutate either
        with pytest.raises(ReplicaDivergence):
            _apply(st, e)
    assert st.tail_lsn(9) == 1  # nothing landed
    st.close()

def test_dedup_seq_zero_first_append_accepted():
    """Review fix: the empty dedup watermark is -1, not 0 — seq 0 is a
    legal first stamp (and the proto3 default when only producer_id is
    set), so a 0-based producer's very first append must be accepted,
    not refused ALREADY_EXISTS as an evicted duplicate."""
    from hstream_tpu.store import dedup, open_store

    st = open_store("mem://")
    assert dedup.lookup(st, "p-zero", 0) is None  # new, not duplicate
    dedup.record(st, "p-zero", 0, 17, 3)
    assert dedup.lookup(st, "p-zero", 0) == (17, 3)  # now remembered
    st.close()


def test_malformed_producer_seq_refused_not_unstamped():
    """Review fix: a stamped ExecuteQuery whose x-producer-seq does not
    parse must be refused INVALID_ARGUMENT — silently running the
    INSERT unstamped would let the client's retry double-append while
    it believes it has exactly-once."""
    import pytest

    from hstream_tpu.common.errors import SQLValidateError
    from hstream_tpu.server.handlers import _producer_from

    class _Ctx:
        def __init__(self, md):
            self._md = md

        def invocation_metadata(self):
            return self._md

    with pytest.raises(SQLValidateError):
        _producer_from(_Ctx([("x-producer-id", "p1"),
                             ("x-producer-seq", "0x2a")]))
    # well-formed stamp still parses; absent stamp still None
    assert _producer_from(_Ctx([("x-producer-id", "p1"),
                                ("x-producer-seq", "42")])) == ("p1", 42)
    assert _producer_from(_Ctx([])) is None


def test_auto_promote_lease_floored_above_heartbeat():
    """Review fix: a lease below the idle-heartbeat cadence would fence
    a healthy idle leader between two heartbeats — FollowerService
    clamps it to 3x _HEARTBEAT_S."""
    from hstream_tpu.store import open_store
    from hstream_tpu.store.replica import _HEARTBEAT_S, FollowerService

    st = open_store("mem://")
    svc = FollowerService(st, node_id="floor-f", lease_timeout_s=0.05)
    try:
        assert svc.lease_timeout_s == _HEARTBEAT_S * 3
    finally:
        svc.close()
        st.close()


def test_auto_promotion_hint_prefers_advertise_addr():
    """Review fix: the auto-promotion leader hint must be the
    client-facing SQL address (--advertise-addr), not the replica's
    StoreReplica listen port — a client following the raw replica
    address would fail UNIMPLEMENTED."""
    from hstream_tpu.store import open_store
    from hstream_tpu.store.replica import FollowerService

    st = open_store("mem://")
    svc = FollowerService(st, node_id="adv-f", listen_addr="repl:1",
                          advertise_addr="sql:1")
    try:
        svc._promote_locked(1, "", "lease-timeout")
        assert svc._leader_hint == "sql:1"
        info = svc.ReplicaInfo(pb.ReplicaInfoRequest(), None)
        assert info.leader_hint == "sql:1"
    finally:
        svc.close()
        st.close()


def test_gateway_rebind_retires_old_channel_instead_of_closing():
    """Review fix: the gateway's leader-hint rebind must not close the
    shared channel out from under concurrent handler threads mid-RPC —
    the old channel is retired and closed only at gateway shutdown."""
    from hstream_tpu.http_gateway import Gateway

    gw = Gateway("127.0.0.1:1")
    old = gw.channel
    gw._follow_leader_hint("127.0.0.1:2")
    assert gw.server_addr == "127.0.0.1:2"
    assert gw.channel is not old and gw._retired == [old]
    assert gw.leader_follows == 1
    # same-hint re-follow is a no-op (concurrent callers rebind once)
    gw._follow_leader_hint("127.0.0.1:2")
    assert gw.leader_follows == 1
    gw.close()
    assert gw._retired == []


# ---- ISSUE 14: concurrency certification ------------------------------------
#
# The three new passes (lockorder/atomicity/waitholding) came up CLEAN
# on the tree — the expected candidates (supervisor corpse/cancel,
# gateway rebind, append-front close) had been fixed by hand in the
# PR 8/11 review rounds, and the passes now pin those shapes via
# fixtures in test_analyze. What this section pins is the live-tree
# contracts behind that verdict: the canonical lock ORDER the static
# graph documents, the one reviewed waiver, and the witness's
# disarmed-cost contract on the real instrumented subsystems.


def test_lockorder_real_tree_graph_acyclic_with_canonical_edges():
    """The whole-program lock graph of THIS tree resolves the
    documented cross-object orders (tasks.state before
    views.materialization via Materialization.snapshot; the scrape
    lock before the gauge internals) and stays acyclic. If the
    cross-class typing regresses these edges vanish; if someone
    introduces an inversion the cycle list goes non-empty — both fail
    here before CI's analyze step even runs."""
    import os
    import sys

    REPO_ROOT = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    sys.path.insert(0, REPO_ROOT)
    from tools.analyze import load_tree
    from tools.analyze.passes import conc, lockorder

    files = load_tree(REPO_ROOT)
    prog = conc.build_program(files)
    edges = lockorder._collect_edges(files, prog)
    got = set(edges)
    assert ("QueryTask.state_lock", "Materialization._lock") in got
    assert ("StatsHolder.scrape_lock", "StatsHolder._gauge_lock") in got
    assert lockorder._cycles(edges) == []


def test_witness_certifies_task_before_materialization_order():
    """The live (armed) witness observes the canonical order on the
    REAL objects: sink-under-state_lock then snapshot both take
    tasks.state before views.materialization — one direction, no
    cycle, and the ledger carries both lock roles."""
    from hstream_tpu.common import locktrace
    from hstream_tpu.common.locktrace import LOCKTRACE

    LOCKTRACE.disarm()
    LOCKTRACE.arm()
    try:
        mat = Materialization(group_cols=["k"])

        class _Task:
            state_lock = locktrace.rlock("tasks.state")
            executor = None

        task = _Task()
        mat.task = task
        # the sink path: task emits closed rows under its state lock
        with task.state_lock:
            mat.add_closed([{"k": "a", "winStart": 1}])
        # the pull path: snapshot takes state_lock then mat._lock
        assert mat.snapshot() == [{"k": "a", "winStart": 1}]
        st = LOCKTRACE.status()
        assert st["edges"].get("tasks.state") == \
            ["views.materialization"]
        assert "views.materialization" not in st["edges"]
        assert st["cycles"] == []
        assert {"tasks.state", "views.materialization"} <= \
            set(st["locks"])
    finally:
        LOCKTRACE.disarm()


def test_witness_disarmed_records_nothing_on_real_subsystems():
    """Disarmed-cost contract on the real instrumented objects: a
    subscription-registry + materialization + supervisor workout with
    the witness disarmed leaves ZERO witness state."""
    from hstream_tpu.common.locktrace import LOCKTRACE
    from hstream_tpu.server.subscriptions import SubscriptionRegistry

    LOCKTRACE.disarm()
    reg = SubscriptionRegistry()
    assert reg.exists("nope") is False
    mat = Materialization(group_cols=["k"])
    mat.add_closed([{"k": "a", "winStart": 1}])
    assert mat.dump() == [{"k": "a", "winStart": 1}]
    st = LOCKTRACE.status()
    assert st["locks"] == {} and st["edges"] == {} \
        and st["cycles"] == []


# ---- ISSUE 16: multi-chip exclusions retired for JOIN + sessions ------------


def test_mesh_exclusions_join_and_sessions_retired():
    """Interval joins and session windows are mesh-sharded since
    ISSUE 16: the retired exclusion strings must be GONE from the
    shared predicate (source pin — a revert would resurrect them
    silently, EXPLAIN and the runtime gate share the predicate),
    while the two remaining exclusions (TOPK planes, stream-TABLE
    joins) must still fire."""
    import inspect

    from hstream_tpu.sql import codegen as cg

    src = inspect.getsource(cg)
    # retired with the sharded join/session lattices
    assert "two-sided host state" not in src
    assert "single-chip session lattice" not in src
    assert "sharded execution of JOIN plans is not supported" not in src

    plan = cg.stream_codegen(
        "SELECT l.k, COUNT(*) AS c FROM l INNER JOIN r "
        "WITHIN (INTERVAL 1 SECOND) ON l.k = r.k GROUP BY l.k, "
        "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;")
    assert cg.mesh_exclusion_reason(plan) is None
    assert "MESH: shardable" in cg.explain_text(plan)

    plan = cg.stream_codegen(
        "SELECT k, COUNT(*) AS c FROM s GROUP BY k, "
        "SESSION (INTERVAL 5 SECOND) EMIT CHANGES;")
    assert cg.mesh_exclusion_reason(plan) is None
    assert "MESH: shardable" in cg.explain_text(plan)

    # the remaining exclusions stay pinned PRESENT
    plan = cg.stream_codegen(
        "SELECT k, TOPK(v, 3) AS t FROM s GROUP BY k, "
        "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;")
    reason = cg.mesh_exclusion_reason(plan)
    assert reason is not None and "TOPK" in reason

    plan = cg.stream_codegen(
        "SELECT l.k, COUNT(*) AS c FROM l INNER JOIN TABLE(t) "
        "ON l.k = t.k GROUP BY l.k, "
        "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;")
    reason = cg.mesh_exclusion_reason(plan)
    assert reason is not None and "stream-TABLE" in reason


# ---- ISSUE 19: protocol certification — triage verdicts pinned --------------
#
# The casdiscipline/timeunit triage found NO true positives on the live
# tree: every finding is a reviewed single-writer exception in
# store/replica.py (waivers pinned load-bearing in test_analyze.py).
# What the waivers LEAN ON is behavior, so the behavior is pinned here:
# each test below is the runtime fact that makes one reviewed waiver
# (or one certified invariant) sound.


def test_placer_lease_clamped_to_three_intervals():
    """The clamp the `cas-lease-raw` rule protects: a lease below 3x
    the placer tick is raised at construction, so no age comparison
    ever runs against a sub-interval lease."""
    from hstream_tpu.placer.core import Placer

    p = Placer(object(), interval_ms=2000, lease_ms=2000)
    assert p.armed and p.lease_ms == 6000
    # a sane lease is untouched, and a disarmed placer never clamps
    assert Placer(object(), interval_ms=1000, lease_ms=5000).lease_ms \
        == 5000
    assert Placer(object(), interval_ms=None, lease_ms=2000).lease_ms \
        == 2000


def test_live_adoption_refuses_fresh_heartbeat():
    """The fresh-lease refusal in try_adopt_live (protocheck mutant
    `fresh-heartbeat-refusal`): an adopt sweep must NOT seize a query
    whose owner heartbeated within the lease."""
    from tools.protocheck.model import SCENARIOS, Model

    model = Model(SCENARIOS["kill-2"])
    with model.engaged():
        pre = model.sched_records()
        model.execute(("adopt", 0))
        post = model.sched_records()
        # both records untouched: every owner's heartbeat is 0ms old
        assert {q: r for q, (_raw, r) in post.items()} == \
            {q: r for q, (_raw, r) in pre.items()}


def test_promote_epoch_guard_keeps_durable_epoch():
    """The guard backing the `cas-epoch-nonmonotone` waiver on
    `_promote_locked`: Promote refuses epoch <= current BEFORE the
    bare assignment runs, so the durable epoch never moves backwards
    even though the write itself is unguarded."""
    from hstream_tpu.proto import api_pb2 as pb
    from tools.protocheck.replica_model import MiniLogStore, _GrpcCtx

    from hstream_tpu.store.replica import META_EPOCH, FollowerService

    f = FollowerService(MiniLogStore(), node_id="r1")
    ok = f.Promote(pb.PromoteRequest(epoch=2, leader_addr="a",
                                     promoted_by="t"), _GrpcCtx())
    assert ok.ok and f.epoch == 2
    again = f.Promote(pb.PromoteRequest(epoch=2, leader_addr="b",
                                        promoted_by="t"), _GrpcCtx())
    assert not again.ok
    assert f.epoch == 2 and f.local.meta_get(META_EPOCH) == b"2"


def test_fenced_replicate_leaves_binding_writes_unrun():
    """The fence backing the `cas-blind-meta-write` waivers in
    `_accept_leader_locked`: a stale-epoch Replicate is refused before
    ANY of the blind single-writer meta writes run, so the durable
    binding only ever changes under an accepted (higher-epoch)
    leader."""
    from hstream_tpu.proto import api_pb2 as pb
    from tools.protocheck.replica_model import MiniLogStore, _GrpcCtx

    from hstream_tpu.store.replica import FollowerService

    store = MiniLogStore()
    f = FollowerService(store, node_id="r1")
    r = f.Replicate(pb.ReplicateRequest(epoch=3, leader_id="L3"),
                    _GrpcCtx())
    assert not r.fenced
    before = store.fingerprint()
    stale = f.Replicate(pb.ReplicateRequest(epoch=2, leader_id="L2"),
                        _GrpcCtx())
    assert stale.fenced and stale.epoch == 3
    assert store.fingerprint() == before
