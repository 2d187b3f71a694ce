"""Read plane (ISSUE 20): snapshot cache exactness, bounded staleness,
closed-only fast path, columnwise/row serve parity, shared-encode
subscription fan-out, and a concurrent-reader exactness stress under
the armed lock-order witness.

The cache's contract is EXACT equality: a cached serve must be
byte-identical (canonical JSON) to the uncached pipeline at the same
version — across window closes, late data, and concurrent mutation.
"""

import json
import threading
import time

import grpc
import numpy as np
import pytest

from hstream_tpu.common import locktrace, records as rec
from hstream_tpu.common.columnar import ColumnarEmit
from hstream_tpu.common.locktrace import LOCKTRACE
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.server import views as views_mod
from hstream_tpu.server.context import ServerContext
from hstream_tpu.server.main import serve
from hstream_tpu.server.readcache import ReadCache
from hstream_tpu.server.views import (
    Materialization,
    filter_rows,
    project_rows,
    serve_select_view,
)
from hstream_tpu.sql.codegen import stream_codegen
from hstream_tpu.store import open_store

from helpers import smoke_tumbling_batch, wait_attached, wait_watermark

BASE = 1_700_000_000_000


def _pull(sql: str):
    """The SELECT of a pull-query statement (SelectViewPlan.select)."""
    return stream_codegen(sql).select


def _canon(rows) -> str:
    """Canonical byte form for exactness comparisons (numpy scalars
    normalize through `float`, dict order through sort_keys)."""
    return json.dumps(list(rows), sort_keys=True, default=float)


class _FakeEx:
    """Executor stand-in with the read-plane surface: a monotone
    read_version, a peek counter, and a controllable live floor."""

    def __init__(self, live_rows=None, live_lo=None):
        self.live_rows = list(live_rows or [])
        self.live_lo = live_lo
        self.peeks = 0
        self.ver = 0

    def peek(self):
        self.peeks += 1
        return list(self.live_rows)

    def read_version(self):
        return ("fake", id(self), self.ver)

    def live_min_win_end(self):
        return self.live_lo


class _FakeTask:
    def __init__(self, ex):
        self.state_lock = locktrace.rlock("tasks.state")
        self.executor = ex


def _view(ex, closed_rows=()):
    mat = Materialization(group_cols=["k"])
    mat.task = _FakeTask(ex)
    if closed_rows:
        mat.add_closed(list(closed_rows))
    return mat


# ---- snapshot cache: exactness + version invalidation -----------------------


def test_cache_hit_is_byte_identical_and_close_invalidates():
    ex = _FakeEx(live_rows=[{"k": "a", "c": 2, "winStart": BASE,
                             "winEnd": BASE + 10_000}])
    mat = _view(ex, [{"k": "a", "c": 5, "winStart": BASE - 10_000,
                      "winEnd": BASE}])
    sel = _pull("SELECT * FROM v;")
    cache = ReadCache()

    r1, how1, x1, _read = cache.serve_view("v", mat, sel, "q1")
    assert (how1, x1, ex.peeks) == ("miss", True, 1)
    r2, how2, x2, _read = cache.serve_view("v", mat, sel, "q1")
    assert (how2, x2, ex.peeks) == ("hit", False, 1)  # no second peek
    assert _canon(r1) == _canon(r2)
    # byte-identical to the uncached pipeline at the same version
    assert _canon(r2) == _canon(serve_select_view(mat, sel))

    # a window close mutates BOTH halves: closed store + executor epoch
    mat.add_closed([{"k": "a", "c": 7, "winStart": BASE,
                     "winEnd": BASE + 10_000}])
    ex.live_rows = []
    ex.ver += 1
    r3, how3, _, _read = cache.serve_view("v", mat, sel, "q1")
    assert how3 == "miss"  # version advanced -> stale entry invalid
    assert _canon(r3) == _canon(serve_select_view(mat, sel))
    assert any(r["c"] == 7 for r in r3)

    # late data changing only the executor half also invalidates
    ex.live_rows = [{"k": "a", "c": 1, "winStart": BASE + 10_000,
                     "winEnd": BASE + 20_000}]
    ex.ver += 1
    r4, how4, _, _read = cache.serve_view("v", mat, sel, "q1")
    assert how4 == "miss"
    assert _canon(r4) == _canon(serve_select_view(mat, sel))
    assert cache.hit_ratio() == pytest.approx(1 / 4)


def test_distinct_statements_cache_separately():
    ex = _FakeEx()
    mat = _view(ex, [{"k": "a", "c": 5, "winStart": BASE,
                      "winEnd": BASE + 10_000},
                     {"k": "b", "c": 9, "winStart": BASE,
                      "winEnd": BASE + 10_000}])
    cache = ReadCache()
    all_sel = _pull("SELECT * FROM v;")
    one_sel = _pull("SELECT * FROM v WHERE k = 'a';")
    rows_all, _, _, _read = cache.serve_view("v", mat, all_sel,
                                      "SELECT * FROM v;")
    rows_one, how, _, _read = cache.serve_view("v", mat, one_sel,
                                        "SELECT * FROM v WHERE k = 'a';")
    assert how == "miss"  # different statement, different entry
    assert len(rows_all) == 2 and len(rows_one) == 1
    assert _canon(rows_one) == _canon(serve_select_view(mat, one_sel))


def test_unversioned_executor_bypasses_cache():
    class _Bare:  # no read_version: exactness unprovable -> never cache
        def peek(self):
            return []

    mat = _view(_Bare(), [{"k": "a", "c": 1, "winStart": BASE,
                           "winEnd": BASE + 10_000}])
    cache = ReadCache()
    sel = _pull("SELECT * FROM v;")
    _, how1, x1, _read = cache.serve_view("v", mat, sel, "q")
    _, how2, x2, _read = cache.serve_view("v", mat, sel, "q")
    assert (how1, how2) == ("bypass", "bypass")
    assert x1 and x2 and cache.stats()["bypasses"] == 2


# ---- bounded staleness ------------------------------------------------------


def test_staleness_bound_expires_hits():
    now = [100.0]
    ex = _FakeEx()
    mat = _view(ex, [{"k": "a", "c": 1, "winStart": BASE,
                      "winEnd": BASE + 10_000}])
    sel = _pull("SELECT * FROM v;")
    cache = ReadCache(max_staleness_ms=250.0, clock=lambda: now[0])
    _, how1, _, _read = cache.serve_view("v", mat, sel, "q")
    now[0] += 0.2  # +200ms: inside the bound
    _, how2, _, _read = cache.serve_view("v", mat, sel, "q")
    now[0] += 0.2  # +400ms total: past the bound, version unchanged
    r3, how3, _, _read = cache.serve_view("v", mat, sel, "q")
    assert (how1, how2, how3) == ("miss", "hit", "miss")
    assert _canon(r3) == _canon(serve_select_view(mat, sel))
    # recompute restamps the entry: fresh again
    _, how4, _, _read = cache.serve_view("v", mat, sel, "q")
    assert how4 == "hit"


# ---- closed-only fast path (satellite: no executor touch) -------------------


def test_closed_only_where_skips_live_peek():
    closed = [{"k": "a", "c": 5, "winStart": BASE - 10_000,
               "winEnd": BASE}]
    ex = _FakeEx(live_rows=[{"k": "a", "c": 1, "winStart": BASE,
                             "winEnd": BASE + 10_000}],
                 live_lo=BASE + 10_000)
    mat = _view(ex, closed)
    # strictly below every live winEnd: the peek is provably empty
    sel = _pull(f"SELECT * FROM v WHERE winEnd <= {BASE};")
    rows = serve_select_view(mat, sel)
    assert ex.peeks == 0
    assert _canon(rows) == _canon(
        project_rows(filter_rows(closed, sel), sel,
                     keep_meta=("winStart", "winEnd")))
    # non-strict bound EQUAL to the live floor can match a live row:
    # the peek must run
    sel2 = _pull(f"SELECT * FROM v WHERE winEnd <= {BASE + 10_000};")
    rows2 = serve_select_view(mat, sel2)
    assert ex.peeks == 1
    assert any(r["winStart"] == BASE for r in rows2)
    # unbounded WHERE always peeks
    serve_select_view(mat, _pull("SELECT * FROM v WHERE c > 0;"))
    assert ex.peeks == 2


def test_closed_only_skips_real_executor_peek():
    """Against a REAL device-backed executor: a closed-bounded pull
    never extracts the arena (live_min_win_end is host arithmetic)."""
    from hstream_tpu.engine import (
        AggKind, AggSpec, AggregateNode, ColumnType, QueryExecutor,
        Schema, SourceNode, TumblingWindow,
    )
    from hstream_tpu.engine.expr import Col

    schema = Schema.of(k=ColumnType.STRING, v=ColumnType.FLOAT)
    node = AggregateNode(
        child=SourceNode(stream="s", schema=schema),
        group_keys=[Col("k")], window=TumblingWindow(10_000, grace_ms=0),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c")], having=None,
        post_projections=[])
    ex = QueryExecutor(node, schema, emit_changes=False, initial_keys=8,
                       batch_capacity=64)
    ex.process([{"k": "a"}, {"k": "b"}], [BASE, BASE + 1000])
    assert ex.live_min_win_end() == BASE + 10_000
    mat = _view(ex, [{"k": "z", "c": 1, "winStart": BASE - 10_000,
                      "winEnd": BASE}])
    mat.task.executor = ex
    peeks = []
    orig = ex.peek
    ex.peek = lambda: (peeks.append(1), orig())[1]
    closed_sel = _pull(f"SELECT * FROM v WHERE winEnd < {BASE + 1};")
    rows = serve_select_view(mat, closed_sel)
    assert peeks == [] and [r["k"] for r in rows] == ["z"]
    live_sel = _pull("SELECT * FROM v;")
    rows_all = serve_select_view(mat, live_sel)
    assert len(peeks) == 1 and {r["k"] for r in rows_all} == {"a", "b",
                                                             "z"}


# ---- columnwise serve parity ------------------------------------------------


def test_where_projection_columnwise_matches_row_path():
    emit = ColumnarEmit(
        {"k": np.array(["a", "b", "c", "d"], object),
         "c": np.array([1, 2, 3, 4], np.int64),
         "t": np.array([1.5, 2.5, 3.5, 4.5]),
         "winStart": np.full(4, BASE, np.int64),
         "winEnd": np.full(4, BASE + 10_000, np.int64)}, 4)
    for sql in ("SELECT * FROM v WHERE c > 1;",
                "SELECT k, c FROM v WHERE c >= 2 AND t < 4.0;",
                "SELECT k AS g, t FROM v;",
                "SELECT * FROM v WHERE k = 'b';",
                "SELECT k FROM v WHERE c > 100;"):
        sel = _pull(sql)
        got = views_mod._select_emit(emit, sel)
        want = project_rows(filter_rows(list(emit), sel), sel,
                            keep_meta=("winStart", "winEnd"))
        assert _canon(got) == _canon(want), sql


def test_columnwise_failure_falls_back_to_exact_rows(monkeypatch):
    emit = ColumnarEmit({"k": np.array(["a", "b"], object),
                         "c": np.array([1, 2], np.int64)}, 2)
    sel = _pull("SELECT * FROM v WHERE c > 1;")
    want = views_mod._select_emit(emit, sel)

    def boom(*a, **kw):
        raise RuntimeError("vector path down")

    monkeypatch.setattr(views_mod, "_select_emit_cols", boom)
    assert _canon(views_mod._select_emit(emit, sel)) == _canon(want)


# ---- budget / eviction / invalidation ---------------------------------------


def test_byte_budget_evicts_and_bounds():
    ex = _FakeEx()
    mat = _view(ex, [{"k": f"k{i}", "c": i, "winStart": BASE,
                      "winEnd": BASE + 10_000} for i in range(50)])
    cache = ReadCache(max_bytes=4096)
    for i in range(30):
        sql = f"SELECT * FROM v WHERE c = {i};"
        cache.serve_view("v", mat, _pull(sql), sql)
    assert cache.nbytes() <= 4096
    assert cache.stats()["evictions"] > 0


def test_drop_view_frees_budget():
    ex = _FakeEx()
    mat = _view(ex, [{"k": "a", "c": 1, "winStart": BASE,
                      "winEnd": BASE + 10_000}])
    cache = ReadCache()
    cache.serve_view("v", mat, _pull("SELECT * FROM v;"), "q")
    assert cache.nbytes() > 0
    cache.invalidate_view("v")
    assert cache.nbytes() == 0
    assert cache.stats()["invalidations"] == 1


# ---- shared-encode subscription fan-out -------------------------------------


def test_fanout_shares_expanded_frames_across_consumers():
    """One columnar sink record, N subscriptions: every consumer gets
    byte-identical frames that are the SAME objects (encode-once), and
    the expansion ran exactly once per payload."""
    from hstream_tpu.common import columnar

    N = 4
    ctx = ServerContext(open_store("mem://"))
    try:
        ctx.streams.create_stream("fanout")
        logid = ctx.streams.get_logid("fanout")
        rows = [{"k": f"g{i}", "c": i, "winStart": BASE + i}
                for i in range(16)]
        packed = columnar.rows_to_payload(rows, BASE)
        assert packed is not None
        ctx.store.append(logid, rec.build_record(packed)
                         .SerializeToString())
        fetched = []
        for i in range(N):
            rt = ctx.subscriptions.create(
                ctx, pb.Subscription(subscription_id=f"fo{i}",
                                     stream_name="fanout"))
            fetched.append(rt.fetch(timeout_ms=200, max_size=256))
        assert all(len(got) == len(rows) for got in fetched)
        first = fetched[0]
        for got in fetched[1:]:
            for (rid_a, pay_a), (rid_b, pay_b) in zip(first, got):
                assert rid_a == rid_b and pay_a == pay_b
                assert pay_a is pay_b  # shared BY REFERENCE
        st = ctx.read_cache.stats()
        assert st["expand_misses"] == 1
        assert st["expand_hits"] == N - 1
        # the delivered frames decode back to the emitted rows
        decoded = [rec.record_to_dict(rec.parse_record(p))
                   for _rid, p in first]
        assert decoded == rows
        # read_out_records carries the subscription drains
        ladder = ctx.stats.stat_ladder("read_out_records", "fanout")
        assert ladder["total"] == float(len(rows) * N)
    finally:
        ctx.shutdown()


def test_fanout_without_cache_still_serves():
    from hstream_tpu.common import columnar

    ctx = ServerContext(open_store("mem://"), read_cache_bytes=0)
    try:
        assert ctx.read_cache is None
        ctx.streams.create_stream("nocache")
        logid = ctx.streams.get_logid("nocache")
        packed = columnar.rows_to_payload(
            [{"k": "a", "c": 1}, {"k": "b", "c": 2}], BASE)
        ctx.store.append(logid, rec.build_record(packed)
                         .SerializeToString())
        rt = ctx.subscriptions.create(
            ctx, pb.Subscription(subscription_id="nc",
                                 stream_name="nocache"))
        got = rt.fetch(timeout_ms=200, max_size=256)
        assert [rec.record_to_dict(rec.parse_record(p))["k"]
                for _r, p in got] == ["a", "b"]
    finally:
        ctx.shutdown()


# ---- end-to-end: pull queries through the server ----------------------------


@pytest.fixture()
def server_stub():
    server, ctx = serve("127.0.0.1", 0, "mem://")
    channel = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    stub = HStreamApiStub(channel)
    yield stub, ctx
    channel.close()
    server.stop(grace=1)
    ctx.shutdown()


def _append(stub, stream, rows, ts):
    req = pb.AppendRequest(stream_name=stream)
    for row, t in zip(rows, ts):
        req.records.append(rec.build_record(row, publish_time_ms=t))
    stub.Append(req)


def test_pull_query_cached_end_to_end(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="rpsrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW rpview AS SELECT city, COUNT(*) AS c "
                  "FROM rpsrc GROUP BY city, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-rpview")
    _append(stub, "rpsrc", [{"city": "sf"}, {"city": "la"},
                            {"city": "la"}], [BASE, BASE + 1, BASE + 2])
    _append(stub, "rpsrc", [{"city": "zz"}], [BASE + 30_000])  # closer
    deadline = time.time() + 30
    rows = []
    while time.time() < deadline:
        resp = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="SELECT * FROM rpview;"))
        rows = [rec.struct_to_dict(s) for s in resp.result_set]
        # the closer lands in two engine steps (the gap guard closes
        # BASE's window, then the closer's own row goes live): wait
        # for both, or the quiesce below can settle between them
        if any(r.get("winStart") == BASE and r.get("city") == "la"
               and r.get("c") == 2 for r in rows) \
                and any(r.get("city") == "zz" for r in rows):
            break
        time.sleep(0.2)
    closed = {r["city"]: r["c"] for r in rows
              if r.get("winStart") == BASE}
    assert closed.get("sf") == 1 and closed.get("la") == 2, rows
    # quiesce: poll until two consecutive pulls agree byte-for-byte
    # (the engine may still be absorbing the closer record), then the
    # next pull must be a version-valid HIT with the identical answer
    def _pull_rows():
        resp = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="SELECT * FROM rpview;"))
        return [rec.struct_to_dict(s) for s in resp.result_set]

    deadline = time.time() + 10
    prev = _canon(rows)
    while time.time() < deadline:
        cur = _canon(_pull_rows())
        if cur == prev:
            break
        prev = cur
        time.sleep(0.1)
    hits0 = ctx.read_cache.stats()["hits"]
    assert _canon(_pull_rows()) == prev
    assert ctx.read_cache.stats()["hits"] > hits0
    # the stat family + counter carry the serves (view-labeled)
    assert ctx.stats.stat_ladder("read_out_records",
                                 "rpview")["total"] > 0
    assert ctx.stats.stream_stat_get("read_extracts", "rpview") >= 1
    # how the computed pulls read the view (ISSUE 36): every pull so
    # far scanned; one that pins the group key reads that key; a hit
    # is neither. Through the counters, `admin stats views`, /metrics.
    from hstream_tpu.admin import _admin
    from hstream_tpu.stats.prometheus import render_metrics

    scanned = ctx.stats.stream_stat_get("read_scanned_pulls", "rpview")
    assert scanned >= 1
    assert ctx.stats.stream_stat_get("read_keyed_pulls", "rpview") == 0
    for _ in range(2):   # computed, then a hit
        resp = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="SELECT * FROM rpview WHERE city = 'la';"))
        got = [rec.struct_to_dict(s) for s in resp.result_set]
        assert {r["city"] for r in got} == {"la"} and len(got) >= 1
    (row,) = [r for r in _admin(stub, "stats", entity="views")
              if r["key"] == "rpview"]
    assert row["read_keyed_pulls"] == 1
    assert row["read_scanned_pulls"] == scanned
    assert row["read_extracts"] >= 1 and "read_out_records_total" in row
    assert ctx.read_cache.stats()["keyed_pulls"] == 1
    text = render_metrics(ctx)
    assert 'hstream_read_keyed_pulls_total{stream="rpview"} 1' in text
    assert (f'hstream_read_scanned_pulls_total{{stream="rpview"}} '
            f'{scanned}') in text
    # late record (GRACE 0: dropped) — the cached serve stays exact vs
    # the uncached pipeline (compared pre-wire, where types match)
    _append(stub, "rpsrc", [{"city": "sf"}], [BASE + 1000])
    mat = ctx.views.get("rpview")
    sel = _pull("SELECT * FROM rpview;")
    deadline = time.time() + 10
    while time.time() < deadline:
        cached, _how, _x, _read = ctx.read_cache.serve_view(
            "rpview", mat, sel, "SELECT * FROM rpview;")
        direct = serve_select_view(mat, sel)
        if _canon(cached) == _canon(direct):
            break
        time.sleep(0.2)
    assert _canon(cached) == _canon(direct)
    closed3 = {r["city"]: r["c"] for r in cached
               if r.get("winStart") == BASE}
    assert closed3.get("la") == 2  # late row did not corrupt the close


def test_drop_view_invalidates_server_cache(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="dvsrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW dview AS SELECT city, COUNT(*) AS c "
                  "FROM dvsrc GROUP BY city, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-dview")
    stub.ExecuteQuery(pb.CommandQuery(stmt_text="SELECT * FROM dview;"))
    stub.ExecuteQuery(pb.CommandQuery(stmt_text="DROP VIEW dview;"))
    assert all(k[1] != "dview" for k in ctx.read_cache._entries
               if k[0] == "snap")


def test_steady_state_pulls_compile_nothing(server_stub, retrace_guard):
    """The read-plane retrace gate (ISSUE 20): over a live view whose
    windows keep closing (1 s windows, 200 ms of stream a batch), 50
    steady batches each followed by a version-miss pull (one batched
    peek extract), the same pull again (a cache hit, no device), a
    closed-only pull (the fast path, never peeks) and a pull that pins
    its key (the keyed peek) compile ZERO new XLA executables."""
    from hstream_tpu.client.producer import encode_batch

    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="rgsrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW rgview AS SELECT device, COUNT(*) AS c, "
                  "SUM(temp) AS t FROM rgsrc GROUP BY device, "
                  "TUMBLING (INTERVAL 1 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    task = wait_attached(ctx, "view-rgview")
    warm, steady = 15, 50
    devices = np.array([f"d{k}" for k in range(100)])

    def step(i):  # the fused-close gate's stream, served
        kids, temp, ts = smoke_tumbling_batch(i)
        stub.AppendColumnar(pb.AppendColumnarRequest(
            stream_name="rgsrc", blocks=[encode_batch(
                ts, {"device": devices[kids], "temp": temp})]))
        wait_watermark(task, int(ts[-1]))
        for sql in ("SELECT * FROM rgview;", "SELECT * FROM rgview;",
                    "SELECT * FROM rgview WHERE winEnd < 1;"):
            stub.ExecuteQuery(pb.CommandQuery(stmt_text=sql))
        if i >= warm:
            # a pull that pins the group key is first met under the
            # guard: its program was built with the snapshot's copy
            # (`_build_pin`), whatever the key and whatever is open
            stub.ExecuteQuery(pb.CommandQuery(
                stmt_text=f"SELECT * FROM rgview WHERE device = "
                          f"'d{i % 100}';"))

    for i in range(warm):
        step(i)
    before = ctx.read_cache.stats()
    with retrace_guard():
        for i in range(warm, warm + steady):
            step(i)
    after = ctx.read_cache.stats()
    # all three kinds of serve ran under the guard: a batch gives one
    # recompute that peeks, hits, and one recompute that does not peek
    d = {k: after[k] - before[k]
         for k in ("hits", "misses", "extracts", "keyed_pulls")}
    assert d["extracts"] >= steady and d["hits"] > 0
    assert d["misses"] - d["extracts"] >= steady
    assert before["keyed_pulls"] == 0 and d["keyed_pulls"] == steady


# ---- concurrent readers under the lock-order witness ------------------------


def test_concurrent_readers_exact_and_cycle_free():
    """N readers hammer the cache while a mutator closes windows under
    the task lock: every served snapshot equals the uncached pipeline
    at SOME committed version (no torn reads, no stale hits), and the
    armed witness sees zero lock cycles."""
    LOCKTRACE.disarm()
    LOCKTRACE.arm()
    try:
        ex = _FakeEx()
        mat = _view(ex)
        sel = _pull("SELECT * FROM v;")
        cache = ReadCache()
        canon_lock = threading.Lock()
        canonical: set[str] = set()

        def commit(row):
            # mutate + record the canonical answer atomically (the
            # same state_lock the read path takes)
            with mat.task.state_lock:
                mat.add_closed([row])
                ex.ver += 1
                with canon_lock:
                    canonical.add(_canon(serve_select_view(mat, sel)))

        with canon_lock:
            canonical.add(_canon(serve_select_view(mat, sel)))
        stop = threading.Event()
        errors: list[str] = []

        def reader():
            while not stop.is_set():
                rows, how, _, _read = cache.serve_view("v", mat, sel, "q")
                got = _canon(rows)
                with canon_lock:
                    ok = got in canonical
                if not ok:
                    errors.append(f"{how}: {got[:120]}")
                    return

        threads = [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        for i in range(60):
            commit({"k": f"k{i % 7}", "c": i, "winStart": BASE + i * 10,
                    "winEnd": BASE + i * 10 + 10_000})
            time.sleep(0.002)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors[:3]
        assert LOCKTRACE.cycles() == []
        st = cache.stats()
        assert st["hits"] + st["shared"] + st["misses"] > 0
    finally:
        LOCKTRACE.disarm()


# ---- the keyed read: a WHERE that pins the group key (ISSUE 36) -------------


class _KeyedFakeEx(_FakeEx):
    """`_FakeEx` with a keyed peek: the live rows by their group key, in
    a dictionary as the executor's key ids are (`==` and `hash`)."""

    def __init__(self, live_rows, key_cols, live_lo):
        super().__init__(live_rows, live_lo)
        self.emitted_key_cols = key_cols
        self.key_peeks = 0
        self._by_key: dict = {}
        for r in self.live_rows:
            self._by_key.setdefault(
                tuple(r[c] for c in key_cols), []).append(r)

    def peek_key(self, key):
        rows = self._by_key.get(key)
        if rows is None:
            return None  # no such key: nothing dispatched
        self.key_peeks += 1
        return list(rows)


_STORES = {  # kind -> (group columns, rows the store keeps)
    "str": (["k"], 100_000), "int": (["n"], 100_000),
    "two": (["k", "j"], 100_000), "evicted": (["k"], 17),
    "reclosed": (["k"], 100_000), "nogroup": (None, 100_000),
}


def _seeded_view(kind: str, seed: int):
    """A view over six closed windows and two open ones, its rows
    drawn from `seed`: string keys `k` ('a'..'e'), integer keys `n`
    (0..4), a second column `j` (0..1); closed batches arrive as lists
    and as columnar emissions in turn. `evicted` keeps 17 rows (the
    oldest windows are gone, one of them in part); `reclosed` closes
    window 1 again, later, with other counts."""
    group_cols, keep = _STORES[kind]
    rng = np.random.default_rng([seed, len(kind)])
    mat = Materialization(group_cols=group_cols, max_closed_rows=keep)

    def rows_of(w, bump=0):
        out = []
        for k in range(5):
            for j in range(2):
                if rng.random() < 0.3:
                    continue
                out.append({"k": "abcde"[k], "n": k, "j": j,
                            "c": int(rng.integers(1, 9)) + bump,
                            "winStart": BASE + w * 10_000,
                            "winEnd": BASE + (w + 1) * 10_000})
        if kind != "two":  # one row a (window, key)
            out = [r for r in out if r["j"] == 0]
        return out

    def add(rows, columnar):
        if columnar and rows:
            rows = ColumnarEmit(
                {c: np.array([r[c] for r in rows],
                             object if c == "k" else np.int64)
                 for c in rows[0]}, len(rows))
        mat.add_closed(rows)

    for w in range(6):
        add(rows_of(w), columnar=w % 2 == 1)
        if kind == "reclosed" and w == 3:
            add(rows_of(1, bump=100), columnar=False)
    live = rows_of(6) + rows_of(7)
    ex = _KeyedFakeEx(live, group_cols or [], live_lo=BASE + 70_000)
    mat.task = _FakeTask(ex)
    return mat, ex


def _scanned(mat, sel):
    """The pull as it was before the keyed read, spelled out: every
    closed row and the whole peek through the WHERE, the projection
    and the sort."""
    ex = mat.task.executor
    live = [] if views_mod._skip_live(ex, sel) else ex.peek()
    return views_mod.serve_parts(mat.dump(), live, sel)


_KEYED_CASES = [
    # (id, store, statement, keyed?, rows expected?)
    ("key_left", "str", "SELECT * FROM v WHERE k = 'b';", True, True),
    ("key_right", "str", "SELECT * FROM v WHERE 'b' = k;", True, True),
    ("key_and_win_end_bound", "str",
     f"SELECT * FROM v WHERE k = 'b' AND winEnd <= {BASE + 40_000};",
     True, True),
    ("key_and_aggregate", "str",
     "SELECT k, c FROM v WHERE k = 'c' AND c > 3;", True, True),
    ("key_twice", "str",
     "SELECT * FROM v WHERE k = 'b' AND k = 'c';", True, False),
    ("or_above_the_equality", "str",
     "SELECT * FROM v WHERE k = 'b' OR c > 7;", False, True),
    ("or_under_an_and", "str",
     "SELECT * FROM v WHERE (k = 'b' OR k = 'c') AND c > 1;", False,
     True),
    ("non_group_column", "str", "SELECT * FROM v WHERE c = 3;", False,
     True),
    ("null_literal", "str", "SELECT * FROM v WHERE k = NULL;", False,
     False),
    ("qualified_column", "str", "SELECT * FROM v WHERE v.k = 'b';",
     False, True),
    ("int_key_by_float_literal", "int",
     "SELECT * FROM v WHERE n = 2.0;", True, True),
    ("int_key_by_fraction", "int", "SELECT * FROM v WHERE n = 2.5;",
     True, False),
    ("two_columns_one_free", "two", "SELECT * FROM v WHERE k = 'b';",
     False, True),
    ("two_columns_both_pinned", "two",
     "SELECT * FROM v WHERE j = 1 AND k = 'b';", True, True),
    ("key_never_seen", "str", "SELECT * FROM v WHERE k = 'zz';", True,
     False),
    ("evicted_windows", "evicted", "SELECT * FROM v WHERE k = 'a';",
     True, True),
    ("evicted_windows_projected", "evicted",
     "SELECT c AS n FROM v WHERE k = 'd';", True, True),
    ("window_reclosed", "reclosed", "SELECT * FROM v WHERE k = 'b';",
     True, True),
    ("no_group_columns", "nogroup", "SELECT * FROM v WHERE k = 'b';",
     False, True),
    ("no_where", "str", "SELECT * FROM v;", False, True),
]


@pytest.mark.parametrize(
    "store,sql,keyed,some", [c[1:] for c in _KEYED_CASES],
    ids=[c[0] for c in _KEYED_CASES])
def test_keyed_pull_gives_the_scan_s_rows_in_its_order(store, sql, keyed,
                                                       some):
    """Over seeded stores, a pull gives the SAME list (rows, order)
    whether its WHERE pinned the group key and it read that key, or it
    read every row: and it is the statement, never a flag, that says
    which."""
    sel = _pull(sql)
    seen = 0
    for seed in range(6):
        mat, ex = _seeded_view(store, seed)
        want = _scanned(mat, sel)
        ex.peeks = 0
        got = serve_select_view(mat, sel)
        assert got == want, (seed, sql)
        assert _canon(got) == _canon(want)
        assert mat.snapshot_parts(sel)[4] is keyed
        if keyed:
            assert ex.peeks == 0   # no whole peek, and no row walked:
            closed = mat.snapshot_parts(sel)[0]
            assert len(closed) <= 6
        seen += len(want)
    assert (seen > 0) is some, "the case is vacuous, or was meant to be"


def test_keyed_pull_skips_the_peek_under_a_win_end_bound():
    mat, ex = _seeded_view("str", 0)
    sel = _pull(f"SELECT * FROM v WHERE k = 'b' AND winEnd <= "
                f"{BASE + 40_000};")
    _closed, live, _v, peeked, keyed = mat.snapshot_parts(sel)
    assert keyed and not peeked and live == []
    assert ex.peeks == 0 and ex.key_peeks == 0


def test_keyed_and_scanned_pulls_are_counted_once_computed():
    """`keyed_pulls` / `scanned_pulls` count computed pulls: a hit is
    neither, a key without a live row is keyed and extracts nothing."""
    mat, ex = _seeded_view("str", 1)
    cache = ReadCache()
    for sql in ("SELECT * FROM v WHERE k = 'b';",
                "SELECT * FROM v WHERE k = 'b';",      # a hit
                "SELECT * FROM v WHERE k = 'zz';",
                "SELECT * FROM v WHERE c > 2;"):
        cache.serve_view("v", mat, _pull(sql), sql)
    st = cache.stats()
    assert (st["keyed_pulls"], st["scanned_pulls"], st["hits"]) == (2, 1, 1)
    assert st["extracts"] == 2 and ex.key_peeks == 1 and ex.peeks == 1
    # an executor whose rows do not carry the key under the view's
    # names keeps the whole peek: keyed store, scanned live half
    ex.emitted_key_cols = None
    rows, _how, extracted, read = cache.serve_view(
        "v", mat, _pull("SELECT * FROM v WHERE k = 'c';"), "q")
    assert read == "keyed" and extracted and ex.peeks == 2
    assert rows == _scanned(mat, _pull("SELECT * FROM v WHERE k = 'c';"))


_PEEK_PLANS = {
    "tumbling": "SELECT device, COUNT(*) AS c, SUM(temp) AS t FROM s "
                "GROUP BY device, TUMBLING (INTERVAL 10 SECOND) "
                "GRACE BY INTERVAL 0 SECOND;",
    "hop_having_projected":
        "SELECT device AS d, COUNT(*) AS c, SUM(temp) + 1 AS t, "
        "APPROX_COUNT_DISTINCT(temp) AS u FROM s GROUP BY device, "
        "HOPPING (INTERVAL 60 SECOND, INTERVAL 10 SECOND) "
        "GRACE BY INTERVAL 0 SECOND HAVING COUNT(*) > 2;",
    "windowless": "SELECT device, COUNT(*) AS c FROM s GROUP BY device;",
}


def _fed_executor(plan_sql: str, mesh=None):
    """A real executor over a little of a sensor stream: 12 devices,
    40 s of event time, so a HOP plan has several open slots and some
    groups fail the HAVING."""
    from hstream_tpu.sql.codegen import make_executor

    plan = stream_codegen("CREATE VIEW v AS " + plan_sql).select
    ex = make_executor(plan, sample_rows=[{"device": "d0", "temp": 1.0}],
                       mesh=mesh, initial_keys=16, batch_capacity=256)
    rng = np.random.default_rng(7)
    for i in range(4):
        n = 60
        ts = BASE + i * 10_000 + np.sort(rng.integers(0, 10_000, n))
        dev = rng.integers(0, 12, n) * rng.integers(0, 2, n)  # uneven
        rows = [{"device": f"d{d}", "temp": float(t % 7)}
                for d, t in zip(dev.tolist(), ts.tolist())]
        ex.process(rows, ts.tolist())
    return plan, ex


@pytest.mark.parametrize("mesh", [None, "1x8"])
@pytest.mark.parametrize("plan", sorted(_PEEK_PLANS))
def test_keyed_peek_is_the_whole_peek_filtered_by_the_key(plan, mesh,
                                                          retrace_guard):
    """`peek_key` gives the whole peek's rows of that key, in its
    order, through HAVING and the projections; a key without an id
    dispatches nothing; built at set-up, the first keyed peek compiles
    nothing; the `[1x8]` executor has no keyed peek and a view over it
    answers the same rows from the whole one."""
    from hstream_tpu.sql.codegen import emitted_group_cols

    if mesh is not None:
        import jax

        from hstream_tpu.parallel import make_mesh

        assert jax.device_count() >= 8, f"{jax.device_count()} devices"
        mesh = make_mesh(n_data=1, n_key=8)
    select, ex = _fed_executor(_PEEK_PLANS[plan], mesh)
    name = emitted_group_cols(select.node)[0]
    assert ex.emitted_key_cols == [name]
    whole = list(ex.peek())
    assert whole, "nothing live: the case is vacuous"
    mat = Materialization(group_cols=[name])
    mat.task = _FakeTask(ex)
    dispatches = []
    fn = ex._extract_slots
    ex._extract_slots = lambda *a: (dispatches.append(len(a)), fn(*a))[1]
    ex.build_peek_key()   # as the task does where it pins (`_build_pin`)
    built = len(dispatches)
    with retrace_guard():
        for d in range(13):   # d12: no batch named it
            key = f"d{d}"
            want = [r for r in whole if r.get(name) == key]
            sel = _pull(f"SELECT * FROM v WHERE {name} = '{key}';")
            before = len(dispatches)
            if mesh is None:
                got = ex.peek_key((key,))
                if d == 12:
                    assert got is None and len(dispatches) == before
                else:
                    assert list(got) == want, key
                    assert dispatches[before:] == [3]   # state, slots, kids
            else:
                assert ex.peek_key is None
            # and through the view, keyed or fallen back
            assert serve_select_view(mat, sel) == \
                views_mod.serve_parts([], whole, sel), key
    assert built == (1 if mesh is None else 0)
    if plan == "hop_having_projected":
        assert len({r["winStart"] for r in whole}) > 2   # several slots
        plain = _fed_executor(_PEEK_PLANS[plan].replace(
            " HAVING COUNT(*) > 2", ""), mesh)[1]
        assert len(list(plain.peek())) > len(whole)   # HAVING dropped some


def test_a_projection_over_the_key_s_name_keeps_the_whole_peek():
    """`SELECT device, COUNT(*) AS device`: the emitted `device` is the
    count, so neither the executor nor a view over it reads by key."""
    select, ex = _fed_executor(
        "SELECT device, COUNT(*) AS device FROM s GROUP BY device, "
        "TUMBLING (INTERVAL 10 SECOND) GRACE BY INTERVAL 0 SECOND;")
    assert ex.emitted_key_cols is None
    mat = Materialization(group_cols=["device"])
    mat.task = _FakeTask(ex)
    whole = list(ex.peek())
    n = whole[0]["device"]
    sel = _pull(f"SELECT * FROM v WHERE device = {n};")
    got = serve_select_view(mat, sel)
    assert got == views_mod.serve_parts([], whole, sel) and got
