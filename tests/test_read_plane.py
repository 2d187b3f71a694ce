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

    r1, how1, x1 = cache.serve_view("v", mat, sel, "q1")
    assert (how1, x1, ex.peeks) == ("miss", True, 1)
    r2, how2, x2 = cache.serve_view("v", mat, sel, "q1")
    assert (how2, x2, ex.peeks) == ("hit", False, 1)  # no second peek
    assert _canon(r1) == _canon(r2)
    # byte-identical to the uncached pipeline at the same version
    assert _canon(r2) == _canon(serve_select_view(mat, sel))

    # a window close mutates BOTH halves: closed store + executor epoch
    mat.add_closed([{"k": "a", "c": 7, "winStart": BASE,
                     "winEnd": BASE + 10_000}])
    ex.live_rows = []
    ex.ver += 1
    r3, how3, _ = cache.serve_view("v", mat, sel, "q1")
    assert how3 == "miss"  # version advanced -> stale entry invalid
    assert _canon(r3) == _canon(serve_select_view(mat, sel))
    assert any(r["c"] == 7 for r in r3)

    # late data changing only the executor half also invalidates
    ex.live_rows = [{"k": "a", "c": 1, "winStart": BASE + 10_000,
                     "winEnd": BASE + 20_000}]
    ex.ver += 1
    r4, how4, _ = cache.serve_view("v", mat, sel, "q1")
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
    rows_all, _, _ = cache.serve_view("v", mat, all_sel,
                                      "SELECT * FROM v;")
    rows_one, how, _ = cache.serve_view("v", mat, one_sel,
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
    _, how1, x1 = cache.serve_view("v", mat, sel, "q")
    _, how2, x2 = cache.serve_view("v", mat, sel, "q")
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
    _, how1, _ = cache.serve_view("v", mat, sel, "q")
    now[0] += 0.2  # +200ms: inside the bound
    _, how2, _ = cache.serve_view("v", mat, sel, "q")
    now[0] += 0.2  # +400ms total: past the bound, version unchanged
    r3, how3, _ = cache.serve_view("v", mat, sel, "q")
    assert (how1, how2, how3) == ("miss", "hit", "miss")
    assert _canon(r3) == _canon(serve_select_view(mat, sel))
    # recompute restamps the entry: fresh again
    _, how4, _ = cache.serve_view("v", mat, sel, "q")
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
    # late record (GRACE 0: dropped) — the cached serve stays exact vs
    # the uncached pipeline (compared pre-wire, where types match)
    _append(stub, "rpsrc", [{"city": "sf"}], [BASE + 1000])
    mat = ctx.views.get("rpview")
    sel = _pull("SELECT * FROM rpview;")
    deadline = time.time() + 10
    while time.time() < deadline:
        cached, _how, _x = ctx.read_cache.serve_view(
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
    peek extract), the same pull again (a cache hit, no device) and a
    closed-only pull (the fast path, never peeks) compile ZERO new XLA
    executables."""
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

    for i in range(warm):
        step(i)
    before = ctx.read_cache.stats()
    with retrace_guard():
        for i in range(warm, warm + steady):
            step(i)
    after = ctx.read_cache.stats()
    # all three kinds of serve ran under the guard: a batch gives one
    # recompute that peeks, hits, and one recompute that does not peek
    d = {k: after[k] - before[k] for k in ("hits", "misses", "extracts")}
    assert d["extracts"] >= steady and d["hits"] > 0
    assert d["misses"] - d["extracts"] >= steady


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
                rows, how, _ = cache.serve_view("v", mat, sel, "q")
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
