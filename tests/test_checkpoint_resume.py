"""Operator-state checkpoint/resume tests.

The reference only checkpoints reader positions (Checkpoint.hs:37-46);
operator state is in-memory (Codegen.hs:374-385), so its restarts
undercount windows. Here snapshots pair state with read LSNs atomically
(engine.snapshot, tasks._snapshot_now): a kill-restarted query must
produce EXACTLY the windows of an uninterrupted run. Covers regression
(d) from round-3 ADVICE: checkpoints committed before windows close.
"""

import time

import grpc
import pytest

from hstream_tpu.common import records as rec
from hstream_tpu.engine.snapshot import restore_executor, snapshot_executor
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.server.main import serve

from helpers import wait_attached
from hstream_tpu.server.tasks import QueryTask, snapshot_key
from hstream_tpu.sql.codegen import make_executor, stream_codegen

BASE = 1_700_000_000_000


# ---- unit: snapshot/restore roundtrips --------------------------------------


def _run_both(sql, batches, split):
    """Feed `batches` to (a) one uninterrupted executor and (b) one that
    is snapshotted/restored after `split` batches; return both output
    row lists."""
    plan = stream_codegen(sql)
    sample = batches[0][0]
    a = make_executor(plan, sample_rows=sample)
    b = make_executor(plan, sample_rows=sample)

    def feed(ex, rows, ts, stream=None):
        if stream is not None:
            return ex.process(rows, ts, stream=stream)
        return ex.process(rows, ts)

    out_a, out_b = [], []
    for i, (rows, ts, *origin) in enumerate(batches):
        stream = origin[0] if origin else None
        out_a.extend(feed(a, rows, ts, stream))
        if i == split:
            blob = snapshot_executor(b, {"mark": 42})
            b, extra = restore_executor(plan, blob)
            assert extra["mark"] == 42
        out_b.extend(feed(b, rows, ts, stream))
    return out_a, out_b


def _norm(rows):
    return sorted(
        tuple(sorted((k, round(v, 6) if isinstance(v, float) else v)
                     for k, v in r.items()))
        for r in rows)


def test_lattice_roundtrip_mid_window():
    sql = ("SELECT device, COUNT(*) AS c, SUM(temp) AS s, MIN(temp) AS lo "
           "FROM s GROUP BY device, TUMBLING (INTERVAL 10 SECOND) "
           "GRACE BY INTERVAL 0 SECOND EMIT CHANGES;")
    batches = [
        ([{"device": "a", "temp": 1.0}, {"device": "b", "temp": 5.0}],
         [BASE, BASE + 100]),
        # snapshot lands here: window still open with a=1, b=1
        ([{"device": "a", "temp": 2.0}], [BASE + 5000]),
        ([{"device": "c", "temp": 9.0}], [BASE + 15_000]),  # closes win 1
        ([{"device": "c", "temp": 1.0}], [BASE + 30_000]),  # closes win 2
    ]
    out_a, out_b = _run_both(sql, batches, split=0)
    assert _norm(out_a) == _norm(out_b)
    closed = [r for r in out_b if r.get("winStart") == BASE]
    got = {r["device"]: r for r in closed}
    assert got["a"]["c"] == 2 and got["a"]["s"] == pytest.approx(3.0)
    assert got["a"]["lo"] == pytest.approx(1.0)


def test_lattice_roundtrip_sketches_and_strings():
    sql = ("SELECT k, APPROX_COUNT_DISTINCT(v) AS d, AVG(v) AS m FROM s "
           "WHERE tag = 'keep' GROUP BY k, "
           "TUMBLING (INTERVAL 10 SECOND) GRACE BY INTERVAL 0 SECOND "
           "EMIT CHANGES;")
    rows1 = [{"k": "x", "v": float(i % 7), "tag": "keep"} for i in range(40)]
    rows1 += [{"k": "x", "v": 99.0, "tag": "drop"}]
    rows2 = [{"k": "x", "v": float(i % 5), "tag": "keep"} for i in range(20)]
    batches = [
        (rows1, [BASE + i for i in range(len(rows1))]),
        (rows2, [BASE + 2000 + i for i in range(len(rows2))]),
        ([{"k": "z", "v": 0.0, "tag": "keep"}], [BASE + 20_000]),
    ]
    out_a, out_b = _run_both(sql, batches, split=0)
    assert _norm(out_a) == _norm(out_b)


def test_session_roundtrip():
    sql = ("SELECT user, COUNT(*) AS c FROM s GROUP BY user, "
           "SESSION (INTERVAL 5 SECOND) GRACE BY INTERVAL 0 SECOND "
           "EMIT CHANGES;")
    batches = [
        ([{"user": "u1"}, {"user": "u2"}], [BASE, BASE + 1000]),
        ([{"user": "u1"}], [BASE + 3000]),   # extends u1's session
        ([{"user": "u1"}], [BASE + 40_000]),  # closes earlier sessions
    ]
    out_a, out_b = _run_both(sql, batches, split=0)
    assert _norm(out_a) == _norm(out_b)


def test_join_roundtrip():
    sql = ("SELECT l.k, COUNT(*) AS c FROM l INNER JOIN r "
           "WITHIN (INTERVAL 5 SECOND) ON l.k = r.k "
           "GROUP BY l.k, TUMBLING (INTERVAL 10 SECOND) "
           "GRACE BY INTERVAL 0 SECOND EMIT CHANGES;")
    batches = [
        ([{"k": "a", "x": 1.0}], [BASE], "l"),
        # snapshot: left row waiting in the side store
        ([{"k": "a", "y": 2.0}], [BASE + 1000], "r"),  # joins with left
        ([{"k": "a", "x": 3.0}], [BASE + 30_000], "l"),
    ]
    out_a, out_b = _run_both(sql, batches, split=0)
    assert _norm(out_a) == _norm(out_b)
    assert any(r.get("c") == 1 for r in out_b)  # the join happened


def test_stateless_roundtrip():
    sql = "SELECT a FROM s WHERE a > 1 EMIT CHANGES;"
    batches = [
        ([{"a": 1}, {"a": 2}], [BASE, BASE + 1]),
        ([{"a": 3}], [BASE + 2]),
    ]
    out_a, out_b = _run_both(sql, batches, split=0)
    assert _norm(out_a) == _norm(out_b)
    assert len(out_b) == 2


# ---- e2e: kill-restart equals uninterrupted run -----------------------------


def _stub_for(server_ctx):
    server, ctx = server_ctx
    channel = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    return HStreamApiStub(channel), channel


def append_rows(stub, stream, rows, ts):
    req = pb.AppendRequest(stream_name=stream)
    for row, t in zip(rows, ts):
        req.records.append(rec.build_record(row, publish_time_ms=t))
    return stub.Append(req)


def _poll_view(stub, view, pred, timeout=30):
    rows = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        resp = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text=f"SELECT * FROM {view};"))
        rows = [rec.struct_to_dict(s) for s in resp.result_set]
        if pred(rows):
            return rows
        time.sleep(0.2)
    return rows


def _kill_restart_flow(stub, ctx, *, stream, view, restart):
    """Shared flow: ingest A -> wait snapshot -> ingest A2 (past the
    snapshot, regression (d)) -> crash -> restart -> ingest B -> the
    closed window must hold A + A2 + B contributions exactly once."""
    stub.CreateStream(pb.Stream(stream_name=stream))
    QueryTask.snapshot_interval_ms = 50
    try:
        stub.ExecuteQuery(pb.CommandQuery(
            stmt_text=f"CREATE VIEW {view} AS SELECT city, COUNT(*) AS c "
                      f"FROM {stream} GROUP BY city, "
                      "TUMBLING (INTERVAL 10 SECOND) "
                      "GRACE BY INTERVAL 0 SECOND;"))
        qid = f"view-{view}"
        wait_attached(ctx, qid)
        # A: 2 sf + 1 la into window [BASE, BASE+10s); stays open
        append_rows(stub, stream,
                    [{"city": "sf"}, {"city": "sf"}, {"city": "la"}],
                    [BASE, BASE + 10, BASE + 20])
        # wait until a snapshot covering A exists
        deadline = time.time() + 20
        while time.time() < deadline:
            blob = ctx.store.meta_get(snapshot_key(qid))
            if blob is not None:
                live = _poll_view(stub, view,
                                  lambda rs: any(r.get("c") == 2
                                                 for r in rs), timeout=1)
                if any(r.get("c") == 2 for r in live):
                    break
            time.sleep(0.05)
        assert ctx.store.meta_get(snapshot_key(qid)) is not None
        # A2: processed but NOT snapshotted (interval cranked up) —
        # the read checkpoint must NOT advance past the state snapshot
        task = ctx.running_queries[qid]
        task.snapshot_interval_ms = 10**9
        append_rows(stub, stream, [{"city": "sf"}], [BASE + 30])
        _poll_view(stub, view,
                   lambda rs: any(r.get("c") == 3 for r in rs))
        # crash: no graceful snapshot
        task.stop(crash=True)
        restart(qid)
        wait_attached(ctx, qid)
        # B: one more sf + the closer
        append_rows(stub, stream, [{"city": "sf"}], [BASE + 40])
        append_rows(stub, stream, [{"city": "zz"}], [BASE + 30_000])
        rows = _poll_view(
            stub, view,
            lambda rs: any(r.get("city") == "sf" and r.get("c") == 4
                           and r.get("winStart") == BASE for r in rs))
        closed = {r["city"]: r["c"] for r in rows
                  if r.get("winStart") == BASE}
        # 4 sf (2 A + 1 A2 replayed once + 1 B), 1 la — no undercount,
        # no double count
        assert closed.get("sf") == 4, rows
        assert closed.get("la") == 1, rows
    finally:
        QueryTask.snapshot_interval_ms = 1000


def test_kill_restart_query_task_mem():
    """Crash + RestartQuery on the mem store backend."""
    server, ctx = serve("127.0.0.1", 0, "mem://")
    stub, channel = _stub_for((server, ctx))
    try:
        def restart(qid):
            stub.RestartQuery(pb.RestartQueryRequest(id=qid))

        _kill_restart_flow(stub, ctx, stream="krs", view="krv",
                           restart=restart)
    finally:
        channel.close()
        server.stop(grace=1)
        ctx.shutdown()


def test_clean_restart_server_native(tmp_path):
    """A GRACEFUL server restart (ctx.shutdown detaches tasks: snapshot
    + status stays RUNNING) must also resume views — not only crashes."""
    store_dir = str(tmp_path / "store")
    server, ctx = serve("127.0.0.1", 0, store_dir)
    stub, channel = _stub_for((server, ctx))
    try:
        stub.CreateStream(pb.Stream(stream_name="crs"))
        stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="CREATE VIEW crv AS SELECT city, COUNT(*) AS c "
                      "FROM crs GROUP BY city, "
                      "TUMBLING (INTERVAL 10 SECOND) "
                      "GRACE BY INTERVAL 0 SECOND;"))
        wait_attached(ctx, "view-crv")
        append_rows(stub, "crs", [{"city": "sf"}, {"city": "la"}],
                    [BASE, BASE + 10])
        _poll_view(stub, "crv", lambda rs: len(rs) >= 2)
        channel.close()
        server.stop(grace=1)
        ctx.shutdown()  # graceful: detach + final snapshot

        server, ctx = serve("127.0.0.1", 0, store_dir)
        stub, channel = _stub_for((server, ctx))
        wait_attached(ctx, "view-crv")
        append_rows(stub, "crs", [{"city": "zz"}], [BASE + 30_000])
        rows = _poll_view(
            stub, "crv",
            lambda rs: any(r.get("city") == "sf" and r.get("c") == 1
                           and r.get("winStart") == BASE for r in rs))
        closed = {r["city"]: r["c"] for r in rows
                  if r.get("winStart") == BASE}
        assert closed.get("sf") == 1 and closed.get("la") == 1, rows
    finally:
        channel.close()
        server.stop(grace=1)
        ctx.shutdown()


def test_kill_restart_server_native(tmp_path):
    """Crash the task, then restart the WHOLE server on the same native
    store directory: boot-time resume_persisted must relaunch the view
    with its snapshotted state."""
    store_dir = str(tmp_path / "store")
    server, ctx = serve("127.0.0.1", 0, store_dir)
    stub, channel = _stub_for((server, ctx))
    QueryTask.snapshot_interval_ms = 50
    try:
        stub.CreateStream(pb.Stream(stream_name="nks"))
        stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="CREATE VIEW nkv AS SELECT city, COUNT(*) AS c "
                      "FROM nks GROUP BY city, "
                      "TUMBLING (INTERVAL 10 SECOND) "
                      "GRACE BY INTERVAL 0 SECOND;"))
        qid = "view-nkv"
        wait_attached(ctx, qid)
        append_rows(stub, "nks",
                    [{"city": "sf"}, {"city": "sf"}, {"city": "la"}],
                    [BASE, BASE + 10, BASE + 20])
        deadline = time.time() + 20
        while time.time() < deadline:
            if ctx.store.meta_get(snapshot_key(qid)) is not None:
                live = _poll_view(stub, "nkv",
                                  lambda rs: any(r.get("c") == 2
                                                 for r in rs), timeout=1)
                if any(r.get("c") == 2 for r in live):
                    break
            time.sleep(0.05)
        task = ctx.running_queries[qid]
        task.stop(crash=True)  # crash the query thread
        channel.close()
        server.stop(grace=1)
        ctx.shutdown()  # closes the native store

        # full server restart on the same directory
        server, ctx = serve("127.0.0.1", 0, store_dir)
        stub, channel = _stub_for((server, ctx))
        wait_attached(ctx, qid)  # boot resume relaunches the view task
        append_rows(stub, "nks", [{"city": "sf"}], [BASE + 40])
        append_rows(stub, "nks", [{"city": "zz"}], [BASE + 30_000])
        rows = _poll_view(
            stub, "nkv",
            lambda rs: any(r.get("city") == "sf" and r.get("c") == 3
                           and r.get("winStart") == BASE for r in rs))
        closed = {r["city"]: r["c"] for r in rows
                  if r.get("winStart") == BASE}
        assert closed.get("sf") == 3, rows
        assert closed.get("la") == 1, rows
    finally:
        QueryTask.snapshot_interval_ms = 1000
        channel.close()
        server.stop(grace=1)
        ctx.shutdown()


# ---- shutdown vs a slow final snapshot (ISSUE 21) ---------------------------


def _view_with_unsnapshotted_state(store_dir, name):
    """A native-store server whose view holds processed rows that no
    periodic snapshot has captured (cadence far in the future), so the
    final snapshot at shutdown is the only thing that can persist them."""
    server, ctx = serve("127.0.0.1", 0, store_dir,
                        snapshot_interval_ms=1 << 30)
    stub, channel = _stub_for((server, ctx))
    stub.CreateStream(pb.Stream(stream_name=f"{name}s"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text=f"CREATE VIEW {name} AS SELECT city, COUNT(*) AS c "
                  f"FROM {name}s GROUP BY city, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    task = wait_attached(ctx, f"view-{name}")
    append_rows(stub, f"{name}s", [{"city": "sf"}, {"city": "sf"}],
                [BASE, BASE + 10])
    _poll_view(stub, name, lambda rs: any(r.get("c") == 2 for r in rs))
    channel.close()
    server.stop(grace=1)
    return ctx, task


def test_shutdown_waits_out_a_slow_final_snapshot(tmp_path):
    """The final snapshot is held (a FAULTS delay at the persist site)
    well past the moment shutdown() has signalled the task. shutdown()
    must not close the owned store under it: the snapshot lands, no
    StoreError, the task ends clean."""
    from hstream_tpu.common.faultinject import FAULTS
    from hstream_tpu.store import open_store

    store_dir = str(tmp_path / "store")
    ctx, task = _view_with_unsnapshotted_state(store_dir, "slowv")
    qid = "view-slowv"
    assert ctx.store.meta_get(snapshot_key(qid)) is None
    FAULTS.arm("snapshot.persist", "delay:1500")
    try:
        t0 = time.monotonic()
        ctx.shutdown()
        held = time.monotonic() - t0
    finally:
        FAULTS.disarm()
    assert held >= 1.4, f"shutdown did not wait for the snapshot ({held})"
    assert not task.is_alive() and task.error is None, task.error
    assert not ctx.events.query(kind="final_snapshot_abandoned")
    re = open_store(store_dir)  # the store was closed cleanly
    try:
        assert re.meta_get(snapshot_key(qid)) is not None
    finally:
        re.close()


def test_shutdown_abandons_a_final_snapshot_past_its_deadline(
        tmp_path, monkeypatch):
    """Held past shutdown's own deadline, the final snapshot is
    knowingly abandoned: shutdown() returns, journals it, and leaves
    the store OPEN so the straggling write still succeeds instead of
    failing against a closed store (and aborting the process at
    interpreter exit)."""
    from hstream_tpu.common.faultinject import FAULTS
    from hstream_tpu.server import context as context_mod

    store_dir = str(tmp_path / "store")
    ctx, task = _view_with_unsnapshotted_state(store_dir, "lostv")
    qid = "view-lostv"
    monkeypatch.setattr(context_mod, "FINAL_SNAPSHOT_WAIT_S", 0.3)
    FAULTS.arm("snapshot.persist", "delay:1500")
    try:
        ctx.shutdown()
        assert task.is_alive()  # still inside its held snapshot
        ev = ctx.events.query(kind="final_snapshot_abandoned")
        assert len(ev) == 1 and qid in ev[0]["message"]
        assert task.wait_written(30)
    finally:
        FAULTS.disarm()
    # the write went to the still-open store: no error, snapshot there
    assert task.error is None, task.error
    assert ctx.store.meta_get(snapshot_key(qid)) is not None
    ctx.store.close()
