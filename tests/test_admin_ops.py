"""Admin store-ops verbs (SendAdminCommand), LDQuery-lite virtual
tables, mesh-exclusion visibility, and k8s manifest sanity."""
from __future__ import annotations

import glob
import json
import os
import time

import grpc
import pytest

from hstream_tpu.common import records as rec
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.server.main import serve

BASE = 1_700_000_000_000
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def server_stub():
    server, ctx = serve("127.0.0.1", 0, "mem://")
    channel = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    stub = HStreamApiStub(channel)
    yield stub, ctx
    channel.close()
    server.stop(grace=1)
    ctx.shutdown()


def admin(stub, command, **kwargs):
    resp = stub.SendAdminCommand(pb.AdminCommandRequest(
        command=command, args=rec.dict_to_struct(kwargs)))
    return json.loads(resp.result)


def append_rows(stub, stream, rows, ts):
    req = pb.AppendRequest(stream_name=stream)
    for row, t in zip(rows, ts):
        req.records.append(rec.build_record(row, publish_time_ms=t))
    return stub.Append(req)


def test_offsets_trim_findtime(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="ops"))
    for i in range(5):
        append_rows(stub, "ops", [{"i": i}], [BASE + i * 1000])
    off = admin(stub, "offsets", stream="ops")
    assert off["tail_lsn"] == 5 and off["trim_point"] == 0
    # find_time operates on APPEND time (store wall clock)
    ft = admin(stub, "find-time", stream="ops", ts_ms=BASE)
    assert ft["lsn"] == 1      # everything appended after BASE (2023)
    far = admin(stub, "find-time", stream="ops",
                ts_ms=int(time.time() * 1000) + 3_600_000)
    assert far["lsn"] == 6     # tail+1: nothing that late
    tr = admin(stub, "trim", stream="ops", lsn=2)
    assert tr["trim_point"] == 2
    off = admin(stub, "offsets", stream="ops")
    assert off["trim_point"] == 2 and off["tail_lsn"] == 5


def test_sub_lag(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="lagged"))
    append_rows(stub, "lagged", [{"i": i} for i in range(4)],
                [BASE + i for i in range(4)])
    stub.CreateSubscription(pb.Subscription(
        subscription_id="lagsub", stream_name="lagged"))
    lag = admin(stub, "sub-lag", subscription="lagsub")
    assert lag["tail_lsn"] == 1    # one appended batch = one LSN
    assert lag["lag"] == 1 - lag["committed_lsn"]
    got = stub.Fetch(pb.FetchRequest(subscription_id="lagsub",
                                     timeout_ms=1000, max_size=10))
    stub.Acknowledge(pb.AcknowledgeRequest(
        subscription_id="lagsub",
        ack_ids=[rr.record_id for rr in got.received_records]))
    deadline = time.time() + 10
    while time.time() < deadline:
        lag = admin(stub, "sub-lag", subscription="lagsub")
        if lag["lag"] == 0:
            break
        time.sleep(0.1)
    assert lag["lag"] == 0


def test_snapshots_and_replicas_and_assignments(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="snapsrc"))
    q = stub.CreateQuery(pb.CreateQueryRequest(
        query_text="SELECT k, COUNT(*) AS c FROM snapsrc GROUP BY k, "
                   "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;"))
    append_rows(stub, "snapsrc", [{"k": "a"}], [BASE])
    # the row has to reach the task first: a query stopped before its
    # first batch has no executor and nothing to persist
    deadline = time.time() + 10
    while time.time() < deadline:
        task = ctx.running_queries.get(q.id)
        if task is not None and task.executor is not None:
            break
        time.sleep(0.02)
    # force a snapshot via terminate (graceful stop persists state)
    stub.TerminateQueries(pb.TerminateQueriesRequest(query_ids=[q.id]))
    snaps = admin(stub, "snapshots")
    assert q.id in snaps and snaps[q.id]["bytes"] > 0
    reps = admin(stub, "replicas")
    assert reps["role"] == "single"
    # assignments: the terminated query's record is dropped
    assert q.id not in admin(stub, "assignments")


def test_admin_cli_quota_and_flow_verbs(server_stub, capsys):
    """The operator CLI's new flow-control verbs end to end:
    quota set/get/list/unset and the live flow status table."""
    from hstream_tpu.admin import main as admin_main

    _, ctx = server_stub
    argv = ["--port", str(ctx.port)]
    assert admin_main(argv + ["quota", "set", "stream/cliq",
                              "--records", "7",
                              "--bytes", "4096"]) == 0
    out = capsys.readouterr().out
    assert "stream/cliq" in out and "7" in out
    assert admin_main(argv + ["quota", "get", "stream/cliq"]) == 0
    assert "4096" in capsys.readouterr().out
    assert admin_main(argv + ["quota", "list"]) == 0
    assert "stream/cliq" in capsys.readouterr().out
    assert admin_main(argv + ["flow"]) == 0
    out = capsys.readouterr().out
    assert "level" in out and "signal" in out and "quota" in out
    assert admin_main(argv + ["quota", "unset", "stream/cliq"]) == 0
    capsys.readouterr()
    assert admin_main(argv + ["quota", "get", "stream/cliq"]) == 0
    assert "unset" in capsys.readouterr().out


def test_virtual_tables(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="vt1", replication_factor=2))
    stub.CreateStream(pb.Stream(stream_name="vt2"))
    append_rows(stub, "vt1", [{"x": 1}], [BASE])
    out = stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="SELECT name, tail_lsn FROM __streams__ "
                  "WHERE replication_factor > 1;"))
    rows = [rec.struct_to_dict(r) for r in out.result_set]
    assert {r["name"] for r in rows} == {"vt1"}
    assert rows[0]["tail_lsn"] == 1
    assert "replication_factor" not in rows[0]  # projection applied
    out = stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="SELECT * FROM __queries__;"))
    assert isinstance(out.result_set, object)
    out = stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="SELECT * FROM __stats__;"))
    rows = [rec.struct_to_dict(r) for r in out.result_set]
    assert any(r.get("stream") == "vt1" for r in rows)


def test_virtual_table_names_are_reserved(server_stub):
    """CREATE STREAM/VIEW colliding with a virtual table is rejected
    (a user view named __streams__ would be unreachable); a user view
    that ALREADY exists under a reserved name (pre-guard state) keeps
    winning the SELECT route (ISSUE 1 satellite)."""
    from hstream_tpu.server.views import Materialization

    stub, ctx = server_stub
    with pytest.raises(grpc.RpcError) as e:
        stub.CreateStream(pb.Stream(stream_name="__streams__"))
    assert e.value.code() == grpc.StatusCode.INTERNAL
    assert "reserved" in e.value.details()
    with pytest.raises(grpc.RpcError) as e:
        stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="CREATE STREAM __queries__ AS SELECT x FROM vt1;"))
    assert "reserved" in e.value.details()
    with pytest.raises(grpc.RpcError) as e:
        stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="CREATE VIEW __views__ AS SELECT x, COUNT(*) AS c "
                      "FROM vt1 GROUP BY x, "
                      "TUMBLING (INTERVAL 10 SECOND);"))
    assert "reserved" in e.value.details()
    assert "__views__" not in ctx.views.names()
    # CreateQuery's user-supplied id becomes the sink STREAM name
    with pytest.raises(grpc.RpcError) as e:
        stub.CreateQuery(pb.CreateQueryRequest(
            id="__streams__",
            query_text="SELECT x, COUNT(*) AS c FROM vt1 GROUP BY x, "
                       "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;"))
    assert "reserved" in e.value.details()
    # pre-existing user view under a reserved name: SELECT routes to IT,
    # not to the virtual table
    mat = Materialization(group_cols=["g"])
    mat.add_closed([{"g": "legacy", "c": 7}])
    ctx.views.register("__stats__", mat)
    try:
        out = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="SELECT * FROM __stats__;"))
        rows = [rec.struct_to_dict(r) for r in out.result_set]
        assert rows == [{"g": "legacy", "c": 7}]
    finally:
        ctx.views.remove("__stats__")
    # with the view gone the virtual table answers again
    out = stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="SELECT * FROM __stats__;"))
    rows = [rec.struct_to_dict(r) for r in out.result_set]
    assert any(r.get("stream") == "vt1" for r in rows)


def test_explain_notes_mesh_exclusion(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="l1"))
    stub.CreateStream(pb.Stream(stream_name="r1"))
    # interval (stream-stream) joins shard since ISSUE 16 — the mesh
    # line must name the shardable topology, not an exclusion
    out = stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="EXPLAIN SELECT l1.k, COUNT(*) AS c FROM l1 "
                  "INNER JOIN r1 WITHIN (INTERVAL 1 SECOND) "
                  "ON l1.k = r1.k GROUP BY l1.k, "
                  "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;"))
    text = rec.struct_to_dict(out.result_set[0])["explain"]
    assert "MESH: shardable" in text and "JOIN" in text
    # TOPK planes have no elementwise shard merge — still excluded
    out = stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="EXPLAIN SELECT k, TOPK(v, 3) AS t FROM l1 "
                  "GROUP BY k, TUMBLING (INTERVAL 10 SECOND) "
                  "EMIT CHANGES;"))
    text = rec.struct_to_dict(out.result_set[0])["explain"]
    assert "MESH: single-chip" in text and "TOPK" in text
    out = stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="EXPLAIN SELECT k, COUNT(*) AS c FROM l1 GROUP BY k, "
                  "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;"))
    text = rec.struct_to_dict(out.result_set[0])["explain"]
    assert "MESH: shardable" in text


def test_k8s_manifests_parse_and_reference_real_entrypoints():
    import yaml

    files = glob.glob(os.path.join(REPO, "k8s", "*.yaml"))
    assert len(files) >= 4
    cmds = []
    for f in files:
        for doc in yaml.safe_load_all(open(f)):
            assert doc and "kind" in doc, f
            tmpl = (doc.get("spec", {}).get("template", {})
                    .get("spec", {}).get("containers", []))
            for c in tmpl:
                cmds.append((c.get("command", []), c.get("args", [])))
    mods = [cmd[2] for cmd, _ in cmds if len(cmd) >= 3 and cmd[1] == "-m"]
    assert "hstream_tpu.server.main" in mods
    assert "hstream_tpu.store.replica" in mods


def test_append_compression_knob():
    """--append-compression zlib round-trips through the store (the
    reference server.hs --compression flag)."""
    from hstream_tpu.server.main import serve as _serve

    server, ctx = _serve("127.0.0.1", 0, "mem://",
                         append_compression="zlib")
    ch = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    stub = HStreamApiStub(ch)
    try:
        stub.CreateStream(pb.Stream(stream_name="z"))
        append_rows(stub, "z", [{"v": "x" * 500}] * 8,
                    [BASE + i for i in range(8)])
        stub.CreateSubscription(pb.Subscription(
            subscription_id="zs", stream_name="z"))
        got = stub.Fetch(pb.FetchRequest(subscription_id="zs",
                                         timeout_ms=2000, max_size=20))
        rows = [rec.record_to_dict(rec.parse_record(r.record))
                for r in got.received_records]
        assert len(rows) == 8 and all(r["v"] == "x" * 500 for r in rows)
    finally:
        ch.close()
        server.stop(grace=1)
        ctx.shutdown()


def test_admin_promote_verb_and_replicas_leader_status():
    """ISSUE 9 operator surface: `admin replicas` reports the leader's
    epoch/fencing/dedup state, `admin promote target=` runs the
    planned handoff (promote + self-fence + seal), the promotions
    counter ticks, and the fenced server refuses further appends with
    the NOT_LEADER hint."""
    import socket

    from hstream_tpu.store import open_store
    from hstream_tpu.store.replica import serve_follower

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    fport = s.getsockname()[1]
    s.close()
    f_store = open_store("mem://")
    fsrv, svc = serve_follower(f_store, f"127.0.0.1:{fport}",
                               node_id="adm-f")
    server, ctx = serve("127.0.0.1", 0, "mem://",
                        replicate=f"127.0.0.1:{fport}",
                        replication_factor=2,
                        replica_ack_timeout_ms=2500)
    channel = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    stub = HStreamApiStub(channel)
    try:
        stub.CreateStream(pb.Stream(stream_name="adm"))
        append_rows(stub, "adm", [{"i": 1}], [BASE])

        out = admin(stub, "replicas")
        assert out["role"] == "leader"
        lead = out["leader"]
        assert lead["epoch"] == 0 and lead["fenced"] is False
        assert lead["ack_timeout_ms"] == 2500  # the threaded flag
        assert lead["dedup_window"] == 0

        res = admin(stub, "promote", target=f"127.0.0.1:{fport}",
                    leader_addr="next:1")
        assert res["ok"] and res["epoch"] == 1
        assert res["node_id"] == "adm-f"
        assert svc.is_leader and svc.epoch == 1
        assert ctx.stats.stream_stat_get("promotions", "_store") == 1

        out = admin(stub, "replicas")
        assert out["leader"]["fenced"] is True
        assert out["leader"]["fenced_by_epoch"] == 1
        assert out["leader"]["leader_hint"] == "next:1"

        try:
            append_rows(stub, "adm", [{"i": 2}], [BASE + 1])
            raise AssertionError("fenced server accepted an append")
        except grpc.RpcError as e:
            assert e.code() == grpc.StatusCode.UNAVAILABLE
            assert "not_leader leader_hint=next:1" in e.details()

        # CLI shaping: the leader-status row leads, sorted keys
        from hstream_tpu.admin import cmd_promote, cmd_replicas

        rows = cmd_replicas(stub, None)
        assert rows[0]["role"] == "leader-status"
        assert rows[0]["fenced"] is True

        class _Args:
            target = None
            replicas = f"127.0.0.1:{fport}"
            leader_addr = None

        res2 = cmd_promote(stub, _Args)[0]
        # leader-death path through the CLI: re-promoting the already
        # promoted follower raises its epoch again
        assert res2["ok"] and res2["epoch"] == 2

        # promote with neither form is a loud usage error
        try:
            admin(stub, "promote")
            raise AssertionError("argless promote accepted")
        except grpc.RpcError as e:
            assert e.code() == grpc.StatusCode.INTERNAL
    finally:
        channel.close()
        server.stop(grace=1)
        try:
            ctx.shutdown()
        except Exception:  # noqa: BLE001 — fenced store refuses final
            pass           # status writes
        svc.close()
        fsrv.stop(grace=1)


def test_admin_locks_verb_arm_ledger_disarm(server_stub, capsys):
    """ISSUE 14: the `admin locks` verb — arm the witness at runtime,
    exercise instrumented subsystems, read the ledger (named locks,
    acquire/contention counts, wait/hold percentiles, order graph,
    cycle reports), then disarm and see a clean slate."""
    from hstream_tpu.admin import main as admin_main
    from hstream_tpu.common.locktrace import LOCKTRACE

    stub, ctx = server_stub
    LOCKTRACE.disarm()
    argv = ["--port", str(ctx.port)]
    try:
        out = admin(stub, "locks", action="arm")
        assert out["armed"] is True
        # drive instrumented paths: context.running + supervisor
        admin(stub, "supervisor")
        stub.ListQueries(pb.ListQueriesRequest())
        out = admin(stub, "locks")
        assert out["armed"] is True and out["cycles"] == []
        assert out["locks"], "armed ledger should have entries"
        some = next(iter(out["locks"].values()))
        assert "acquires" in some and "contentions" in some
        assert "wait_p50_ms" in some and "hold_p99_ms" in some
        # CLI rendering
        assert admin_main(argv + ["locks"]) == 0
        text = capsys.readouterr().out
        assert "(witness)" in text and "armed" in text
        out = admin(stub, "locks", action="disarm")
        assert out["armed"] is False and out["locks"] == {}
        # unknown action refused loudly
        with pytest.raises(grpc.RpcError):
            admin(stub, "locks", action="explode")
    finally:
        LOCKTRACE.disarm()
