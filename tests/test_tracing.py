"""Step-tracing subsystem (SURVEY §5.1): per-stage rings on query
tasks, exposed via GetQueryTrace and the admin CLI."""

import time

import grpc
import pytest

from hstream_tpu.common import records as rec
from hstream_tpu.common.tracing import QueryTracer, trace_span
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.server.main import serve

from helpers import wait_attached

BASE = 1_700_000_000_000


def test_tracer_summary():
    tr = QueryTracer(capacity=4)
    for ms in (1, 2, 3, 10):
        tr.record("step", ms / 1e3)
    s = tr.summary()["step"]
    assert s["count"] == 4
    assert s["total_ms"] == pytest.approx(16.0, rel=0.01)
    assert s["p50_ms"] == pytest.approx(3.0, rel=0.01)
    with trace_span(tr, "emit"):
        time.sleep(0.003)
    assert tr.summary()["emit"]["count"] == 1
    assert tr.summary()["emit"]["mean_ms"] >= 2.0
    with trace_span(None, "noop"):  # tracer-less spans are free
        pass


def test_query_trace_rpc_and_admin():
    server, ctx = serve("127.0.0.1", 0, "mem://")
    ch = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    stub = HStreamApiStub(ch)
    try:
        stub.CreateStream(pb.Stream(stream_name="trsrc"))
        stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="CREATE VIEW trview AS SELECT k, COUNT(*) AS c "
                      "FROM trsrc GROUP BY k, "
                      "TUMBLING (INTERVAL 10 SECOND) "
                      "GRACE BY INTERVAL 0 SECOND;"))
        wait_attached(ctx, "view-trview")
        req = pb.AppendRequest(stream_name="trsrc")
        for i in range(10):
            req.records.append(rec.build_record(
                {"k": f"k{i % 2}"}, publish_time_ms=BASE + i))
        stub.Append(req)
        deadline = time.time() + 20
        summary = {}
        while time.time() < deadline:
            summary = rec.struct_to_dict(stub.GetQueryTrace(
                pb.GetQueryRequest(id="view-trview")))
            if "step" in summary and "decode" in summary:
                break
            time.sleep(0.1)
        assert summary["step"]["count"] >= 1
        assert summary["decode"]["mean_ms"] >= 0
        # admin CLI renders it
        from hstream_tpu import admin

        class A:
            id = "view-trview"

        rows = admin.cmd_trace(stub, A)
        assert any(r["stage"] == "step" for r in rows)
        # unknown query -> NOT_FOUND
        with pytest.raises(grpc.RpcError) as ei:
            stub.GetQueryTrace(pb.GetQueryRequest(id="nope"))
        assert ei.value.code() == grpc.StatusCode.NOT_FOUND
    finally:
        ch.close()
        server.stop(grace=1)
        ctx.shutdown()


