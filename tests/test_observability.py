"""Observability plane tests (ISSUE 3): /metrics exposition
correctness (golden file, label escaping, histogram bucket
monotonicity, naming), the event journal (ring bounding under
concurrent writers, admin verb, GET /events), request correlation
client -> gateway -> handler log record, and the registry lint."""

import io
import json
import logging
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import grpc
import pytest

from hstream_tpu.client import Client
from hstream_tpu.common.logger import current_request_id
from hstream_tpu.http_gateway import serve_gateway
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.server.main import serve
from hstream_tpu.stats import GAUGES, HISTOGRAMS, Histogram, StatsHolder
from hstream_tpu.stats.events import EventJournal
from hstream_tpu.stats.prometheus import (
    escape_label_value,
    render_holder,
    render_metrics,
)

BASE = 1_700_000_000_000
GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "metrics_golden.txt")


@pytest.fixture(scope="module")
def stack():
    # trace_sample=1.0: every stamped request records spans (ISSUE 13
    # roundtrip tests); everything else is unaffected
    server, ctx = serve("127.0.0.1", 0, "mem://", metrics_port=0,
                        trace_sample=1.0)
    addr = f"127.0.0.1:{ctx.port}"
    httpd, gw = serve_gateway(addr, port=0)
    http_base = f"http://127.0.0.1:{httpd.server_port}"
    channel = grpc.insecure_channel(addr)
    stub = HStreamApiStub(channel)
    yield addr, http_base, stub, ctx
    channel.close()
    httpd.shutdown()
    gw.close()
    server.stop(grace=1)
    ctx.shutdown()


def _http(method, base, path, body=None, headers=None):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(base + path, data=data, method=method)
    if data:
        req.add_header("Content-Type", "application/json")
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req) as resp:
        return resp.status, resp.read(), dict(resp.headers)


# ---- StatsHolder registry semantics (satellite fixes) ----------------------


def test_peek_rate_unregistered_raises_like_ts():
    stats = StatsHolder()
    with pytest.raises(KeyError):
        stats.time_series_peek_rate("no_such_series", "s")
    with pytest.raises(KeyError):
        stats._ts("no_such_series", "s")
    # registered-but-unseen stream still peeks 0.0 without allocating
    assert stats.time_series_peek_rate("append_in_bytes", "s") == 0.0
    assert stats.time_series_streams("append_in_bytes") == []


def test_time_series_fixed_rings_stay_bounded():
    """The MultiLevelTimeSeries rings are fixed lists — adds move a
    cursor, never grow a dict — and an idle gap wider than a ring
    zeroes it instead of leaking stale buckets (exactness against
    brute-force recounts lives in tests/test_cluster_stats.py)."""
    from hstream_tpu.stats.timeseries import MultiLevelTimeSeries

    ts = MultiLevelTimeSeries()
    for i in range(300):
        ts.add(1.0, now=1000.0 + i)
    assert [lv.n for lv in ts.levels] == [60, 60, 60]
    # 1min level holds exactly the last 60 seconds' adds
    assert ts.sum("1min", now=1299.0) == 60.0
    assert ts.rate("1min", now=1299.0) == 1.0
    # all-time never windows
    assert ts.all_time() == (300.0, 300)
    # an idle gap wider than the 1min ring drains it; wider levels
    # still hold what their windows cover
    assert ts.sum("1min", now=1299.0 + 120) == 0.0
    assert ts.sum("10min", now=1299.0 + 120) > 0.0
    with pytest.raises(KeyError):
        ts.rate("2min")


def test_stat_family_cardinality_bounded():
    """A client looping over random stream names must not grow the
    series map without bound: past TS_MAX_LABELS keys per family, new
    keys fold into one overflow series (the histogram discipline)."""
    from hstream_tpu.stats import TS_MAX_LABELS, TS_OVERFLOW_LABEL

    stats = StatsHolder()
    for i in range(TS_MAX_LABELS + 40):
        stats.stat_add("append_in_bytes", f"junk-{i}", 10.0)
    keys = stats.stat_keys("append_in_bytes")
    assert len(keys) == TS_MAX_LABELS + 1
    assert TS_OVERFLOW_LABEL in keys
    lad = stats.stat_ladder("append_in_bytes", TS_OVERFLOW_LABEL)
    assert lad["total"] == 400.0
    # existing keys keep accumulating normally past the cap
    stats.stat_add("append_in_bytes", "junk-0", 5.0)
    assert stats.stat_ladder("append_in_bytes", "junk-0")["total"] == 15.0
    # other families are unaffected by this family's fold
    stats.stat_add("record_bytes", "fresh", 1.0)
    assert stats.stat_keys("record_bytes") == ["fresh"]


def test_unregistered_gauge_and_histogram_raise():
    stats = StatsHolder()
    with pytest.raises(KeyError):
        stats.gauge_set("bogus_gauge", "", 1.0)
    with pytest.raises(KeyError):
        stats.observe("bogus_hist", "", 1.0)


def test_histogram_label_cardinality_bounded():
    """A client looping over garbage stream names (failed RPCs still
    observe latency) must not grow /metrics without bound: past the
    per-metric cap, new labels fold into one overflow series."""
    from hstream_tpu.stats import HIST_MAX_LABELS, HIST_OVERFLOW_LABEL

    stats = StatsHolder()
    for i in range(HIST_MAX_LABELS + 50):
        stats.observe("append_latency_ms", f"junk-{i}", 1.0)
    hists = stats.histograms_snapshot()
    assert len(hists) == HIST_MAX_LABELS + 1
    overflow = hists[("append_latency_ms", HIST_OVERFLOW_LABEL)]
    assert overflow.count == 50
    # existing labels keep observing normally past the cap
    stats.observe("append_latency_ms", "junk-0", 1.0)
    assert hists[("append_latency_ms", "junk-0")].count == 2


def test_gauge_fn_samples_and_drops_dead():
    stats = StatsHolder()
    items = [1, 2, 3]
    stats.gauge_fn("event_journal_size", "", lambda: len(items))
    assert stats.gauges_snapshot()[("event_journal_size", "")] == 3.0
    items.append(4)
    assert stats.gauges_snapshot()[("event_journal_size", "")] == 4.0

    def dead():
        raise RuntimeError("subsystem gone")

    stats.gauge_fn("running_queries", "", dead)
    snap = stats.gauges_snapshot()  # drops the raising sampler
    assert ("running_queries", "") not in snap
    assert ("running_queries", "") not in stats.gauges_snapshot()
    assert stats.gauge_labels("running_queries") == []


# ---- exposition correctness ------------------------------------------------


def _golden_holder() -> StatsHolder:
    """Deterministic holder state for the golden-file exposition."""
    stats = StatsHolder()
    stats.stream_stat_add("append_total", "s1", 3)
    stats.stream_stat_add("append_payload_bytes", "s1", 4096)
    stats.stream_stat_add("record_total", "s2", 7)
    # freshness/attribution counters (ISSUE 13): late drops are
    # query-labeled, factory recompiles family-labeled — both must
    # render (and survive liveness filtering, asserted elsewhere)
    stats.stream_stat_add("late_drops", "q1", 2)
    stats.stream_stat_add("factory_recompiles", "step", 1)
    stats.stream_stat_add("device_h2d_bytes", "s1", 1024)
    stats.stream_stat_add("device_d2h_bytes", "s1", 512)
    # rate ladders (ISSUE 15): adds stamped far in the past render a
    # deterministic 0.0 in every trailing window — the golden checks
    # the family/scope label plumbing and the stream_rate ladder
    # layout, not wall-clock-dependent values
    stats.stat_add("append_in_bytes", "s1", 4096.0, now=BASE / 1000)
    stats.stat_add("append_in_records", "s1", 3.0, now=BASE / 1000)
    stats.stat_add("delivered_records", "sub1", 7.0, now=BASE / 1000)
    stats.stat_add("emit_rows", "q1", 5.0, now=BASE / 1000)
    stats.gauge_set("overload_level", "", 1)
    stats.gauge_set("running_queries", "", 2)
    stats.gauge_set("pipeline_occupancy", "q1", 0.5)
    # freshness plane gauges (query-labeled)
    stats.gauge_set("query_watermark_ms", "q1", 1_700_000_000_000)
    stats.gauge_set("query_watermark_lag_ms", "q1", 250.0)
    stats.gauge_set("query_health_level", "q1", 1)
    # device cost plane gauges (ISSUE 18): per-query HBM total, one
    # per-plane series (composite "qid/plane" label splits into
    # {query, plane} at render), process total + backend cross-check
    stats.gauge_set("device_hbm_bytes", "q1", 4096)
    stats.gauge_set("device_arena_bytes", "q1/count", 2048)
    stats.gauge_set("device_arena_bytes", "q1/agg0_sum", 2048)
    stats.gauge_set("device_hbm_total_bytes", "", 4096)
    stats.gauge_set("device_hbm_backend_bytes", "", 8192)
    for v in (0.4, 3.0, 40.0):
        stats.observe("append_latency_ms", "s1", v)
    # freshness histograms: per-stage lag + visible latency + emit
    for stage, v in (("ingest", 4.0), ("engine", 30.0),
                     ("delivery", 120.0)):
        stats.observe("freshness_lag_ms", stage, v)
    stats.observe("append_visible_latency_ms", "q1", 45.0)
    stats.observe("emit_latency_ms", "q1", 12.0)
    stats.observe("kernel_dispatch_ms", "step", 1.5)
    # device-time sampler histogram (ISSUE 18) next to the host wall
    stats.observe("kernel_device_ms", "step", 0.9)
    # lock-order witness ledger (ISSUE 14): wait/hold + contention
    stats.stream_stat_add("lock_contention", "tasks.state", 3)
    stats.observe("lock_wait_ms", "tasks.state", 0.8)
    stats.observe("lock_hold_ms", "tasks.state", 2.0)
    # read plane (ISSUE 20): view-labeled extract counter, the
    # read_out_records rate ladder, and the cache gauges
    stats.stream_stat_add("read_extracts", "v1", 2)
    # how its computed pulls read the view (ISSUE 36)
    stats.stream_stat_add("read_keyed_pulls", "v1", 5)
    stats.stream_stat_add("read_scanned_pulls", "v1", 1)
    stats.stat_add("read_out_records", "v1", 9.0, now=BASE / 1000)
    stats.gauge_set("read_cache_hit_ratio", "", 0.75)
    stats.gauge_set("read_cache_bytes", "", 16384)
    # the window lattice's key dictionary and its top close (ISSUE 33):
    # query-labelled counters and the two gauges
    for name, v in (("key_retirements", 2), ("keys_retired", 900),
                    ("key_ids_reused", 850), ("close_rows_kept", 7),
                    ("close_groups", 4000), ("close_tie_refetches", 1)):
        stats.stream_stat_add(name, "q1", v)
    stats.gauge_set("keys_live", "q1", 600)
    stats.gauge_set("key_capacity", "q1", 1024)
    # how the append door read its blocks' headers, and the string
    # dictionaries a query built (ISSUE 34)
    stats.stat_add("append_headers_lazy", "s1", 5.0, now=BASE / 1000)
    stats.stat_add("append_headers_eager", "s1", 1.0, now=BASE / 1000)
    stats.stat_add("dictionaries_built", "q1", 2.0, now=BASE / 1000)
    return stats


def test_metrics_golden_file():
    """The exposition of a fixed holder state matches the checked-in
    golden byte-for-byte (naming, ordering, HELP/TYPE headers, label
    quoting, bucket layout). Regenerate deliberately with:
    python -c "from tests.test_observability import _write_golden; \
_write_golden()" (from the repo root, tests on sys.path)."""
    got = render_holder(_golden_holder())
    with open(GOLDEN, encoding="utf-8") as f:
        want = f.read()
    assert got == want


def _write_golden() -> None:
    with open(GOLDEN, "w", encoding="utf-8") as f:
        f.write(render_holder(_golden_holder()))


def test_label_escaping():
    assert escape_label_value('a"b') == 'a\\"b'
    assert escape_label_value("a\\b") == "a\\\\b"
    assert escape_label_value("a\nb") == "a\\nb"
    stats = StatsHolder()
    evil = 'str"eam\\with\nnasties'
    stats.stream_stat_add("append_total", evil)
    text = render_holder(stats)
    line = [ln for ln in text.splitlines()
            if ln.startswith("hstream_append_total{")][0]
    assert line == ('hstream_append_total{stream='
                    '"str\\"eam\\\\with\\nnasties"} 1')


def test_histogram_bucket_monotonicity_and_naming():
    h = Histogram((1.0, 5.0, 25.0))
    for v in (0.2, 0.7, 3.0, 100.0, 4.0, 30.0):
        h.observe(v)
    cum, total_sum, count = h.snapshot()
    assert count == 6 and abs(total_sum - 137.9) < 1e-9
    assert cum == sorted(cum), "cumulative buckets must be monotone"
    assert cum[-1] == count, "+Inf bucket must equal _count"
    stats = StatsHolder()
    stats.observe("fetch_latency_ms", "sub1", 2.0)
    text = render_holder(stats)
    assert "hstream_fetch_latency_ms_bucket{subscription=\"sub1\"," in text
    assert 'le="+Inf"' in text
    assert "hstream_fetch_latency_ms_sum{subscription=\"sub1\"}" in text
    assert "hstream_fetch_latency_ms_count{subscription=\"sub1\"}" in text
    # counters carry the _total suffix exactly once
    stats.stream_stat_add("append_total", "s")
    stats.stream_stat_add("shed_total", "s")
    text = render_holder(stats)
    assert "hstream_append_total{" in text
    assert "hstream_append_total_total" not in text
    assert "hstream_shed_total{" in text


def test_histogram_percentiles():
    h = Histogram((1.0, 10.0, 100.0))
    for _ in range(99):
        h.observe(0.5)
    h.observe(50.0)
    assert h.percentile(50) <= 1.0
    assert 10.0 <= h.percentile(100) <= 100.0
    assert Histogram((1.0,)).percentile(50) is None


def test_live_metrics_endpoint_covers_registries(stack):
    """GET /metrics (gateway) renders valid exposition lines covering
    counters, rates, >= 6 gauges and >= 3 histograms after the RPC
    surface has been exercised."""
    addr, base, stub, ctx = stack
    from hstream_tpu.common import records as rec

    stub.CreateStream(pb.Stream(stream_name="mx"))
    req = pb.AppendRequest(stream_name="mx")
    for i in range(3):
        req.records.append(rec.build_record(
            {"k": "a", "v": i}, publish_time_ms=BASE + i))
    stub.Append(req)
    stub.ExecuteQuery(pb.CommandQuery(stmt_text="SHOW STREAMS;"))
    stub.CreateSubscription(pb.Subscription(
        subscription_id="mxsub", stream_name="mx"))
    stub.Fetch(pb.FetchRequest(subscription_id="mxsub",
                               timeout_ms=200, max_size=10))
    # a running query task exercises stage histograms + pipeline gauges
    q = stub.CreateQuery(pb.CreateQueryRequest(
        query_text="SELECT k, COUNT(*) AS c FROM mx GROUP BY k, "
                   "TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;"))
    from helpers import wait_attached

    wait_attached(ctx, q.id)
    req2 = pb.AppendRequest(stream_name="mx")
    for i in range(4):
        req2.records.append(rec.build_record(
            {"k": "b", "v": i}, publish_time_ms=BASE + 100 + i))
    stub.Append(req2)
    deadline = time.time() + 20
    while time.time() < deadline:
        task = ctx.running_queries.get(q.id)
        if task is not None and task.executor is not None:
            break
        time.sleep(0.05)

    code, body, headers = _http("GET", base, "/metrics")
    assert code == 200
    assert headers["Content-Type"].startswith("text/plain")
    text = body.decode()
    # structural validity: every non-comment line is `name{labels} value`
    line_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? -?[0-9.e+-]+$|'
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [0-9.]*inf$', re.I)
    for ln in text.splitlines():
        if ln.startswith("#") or not ln:
            continue
        assert line_re.match(ln), f"malformed exposition line: {ln}"
    assert "hstream_append_total{" in text
    assert "hstream_append_in_bytes_rate{" in text
    gauges_seen = {g for g in GAUGES if f"hstream_{g}" in text}
    assert len(gauges_seen) >= 6, gauges_seen
    hists_seen = {h for h, _b, _l in HISTOGRAMS
                  if f"hstream_{h}_bucket" in text}
    assert len(hists_seen) >= 3, hists_seen
    # bucket monotonicity on the live append histogram
    buckets = [float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
               if ln.startswith("hstream_append_latency_ms_bucket{"
                                "stream=\"mx\"")]
    assert buckets and buckets == sorted(buckets)
    stub.DeleteQuery(pb.DeleteQueryRequest(id=q.id))
    stub.DeleteSubscription(pb.DeleteSubscriptionRequest(
        subscription_id="mxsub"))


def test_standalone_exporter(stack):
    """--metrics-port serves /metrics + /events straight off the server
    process (no gateway hop)."""
    _, _, _, ctx = stack
    port = ctx.metrics_httpd.server_port
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics") as r:
        assert r.status == 200
        assert "hstream_running_queries" in r.read().decode()
    ctx.events.append("query_restarted", "exporter probe", query="p1")
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/events?kind=query_restarted"
            f"&limit=5") as r:
        events = json.loads(r.read())
    assert any(e["message"] == "exporter probe" for e in events)


# ---- event journal ---------------------------------------------------------


def test_journal_ring_bounds_under_concurrent_writers():
    j = EventJournal(capacity=100)
    n_threads, per_thread = 8, 500

    def writer(i):
        for k in range(per_thread):
            j.append("shed_level", f"w{i}-{k}", level="defer")

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(j) == 100
    assert j.last_seq == n_threads * per_thread
    entries = j.query(limit=1000)
    seqs = [e["seq"] for e in entries]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    assert seqs[-1] == j.last_seq


def test_journal_rejects_unregistered_kind():
    j = EventJournal()
    with pytest.raises(KeyError):
        j.append("made_up_kind", "nope")


def test_journal_query_filters():
    j = EventJournal(capacity=10)
    j.append("shed_level", "a", level="defer")
    j.append("query_died", "b", query="q1")
    j.append("shed_level", "c", level="admit")
    assert [e["message"] for e in j.query(kind="shed_level")] == ["a", "c"]
    assert [e["message"] for e in j.query(since=2)] == ["c"]
    assert len(j.query(limit=1)) == 1


def test_events_admin_verb_and_gateway_route(stack):
    addr, base, stub, ctx = stack
    from hstream_tpu.common import records as rec

    # a real ladder transition journals itself
    ctx.flow.overload.note("step_latency_ms", 1e6, source="evt-test")
    resp = stub.SendAdminCommand(pb.AdminCommandRequest(
        command="events",
        args=rec.dict_to_struct({"kind": "shed_level", "limit": 10})))
    events = json.loads(resp.result)["events"]
    assert events and events[-1]["kind"] == "shed_level"
    code, body, _ = _http("GET", base,
                          "/events?kind=shed_level&limit=5")
    assert code == 200
    assert any(e["kind"] == "shed_level" for e in json.loads(body))
    # admin CLI renders the same verb
    from hstream_tpu.admin import main as admin_main

    host, port = addr.split(":")
    import contextlib

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = admin_main(["--host", host, "--port", port,
                         "events", "--kind", "shed_level"])
    assert rc == 0 and "shed_level" in buf.getvalue()
    # let the detector's source expire instead of pinning REJECT for
    # the rest of the module (10s staleness; force recompute now)
    ctx.flow.overload._sigs["step_latency_ms"].sources.clear()
    ctx.flow.overload.effective_level()


# ---- request correlation ---------------------------------------------------


class _Capture(logging.Handler):
    """Captures (message, active request id) pairs: emit runs in the
    logging thread, where the handler's contextvar is bound."""

    def __init__(self):
        super().__init__()
        self.records: list[tuple[str, str]] = []

    def emit(self, record):
        self.records.append((record.getMessage(), current_request_id()))


def test_correlation_id_client_gateway_handler(stack):
    """One id follows a request end to end: the HTTP caller's
    X-Request-Id reaches the handler's log records (via gRPC metadata
    and the logger contextvar) and echoes back on the response."""
    addr, base, stub, ctx = stack
    cap = _Capture()
    root = logging.getLogger("hstream_tpu")
    root.addHandler(cap)
    old_slow = ctx.slow_request_ms
    ctx.slow_request_ms = 0.0  # every RPC logs a slow-request line
    try:
        _http("POST", base, "/streams", {"name": "corr"})
        code, _, headers = _http(
            "POST", base, "/streams/corr/append",
            {"records": [{"a": 1}]},
            headers={"X-Request-Id": "corr-test-1"})
        assert code == 200
        assert headers["X-Request-Id"] == "corr-test-1"
        hits = [rid for msg, rid in cap.records
                if "slow request" in msg and "Append" in msg]
        assert "corr-test-1" in hits
        # gateway mints an id when the caller sends none
        cap.records.clear()
        code, _, headers = _http("POST", base, "/streams/corr/append",
                                 {"records": [{"a": 2}]})
        minted = headers["X-Request-Id"]
        assert minted.startswith("gw-")
        assert any(rid == minted for msg, rid in cap.records
                   if "slow request" in msg and "Append" in msg)
        # the SQL client stamps its own ids on direct gRPC calls
        cap.records.clear()
        client = Client(addr, out=io.StringIO())
        try:
            client.execute("SHOW STREAMS;")
            assert client.last_request_id is not None
            assert any(rid == client.last_request_id
                       for msg, rid in cap.records
                       if "slow request" in msg
                       and "ExecuteQuery" in msg)
        finally:
            client.close()
    finally:
        ctx.slow_request_ms = old_slow
        root.removeHandler(cap)


def test_slow_request_threshold_gates_logging(stack):
    _, base, _, ctx = stack
    cap = _Capture()
    root = logging.getLogger("hstream_tpu")
    root.addHandler(cap)
    old_slow = ctx.slow_request_ms
    ctx.slow_request_ms = 60_000.0  # nothing is that slow
    try:
        _http("GET", base, "/streams")
        assert not any("slow request" in msg
                       for msg, _rid in cap.records)
    finally:
        ctx.slow_request_ms = old_slow
        root.removeHandler(cap)


def test_query_tracer_carries_request_id(stack):
    addr, base, stub, ctx = stack
    from hstream_tpu.common import records as rec
    from helpers import wait_attached

    stub.CreateStream(pb.Stream(stream_name="tracesrc"))
    q = stub.CreateQuery(
        pb.CreateQueryRequest(
            query_text="SELECT k, COUNT(*) AS c FROM tracesrc GROUP BY "
                       "k, TUMBLING (INTERVAL 10 SECOND) EMIT CHANGES;"),
        metadata=(("x-request-id", "trace-rid-9"),))
    task = wait_attached(ctx, q.id)
    assert task.tracer.request_id == "trace-rid-9"
    req = pb.AppendRequest(stream_name="tracesrc")
    req.records.append(rec.build_record({"k": "z"},
                                        publish_time_ms=BASE))
    stub.Append(req)
    deadline = time.time() + 20
    while time.time() < deadline:
        summary = task.tracer.summary()
        if summary.get("request"):
            break
        time.sleep(0.05)
    assert task.tracer.summary()["request"]["id"] == "trace-rid-9"
    stub.DeleteQuery(pb.DeleteQueryRequest(id=q.id))


# ---- freshness / trace spans / health plane (ISSUE 13) ---------------------


def _append_rows(stub, stream, rows_ts, key="k"):
    from hstream_tpu.common import records as rec

    req = pb.AppendRequest(stream_name=stream)
    for kval, ts in rows_ts:
        req.records.append(rec.build_record({key: kval},
                                            publish_time_ms=ts))
    stub.Append(req)


def _wait_watermark(ctx, qid, target, timeout=20):
    from hstream_tpu.server.health import _executor_watermark

    deadline = time.time() + timeout
    while time.time() < deadline:
        task = ctx.running_queries.get(qid)
        if task is not None:
            wm = _executor_watermark(task)
            if wm is not None and wm >= target:
                return task
        time.sleep(0.05)
    raise TimeoutError(f"query {qid} never reached watermark {target}")


def test_trace_export_roundtrip(stack):
    """Client -> gateway -> handler -> task spans share ONE trace id
    (the request id), and the export is valid Chrome trace-event
    JSON."""
    addr, base, stub, ctx = stack
    stub.CreateStream(pb.Stream(stream_name="trsrc"))
    req = urllib.request.Request(
        base + "/queries",
        data=json.dumps({"sql": "SELECT k, COUNT(*) AS c FROM trsrc "
                                "GROUP BY k, TUMBLING (INTERVAL 1 "
                                "SECOND) GRACE BY INTERVAL 0 SECOND "
                                "EMIT CHANGES;",
                         "id": "qtr1"}).encode(),
        method="POST",
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "trace-rt-7"})
    with urllib.request.urlopen(req) as r:
        assert json.loads(r.read())["id"] == "qtr1"
    now = int(time.time() * 1000)
    _append_rows(stub, "trsrc", [(f"k{i % 3}", now + i)
                                 for i in range(32)])
    _wait_watermark(ctx, "qtr1", now + 31)
    code, body, _ = _http("GET", base, "/queries/qtr1/trace")
    assert code == 200
    trace = json.loads(body)
    events = trace["traceEvents"]
    assert events, "no spans exported"
    assert {e["args"]["trace_id"] for e in events} == {"trace-rt-7"}
    names = {e["name"] for e in events}
    assert "rpc" in names, names          # the CreateQuery handler span
    assert "step" in names, names         # the task's device-step span
    for e in events:
        assert e["ph"] == "X"
        assert isinstance(e["ts"], (int, float))
        assert e["dur"] >= 1
        assert e["args"]["span_id"]
    # the handler span parents the task's stage spans (one chain)
    rpc = next(e for e in events if e["name"] == "rpc")
    stage = next(e for e in events if e["name"] == "step")
    assert stage["args"]["parent_id"] == rpc["args"]["span_id"]
    # the gateway hop named itself as the handler span's parent
    assert rpc["args"]["parent_id"] == "gw-trace-rt-7"
    # admin trace --spans prints the same export as JSON
    from hstream_tpu.admin import main as admin_main
    import contextlib

    host, port = addr.split(":")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = admin_main(["--host", host, "--port", port,
                         "trace", "qtr1", "--spans"])
    assert rc == 0
    spans = json.loads(buf.getvalue().splitlines()[0])
    assert spans["traceEvents"]
    stub.DeleteQuery(pb.DeleteQueryRequest(id="qtr1"))


def test_unsampled_requests_record_no_spans(stack):
    """A request with no request id has no trace id, so nothing lands
    in the rings even with tracing armed (sampling is per-trace and
    deterministic)."""
    addr, base, stub, ctx = stack
    before = ctx.tracing.spans("_rpc")
    stub.ListStreams(pb.ListStreamsRequest())  # bare: no metadata
    assert ctx.tracing.spans("_rpc") == before


def test_freshness_plane_on_live_server(stack):
    """Watermark gauges, per-stage lag histograms, append->visible and
    emit latency, kernel-family dispatch histograms, and the late-drop
    counter all surface on /metrics from a live query."""
    addr, base, stub, ctx = stack
    stub.CreateStream(pb.Stream(stream_name="fpsrc"))
    q = stub.CreateQuery(pb.CreateQueryRequest(
        query_text="SELECT k, COUNT(*) AS c FROM fpsrc GROUP BY k, "
                   "TUMBLING (INTERVAL 1 SECOND) GRACE BY INTERVAL 0 "
                   "SECOND EMIT CHANGES;", id="qfp1"))
    now = int(time.time() * 1000)
    _append_rows(stub, "fpsrc", [(f"k{i % 4}", now + i)
                                 for i in range(64)])
    _wait_watermark(ctx, q.id, now + 63)
    # one LATE record: past close at the current watermark
    _append_rows(stub, "fpsrc", [("late", now - 3_600_000),
                                 ("fresh", now + 100)])
    deadline = time.time() + 20
    while time.time() < deadline:
        if ctx.stats.stream_stat_get("late_drops", q.id) >= 1:
            break
        time.sleep(0.05)
    assert ctx.stats.stream_stat_get("late_drops", q.id) >= 1
    code, body, _ = _http("GET", base, "/metrics")
    text = body.decode()
    assert f'hstream_query_watermark_ms{{query="{q.id}"}}' in text
    assert f'hstream_query_watermark_lag_ms{{query="{q.id}"}}' in text
    assert f'hstream_query_health_level{{query="{q.id}"}}' in text
    assert 'hstream_freshness_lag_ms_bucket{stage="ingest"' in text
    assert 'hstream_freshness_lag_ms_bucket{stage="engine"' in text
    assert ('hstream_append_visible_latency_ms_bucket{consumer='
            f'"{q.id}"') in text
    assert f'hstream_emit_latency_ms_bucket{{query="{q.id}"' in text
    assert 'hstream_kernel_dispatch_ms_bucket{family="step"' in text
    assert re.search(
        rf'hstream_late_drops_total\{{stream="{q.id}"\}} [1-9]', text)
    stub.DeleteQuery(pb.DeleteQueryRequest(id=q.id))


def test_delivery_stage_lag_from_subscription(stack):
    addr, base, stub, ctx = stack
    from hstream_tpu.common import records as rec

    stub.CreateStream(pb.Stream(stream_name="dlsrc"))
    req = pb.AppendRequest(stream_name="dlsrc")
    req.records.append(rec.build_record({"a": 1}))
    stub.Append(req)
    stub.CreateSubscription(pb.Subscription(
        subscription_id="dlsub", stream_name="dlsrc"))
    got = stub.Fetch(pb.FetchRequest(subscription_id="dlsub",
                                     timeout_ms=500, max_size=10))
    assert got.received_records
    code, body, _ = _http("GET", base, "/metrics")
    text = body.decode()
    assert 'hstream_freshness_lag_ms_bucket{stage="delivery"' in text
    assert ('hstream_append_visible_latency_ms_bucket{consumer='
            '"dlsub"') in text
    stub.DeleteSubscription(pb.DeleteSubscriptionRequest(
        subscription_id="dlsub"))


def test_health_endpoint_ok_and_unknown(stack):
    addr, base, stub, ctx = stack
    stub.CreateStream(pb.Stream(stream_name="hlsrc"))
    q = stub.CreateQuery(pb.CreateQueryRequest(
        query_text="SELECT k, COUNT(*) AS c FROM hlsrc GROUP BY k, "
                   "TUMBLING (INTERVAL 1 SECOND) EMIT CHANGES;",
        id="qhl1"))
    now = int(time.time() * 1000)
    _append_rows(stub, "hlsrc", [("a", now)])
    _wait_watermark(ctx, q.id, now)
    code, body, _ = _http("GET", base, f"/queries/{q.id}/health")
    assert code == 200
    h = json.loads(body)
    assert h["verdict"] == "OK" and h["level"] == 0, h
    assert h["reasons"] == []
    assert h["watermark_ms"] == now
    assert h["thresholds"]["stalled_after_ms"] == 30000.0
    # unknown query -> 404 through the typed-error mapping
    try:
        urllib.request.urlopen(base + "/queries/nope/health")
        assert False, "expected 404"
    except urllib.error.HTTPError as e:
        assert e.code == 404
    stub.DeleteQuery(pb.DeleteQueryRequest(id=q.id))


def test_health_stalled_crash_loop_journals_event(stack):
    """A crash-looped query reads STALLED (reason crash_loop) and the
    transition journals exactly one query_stalled event; operator
    RestartQuery resets the breaker and health recovers."""
    addr, base, stub, ctx = stack
    stub.CreateStream(pb.Stream(stream_name="clsrc"))
    q = stub.CreateQuery(pb.CreateQueryRequest(
        query_text="SELECT k, COUNT(*) AS c FROM clsrc GROUP BY k, "
                   "TUMBLING (INTERVAL 1 SECOND) EMIT CHANGES;",
        id="qcl1"))
    from helpers import wait_attached

    task = wait_attached(ctx, q.id)
    # kill the task for real (crash mode: no final snapshot, status
    # stays RUNNING), then feed the supervisor a crash loop
    task.stop(crash=True)
    deadline = time.time() + 10
    while q.id in ctx.running_queries and time.time() < deadline:
        time.sleep(0.02)
    assert q.id not in ctx.running_queries
    info = ctx.persistence.get_query(q.id)
    sup = ctx.supervisor
    for _ in range(sup.BREAKER_K):
        sup.note_death(info, RuntimeError("boom"))
    assert q.id in sup.status()["breaker_open"]
    seq0 = ctx.events.last_seq
    code, body, _ = _http("GET", base, f"/queries/{q.id}/health")
    h = json.loads(body)
    assert h["verdict"] == "STALLED" and "crash_loop" in h["reasons"]
    events = ctx.events.query(kind="query_stalled", since=seq0 - 50)
    assert any(e.get("query") == q.id for e in events)
    # re-evaluation does NOT re-journal (transition memory)
    n_before = len(ctx.events.query(kind="query_stalled", limit=1000))
    _http("GET", base, f"/queries/{q.id}/health")
    assert len(ctx.events.query(kind="query_stalled",
                                limit=1000)) == n_before
    # operator restart closes the breaker; health recovers
    stub.RestartQuery(pb.RestartQueryRequest(id=q.id))
    code, body, _ = _http("GET", base, f"/queries/{q.id}/health")
    h = json.loads(body)
    assert h["verdict"] == "OK", h
    stub.DeleteQuery(pb.DeleteQueryRequest(id=q.id))


def test_health_unowned_only_when_this_node_owns(stack):
    """A RUNNING query with no local task is STALLED(unowned) only
    when the scheduler record names THIS node (or nobody) — a query
    owned by a live peer is that peer's to judge, never false
    distress from a bystander's scrape."""
    import json as _json

    from hstream_tpu.server import scheduler
    from hstream_tpu.server.persistence import (
        QueryInfo,
        TaskStatus,
        now_ms,
    )

    addr, base, stub, ctx = stack
    info = QueryInfo(query_id="qpeer1", sql="SELECT 1;",
                     created_time_ms=now_ms(),
                     status=TaskStatus.RUNNING, sink="qpeer1")
    ctx.persistence.insert_query(info)
    try:
        # owned by a live PEER (higher epoch): not ours to judge
        ctx.config.put(
            scheduler._key("qpeer1"),
            _json.dumps({"node": "server-9@peer:6570",
                         "epoch": ctx.boot_epoch + 1}).encode())
        code, body, _ = _http("GET", base, "/queries/qpeer1/health")
        h = json.loads(body)
        assert h["verdict"] == "OK", h
        assert h["owner"] == "server-9@peer:6570"
        # re-owned by THIS node, still no task: genuinely unowned
        cur = ctx.config.get(scheduler._key("qpeer1"))
        ctx.config.put(
            scheduler._key("qpeer1"),
            _json.dumps({"node": scheduler.node_name(ctx),
                         "epoch": ctx.boot_epoch}).encode(),
            base_version=cur[0])
        code, body, _ = _http("GET", base, "/queries/qpeer1/health")
        h = json.loads(body)
        assert h["verdict"] == "STALLED" and "unowned" in h["reasons"]
    finally:
        ctx.persistence.remove_query("qpeer1")
        cur = ctx.config.get(scheduler._key("qpeer1"))
        if cur is not None:
            ctx.config.delete(scheduler._key("qpeer1"),
                              base_version=cur[0])


def test_host_device_session_freshness_parity():
    """The freshness plane reads the same host-mirror values whichever
    engine ran the batch: device and host session executors agree on
    the watermark AND the late-drop count for an identical feed."""
    import numpy as np

    from hstream_tpu.engine import ColumnType, Schema
    from hstream_tpu.engine.expr import Col
    from hstream_tpu.engine.plan import (
        AggKind,
        AggregateNode,
        AggSpec,
        SourceNode,
    )
    from hstream_tpu.engine.session import SessionExecutor
    from hstream_tpu.engine.window import SessionWindow

    def mk():
        schema = Schema.of(u=ColumnType.STRING, v=ColumnType.FLOAT)
        node = AggregateNode(
            child=SourceNode("s", schema), group_keys=[Col("u")],
            window=SessionWindow(1_000, grace_ms=0),
            aggs=[AggSpec(AggKind.COUNT_ALL, "c")])
        return SessionExecutor(node, schema, emit_changes=False)

    dev, host = mk(), mk()
    host.use_device_sessions = False
    base = 1_700_000_000_000
    users = np.array(["a", "b", "c", "d"])
    feeds = [
        (base + np.arange(8, dtype=np.int64) * 100,
         {"u": users[np.arange(8) % 4], "v": np.ones(8, np.float32)}),
        # far ahead: closes the first sessions and advances the wm
        (base + 60_000 + np.arange(8, dtype=np.int64) * 100,
         {"u": users[np.arange(8) % 4], "v": np.ones(8, np.float32)}),
        # LATE: all 8 records are past gap+grace at the watermark
        (base + 10_000 + np.arange(8, dtype=np.int64),
         {"u": users[np.arange(8) % 4], "v": np.ones(8, np.float32)}),
    ]
    out_dev, out_host = [], []
    for ts, cols in feeds:
        out_dev.extend(dev.process_columnar(ts, dict(cols)))
        out_host.extend(host.process_columnar(ts, dict(cols)))
    out_dev.extend(dev.drain_closed())
    out_host.extend(host.drain_closed())
    assert dev._dev is not None, "device path did not activate"
    assert dev.watermark == host.watermark
    assert dev.late_drops == host.late_drops == 8
    assert len(out_dev) == len(out_host)


def test_query_label_counters_survive_stream_filter():
    """late_drops / kernel_recompiles series are query-labeled: the
    live-STREAM filter must not drop them (bounded by query existence
    instead), and factory_recompiles is never liveness-filtered."""
    stats = StatsHolder()
    stats.stream_stat_add("late_drops", "q9", 4)
    stats.stream_stat_add("kernel_recompiles", "q9", 2)
    stats.stream_stat_add("factory_recompiles", "probe", 1)
    text = render_holder(stats, live_streams=set(), live_queries={"q9"})
    assert 'hstream_late_drops_total{stream="q9"} 4' in text
    assert 'hstream_kernel_recompiles_total{stream="q9"} 2' in text
    assert 'hstream_factory_recompiles_total{stream="probe"} 1' in text
    # deleted query: its series leave the exposition
    text = render_holder(stats, live_streams=set(), live_queries=set())
    assert "q9" not in text
    assert 'hstream_factory_recompiles_total{stream="probe"} 1' in text


def test_lock_label_counters_survive_stream_filter():
    """lock_contention is labeled by a traced-lock ROLE name — never a
    stream, so the liveness filter must not drop it; the wait/hold
    histograms carry the `lock` label key (ISSUE 14)."""
    stats = StatsHolder()
    stats.stream_stat_add("lock_contention", "tasks.state", 5)
    stats.observe("lock_wait_ms", "tasks.state", 1.2)
    stats.observe("lock_hold_ms", "scheduler.supervisor", 0.3)
    text = render_holder(stats, live_streams=set(), live_queries=set())
    assert 'hstream_lock_contention_total{stream="tasks.state"} 5' \
        in text
    assert 'hstream_lock_wait_ms_count{lock="tasks.state"} 1' in text
    assert 'hstream_lock_hold_ms_count{lock="scheduler.supervisor"} 1' \
        in text


# ---- /overview wiring (satellite) ------------------------------------------


def test_overview_includes_flow_and_pipeline(stack):
    _, base, stub, ctx = stack
    code, body, _ = _http("GET", base, "/overview")
    assert code == 200
    ov = json.loads(body)
    assert ov["flow"]["level"] in ("admit", "defer", "reject")
    assert "shed" in ov["flow"] and "signals" in ov["flow"]
    assert "pipeline_stages" in ov


# ---- registry lint ---------------------------------------------------------


def test_json_append_hits_native_decoder(stack):
    """ISSUE 5 satellite: a multi-record JSON append must be decoded by
    the libjsondec batch decoder, not the per-record Python fallback —
    and the native/fallback split is visible in /metrics."""
    from hstream_tpu.common import jsondec
    from hstream_tpu.common import records as rec

    if jsondec.load() is None:
        pytest.skip("native jsondec unavailable (no toolchain)")
    addr, http_base, stub, ctx = stack
    stub.CreateStream(pb.Stream(stream_name="njd"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE STREAM njd_out AS SELECT device, COUNT(*) "
                  "AS c FROM njd GROUP BY device, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND EMIT CHANGES;"))
    from helpers import wait_any_attached

    wait_any_attached(ctx)
    req = pb.AppendRequest(stream_name="njd")
    for i in range(64):
        req.records.append(rec.build_record(
            {"device": f"d{i % 4}", "temp": 1.5},
            publish_time_ms=BASE + i))
    stub.Append(req)
    deadline = time.time() + 20
    while time.time() < deadline:
        if ctx.stats.stream_stat_get("json_decode_native", "njd") >= 64:
            break
        time.sleep(0.05)
    native = ctx.stats.stream_stat_get("json_decode_native", "njd")
    assert native >= 64, f"native decode counter stuck at {native}"
    assert ctx.stats.stream_stat_get("json_decode_fallback", "njd") == 0
    body = render_metrics(ctx)
    assert re.search(
        r'hstream_json_decode_native_total\{stream="njd"\} \d+', body)


def test_metrics_lint_passes():
    """The registry check now lives in the analysis suite (ISSUE 4)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "tools.analyze", "--only", "registry"],
        capture_output=True, text=True, cwd=repo)
    assert r.returncode == 0, r.stdout + r.stderr


def test_metrics_lint_shim_forwards():
    """The deprecated tools/metrics_lint.py entry point still works
    (forwards to the registry pass with a deprecation warning)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "metrics_lint.py")],
        capture_output=True, text=True, cwd=repo)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "DEPRECATED" in r.stderr
