"""The device cost plane (ISSUE 18): HBM arena accounting, the
compiled-program inventory, and the flight recorder.

Accounting tests gate EXACTNESS: `device_plane_bytes()` must equal a
brute-force recompute (shape x itemsize per plane, computed here from
first principles, not via `nbytes`) for the fixed-window lattice, the
device join stores, and the session arena — before and after capacity
growth and code-space compaction. The inventory test pins one row per
distinct shape key under RetraceGuard; the flight-recorder tests pin
exactly one bundle per distress edge and survival across query
deletion (the black box outlives the aircraft).
"""

from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import grpc
import numpy as np
import pytest

from hstream_tpu.common import records as rec
from hstream_tpu.engine import ColumnType, Schema
from hstream_tpu.engine.expr import Col
from hstream_tpu.engine.plan import AggKind, AggregateNode, AggSpec, SourceNode
from hstream_tpu.engine.executor import QueryExecutor
from hstream_tpu.engine.session import SessionExecutor
from hstream_tpu.engine.window import SessionWindow, TumblingWindow
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.http_gateway import serve_gateway
from hstream_tpu.server.main import serve
from hstream_tpu.sql.codegen import make_executor, stream_codegen

from helpers import smoke_tumbling_config, wait_attached

BASE = 1_700_000_000_000

SCHEMA = Schema.of(k=ColumnType.STRING, v=ColumnType.FLOAT)


def _brute_bytes(planes) -> dict[str, int]:
    """Independent recompute of per-plane device bytes from shape and
    dtype — deliberately NOT via `nbytes`, so the accounting plane's
    own walk has something honest to be compared against."""
    out: dict[str, int] = {}
    for name, arr in dict(planes).items():
        n = 1
        for d in arr.shape:
            n *= int(d)
        nb = n * np.dtype(arr.dtype).itemsize
        if nb:
            out[str(name)] = nb
    return out


# ---- HBM arena accounting: exact against brute force -----------------------


def test_fixed_window_arena_bytes_exact_across_grow():
    node = AggregateNode(
        child=SourceNode("s", SCHEMA), group_keys=[Col("k")],
        window=TumblingWindow(10_000, grace_ms=0),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c"),
              AggSpec(AggKind.SUM, "s", input=Col("v"))],
        having=None, post_projections=[])
    ex = QueryExecutor(node, SCHEMA, emit_changes=False,
                       initial_keys=8, batch_capacity=256)
    rows = [{"k": f"k{i % 4}", "v": 1.0} for i in range(16)]
    ex.process(rows, [BASE + i for i in range(16)])
    got = ex.device_plane_bytes()
    assert got == _brute_bytes(ex.state)
    assert got and got == {k: v for k, v in got.items() if v > 0}
    before_total = sum(got.values())
    # key growth: > initial_keys distinct keys pads every keyed plane
    rows = [{"k": f"g{i}", "v": 1.0} for i in range(50)]
    ex.process(rows, [BASE + i for i in range(50)])
    got2 = ex.device_plane_bytes()
    assert got2 == _brute_bytes(ex.state)
    assert sum(got2.values()) > before_total


def test_join_store_bytes_exact_with_prefixed_planes():
    sql = ("SELECT l.k, COUNT(*) AS c FROM l INNER JOIN r "
           "WITHIN (INTERVAL 1 SECOND) ON l.k = r.k "
           "GROUP BY l.k, TUMBLING (INTERVAL 10 SECOND) "
           "GRACE BY INTERVAL 0 SECOND EMIT CHANGES;")
    ex = make_executor(stream_codegen(sql),
                       sample_rows=[{"k": "k0", "x": 1.0}])
    rng = np.random.default_rng(5)
    for b in range(8):
        rows = [{"k": f"k{int(i)}", "x": 1.0}
                for i in rng.integers(0, 30, 128)]
        ts = (BASE + b * 500
              + rng.integers(0, 400, 128).astype(np.int64)).tolist()
        ex.process(rows, ts, stream="l" if b % 2 else "r")
    assert ex._dev is not None, "device join path did not activate"
    want = {f"agg.{k}": v
            for k, v in _brute_bytes(ex._inner.state).items()}
    for side in ("l", "r"):
        for k, v in _brute_bytes(ex._dev["stores"][side]).items():
            want[f"{side}.{k}"] = v
    got = ex.device_plane_bytes()
    assert got == want
    # all three prefixes present: both stores and the inner lattice
    prefixes = {p.split(".", 1)[0] for p in got}
    assert {"l", "r", "agg"} <= prefixes


@pytest.mark.parametrize("mode", ["segment", "record"])
def test_session_arena_bytes_exact_across_compaction(mode):
    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.SUM, "s", input=Col("v"))]
    node = AggregateNode(
        child=SourceNode("s", SCHEMA), group_keys=[Col("k")],
        window=SessionWindow(500, grace_ms=0), aggs=aggs,
        having=None, post_projections=[])
    ex = SessionExecutor(node, SCHEMA, emit_changes=False)
    ex.use_device_sessions = True
    ex.device_session_mode = mode
    assert ex.device_plane_bytes() == {}  # nothing resident yet
    ex._KEY_CACHE_MAX = 64  # force code-space compaction quickly
    rng = np.random.default_rng(3)
    before_compaction = None
    for b in range(8):
        ks = [f"k{b}_{int(i)}" for i in rng.integers(0, 40, 120)]
        ts = (BASE + b * 5000 + rng.integers(0, 400, 120)).tolist()
        ex.process([{"k": k, "v": 1.0} for k in ks], ts)
        if before_compaction is None and ex._dev is not None:
            before_compaction = ex.device_plane_bytes()
            assert before_compaction == _brute_bytes(ex._dev["arena"])
    assert ex._dev is not None
    assert ex.session_stats["remap_dispatches"] >= 1
    assert ex.device_plane_bytes() == _brute_bytes(ex._dev["arena"])
    assert before_compaction is not None and before_compaction


def test_plane_bytes_skips_non_arrays_and_empty():
    from hstream_tpu.stats.devicecost import plane_bytes

    got = plane_bytes({"a": np.zeros((4, 2), np.float32),
                       "empty": np.zeros((0,), np.int32),
                       "scalarish": 7})
    assert got == {"a": 32}


# ---- per-dispatch device time ------------------------------------------------


def test_armed_sampler_records_and_compiles_nothing(retrace_guard):
    """The sampler ARMED at rate 1 fences and times every dispatch: it
    records samples for every family it ticked, and 50 steady batches
    still compile nothing (block_until_ready is a sync, never a trace).
    The DISARMED half of the contract, no state after a served run, is
    tests/test_append_framed.py::test_served_steady_state_compiles_nothing."""
    from hstream_tpu.stats.devicecost import DEVICE_TIME

    assert not DEVICE_TIME.active
    ex, feed, warm = smoke_tumbling_config()
    DEVICE_TIME.arm(1)
    try:
        for i in range(warm):
            feed(i)
        ex.block_until_ready()
        with retrace_guard():
            for i in range(warm, warm + 50):
                feed(i)
            ex.block_until_ready()
        state = DEVICE_TIME.state()
    finally:
        DEVICE_TIME.disarm()
        DEVICE_TIME.reset()
    assert state["counts"] and all(
        state["samples"].get(family) for family in state["counts"])
    assert not any(DEVICE_TIME.state().values())


# ---- compiled-program inventory --------------------------------------------


def test_program_inventory_one_row_per_shape_key():
    import jax
    import jax.numpy as jnp

    from hstream_tpu.common.tracing import RetraceGuard, kernel_family
    from hstream_tpu.stats.devicecost import PROGRAMS

    PROGRAMS.install()  # raises if the compile funnel seam is absent
    assert PROGRAMS.summary()["installed"]
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    # build inputs OUTSIDE the guarded regions: the ones-fill is its
    # own (cached) compile and must not pollute the counts
    x8 = jnp.ones((8,), jnp.float32)
    x16 = jnp.ones((16,), jnp.float32)
    keys0 = {r["shape_key"] for r in PROGRAMS.rows()}

    with RetraceGuard() as g:
        with kernel_family("step", None):
            fn(x8).block_until_ready()
    assert g.count == 1
    new = [r for r in PROGRAMS.rows() if r["shape_key"] not in keys0]
    assert len(new) == 1, new
    row = new[0]
    assert row["compiles"] == 1 and row["compile_ms"] > 0
    assert row["family"] == "step"  # attributed to the active scope
    keys1 = keys0 | {row["shape_key"]}

    # same shape again: cache hit, no compile, NO new row
    with RetraceGuard() as g2:
        fn(x8).block_until_ready()
    assert g2.count == 0
    assert {r["shape_key"] for r in PROGRAMS.rows()} == keys1

    # a distinct shape is a distinct shape key: exactly one new row
    with RetraceGuard() as g3:
        fn(x16).block_until_ready()
    assert g3.count == 1
    new2 = [r for r in PROGRAMS.rows() if r["shape_key"] not in keys1]
    assert len(new2) == 1 and new2[0]["shape_key"] != row["shape_key"]

    s = PROGRAMS.summary()
    assert s["installed"] and s["programs"] >= 2
    assert s["total_compiles"] >= 2


def test_program_inventory_lru_bound_folds_into_evicted():
    from hstream_tpu.stats.devicecost import ProgramInventory

    inv = ProgramInventory()
    inv.MAX_ROWS = 4

    class _Exe:  # minimal stand-in for a LoadedExecutable
        def hlo_modules(self):
            return []

        def cost_analysis(self):
            return [{"flops": 10.0, "bytes accessed": 20.0}]

    for i in range(6):
        inv._record(_Exe(), 1.0, (None, f"module-{i}"))
    assert len(inv.rows()) == 4
    assert inv.evicted == 2
    assert inv.summary()["evicted"] == 2
    assert all(r["flops"] == 10.0 and r["bytes_accessed"] == 20.0
               for r in inv.rows())


# ---- flight recorder --------------------------------------------------------


@pytest.fixture(scope="module")
def stack():
    server, ctx = serve("127.0.0.1", 0, "mem://", metrics_port=0)
    addr = f"127.0.0.1:{ctx.port}"
    httpd, gw = serve_gateway(addr, port=0)
    base = f"http://127.0.0.1:{httpd.server_port}"
    channel = grpc.insecure_channel(addr)
    stub = HStreamApiStub(channel)
    yield base, stub, ctx
    channel.close()
    httpd.shutdown()
    gw.close()
    server.stop(grace=1)
    ctx.shutdown()


def _http(base, path):
    try:
        with urllib.request.urlopen(base + path) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _admin(stub, command, **kwargs):
    resp = stub.SendAdminCommand(pb.AdminCommandRequest(
        command=command, args=rec.dict_to_struct(kwargs)))
    return json.loads(resp.result)


def test_flightrec_once_per_episode_and_survives_deletion(stack):
    """Breaker-open writes one bundle (crash_loop_open), the STALLED
    health transition writes one more (query_stalled) — and ONLY one
    each: re-evaluating health does not re-snapshot. The bundles stay
    readable over the wire after the query is deleted."""
    base, stub, ctx = stack
    stub.CreateStream(pb.Stream(stream_name="frsrc"))
    q = stub.CreateQuery(pb.CreateQueryRequest(
        query_text="SELECT k, COUNT(*) AS c FROM frsrc GROUP BY k, "
                   "TUMBLING (INTERVAL 1 SECOND) EMIT CHANGES;",
        id="qfr1"))
    task = wait_attached(ctx, q.id)
    # kill for real (crash: status stays RUNNING), then feed the
    # supervisor a crash loop until the breaker opens
    task.stop(crash=True)
    deadline = time.time() + 10
    while q.id in ctx.running_queries and time.time() < deadline:
        time.sleep(0.02)
    assert q.id not in ctx.running_queries
    info = ctx.persistence.get_query(q.id)
    sup = ctx.supervisor
    n_ev0 = len(ctx.events.query(kind="flightrec_written", limit=1000))
    for _ in range(sup.BREAKER_K):
        sup.note_death(info, RuntimeError("boom"))
    assert q.id in sup.status()["breaker_open"]

    bundles = ctx.flightrec.bundles(q.id)
    assert len(bundles) == 1
    assert bundles[0]["trigger"] == "crash_loop_open"
    ev = ctx.events.query(kind="flightrec_written", limit=1000)
    assert len(ev) == n_ev0 + 1 and ev[-1]["query"] == q.id

    # the STALLED transition: exactly one more bundle, with the
    # already-computed verdict inside
    code, body = _http(base, f"/queries/{q.id}/health")
    assert code == 200
    assert json.loads(body)["verdict"] == "STALLED"
    bundles = ctx.flightrec.bundles(q.id)
    assert len(bundles) == 2
    b = bundles[-1]
    assert b["trigger"] == "query_stalled"
    assert b["health"]["verdict"] == "STALLED"
    assert "crash_loop" in b["health"]["reasons"]
    # every postmortem section captured
    for section in ("events", "spans", "stat_ladder", "programs",
                    "hbm"):
        assert section in b, section
    assert any(e.get("kind") == "query_stalled" for e in b["events"])
    assert b["programs"]["summary"]["installed"] is True
    assert b["hbm"]["total"] == 0  # task already dead: nothing resident

    # re-evaluation is NOT a new episode: no third bundle
    _http(base, f"/queries/{q.id}/health")
    _http(base, f"/queries/{q.id}/health")
    assert len(ctx.flightrec.bundles(q.id)) == 2
    assert len(ctx.events.query(kind="flightrec_written",
                                limit=1000)) == n_ev0 + 2

    # served over the wire: admin verb and gateway route agree
    got = _admin(stub, "flightrec", query=q.id)
    assert got["query"] == q.id and len(got["bundles"]) == 2
    code, body = _http(base, f"/queries/{q.id}/flightrec")
    assert code == 200
    assert len(json.loads(body)["bundles"]) == 2

    # deleting the query must NOT shred the black box
    stub.DeleteQuery(pb.DeleteQueryRequest(id=q.id))
    code, _ = _http(base, f"/queries/{q.id}/health")
    assert code == 404  # the query is gone...
    code, body = _http(base, f"/queries/{q.id}/flightrec")
    assert code == 200
    assert len(json.loads(body)["bundles"]) == 2
    assert q.id in ctx.flightrec.summary()["queries"]


def test_flightrec_two_slot_rotation(stack):
    base, stub, ctx = stack
    fr = ctx.flightrec
    seqs = [fr.snapshot("rotq", trigger="query_stalled")["seq"]
            for _ in range(3)]
    kept = fr.bundles("rotq")
    assert [b["seq"] for b in kept] == seqs[-2:]  # newest two, in order
    assert fr.summary()["queries"]["rotq"] == 2
    # no-bundle query: admin verb raises the typed not-found error
    with pytest.raises(grpc.RpcError):
        _admin(stub, "flightrec", query="never-distressed")


def test_admin_programs_and_gateway_route(stack):
    base, stub, ctx = stack
    got = _admin(stub, "programs")
    assert got["summary"]["installed"] is True
    assert got["summary"]["programs"] == len(got["programs"])
    assert got["programs"], "server boot compiled nothing?"
    for row in got["programs"]:
        assert row["shape_key"] and row["compiles"] >= 1
    code, body = _http(base, "/programs")
    assert code == 200
    assert json.loads(body)["summary"]["installed"] is True


def test_device_gauges_on_live_metrics_match_brute_force(stack):
    """`device_hbm_bytes{query}` on a live server equals the
    brute-force plane recompute, per plane and in total — the
    acceptance-criteria exactness check, over /metrics."""
    base, stub, ctx = stack
    stub.CreateStream(pb.Stream(stream_name="dgsrc"))
    q = stub.CreateQuery(pb.CreateQueryRequest(
        query_text="SELECT k, COUNT(*) AS c FROM dgsrc GROUP BY k, "
                   "TUMBLING (INTERVAL 1 SECOND) EMIT CHANGES;",
        id="qdg1"))
    task = wait_attached(ctx, q.id)
    req = pb.AppendRequest(stream_name="dgsrc")
    now = int(time.time() * 1000)
    for i in range(8):
        req.records.append(rec.build_record({"k": f"k{i % 3}"},
                                            publish_time_ms=now + i))
    stub.Append(req)
    deadline = time.time() + 10
    while not task.device_plane_bytes() and time.time() < deadline:
        time.sleep(0.05)
    planes = task.device_plane_bytes()
    assert planes, "executor never became device-resident"
    ex = task.executor
    assert planes == _brute_bytes(ex.state)

    from hstream_tpu.stats.prometheus import render_metrics

    text = render_metrics(ctx)
    want_total = sum(planes.values())
    line = [ln for ln in text.splitlines()
            if ln.startswith(f'hstream_device_hbm_bytes{{query="{q.id}"')]
    assert line and line[0].split()[-1] == str(want_total)
    for plane, nb in planes.items():
        pl = [ln for ln in text.splitlines()
              if ln.startswith('hstream_device_arena_bytes{')
              and f'query="{q.id}"' in ln and f'plane="{plane}"' in ln]
        assert pl and pl[0].split()[-1] == str(nb), plane
    # process-total gauge folds every live query
    tot = [ln for ln in text.splitlines()
           if ln.startswith("hstream_device_hbm_total_bytes")]
    assert tot and int(float(tot[0].split()[-1])) >= want_total
    stub.DeleteQuery(pb.DeleteQueryRequest(id=q.id))
    # stale series sweep: the deleted query's series disappear
    deadline = time.time() + 10
    while q.id in ctx.running_queries and time.time() < deadline:
        time.sleep(0.02)
    text = render_metrics(ctx)
    assert f'hstream_device_hbm_bytes{{query="{q.id}"' not in text
