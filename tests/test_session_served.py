"""The device session lattice on the served path, at the NEXmark Q11
deployment's toy sizes: the view a session query leaves behind equals
the benchmark's plain reference (`benchmarks/references/user_sessions`)
row for row, in the mode an accelerator picks ("record") as well as the
CPU's default ("segment"); what a session or a join query consumes is
counted; the session path's stages are spans of their own.

One served run a (mode, seed): the generator's frames appended over
gRPC, one call each, through the door and the store; closed sessions
pulled before and after the closer, as `benchmarks/run.py` does it.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.generators import nexmark as gen  # noqa: E402
from benchmarks.harness import manifest, served  # noqa: E402
from benchmarks.references import user_sessions as ref  # noqa: E402
from hstream_tpu.common.tracing import TRACE_PARENT, TRACE_STAGES  # noqa: E402
from hstream_tpu.engine import lattice  # noqa: E402
from hstream_tpu.engine.session import SessionExecutor  # noqa: E402
from hstream_tpu.server.main import serve  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nexmark_q11.json")) as _f:
    CONFIG = json.load(_f)
DRY = manifest.size_of(CONFIG, True)
N_FRAMES = gen.warm_frames(DRY) + 12
SESSION_STAGES = {s for s in TRACE_STAGES if s.startswith("session_")}
CASES = [(mode, seed) for seed in (2**31 + 29, 31)
         for mode in ("record", "segment")]


def _stages(ctx) -> dict:
    return {label: h.snapshot()[2]
            for (metric, label), h in ctx.stats.histograms_snapshot().items()
            if metric == "stage_latency_ms"}


def _admin_stats_queries(client) -> dict:
    from hstream_tpu.common import records as rec
    from hstream_tpu.proto import api_pb2 as pb

    resp = client.stub.SendAdminCommand(pb.AdminCommandRequest(
        command="stats", args=rec.dict_to_struct({"entity": "queries"})))
    return json.loads(resp.result)


@pytest.fixture(scope="module", params=CASES,
                ids=[f"{m}-{s}" for m, s in CASES])
def run(request):
    mode, seed = request.param
    init = SessionExecutor.__init__

    def forced(self, *a, **kw):
        init(self, *a, **kw)
        self.device_session_mode = mode

    patch = pytest.MonkeyPatch()
    patch.setattr(SessionExecutor, "__init__", forced)
    server, ctx = serve("127.0.0.1", 0, "mem://")
    client = served.Client(ctx.port)
    out = {"mode": mode, "seed": seed}
    try:
        for st in gen.streams(DRY):
            client.sql(f"CREATE STREAM {st['name']};")
        client.sql(DRY["sql"])
        task = served.wait_task(ctx, f"view-{DRY['view']}")
        out["consumed_at_start"] = served.consumed_events(ctx, task)
        for i in range(N_FRAMES):
            client.append_call([gen.frame(DRY, seed, i)])
        served.wait_consumed(ctx, task, 300)
        out["consumed"] = served.consumed_events(ctx, task)
        plan = gen.pulls(DRY, N_FRAMES)
        before = client.sql(plan["before"][0]["sql"])
        out["stats"] = _admin_stats_queries(client)[task.info.query_id]
        with task.state_lock:
            ex = task.executor
        out["gauges_before_closer"] = ex.session_gauges()
        out["codes_minted"] = len(ex._code_rev)
        for closer in gen.closers(DRY, N_FRAMES):
            client.append_call([closer])
        served.wait_consumed(ctx, task, 300)
        after = client.sql(plan["after"][0]["sql"])
        seen = {tuple(sorted(r.items())) for r in before}
        out["before"], out["after"] = before, after
        out["served"] = {
            "final": before + [r for r in after
                               if tuple(sorted(r.items())) not in seen],
            "complete": ["before_closer", "after_closer"], "pulls": [],
            "horizon": plan["horizon"]}
        out["guarantees"] = served.on_device(ctx, task, DRY["executor"])
        out["dev_mode"] = ex._dev["mode"] if ex._dev is not None else None
        out["stages"] = _stages(ctx)
        out["everything"] = client.sql(
            f"SELECT * FROM {DRY['view']};")  # open sessions too
    finally:
        patch.undo()
        client.close()
        server.stop(grace=1)
        ctx.shutdown()
    return out


def test_the_view_equals_the_plain_reference_row_for_row(run):
    ses = ref.sessions(DRY, run["seed"], N_FRAMES)
    numbers = ref.compare(DRY, run["seed"], N_FRAMES, run["served"],
                          ses=ses)
    assert numbers == {"rows_missing": 0, "rows_extra": 0,
                       "bids_mismatch": 0, "bounds_mismatch": 0}
    # the toy view holds EVERY session of the run, each exactly once
    assert len(run["served"]["final"]) == len(ses["bidder"])
    assert len(run["after"]) == len(ses["bidder"])
    closed_before = int((ses["cycle"] < N_FRAMES).sum())
    assert 0 < closed_before == len(run["before"]) < len(run["after"])
    assert sum(r["bids"] for r in run["after"]) \
        == N_FRAMES * DRY["frame_rows"]
    # ids past 2^24 come back as the integers that went in
    assert {int(r["bidder"]) for r in run["after"]} \
        == set(ses["bidder"].tolist())
    assert min(r["bidder"] for r in run["after"]) > 2**24


def test_the_path_compared_is_the_path_that_ran(run):
    assert run["dev_mode"] == run["mode"]
    assert run["guarantees"] == {"executor_wrong": 0, "device_fallbacks": 0,
                                 "late_drops": 0, "query_not_running": 0}
    # the closer's own session is the one left open
    open_rows = [r for r in run["everything"]
                 if r["winEnd"] > run["served"]["horizon"]]
    assert [r["bidder"] for r in open_rows] == [gen.CLOSER_BIDDER]


def test_consumed_events_counts_the_bids_a_session_query_took(run):
    assert run["consumed_at_start"] == 0
    assert run["consumed"] == N_FRAMES * DRY["frame_rows"]
    assert run["stats"]["consumed_events_total"] == run["consumed"]


def test_session_stats_and_the_arena_show_in_admin_stats_queries(run):
    stats, g = run["stats"], run["gauges_before_closer"]
    assert stats["session_batches"] == N_FRAMES == g["batches"]
    assert stats["session_step_dispatches"] == N_FRAMES
    assert stats["session_close_cycles"] == g["close_cycles"] > 0
    assert stats["session_close_fetches"] == g["close_fetches"] > 0
    assert stats["session_grows"] == g["grows"] > 0
    assert stats["session_remap_dispatches"] == 0
    assert 0 < stats["session_live"] == g["live"] <= g["arena_cap"]
    assert stats["session_arena_cap"] == g["arena_cap"]
    ses = ref.sessions(DRY, run["seed"], N_FRAMES)
    assert g["live"] == int((ses["cycle"] == N_FRAMES).sum())


def test_the_decode_columns_counters_show_in_admin_stats_queries(run):
    """`code_cols_builds` / `code_cols_appended` (ISSUE 30) reach `admin
    stats queries` as `session_<stat>`: the columns were made once, at
    activation, and every code a close had to name was appended."""
    stats, g = run["stats"], run["gauges_before_closer"]
    assert stats["session_code_cols_builds"] == g["code_cols_builds"] == 1
    assert stats["session_code_cols_appended"] == g["code_cols_appended"]
    ses = ref.sessions(DRY, run["seed"], N_FRAMES)
    bidders = set(ses["bidder"].tolist())
    closed = set(ses["bidder"][ses["cycle"] < N_FRAMES].tolist())
    assert run["codes_minted"] == len(bidders)
    assert len(closed) <= g["code_cols_appended"] <= len(bidders)


def test_the_mirror_counters_show_in_admin_stats_queries(run):
    """`mirror_rows_merged` / `mirror_full_merges` (ISSUE 32) reach
    `admin stats queries` as `session_<stat>`: one batch, the first
    after activation, handed the chain merge every mirror row; the rest
    handed it the open sessions of the bidders they named, a small part
    of the sessions open."""
    stats, g = run["stats"], run["gauges_before_closer"]
    assert stats["session_mirror_full_merges"] \
        == g["mirror_full_merges"] == 1
    assert stats["session_mirror_rows_merged"] == g["mirror_rows_merged"]
    assert 0 < g["mirror_rows_merged"] < N_FRAMES * g["live"] // 8


def test_the_session_paths_stages_are_spans_inside_step(run):
    stages = run["stages"]
    batches = N_FRAMES + 1  # and the closer
    for s in ("session_key_codes", "session_mirror"):
        assert stages[s] == batches, s
    assert stages["step"] == batches
    cycles = run["gauges_before_closer"]["close_cycles"] + 1
    for s in ("session_close", "session_close_fetch",
              "session_close_decode"):
        assert stages[s] == cycles, s
    # packing raw records is the record mode's; no compaction this small
    assert stages.get("session_pack", 0) == (
        batches if run["mode"] == "record" else 0)
    assert "session_remap" not in stages
    assert set(stages) & SESSION_STAGES == {
        s for s in SESSION_STAGES
        if s != "session_remap" and (s != "session_pack"
                                     or run["mode"] == "record")}
    for s in SESSION_STAGES:  # each nests in `step`, directly or not
        top = s
        while top in TRACE_PARENT:
            top = TRACE_PARENT[top]
        assert top == "step", s
    # a session query has no ingest pipeline and no key table
    assert not set(stages) & {"key_encode", "ring_wait", "stage_wait",
                              "encode", "close"}


def test_pinned_session_program_names():
    """A trace names a program `jit_<function>`: `session_step_roofline`
    finds the step by the constant, the constant is the function's."""
    from hstream_tpu.engine.plan import AggKind, AggSpec
    from hstream_tpu.engine.types import Schema

    spec = lattice.SessionSpec(aggs=(AggSpec(AggKind.COUNT_ALL, "c"),))
    step = lattice.session_step_kernel(spec, Schema(()), (), 256, 4096)
    assert "jit_" + step.__name__ == lattice.SESSION_STEP_PROGRAM \
        == "jit_session_step"
    assert ("jit_" + lattice.session_extract_kernel(spec, 256, 8).__name__
            == lattice.SESSION_EXTRACT_PROGRAM == "jit_session_extract")
    assert ("jit_" + lattice.session_remap_kernel(256, 1024).__name__
            == lattice.SESSION_REMAP_PROGRAM == "jit_session_remap")
    # the window lattice's step keeps its own name
    assert lattice.STEP_PROGRAM == "jit_step"


# ---- a window query and a join query, for contrast -------------------------


@pytest.fixture(scope="module")
def other_queries():
    """One served tumbling view and one served join view, a few framed
    batches each: (stages, consumed by the tumbling query, consumed by
    the join query, rows the join's streams were sent)."""
    base = gen.BASE
    server, ctx = serve("127.0.0.1", 0, "mem://")
    client = served.Client(ctx.port)
    try:
        for s in ("w", "l", "r"):
            client.sql(f"CREATE STREAM {s};")
        client.sql("CREATE VIEW wv AS SELECT k, COUNT(*) AS c FROM w "
                   "GROUP BY k, TUMBLING (INTERVAL 10 SECOND) "
                   "GRACE BY INTERVAL 0 SECOND;")
        client.sql("CREATE VIEW jv AS SELECT l.k, COUNT(*) AS c FROM l "
                   "INNER JOIN r WITHIN (INTERVAL 5 SECOND) ON l.k = r.k "
                   "GROUP BY l.k, TUMBLING (INTERVAL 10 SECOND) "
                   "GRACE BY INTERVAL 0 SECOND;")
        wtask = served.wait_task(ctx, "view-wv")
        jtask = served.wait_task(ctx, "view-jv")
        rng = np.random.default_rng(5)
        sent = 0
        for i in range(6):
            ts = base + i * 4000 + np.sort(rng.integers(0, 4000, 64))
            keys = np.array([f"k{j % 5}" for j in range(64)])
            client.append_call([("w", ts, {"k": keys, "v": np.ones(64)},
                                 64)])
            for stream, col in (("l", "x"), ("r", "y")):
                client.append_call([(stream, ts,
                                     {"k": keys, col: np.ones(64)}, 64)])
                sent += 64
        served.wait_consumed(ctx, wtask, 120)
        served.wait_consumed(ctx, jtask, 120)
        return (_stages(ctx), served.consumed_events(ctx, wtask),
                served.consumed_events(ctx, jtask), sent)
    finally:
        client.close()
        server.stop(grace=1)
        ctx.shutdown()


def test_a_window_query_shows_none_of_the_session_stages(other_queries):
    stages, consumed, _joined, _sent = other_queries
    assert not set(stages) & SESSION_STAGES
    assert {"key_encode", "step"} <= set(stages)
    assert consumed == 6 * 64


def test_consumed_events_moves_for_a_join_query(other_queries):
    _stages_seen, _consumed, joined, sent = other_queries
    assert joined == sent == 2 * 6 * 64


# ---- the device's chain scan against the host mirror's ---------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_devices_chains_are_the_mirrors_on_equal_starts(seed):
    """The device's sort + segmented scan and the host mirror's merge
    cut the same chains where many entries share one (code, start)
    with unequal ends: the arena's slots stay the mirror's rows."""
    import jax

    from hstream_tpu.engine.session import merge_chains_np

    rng = np.random.default_rng(seed)
    n, cap, gap = 4000, 4096, 50
    code = rng.integers(0, 40, n)
    start = rng.integers(0, 60, n) * 25          # many equal starts
    end = start + rng.integers(0, 4, n) * 30     # with unequal ends
    dest = np.asarray(jax.jit(lattice._session_chain_slots,
                              static_argnums=(4,))(
        code.astype(np.int32), start.astype(np.int32),
        end.astype(np.int32), np.int32(gap), cap))
    mcode, mt0, mt1, _fanin = merge_chains_np(code, start, end, gap)
    assert dest.max() + 1 == len(mcode) < n
    for got, want in ((code, mcode), (start, mt0)):
        low = np.full(len(mcode), np.iinfo(np.int64).max)
        np.minimum.at(low, dest, got)
        assert np.array_equal(low, want)
    high = np.zeros(len(mcode), np.int64)
    np.maximum.at(high, dest, end)
    assert np.array_equal(high, mt1)
    ties = np.unique(np.stack([code, start]), axis=1).shape[1]
    assert ties < n / 1.5  # the case at issue is there


# ---- a snapshot of device-resident sessions is columns, taken by reference ---


def test_device_capture_is_columnar_and_restores_every_accumulator():
    """Phase 1 of a snapshot runs under the task's state lock: for
    device-resident sessions it takes the arena's planes by reference
    and the mirror's rows, no fetch and no object a session (4.6 s
    under the lock at 336 000 open sessions before). The blob restores
    to the sessions the degrade path's host view gives, for every
    accumulator kind."""
    import jax

    from test_session_device import BASE as T0, make_ex
    from hstream_tpu.engine import snapshot as snap
    from hstream_tpu.engine.expr import Col
    from hstream_tpu.engine.plan import AggKind, AggSpec
    from types import SimpleNamespace

    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.AVG, "a", input=Col("v")),
            AggSpec(AggKind.MIN, "lo", input=Col("v")),
            AggSpec(AggKind.MAX, "hi", input=Col("v")),
            AggSpec(AggKind.SUM, "s", input=Col("v")),
            AggSpec(AggKind.APPROX_COUNT_DISTINCT, "d", input=Col("v")),
            AggSpec(AggKind.APPROX_QUANTILE, "q", input=Col("v"),
                    quantile=0.5)]
    ex = make_ex(aggs, device=True, mode="record", gap=1000, grace=0)
    rng = np.random.default_rng(7)
    for b in range(3):
        n = 200
        rows = [{"k": f"u{int(k)}", "v": float(v)} for k, v in zip(
            rng.integers(0, 30, n), rng.integers(0, 50, n))]
        ex.process(rows, (T0 + b * 700 + rng.integers(0, 600, n)).tolist())
    assert ex._dev is not None
    meta, arrays = snap.capture_executor(ex)
    assert "sessions" not in meta and set(meta["device"]) == {"keys",
                                                              "planes"}
    planes = {k: v for k, v in arrays.items()
              if k.startswith("sess.plane.")}
    assert planes and all(isinstance(v, jax.Array) for v in planes.values())
    assert all(v is ex._dev["arena"][k[len("sess.plane."):]]
               for k, v in planes.items())  # by reference, not fetched
    live = int(ex._dev["mir_live"].sum())
    assert len(arrays["sess.key"]) == live > len(meta["device"]["keys"]) - 1
    want = ex._host_sessions_view()
    restored, _ = snap.restore_executor(
        SimpleNamespace(node=ex.node), snap.serialize_capture(meta, arrays))
    assert restored.sessions.keys() == want.keys()
    for key, sess in want.items():
        got = restored.sessions[key]
        assert [(s.start, s.end) for s in got] \
            == [(s.start, s.end) for s in sess]
        for g, w in zip(got, sess):
            assert g.accs.keys() == w.accs.keys()
            for name in w.accs:
                assert np.array_equal(np.asarray(g.accs[name]),
                                      np.asarray(w.accs[name])), name
