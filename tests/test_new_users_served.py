"""NEXmark Q8, new users, on the served path at the deployment's toy
sizes: the view a window join leaves behind equals the benchmark's plain
reference (`benchmarks/references/new_users`) row for row over every
window; the path compared is the path that ran (JoinExecutor on the
device, no fallback, no late row, none past retention); the join's
stages are spans of their own and its counts reach `admin stats
queries` and /metrics; and a task killed mid-stream restarts from its
snapshot to the same view.

One served run a seed: the generator's frames appended over gRPC, one
call each, through the door and the store, both logs read by one task;
closed windows pulled before and after the closers, as
`benchmarks/run.py` does it.
"""

import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.generators import nexmark_q8 as gen  # noqa: E402
from benchmarks.harness import manifest, served  # noqa: E402
from benchmarks.references import new_users as ref  # noqa: E402
from hstream_tpu.common.tracing import TRACE_PARENT, TRACE_STAGES  # noqa: E402
from hstream_tpu.engine.join import JoinExecutor  # noqa: E402
from hstream_tpu.server.main import serve  # noqa: E402
from hstream_tpu.stats.prometheus import render_metrics  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nexmark_q8.json")) as _f:
    CONFIG = json.load(_f)
DRY = manifest.size_of(CONFIG, True)
N_FRAMES = gen.warm_frames(DRY) + 41     # an odd count: a span half sent
JOIN_STAGES = {s for s in TRACE_STAGES if s.startswith("join_")}
ZERO = {"rows_missing": 0, "rows_extra": 0, "name_mismatch": 0,
        "auctions_mismatch": 0, "window_mismatch": 0}


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


def _served(before, after, plan) -> dict:
    seen = {tuple(sorted(r.items())) for r in before}
    return {"final": before + [r for r in after
                               if tuple(sorted(r.items())) not in seen],
            "complete": ["before_closer", "after_closer"], "pulls": [],
            "horizon": plan["horizon"]}


@pytest.fixture(scope="module", params=[2**31 + 35, 35])
def run(request):
    seed = request.param
    server, ctx = serve("127.0.0.1", 0, "mem://")
    client = served.Client(ctx.port)
    out = {"seed": seed}
    try:
        for st in gen.streams(DRY):
            client.sql(f"CREATE STREAM {st['name']};")
        client.sql(DRY["sql"])
        task = served.wait_task(ctx, f"view-{DRY['view']}")
        qid = task.info.query_id
        for i in range(N_FRAMES):
            client.append_call([gen.frame(DRY, seed, i)])
        served.wait_consumed(ctx, task, 300)
        out["consumed"] = served.consumed_events(ctx, task)
        plan = gen.pulls(DRY, N_FRAMES)
        before = client.sql(plan["before"][0]["sql"])
        with task.state_lock:
            ex = task.executor
        out["gauges_before_closers"] = ex.join_gauges()
        for closer in gen.closers(DRY, N_FRAMES):
            client.append_call([closer])
        served.wait_consumed(ctx, task, 300)
        after = client.sql(plan["after"][0]["sql"])
        task._note_device_fallbacks()
        out["before"], out["after"] = before, after
        out["served"] = _served(before, after, plan)
        out["guarantees"] = served.on_device(ctx, task, DRY["executor"])
        out["executor"] = type(ex).__name__
        out["on_device"] = ex._dev is not None
        out["stats"] = _admin_stats_queries(client)[qid]
        out["gauges"] = ex.join_gauges()
        out["stages"] = _stages(ctx)
        out["metrics"] = render_metrics(ctx)
        out["qid"] = qid
        out["explain"] = client.sql("EXPLAIN " + DRY["sql"])[0]["explain"]
    finally:
        client.close()
        server.stop(grace=1)
        ctx.shutdown()
    return out


def test_the_view_equals_the_plain_reference_over_every_window(run):
    ans = ref.answers(DRY, run["seed"], N_FRAMES)
    assert len(ans) >= 3 and all(len(v[0]) > 50 for v in ans.values())
    numbers = ref.compare(DRY, run["seed"], N_FRAMES, run["served"],
                          ans=ans)
    assert numbers == ZERO
    # the toy view holds EVERY window of the run, each row exactly once
    want = ref.rows_from(DRY, ans)
    key = lambda r: (r["winStart"], r["person.id"])  # noqa: E731
    assert sorted(run["after"], key=key) == sorted(want, key=key)
    closed = [r for r in want
              if r["winEnd"] <= gen.last_time(DRY, N_FRAMES)]
    assert 0 < len(closed) == len(run["before"]) < len(run["after"])
    # ids past 2^24 come back as the integers that went in, with names
    assert min(r["person.id"] for r in run["after"]) > 2**24
    assert all(isinstance(r["person.name"], str) and " " in
               r["person.name"] for r in run["after"])


def test_the_path_compared_is_the_path_that_ran(run):
    assert run["executor"] == "JoinExecutor" == JoinExecutor.__name__
    assert run["on_device"]
    assert run["guarantees"] == {"executor_wrong": 0, "device_fallbacks": 0,
                                 "late_drops": 0, "query_not_running": 0}
    assert run["gauges"]["rows_past_retention"] == 0
    assert "WITHIN WINDOW [window join" in run["explain"]


def test_consumed_events_counts_both_streams(run):
    n_p, n_a = gen.spans_of(N_FRAMES)
    per = DRY["span_epochs"]
    assert run["consumed"] == n_p * per + 3 * n_a * per
    assert run["stats"]["consumed_events_total"] == run["consumed"] + 2


def test_the_joins_counts_show_in_admin_stats_queries_and_metrics(run):
    stats, g, qid = run["stats"], run["gauges"], run["qid"]
    for name in ("rows_past_retention", "codes_live", "codes_reclaimed",
                 "store_rows_left", "store_rows_right", "matches",
                 "evict_dispatches", "fused_batches"):
        assert stats[f"join_{name}"] == g[name], name
    assert stats["join_rows_past_retention"] == 0
    assert stats["join_codes_reclaimed"] > 500
    assert stats["join_evict_dispatches"] >= 3
    assert stats["join_matches"] == sum(r["auctions"]
                                        for r in run["after"])
    # bounded by the open window: the closers' own two rows
    assert (stats["join_store_rows_left"],
            stats["join_store_rows_right"]) == (1, 1)
    assert stats["join_codes_live"] == 2
    assert stats["keys_live"] <= stats["key_capacity"]
    text = run["metrics"]
    for line in (
            f'hstream_join_codes_reclaimed_total{{stream="{qid}"}} '
            f'{stats["join_codes_reclaimed"]}',
            f'hstream_join_matches_total{{stream="{qid}"}} '
            f'{stats["join_matches"]}',
            "# HELP hstream_join_rows_past_retention_total rows a window "
            "join met behind its eviction bound"):
        assert line in text, line
    assert f'hstream_join_rows_past_retention_total{{stream="{qid}"}}' \
        not in text                      # a counter that never moved
    assert f'hstream_late_drops_total{{stream="{qid}"}}' not in text


def test_the_join_paths_stages_are_spans_inside_step(run):
    stages = run["stages"]
    batches = N_FRAMES + 2               # and the closers
    assert stages["step"] == batches
    on_device = run["gauges"]["probe_batches"]
    assert 0 < batches - on_device <= 6  # the first few ran on the host
    assert stages["join_key_codes"] == on_device
    assert stages["join_shadow"] == on_device == stages["join_pack"]
    assert stages["join_fetch"] >= run["gauges"]["fused_batches"] - 2
    assert stages["join_evict"] >= len(
        {r["winStart"] for r in run["after"]})
    assert JOIN_STAGES >= {"join_key_codes", "join_shadow", "join_pack",
                           "join_fetch", "join_decode", "join_evict"}
    assert all(TRACE_PARENT[s] == "step" for s in JOIN_STAGES)
    assert not [s for s in stages if s.startswith("session_")]
    assert "key_encode" not in stages    # the join names its own keys


def test_a_task_killed_mid_stream_restarts_to_the_same_view():
    seed = 2**31 + 37
    server, ctx = serve("127.0.0.1", 0, "mem://")
    client = served.Client(ctx.port)
    try:
        ctx.supervisor.BACKOFF_BASE_S = 0.05
        for st in gen.streams(DRY):
            client.sql(f"CREATE STREAM {st['name']};")
        client.sql(DRY["sql"])
        qid = f"view-{DRY['view']}"
        task = served.wait_task(ctx, qid)
        cut = gen.warm_frames(DRY) + 7   # inside a window
        for i in range(cut):
            client.append_call([gen.frame(DRY, seed, i)])
        served.wait_consumed(ctx, task, 300)
        with task.state_lock:
            assert task.executor._dev is not None
        task._snapshot_now(sync=True)
        ctx.faults.arm("task.step", "fail:1")
        for i in range(cut, cut + 6):    # the first of these kills it
            client.append_call([gen.frame(DRY, seed, i)])
        deadline = time.monotonic() + 60
        while ctx.supervisor.restarts < 1 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert ctx.supervisor.restarts == 1
        task = served.wait_task(ctx, qid)
        for i in range(cut + 6, N_FRAMES):
            client.append_call([gen.frame(DRY, seed, i)])
        served.wait_consumed(ctx, task, 300)
        plan = gen.pulls(DRY, N_FRAMES)
        before = client.sql(plan["before"][0]["sql"])
        for closer in gen.closers(DRY, N_FRAMES):
            client.append_call([closer])
        served.wait_consumed(ctx, task, 300)
        after = client.sql(plan["after"][0]["sql"])
        numbers = ref.compare(DRY, seed, N_FRAMES,
                              _served(before, after, plan))
        assert numbers == ZERO
        assert len(after) == len(ref.rows_from(
            DRY, ref.answers(DRY, seed, N_FRAMES)))
        assert served.on_device(ctx, task, "JoinExecutor") == {
            "executor_wrong": 0, "device_fallbacks": 0, "late_drops": 0,
            "query_not_running": 0}
        with task.state_lock:
            ex = task.executor
        assert ex._dev is not None       # re-activated after the restore
        assert ex.join_stats["rows_past_retention"] == 0
    finally:
        ctx.faults.disarm("task.step")
        client.close()
        server.stop(grace=1)
        ctx.shutdown()
