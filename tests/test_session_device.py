"""Device session lattice vs the host reference engine (ISSUE 10).

The device path (engine.lattice session kernels + the SessionExecutor
mirror) must be row-equivalent to the retained host merge engine across
out-of-order rows straddling the gap timeout, late-record drops,
cross-batch session extension, key growth + code-space compaction,
snapshot roundtrips, and watermark-driven closes — in BOTH kernel modes
(record: fully fused sort+scan step; segment: host-pre-reduced segment
planes merged on device). Float aggregates compare with a small relative
tolerance (the device accumulates in f32, the host in f64); counts,
min/max of f32-exact values, and HLL registers compare exactly;
APPROX_QUANTILE compares within one DDSketch bucket (bin edges are
computed in f32 on device, f64 on host).
"""
from __future__ import annotations

import numpy as np
import pytest
from helpers import (
    assert_code_columns_fresh,
    assert_mirror_tracks_the_arena,
    mirror_run,
    typed,
)

from hstream_tpu.engine import ColumnType, Schema
from hstream_tpu.engine.expr import Col
from hstream_tpu.engine.plan import AggKind, AggregateNode, AggSpec, SourceNode
from hstream_tpu.engine.session import (
    SessionExecutor,
    merge_chains_np,
    merge_into_mirror_np,
)
from hstream_tpu.engine.window import SessionWindow

BASE = 1_700_000_000_000

MODES = ["segment", "record"]

SCHEMA = Schema.of(k=ColumnType.STRING, v=ColumnType.FLOAT)


def make_ex(aggs, *, device, mode=None, gap=1000, grace=500,
            emit_changes=False, having=None, projections=None):
    node = AggregateNode(
        child=SourceNode("s", SCHEMA), group_keys=[Col("k")],
        window=SessionWindow(gap, grace_ms=grace), aggs=aggs,
        having=having, post_projections=projections or [])
    ex = SessionExecutor(node, SCHEMA, emit_changes=emit_changes)
    ex.use_device_sessions = device
    ex.device_session_mode = mode
    return ex


def gen(seed, n_batches=8, batch=300, keys=12, late_frac=0.15):
    """Randomized workload with out-of-order rows straddling the gap
    timeout and genuinely-late records (past grace under the
    watermark). Values are small integers so f32 sums stay exact."""
    rng = np.random.default_rng(seed)
    batches, t = [], BASE
    for _ in range(n_batches):
        ks = rng.integers(0, keys, batch)
        ts = t + rng.integers(0, 4000, batch)
        late = rng.random(batch) < late_frac
        ts = np.where(late, ts - rng.integers(3000, 20_000, batch), ts)
        vs = rng.integers(0, 1000, batch)
        rows = [{"k": f"u{int(k)}", "v": float(v)}
                for k, v in zip(ks, vs)]
        batches.append((rows, ts.tolist()))
        t += 2500
    return batches


def assert_rows_close(got, want, rtol=1e-5):
    """Row-set equality with relative tolerance on float fields (rows
    matched by their exact non-float fields)."""
    def key(r):
        return tuple(sorted((k, v) for k, v in r.items()
                            if not isinstance(v, float)))

    gd: dict = {}
    wd: dict = {}
    for r in got:
        gd.setdefault(key(r), []).append(r)
    for r in want:
        wd.setdefault(key(r), []).append(r)
    assert set(gd) == set(wd), sorted(set(gd) ^ set(wd))[:4]
    for k in gd:
        assert len(gd[k]) == len(wd[k]), k
        for rg, rw in zip(
                sorted(gd[k], key=lambda r: sorted(r.items(), key=str)),
                sorted(wd[k], key=lambda r: sorted(r.items(), key=str))):
            for c, v in rw.items():
                if isinstance(v, float):
                    assert np.isclose(rg[c], v, rtol=rtol,
                                      atol=1e-9), (k, c, rg[c], v)


EXACT_AGGS = [
    AggSpec(AggKind.COUNT_ALL, "c"),
    AggSpec(AggKind.COUNT, "n", input=Col("v")),
    AggSpec(AggKind.SUM, "s", input=Col("v")),
    AggSpec(AggKind.AVG, "a", input=Col("v")),
    AggSpec(AggKind.MIN, "lo", input=Col("v")),
    AggSpec(AggKind.MAX, "hi", input=Col("v")),
    AggSpec(AggKind.APPROX_COUNT_DISTINCT, "d", input=Col("v")),
]

SKETCH_AGGS = [
    AggSpec(AggKind.APPROX_QUANTILE, "p50", input=Col("v"), quantile=0.5),
    AggSpec(AggKind.APPROX_QUANTILE, "p99", input=Col("v"),
            quantile=0.99),
]


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_device_host_equivalence_out_of_order(mode, seed):
    """Random out-of-order + late workload: closed rows, open-session
    peeks, and final state agree between engines in both modes."""
    exd = make_ex(EXACT_AGGS, device=True, mode=mode)
    exh = make_ex(EXACT_AGGS, device=False)
    od, oh = [], []
    for rows, ts in gen(seed):
        od.extend(exd.process(rows, ts))
        oh.extend(exh.process(rows, ts))
    assert exd._dev is not None and exd._dev["mode"] == mode
    assert exd.device_fallbacks == 0
    assert_rows_close(od, oh)
    assert_rows_close(list(exd.peek()), list(exh.peek()))


@pytest.mark.parametrize("mode", MODES)
def test_quantile_within_one_bucket(mode):
    exd = make_ex(SKETCH_AGGS, device=True, mode=mode)
    exh = make_ex(SKETCH_AGGS, device=False)
    od, oh = [], []
    for rows, ts in gen(7):
        od.extend(exd.process(rows, ts))
        oh.extend(exh.process(rows, ts))
    assert exd._dev is not None
    # one-bucket tolerance: DDSketch bin edges are f32 on device
    assert_rows_close(od, oh, rtol=0.08)


@pytest.mark.parametrize("mode", MODES)
def test_cross_batch_session_extension(mode):
    """A session extended across many batches (every batch within gap)
    closes once, with the accumulated aggregates of all batches."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.SUM, "s", input=Col("v"))]
    exd = make_ex(aggs, device=True, mode=mode, gap=1000, grace=0)
    exh = make_ex(aggs, device=False, gap=1000, grace=0)
    for b in range(6):
        rows = [{"k": "a", "v": 1.0}]
        for ex in (exd, exh):
            out = ex.process(rows, [BASE + b * 900])
            assert list(out) == []
    closed_d, closed_h = None, None
    for ex in (exd, exh):
        out = ex.process([{"k": "z", "v": 0.0}], [BASE + 100_000])
        rows = [r for r in out if r["k"] == "a"]
        assert len(rows) == 1
        if ex is exd:
            closed_d = rows[0]
        else:
            closed_h = rows[0]
    assert closed_d == closed_h
    assert closed_d["c"] == 6 and closed_d["s"] == 6.0
    assert closed_d["winStart"] == BASE
    assert closed_d["winEnd"] == BASE + 5 * 900 + 1000


@pytest.mark.parametrize("mode", MODES)
def test_multi_session_merge_within_limit(mode):
    """A batch bridging several open sessions of one key merges them
    all (within chain_merge_limit) identically to the host. Grace keeps
    the disjoint sessions open and the bridge records in-grace."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.MIN, "lo", input=Col("v")),
            AggSpec(AggKind.MAX, "hi", input=Col("v"))]
    exd = make_ex(aggs, device=True, mode=mode, gap=100, grace=5000)
    exh = make_ex(aggs, device=False, gap=100, grace=5000)
    # 5 disjoint sessions (400ms apart >> gap), all open under grace
    opens = [({"k": "a", "v": float(i)}, BASE + i * 400)
             for i in range(5)]
    for ex in (exd, exh):
        for row, t in opens:
            ex.process([row], [t])
    assert len(list(exh.peek())) == 5
    # one batch of bridge records every 80ms chains them all into ONE
    bridge_ts = list(range(BASE + 50, BASE + 5 * 400, 80))
    bridge = [{"k": "a", "v": 99.0} for _ in bridge_ts]
    for ex in (exd, exh):
        ex.process(bridge, bridge_ts)
    assert exd.device_fallbacks == 0  # within the limit: no fallback
    pd, ph = list(exd.peek()), list(exh.peek())
    assert_rows_close(pd, ph)
    assert len(pd) == 1 and pd[0]["c"] == 5 + len(bridge)
    assert pd[0]["lo"] == 0.0 and pd[0]["hi"] == 99.0


def test_chain_limit_triggers_host_fallback():
    """A batch merging more open sessions than chain_merge_limit
    degrades to the host engine — identical results, counted."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    exd = make_ex(aggs, device=True, mode="segment", gap=100,
                  grace=5000)
    exh = make_ex(aggs, device=False, gap=100, grace=5000)
    exd.chain_merge_limit = 3
    opens_ts = [BASE + i * 400 for i in range(6)]
    for ex in (exd, exh):
        for t in opens_ts:
            ex.process([{"k": "a", "v": 1.0}], [t])
    assert exd._dev is not None
    bridge_ts = list(range(BASE + 50, BASE + 6 * 400, 80))
    bridge = [{"k": "a", "v": 1.0} for _ in bridge_ts]
    od = exd.process(bridge, bridge_ts)
    oh = exh.process(bridge, bridge_ts)
    assert exd._dev is None and exd.use_device_sessions is False
    assert exd.device_fallbacks == 1
    assert list(od) == list(oh)
    # the degraded executor carries on, still host-identical
    od = exd.process([{"k": "z", "v": 0.0}], [BASE + 100_000])
    oh = exh.process([{"k": "z", "v": 0.0}], [BASE + 100_000])
    assert_rows_close(od, oh)
    assert exd.sessions.keys() == exh.sessions.keys()


@pytest.mark.parametrize("mode", MODES)
def test_key_growth_and_code_compaction(mode):
    """Key cardinality past the cache bound triggers the code-space
    compaction (order-preserving remap kernel) instead of a cache
    clear; results stay host-identical across the remap."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.SUM, "s", input=Col("v"))]
    exd = make_ex(aggs, device=True, mode=mode, gap=500, grace=0)
    exh = make_ex(aggs, device=False, gap=500, grace=0)
    exd._KEY_CACHE_MAX = 64  # force compaction quickly
    od, oh = [], []
    rng = np.random.default_rng(3)
    for b in range(8):
        # fresh key names every batch: cardinality grows past the bound
        ks = [f"k{b}_{int(i)}" for i in rng.integers(0, 40, 120)]
        ts = (BASE + b * 5000 + rng.integers(0, 400, 120)).tolist()
        rows = [{"k": k, "v": 1.0} for k in ks]
        od.extend(exd.process(rows, ts))
        oh.extend(exh.process(rows, ts))
    assert exd._dev is not None
    assert exd.session_stats["remap_dispatches"] >= 1
    assert_rows_close(od, oh)
    assert_rows_close(list(exd.peek()), list(exh.peek()))


@pytest.mark.parametrize("mode", MODES)
def test_no_key_code_reaches_the_arenas_sentinel(mode):
    """The kernels read a code at or above `SESSION_SENT_CODE` as an
    empty slot. A dictionary the live-set rule would let grow (under
    twice the open sessions, or under the cache bound) is compacted all
    the same before a batch could mint such a code."""
    from hstream_tpu.engine import lattice

    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    exd = make_ex(aggs, device=True, mode=mode, gap=500, grace=0)
    exh = make_ex(aggs, device=False, gap=500, grace=0)
    od, oh = [], []

    def feed(b):
        ks = [f"k{b}_{i % 30}" for i in range(90)]
        ts = [BASE + b * 200 + i for i in range(90)]
        rows = [{"k": k, "v": 1.0} for k in ks]
        od.extend(exd.process(rows, ts))
        oh.extend(exh.process(rows, ts))

    feed(0)
    feed(1)
    assert exd._dev is not None
    assert exd.session_stats["remap_dispatches"] == 0
    # codes of long-closed keys, up to 50 short of the sentinel: under
    # the cache bound by count (`_code_of` is as it was), so only the
    # sentinel rule can see them
    exd._code_rev.extend([("gone",)] * (
        lattice.SESSION_SENT_CODE - 50 - len(exd._code_rev)))
    feed(2)  # 90 rows could mint codes past the sentinel
    assert exd._dev is not None and exd.device_fallbacks == 0
    assert exd.session_stats["remap_dispatches"] == 1
    assert len(exd._code_rev) < 200
    feed(3)
    feed(40)  # closes everything before it
    assert_rows_close(od, oh)
    assert_rows_close(list(exd.peek()), list(exh.peek()))


def test_open_keys_that_fill_the_code_space_degrade_to_the_host():
    """Where a compaction cannot get under the sentinel (the open
    sessions' own codes and the batch leave no room), the executor
    degrades to the host engine with the reason, and counts it."""
    from hstream_tpu.engine import lattice

    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    exd = make_ex(aggs, device=True, mode="record", gap=500, grace=0)
    exh = make_ex(aggs, device=False, gap=500, grace=0)
    rows = [{"k": f"k{i % 7}", "v": 1.0} for i in range(40)]
    ts = [BASE + i for i in range(40)]
    exd.process(rows, ts)
    exh.process(rows, ts)
    assert exd._dev is not None
    # a batch as large as the code space itself: no compaction helps
    reasons, degrade = [], exd._degrade_to_host
    exd._degrade_to_host = lambda why: (reasons.append(why),
                                        degrade(why))[1]
    exd._bound_key_cache(lattice.SESSION_SENT_CODE)
    assert exd._dev is None and exd.device_fallbacks == 1
    assert "under the arena's sentinel" in reasons[0]
    later = [BASE + 5000 + i for i in range(40)]
    assert_rows_close(exd.process(rows, later), exh.process(rows, later))


@pytest.mark.parametrize("mode", MODES)
def test_snapshot_roundtrip_in_device_mode(mode):
    """Snapshot taken while sessions are device-resident restores into
    the host engine, re-activates lazily, and continues identically."""
    from types import SimpleNamespace

    from hstream_tpu.engine import snapshot as snap

    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.SUM, "s", input=Col("v")),
            AggSpec(AggKind.APPROX_COUNT_DISTINCT, "d", input=Col("v"))]
    exd = make_ex(aggs, device=True, mode=mode)
    exh = make_ex(aggs, device=False)
    batches = gen(11, n_batches=5)
    for rows, ts in batches[:3]:
        exd.process(rows, ts)
        exh.process(rows, ts)
    assert exd._dev is not None
    blob = snap.snapshot_executor(exd)
    plan = SimpleNamespace(node=exd.node)  # restore only reads .node
    restored, _extra = snap.restore_executor(plan, blob)
    assert isinstance(restored, SessionExecutor)
    assert restored._dev is None  # restores host-side
    od, oh = [], []
    for rows, ts in batches[3:]:
        od.extend(restored.process(rows, ts))
        oh.extend(exh.process(rows, ts))
    assert restored._dev is not None  # re-activated lazily
    assert_rows_close(od, oh)
    assert_rows_close(list(restored.peek()), list(exh.peek()))


@pytest.mark.parametrize("mode", MODES)
def test_watermark_close_parity(mode):
    """Sessions close at exactly wm >= end + 2*gap + grace on both
    engines — no earlier, no later."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    gap, grace = 1000, 300
    exd = make_ex(aggs, device=True, mode=mode, gap=gap, grace=grace)
    exh = make_ex(aggs, device=False, gap=gap, grace=grace)
    for ex in (exd, exh):
        ex.process([{"k": "a", "v": 1.0}], [BASE])
    # one below the close boundary: nothing closes
    boundary = BASE + 2 * gap + grace
    for ex in (exd, exh):
        out = ex.process([{"k": "z", "v": 0.0}], [boundary - 1])
        assert [r for r in out if r["k"] == "a"] == []
    # at the boundary: closes on both
    outs = []
    for ex in (exd, exh):
        out = ex.process([{"k": "z", "v": 0.0}], [boundary])
        outs.append([r for r in out if r["k"] == "a"])
    assert outs[0] == outs[1] and len(outs[0]) == 1


@pytest.mark.parametrize("mode", MODES)
def test_columnar_feed_equivalence(mode):
    """process_columnar (the server's _session_columns feed shape)
    matches the row path on both engines, nulls included."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.SUM, "s", input=Col("v"))]
    exd = make_ex(aggs, device=True, mode=mode)
    exh = make_ex(aggs, device=False)
    rng = np.random.default_rng(5)
    od, oh = [], []
    for b in range(6):
        n = 200
        ks = np.array([f"u{int(i)}" for i in rng.integers(0, 10, n)])
        vs = rng.integers(0, 100, n).astype(np.float32)
        ts = BASE + b * 2500 + rng.integers(0, 4000, n)
        nulls = {"v": rng.random(n) < 0.1}
        od.extend(exd.process_columnar(ts, {"k": ks, "v": vs}, nulls))
        rows = [({"k": str(k)} if isnull else
                 {"k": str(k), "v": float(v)})
                for k, v, isnull in zip(ks, vs, nulls["v"])]
        oh.extend(exh.process(rows, ts.tolist()))
    assert exd._dev is not None
    assert_rows_close(od, oh)
    assert_rows_close(list(exd.peek()), list(exh.peek()))


@pytest.mark.parametrize("mode", MODES)
def test_one_dispatch_zero_fetch_ingest_contract(mode):
    """The session ingest contract: exactly ONE step dispatch per
    micro-batch and ZERO fetches outside close cycles; each close cycle
    is one extract dispatch + one fetch."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.SUM, "s", input=Col("v"))]
    ex = make_ex(aggs, device=True, mode=mode, gap=1000, grace=0)
    rng = np.random.default_rng(9)
    for b in range(10):
        n = 256
        rows = [{"k": f"u{int(i)}", "v": 1.0}
                for i in rng.integers(0, 20, n)]
        ts = (BASE + b * 10_000 + rng.integers(0, 900, n)).tolist()
        ex.process(rows, ts)
    st = ex.session_stats
    assert st["step_dispatches"] == st["batches"]
    assert st["close_dispatches"] == st["close_cycles"]
    assert st["close_fetches"] == st["close_cycles"]


@pytest.mark.parametrize("mode", MODES)
def test_deferred_close_drain_single_stacked_fetch(mode):
    """defer_close_decode holds packed closes as device values; one
    drain fetches every same-shape cycle in a single stacked transfer
    with rows identical to the synchronous path."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.SUM, "s", input=Col("v"))]
    exd = make_ex(aggs, device=True, mode=mode, gap=1000, grace=0)
    exs = make_ex(aggs, device=True, mode=mode, gap=1000, grace=0)
    exd.defer_close_decode = True
    rng = np.random.default_rng(13)
    sync_rows = []
    for b in range(6):
        n = 128
        rows = [{"k": f"u{int(i)}", "v": 1.0}
                for i in rng.integers(0, 8, n)]
        ts = (BASE + b * 10_000 + rng.integers(0, 900, n)).tolist()
        out = exd.process(rows, ts)
        assert list(out) == []  # all emission deferred
        sync_rows.extend(exs.process(rows, ts))
    assert exd.has_pending_closes()
    fetches_before = exd.session_stats["close_fetches"]
    drained = list(exd.drain_closed())
    # every same-shape cycle rode one stacked transfer
    assert exd.session_stats["close_fetches"] - fetches_before \
        <= len({tuple()})  # exactly one shape group here
    assert_rows_close(drained, sync_rows)
    assert not exd.has_pending_closes()


def test_emit_changes_and_topk_refuse_device():
    """Host-only configs never activate the device path (a refusal, not
    a counted failure)."""
    ex = make_ex([AggSpec(AggKind.COUNT_ALL, "c")], device=True,
                 emit_changes=True)
    ex.process([{"k": "a", "v": 1.0}], [BASE])
    assert ex._dev is None and ex._device_refusal is not None
    assert ex.device_fallbacks == 0
    ex2 = make_ex([AggSpec(AggKind.TOPK, "t", input=Col("v"), k=3)],
                  device=True)
    ex2.process([{"k": "a", "v": 1.0}], [BASE])
    assert ex2._dev is None and "host-only" in ex2._device_refusal


def test_host_emission_is_columnar():
    """Satellite: peek() and close_due_sessions() ride ColumnarEmit on
    the HOST engine too (sessions were the last per-row-dict emitter)."""
    from hstream_tpu.common.columnar import ColumnarEmit

    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.SUM, "s", input=Col("v"))]
    ex = make_ex(aggs, device=False)
    ex.process([{"k": "a", "v": 1.0}, {"k": "b", "v": 2.0}],
               [BASE, BASE + 10])
    peeked = ex.peek()
    assert isinstance(peeked, ColumnarEmit)
    assert {r["k"] for r in peeked} == {"a", "b"}
    out = ex.process([{"k": "z", "v": 0.0}], [BASE + 100_000])
    # the lone close batch stays columnar end-to-end (extend_rows)
    assert isinstance(out, ColumnarEmit)
    assert {r["k"] for r in out} == {"a", "b", "z"} - {"z"} or \
        {r["k"] for r in out} <= {"a", "b", "z"}


def test_device_emission_is_columnar():
    from hstream_tpu.common.columnar import ColumnarEmit

    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    ex = make_ex(aggs, device=True, gap=1000, grace=0)
    ex.process([{"k": "a", "v": 1.0}], [BASE])
    assert isinstance(ex.peek(), ColumnarEmit)
    out = ex.process([{"k": "z", "v": 0.0}], [BASE + 100_000])
    assert isinstance(out, ColumnarEmit)


@pytest.mark.parametrize("mode", MODES)
def test_having_and_projections_parity(mode):
    """HAVING + projections evaluate columnwise on both engines with
    the same drop semantics."""
    from hstream_tpu.engine.expr import BinOp, Lit

    aggs = [AggSpec(AggKind.COUNT_ALL, "c"),
            AggSpec(AggKind.SUM, "s", input=Col("v"))]
    having = BinOp(">", Col("c"), Lit(2))
    projections = [("key", Col("k")), ("total", Col("s"))]
    exd = make_ex(aggs, device=True, mode=mode, having=having,
                  projections=projections)
    exh = make_ex(aggs, device=False, having=having,
                  projections=projections)
    od, oh = [], []
    for rows, ts in gen(17, n_batches=5):
        od.extend(exd.process(rows, ts))
        oh.extend(exh.process(rows, ts))
    assert exd._dev is not None
    assert len(oh) > 0  # HAVING actually filtered a nonempty set
    assert_rows_close(od, oh)


@pytest.mark.parametrize("mode", MODES)
def test_where_filter_parity(mode):
    from hstream_tpu.engine.expr import BinOp, Lit
    from hstream_tpu.engine.plan import FilterNode

    schema = SCHEMA
    pred = BinOp(">", Col("v"), Lit(100.0))
    node = AggregateNode(
        child=FilterNode(child=SourceNode("s", schema), predicate=pred),
        group_keys=[Col("k")],
        window=SessionWindow(1000, grace_ms=500),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c"),
              AggSpec(AggKind.SUM, "s", input=Col("v"))])
    exd = SessionExecutor(node, schema)
    exd.device_session_mode = mode
    exh = SessionExecutor(node, schema)
    exh.use_device_sessions = False
    od, oh = [], []
    for rows, ts in gen(21, n_batches=6):
        od.extend(exd.process(rows, ts))
        oh.extend(exh.process(rows, ts))
    assert exd._dev is not None
    assert_rows_close(od, oh)
    # watermark advances on filtered-out records too (pre-filter max)
    assert exd.watermark == exh.watermark


def test_pinned_anchor_span_degrades_to_host_not_crash():
    """Review finding (ISSUE 10): an ancient open session pins the
    rebase anchor; once relative time reaches the device range the
    executor must DEGRADE to the host engine (which has no int32
    bound) instead of desyncing the mirror and crash-looping."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    # a ~9h grace keeps every session open across the whole run, so the
    # FIRST session pins the rebase anchor at BASE while stream time
    # advances 500s per batch past the (shrunk) relative range
    exd = make_ex(aggs, device=True, mode="segment", gap=1000,
                  grace=1 << 25)
    exh = make_ex(aggs, device=False, gap=1000, grace=1 << 25)
    exd.REBASE_THRESHOLD = 1 << 22  # ~70 min, keeps the test fast
    od, oh = [], []
    for b in range(12):
        rows = [{"k": "pin", "v": 1.0},
                {"k": f"s{b}", "v": 1.0}]
        ts = [BASE + b * 500_000, BASE + b * 500_000 + 10]
        od.extend(exd.process(rows, ts))
        oh.extend(exh.process(rows, ts))
    assert exd._dev is None and exd.device_fallbacks == 1
    assert exd.use_device_sessions is False
    assert_rows_close(od, oh)
    assert_rows_close(list(exd.peek()), list(exh.peek()))


def test_huge_gap_grace_refuses_device():
    """2*gap + grace past the int32 relative budget is a plan-time
    refusal (the close rule would not fit the device time range)."""
    ex = make_ex([AggSpec(AggKind.COUNT_ALL, "c")], device=True,
                 gap=1 << 29, grace=1 << 29)
    ex.process([{"k": "a", "v": 1.0}], [BASE])
    assert ex._dev is None
    assert "relative-time range" in ex._device_refusal
    assert ex.device_fallbacks == 0


def test_peek_does_not_skew_close_accounting():
    """Review finding: pull-query peeks must not count into the
    close-path dispatch/fetch budget the bench asserts on."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    ex = make_ex(aggs, device=True, gap=1000, grace=0)
    ex.process([{"k": "a", "v": 1.0}], [BASE])
    for _ in range(3):
        ex.peek()
    st = ex.session_stats
    assert st["peek_dispatches"] == 3
    assert st["close_dispatches"] == st["close_cycles"]
    assert st["close_fetches"] == st["close_cycles"]


def test_snapshot_guard_requires_drained_closes():
    """Deferred session closes block a snapshot until drained (the
    packed device buffers are the only copy of those rows)."""
    from hstream_tpu.common.errors import SQLCodegenError
    from hstream_tpu.engine import snapshot as snap

    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    ex = make_ex(aggs, device=True, gap=1000, grace=0)
    ex.defer_close_decode = True
    ex.process([{"k": "a", "v": 1.0}], [BASE])
    ex.process([{"k": "z", "v": 0.0}], [BASE + 100_000])
    assert ex.has_pending_closes()
    with pytest.raises(SQLCodegenError, match="deferred session"):
        snap.snapshot_executor(ex)
    rows = ex.flush_changes()  # the task's pre-snapshot drain surface
    assert [r["k"] for r in rows] == ["a"]
    snap.snapshot_executor(ex)  # drained: snapshot proceeds


def test_close_extract_dispatch_failure_degrades_not_dies():
    """Review finding: a kernel failure at the close-extract DISPATCH
    (mirror not yet retired) degrades to the host engine, which closes
    the same due set — instead of killing the query."""
    from hstream_tpu.common.faultinject import FAULTS

    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    exd = make_ex(aggs, device=True, gap=1000, grace=0)
    exh = make_ex(aggs, device=False, gap=1000, grace=0)
    for ex in (exd, exh):
        ex.process([{"k": "a", "v": 1.0}], [BASE])
    try:
        # hit 1 = the closer batch's step dispatch (passes), hit 2 =
        # the close extract dispatch (fails)
        FAULTS.arm("device.session.dispatch", "fail:2")
        od = exd.process([{"k": "z", "v": 0.0}], [BASE + 100_000])
    finally:
        FAULTS.disarm()
    oh = exh.process([{"k": "z", "v": 0.0}], [BASE + 100_000])
    assert exd.device_fallbacks == 1 and exd._dev is None
    assert_rows_close(od, oh)
    assert any(r["k"] == "a" for r in od)  # the close still emitted


def test_peek_extract_dispatch_failure_degrades_not_dies():
    from hstream_tpu.common.faultinject import FAULTS

    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    exd = make_ex(aggs, device=True, gap=1000, grace=0)
    exh = make_ex(aggs, device=False, gap=1000, grace=0)
    for ex in (exd, exh):
        ex.process([{"k": "a", "v": 1.0}], [BASE])
    try:
        FAULTS.arm("device.session.dispatch", "fail:1")
        pd = list(exd.peek())
    finally:
        FAULTS.disarm()
    assert exd.device_fallbacks == 1 and exd._dev is None
    assert_rows_close(pd, list(exh.peek()))


def test_degrade_with_pending_deferred_closes_keeps_keys():
    """Review finding: pending deferred closes must resolve their key
    columns AT degrade time — a later host-mode key-cache clear rebuilds
    the code dictionary and lazy decode would read wrong keys."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    ex = make_ex(aggs, device=True, gap=1000, grace=0)
    ex.defer_close_decode = True
    ex.process([{"k": "a", "v": 1.0}], [BASE])
    ex.process([{"k": "closer", "v": 0.0}], [BASE + 100_000])
    assert ex.has_pending_closes()
    ex._degrade_to_host("test: simulate a mid-stream device loss")
    # host-mode cache bound clears the code dictionary wholesale
    ex._KEY_CACHE_MAX = 0
    ex.process([{"k": f"n{i}", "v": 1.0} for i in range(4)],
               [BASE + 200_000 + i for i in range(4)])
    rows = list(ex.drain_closed())
    assert [r["k"] for r in rows] == ["a"]  # the ORIGINAL key survives


def test_late_records_merge_into_open_sessions_on_device():
    """A late record that overlaps an open session merges (not drops) —
    the mirror's sequential late walk preserves the reference's
    record-at-a-time drop-vs-merge decisions."""
    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    for mode in MODES:
        exd = make_ex(aggs, device=True, mode=mode, gap=1000, grace=0)
        exh = make_ex(aggs, device=False, gap=1000, grace=0)
        for ex in (exd, exh):
            ex.process([{"k": "a", "v": 1.0}], [BASE + 10_000])
            # late but overlapping "a"'s session: merges; late and far
            # from any session: drops
            ex.process(
                [{"k": "a", "v": 1.0}, {"k": "a", "v": 1.0}],
                [BASE + 9_500, BASE + 2_000])
        pd, ph = list(exd.peek()), list(exh.peek())
        assert pd == ph
        assert pd[0]["c"] == 2  # merged one, dropped one


# ---- the decode columns of the code dictionary (ISSUE 30) -------------------
#
# A close cycle names the keys of the rows it closed by a gather from
# one object array a group column (`_code_rev_columns`). The arrays are
# kept incrementally: the oracle everywhere below is a from-scratch
# build of `_code_rev` (helpers.fresh_code_columns) and the host engine
# on the same input.

WIDE = Schema.of(k=ColumnType.STRING, u=ColumnType.INT, f=ColumnType.FLOAT,
                 v=ColumnType.FLOAT)

# group columns, and whether the first carries null cells
KEY_SHAPES = {
    "int": (("u",), False),
    "string": (("k",), False),
    "null": (("k",), True),
    "two": (("k", "u"), False),
    "three": (("k", "u", "f"), False),
    "none": ((), False),
}


def make_keyed(groups, *, device, mode=None):
    node = AggregateNode(
        child=SourceNode("s", WIDE), group_keys=[Col(g) for g in groups],
        window=SessionWindow(500, grace_ms=0),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c"),
              AggSpec(AggKind.SUM, "s", input=Col("v"))])
    ex = SessionExecutor(node, WIDE)
    ex.use_device_sessions = device
    ex.device_session_mode = mode
    return ex


def churn(shape, seed, n_batches=10):
    """A stream whose keys churn: every batch names ids none before it
    named and, two seconds of event time on, closes the sessions of the
    batch before (gap 500 ms). Yields (ts, cols, nulls) as the server
    feeds `process_columnar`."""
    groups, with_nulls = KEY_SHAPES[shape]
    rng = np.random.default_rng(seed)
    batch = 120
    for b in range(n_batches):
        ids = rng.integers(b * 25, b * 25 + 60, batch)
        ts = BASE + b * 2000 + rng.integers(0, 400, batch)
        cols = {"k": np.array([f"k{int(i)}" for i in ids]),
                "u": (ids if groups == ("u",) else ids % 3).astype(
                    np.int64),
                "f": ((ids % 5) * 0.5).astype(np.float32),
                "v": rng.integers(0, 9, batch).astype(np.float32)}
        nulls = {"k": rng.random(batch) < 0.1} if with_nulls else {}
        yield ts, cols, nulls


def keyed_rows(out, groups):
    """Emitted rows in a canonical order, group cells with their
    types."""
    return sorted(
        (typed(r[g] for g in groups),
         tuple((k, v) for k, v in sorted(r.items()) if k not in groups))
        for r in out)


def feed(ex, how, ts, cols, nulls):
    if how == "cols":
        return list(ex.process_columnar(ts, cols, nulls))
    rows = SessionExecutor._rows_from_cols(cols, nulls, len(ts))
    return list(ex.process(rows, ts.tolist()))


@pytest.mark.parametrize("shape,how,mode", [
    ("int", "cols", None), ("string", "cols", None),
    ("null", "cols", None), ("two", "cols", None),
    ("three", "cols", None), ("none", "cols", None),
    ("string", "rows", "record"), ("null", "rows", None),
    ("two", "rows", "record"), ("three", "cols", "record")])
def test_decode_columns_equal_a_fresh_build_after_every_batch(
        shape, how, mode):
    """Through a long churning stream the incremental columns equal a
    from-scratch build of `_code_rev` after EVERY batch, and the closed
    rows and the open ones are the host engine's, value for value and
    (the key cells) type for type."""
    groups = KEY_SHAPES[shape][0]
    exd = make_keyed(groups, device=True, mode=mode)
    exh = make_keyed(groups, device=False)
    closed = 0
    for ts, cols, nulls in churn(shape, 7):
        od = feed(exd, how, ts, cols, nulls)
        oh = feed(exh, how, ts, cols, nulls)
        assert exd._dev is not None and exd.device_fallbacks == 0
        assert_code_columns_fresh(exd)
        assert keyed_rows(od, groups) == keyed_rows(oh, groups)
        assert keyed_rows(exd.peek(), groups) \
            == keyed_rows(exh.peek(), groups)
        closed += len(od)
    assert closed > 0
    assert exd.session_stats["code_cols_builds"] == 1  # activation
    assert exd.session_stats["code_cols_appended"] == len(exd._code_rev)


@pytest.mark.parametrize("mode", MODES)
def test_a_close_decodes_what_was_minted_not_the_dictionary(mode):
    """Cost by counter, not by clock: over N batches the columns are
    never made anew (`code_cols_builds` stands where activation left
    it) and each batch's close appends exactly the codes the batch
    minted, however large the dictionary has grown."""
    exd = make_keyed(("k",), device=True, mode=mode)
    walked, extend = [], exd._extend_code_cols

    def extending():
        walked.append(extend())
        return walked[-1]

    exd._extend_code_cols = extending
    expect, waiting = [0], 0  # activation walks an empty dictionary
    for ts, cols, nulls in churn("string", 21, n_batches=14):
        before = len(exd._code_rev)
        cycles = exd.session_stats["close_cycles"]
        exd.process_columnar(ts, cols, nulls)
        waiting += len(exd._code_rev) - before
        if exd.session_stats["close_cycles"] > cycles:
            expect.append(waiting)  # what this close had to append
            waiting = 0
    st = exd.session_stats
    assert exd._dev is not None and st["remap_dispatches"] == 0
    assert st["close_cycles"] >= 12
    assert st["code_cols_builds"] == 1
    assert st["code_cols_appended"] == len(exd._code_rev) == sum(walked)
    # one walk a close cycle, of the codes minted since the last and
    # no more, while the dictionary grows past any of them
    assert walked == expect and len(walked) == 1 + st["close_cycles"]
    assert max(walked) < len(exd._code_rev) // 3


@pytest.mark.parametrize("mode", MODES)
def test_decode_columns_across_a_compaction(mode):
    """`_compact_codes_device` sets the columns with the dictionary it
    makes (a take of the old ones): equal to a fresh build right after,
    and after every later batch; rows stay the host engine's."""
    exd = make_keyed(("k", "u"), device=True, mode=mode)
    exh = make_keyed(("k", "u"), device=False)
    exd._KEY_CACHE_MAX = 64  # compacts every few batches
    builds = []
    compact = exd._compact_codes_device

    def compacting():
        compact()
        builds.append(exd.session_stats["code_cols_builds"])
        assert exd._code_cols_filled == len(exd._code_rev)
        assert_code_columns_fresh(exd)

    exd._compact_codes_device = compacting
    for ts, cols, nulls in churn("two", 5, n_batches=12):
        od = feed(exd, "cols", ts, cols, nulls)
        oh = feed(exh, "cols", ts, cols, nulls)
        assert_code_columns_fresh(exd)
        assert keyed_rows(od, ("k", "u")) == keyed_rows(oh, ("k", "u"))
    assert exd._dev is not None and exd.device_fallbacks == 0
    remaps = exd.session_stats["remap_dispatches"]
    assert remaps >= 2
    # one build at activation, one a compaction, none between
    assert builds == list(range(2, 2 + remaps))
    assert exd.session_stats["code_cols_builds"] == 1 + remaps
    assert keyed_rows(exd.peek(), ("k", "u")) \
        == keyed_rows(exh.peek(), ("k", "u"))


@pytest.mark.parametrize("mode", MODES)
def test_deferred_closes_keep_their_keys_across_a_compaction(mode):
    """Deferred closes decode by the codes their buffers hold: they are
    resolved against the OLD dictionary before a compaction replaces
    it, so a drain after it names the keys a synchronous close
    named."""
    exd = make_keyed(("k",), device=True, mode=mode)
    exs = make_keyed(("k",), device=True, mode=mode)
    exd.defer_close_decode = True
    exd._KEY_CACHE_MAX = exs._KEY_CACHE_MAX = 64
    crossing, compact = [], exd._compact_codes_device

    def compacting():
        crossing.append(len(exd._pending_closes))
        compact()

    exd._compact_codes_device = compacting
    drained, sync = [], []
    for i, (ts, cols, nulls) in enumerate(churn("string", 9,
                                                n_batches=12)):
        assert feed(exd, "cols", ts, cols, nulls) == []
        sync.extend(feed(exs, "cols", ts, cols, nulls))
        if i % 4 == 3:  # several cycles, and a compaction, in between
            drained.extend(exd.drain_closed())
            assert_code_columns_fresh(exd)
    drained.extend(exd.drain_closed())
    assert exd.session_stats["remap_dispatches"] >= 2
    assert max(crossing) > 0  # pending cycles crossed a compaction
    assert keyed_rows(drained, ("k",)) == keyed_rows(sync, ("k",))
    assert [r["k"] for r in drained] == [r["k"] for r in sync]


def test_decode_columns_across_a_degrade_and_the_host_reset():
    """`_degrade_to_host` leaves the columns equal to a fresh build (a
    pending close is resolved through them first); the host engine's
    wholesale reset of the dictionary resets them with it, counted."""
    exd = make_keyed(("k", "u"), device=True)
    exh = make_keyed(("k", "u"), device=False)
    exd.defer_close_decode = True
    batches = list(churn("two", 3, n_batches=9))
    od, oh = [], []
    for ts, cols, nulls in batches[:4]:
        od.extend(feed(exd, "cols", ts, cols, nulls))
        oh.extend(feed(exh, "cols", ts, cols, nulls))
    assert exd.has_pending_closes()
    exd._degrade_to_host("test: a device lost mid-stream")
    assert_code_columns_fresh(exd)
    builds = exd.session_stats["code_cols_builds"]
    exd._KEY_CACHE_MAX = 32  # the host engine drops the dictionary
    for ts, cols, nulls in batches[4:]:
        size = len(exd._code_of)
        od.extend(feed(exd, "cols", ts, cols, nulls))
        oh.extend(feed(exh, "cols", ts, cols, nulls))
        if size > 32:
            builds += 1
        assert exd.session_stats["code_cols_builds"] == builds
        assert_code_columns_fresh(exd)
    od.extend(exd.drain_closed())
    assert builds > 2
    assert keyed_rows(od, ("k", "u")) == keyed_rows(oh, ("k", "u"))
    assert keyed_rows(exd.peek(), ("k", "u")) \
        == keyed_rows(exh.peek(), ("k", "u"))


@pytest.mark.parametrize("mode", MODES)
def test_decode_columns_across_capture_and_restore(mode):
    """A snapshot of device-resident sessions reads `_code_rev` itself;
    the restored executor mints its codes at activation and builds the
    columns there, once, then appends."""
    from types import SimpleNamespace

    from hstream_tpu.engine import snapshot as snap

    exd = make_keyed(("k", "u"), device=True, mode=mode)
    exh = make_keyed(("k", "u"), device=False)
    batches = list(churn("two", 13, n_batches=8))
    for ts, cols, nulls in batches[:4]:
        feed(exd, "cols", ts, cols, nulls)
        feed(exh, "cols", ts, cols, nulls)
    meta, _arrays = exd.capture_device()
    live = exd._dev["mir_live"]
    assert typed(meta["keys"]) == typed(
        exd._code_rev[c] for c in np.unique(exd._dev["mir_code"][live]))
    restored, _extra = snap.restore_executor(
        SimpleNamespace(node=exd.node), snap.snapshot_executor(exd))
    restored.device_session_mode = mode
    assert restored._dev is None and restored._code_rev == []
    od, oh = [], []
    for ts, cols, nulls in batches[4:]:
        od.extend(feed(restored, "cols", ts, cols, nulls))
        oh.extend(feed(exh, "cols", ts, cols, nulls))
        assert_code_columns_fresh(restored)
    assert restored._dev is not None
    st = restored.session_stats
    # activation walked the open keys the snapshot held, counted as the
    # one build; every code since was appended
    assert st["code_cols_builds"] == 1
    assert 0 < len(restored._code_rev) - st["code_cols_appended"] \
        == len(meta["keys"])
    assert keyed_rows(od, ("k", "u")) == keyed_rows(oh, ("k", "u"))
    assert keyed_rows(restored.peek(), ("k", "u")) \
        == keyed_rows(exh.peek(), ("k", "u"))


# ---- the mirror update: a merge into a sorted array (ISSUE 32) --------------

MIRROR_GAP = 100


def open_mirror(rng, keys, per_key):
    """A mirror as a chain merge leaves it: `per_key` open sessions for
    each code of `keys`, sorted by (code, t0), sessions of one code
    more than a gap apart."""
    code = np.repeat(np.asarray(keys, np.int64), per_key)
    t0 = np.tile(np.arange(per_key) * 1000, len(keys)) \
        + rng.integers(0, 300, len(code))
    t1 = t0 + rng.integers(0, 400, len(code))
    mcode, mt0, mt1, _ = merge_chains_np(code, t0, t1, MIRROR_GAP)
    assert len(mcode) == len(code)  # already chains of one
    return mcode, mt0, mt1


def segments(rng, keys, n):
    """`n` batch segments over `keys`, sorted by (code, t0) as the
    segmentation's argsort leaves them."""
    code = rng.choice(np.asarray(keys, np.int64), n)
    t0 = rng.integers(-200, 5200, n)
    t1 = t0 + rng.integers(0, 250, n)
    order = np.lexsort((t0, code))
    return code[order], t0[order], t1[order]


def _rows(*ivs):
    """(code, t0, t1) arrays of the given (code, t0, t1) intervals."""
    a = np.array(ivs, np.int64).reshape(-1, 3)
    return a[:, 0], a[:, 1], a[:, 2]


def union_merge(mc, m0, m1, live, sc, s0, s1):
    """The oracle: `merge_chains_np` over the live mirror rows and the
    segments together, as every batch ran it before ISSUE 32."""
    return merge_chains_np(np.concatenate([mc[live], sc]),
                           np.concatenate([m0[live], s0]),
                           np.concatenate([m1[live], s1]), MIRROR_GAP,
                           n_first=int(live.sum()))


def _mirror_case(name):
    """(mirror, live mask, segments) of one named case."""
    rng = np.random.default_rng(sum(name.encode()))
    keys = list(range(3, 60, 2))
    mir = open_mirror(rng, keys, 4)
    live = np.ones(len(mir[0]), np.bool_)
    if name == "empty_mirror":
        return _rows(), np.ones(0, np.bool_), segments(rng, keys, 40)
    if name == "no_open_key":      # even codes: none is in the mirror
        return mir, live, segments(rng, [2, 8, 30, 58, 60, 90], 25)
    if name == "every_key":
        return mir, live, segments(rng, keys, 400)
    if name == "few_keys":
        return mir, live, segments(rng, keys[5:9], 12)
    if name == "several_open_sessions":
        return mir, live, segments(rng, keys[:2], 6)
    if name == "dead_rows":
        return mir, rng.random(len(live)) < 0.6, segments(rng, keys, 60)
    if name == "all_rows_dead":
        return mir, ~live, segments(rng, keys, 30)
    # one key, two open sessions [0, 50] and [400, 450], gap 100
    two = _rows((7, 0, 50), (7, 400, 450), (9, 0, 10))
    seg = {
        "bridge": _rows((7, 120, 320)),          # joins both
        "gap_away_joins": _rows((7, 150, 200)),   # 150 - 50 == gap
        "gap_plus_one_splits": _rows((7, 151, 299)),  # and 400 - 299
        "joins_the_later_only": _rows((7, 151, 300)),  # 400 - 300 == gap
        "first_and_last_codes": _rows((1, 5, 6), (7, 60, 70), (11, 0, 1)),
    }[name]
    return two, np.ones(3, np.bool_), seg


MIRROR_CASES = [
    "empty_mirror", "no_open_key", "every_key", "few_keys",
    "several_open_sessions", "dead_rows", "all_rows_dead", "bridge",
    "gap_away_joins", "gap_plus_one_splits", "joins_the_later_only",
    "first_and_last_codes"]


@pytest.mark.parametrize("name", MIRROR_CASES)
def test_the_mirror_update_equals_the_merge_of_the_union(name):
    """`merge_into_mirror_np` returns what `merge_chains_np` returns
    over the live mirror rows and the segments together: the same
    arrays, element for element, the same fanin; and it hands the chain
    merge the rows of the named keys alone."""
    (mc, m0, m1), live, (sc, s0, s1) = _mirror_case(name)
    want = union_merge(mc, m0, m1, live, sc, s0, s1)
    *got, touched = merge_into_mirror_np(mc, m0, m1, live, sc, s0, s1,
                                         MIRROR_GAP)
    for g, w in zip(got[:3], want[:3]):
        assert g.dtype == w.dtype == np.int64
        np.testing.assert_array_equal(g, w)
    assert got[3] == want[3]
    assert touched == int(np.isin(mc[live], sc).sum())
    # a mirror whose order is not known: every live row is touched, and
    # the rows may come in any order
    shuffle = np.random.default_rng(1).permutation(len(mc))
    *got, touched = merge_into_mirror_np(
        mc[shuffle], m0[shuffle], m1[shuffle], live[shuffle], sc, s0, s1,
        MIRROR_GAP, ordered=False)
    for g, w in zip(got[:3], want[:3]):
        np.testing.assert_array_equal(g, w)
    assert got[3] == want[3] and touched == int(live.sum())


def test_the_named_cases_say_what_they_name():
    """The hand-made cases do what their names say (the property test
    would pass as well on cases that all did the same thing)."""
    def chains(name):
        (mc, m0, m1), live, seg = _mirror_case(name)
        c, t0, t1, fanin, _ = merge_into_mirror_np(mc, m0, m1, live,
                                                   *seg, MIRROR_GAP)
        return list(zip(c.tolist(), t0.tolist(), t1.tolist())), fanin

    assert chains("bridge") == ([(7, 0, 450), (9, 0, 10)], 2)
    assert chains("gap_away_joins") == (
        [(7, 0, 200), (7, 400, 450), (9, 0, 10)], 1)
    assert chains("gap_plus_one_splits") == (
        [(7, 0, 50), (7, 151, 299), (7, 400, 450), (9, 0, 10)], 1)
    assert chains("joins_the_later_only") == (
        [(7, 0, 50), (7, 151, 450), (9, 0, 10)], 1)
    assert chains("first_and_last_codes")[0] == [
        (1, 5, 6), (7, 0, 70), (7, 400, 450), (9, 0, 10), (11, 0, 1)]


@pytest.mark.parametrize("seed", range(8))
def test_the_mirror_update_over_random_streams(seed):
    """Batch after batch over a seeded stream, feeding each result back
    as the next mirror and closing rows at random in between: the
    update and the merge of the union never part."""
    rng = np.random.default_rng(seed)
    keys = np.arange(0, 400, int(rng.integers(1, 4)))
    mc, m0, m1 = _rows()
    merged = 0
    for b in range(30):
        n = int(rng.integers(1, 80))
        sc = rng.choice(keys, n)
        s0 = b * 150 + rng.integers(0, 600, n)
        s1 = s0 + rng.integers(0, 120, n)
        order = np.lexsort((s0, sc))
        sc, s0, s1 = sc[order], s0[order], s1[order]
        live = rng.random(len(mc)) < 0.9
        want = union_merge(mc, m0, m1, live, sc, s0, s1)
        mc, m0, m1, fanin, touched = merge_into_mirror_np(
            mc, m0, m1, live, sc, s0, s1, MIRROR_GAP)
        for g, w in zip((mc, m0, m1), want[:3]):
            np.testing.assert_array_equal(g, w)
        assert fanin == want[3]
        merged += touched
    assert 0 < merged < 30 * len(mc)


@pytest.mark.parametrize("mode", MODES)
def test_the_mirror_is_the_arena_after_every_step(mode):
    """Row i of the mirror is slot i of the arena, batch after batch,
    through closes, an arena growth and code compactions; on one device
    a compaction keeps the mirror's order, so only the first batch
    hands the chain merge every row."""
    exd = make_ex([AggSpec(AggKind.COUNT_ALL, "c")], device=True,
                  mode=mode, gap=500, grace=0)
    exd._KEY_CACHE_MAX = 64
    st = assert_mirror_tracks_the_arena(exd, mirror_run(400))
    assert st["grows"] >= 1 and st["remap_dispatches"] >= 2
    assert st["close_cycles"] >= 10
    assert st["mirror_full_merges"] == 1


@pytest.mark.parametrize("mode", MODES)
def test_a_batch_merges_the_rows_of_its_keys_not_the_open_sessions(mode):
    """Cost by counter, not by clock: with 1 500 sessions open and a
    dozen keys a batch, `mirror_rows_merged` grows with each batch by
    the open sessions of the keys the batch names and by no more, and
    no batch after the first takes the whole mirror."""
    exd = make_ex([AggSpec(AggKind.COUNT_ALL, "c")], device=True,
                  mode=mode, gap=1000, grace=600_000)
    rng = np.random.default_rng(5)
    ids = np.arange(1500)
    exd.process([{"k": f"u{i}", "v": 1.0} for i in ids],
                (BASE + rng.integers(0, 500, len(ids))).tolist())
    st = exd.session_stats
    assert st["mirror_rows_merged"] == 0  # the mirror was empty
    assert st["mirror_full_merges"] == 1  # the batch behind activation
    grown = []
    for b in range(1, 9):
        named = rng.choice(ids, 12, replace=False)
        fresh = [f"n{b}_{j}" for j in range(3)]  # no open session yet
        open_of = {c: int(n) for c, n in zip(*np.unique(
            exd._dev["mir_code"], return_counts=True))}
        expect = sum(open_of[exd._code_of[(f"u{int(i)}",)]]
                     for i in named)
        before = st["mirror_rows_merged"]
        # 5 s on: in grace, more than a gap from every open session, so
        # a named key holds one session more after each batch
        exd.process([{"k": k, "v": 1.0}
                     for k in [f"u{int(i)}" for i in named] + fresh],
                    [BASE + b * 5000] * 15)
        grown.append(st["mirror_rows_merged"] - before)
        assert grown[-1] == expect >= 12
    live = int(exd._dev["mir_live"].sum())
    assert live == 1500 + 8 * 15 and exd.device_fallbacks == 0
    assert st["mirror_full_merges"] == 1
    assert max(grown) < live // 50 and sum(grown) > 8 * 12
