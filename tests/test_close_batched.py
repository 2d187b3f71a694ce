"""Fused window-close tests (ISSUE 5).

The close path's contract: one lattice-kernel dispatch and one
device->host fetch per close cycle, however many windows are due, with
results held columnar (common.columnar.ColumnarEmit) until a row-shaped
consumer materializes them. Equivalence is asserted against the legacy
per-slot kernels (lattice.build_extract_slot / build_reset_slot, kept
compiled exactly for this reference role).
"""

import numpy as np
import pytest

from hstream_tpu.common.columnar import (
    ColumnarEmit,
    decode_columnar,
    extend_rows,
    rows_to_payload,
    to_rows,
)
from hstream_tpu.engine import (
    AggKind,
    AggSpec,
    AggregateNode,
    ColumnType,
    HoppingWindow,
    QueryExecutor,
    Schema,
    SourceNode,
    TumblingWindow,
)
from hstream_tpu.engine import lattice
from hstream_tpu.engine.expr import BinOp, Col, Lit, UnOp

SCHEMA = Schema.of(device=ColumnType.STRING, temp=ColumnType.FLOAT)
BASE = 1_700_000_000_000

COUNT = AggSpec(AggKind.COUNT_ALL, "cnt")
SUM_T = AggSpec(AggKind.SUM, "total", input=Col("temp"))
MIN_T = AggSpec(AggKind.MIN, "mn", input=Col("temp"))
AVG_T = AggSpec(AggKind.AVG, "avg", input=Col("temp"))
UNIQ_T = AggSpec(AggKind.APPROX_COUNT_DISTINCT, "u", input=Col("temp"))


def make_exec(aggs, window, *, emit_changes=False, having=None,
              post=None, initial_keys=8):
    node = AggregateNode(
        child=SourceNode("s", SCHEMA), group_keys=[Col("device")],
        window=window, aggs=list(aggs), having=having,
        post_projections=post or [])
    return QueryExecutor(node, SCHEMA, emit_changes=emit_changes,
                         initial_keys=initial_keys, batch_capacity=256)


def rows_of(*pairs):
    rows = [{"device": d, "temp": t} for d, t, _ in pairs]
    ts = [BASE + off for _, _, off in pairs]
    return rows, ts


def gen(n, n_keys=6, span_ms=35_000, seed=0):
    rng = np.random.default_rng(seed)
    rows = [{"device": f"d{int(k)}", "temp": float(t)}
            for k, t in zip(rng.integers(0, n_keys, n),
                            rng.normal(10, 4, n).astype(np.float32))]
    ts = [BASE + int(t) for t in np.sort(rng.integers(0, span_ms, n))]
    return rows, ts


def by_key(emitted):
    return {(r["device"], r.get("winStart")): r for r in emitted}


def close_per_slot(ex, starts):
    """The LEGACY close: one extract_slot + one reset_slot dispatch per
    window, per-kid row decode — the reference the fused path must
    match exactly."""
    rows = []
    for s in sorted(starts):
        ow = ex._open.pop(s)
        if not ex.emit_changes:
            packed = np.asarray(ex._extract_slot(ex.state,
                                                 np.int32(ow.slot)))
            count, _sr, outs = lattice.unpack_extract_rows(ex.spec,
                                                           packed)
            for kid in np.nonzero(count > 0)[0]:
                row = ex._agg_row(int(kid), outs, int(kid), s)
                if row is not None:
                    rows.append(row)
        ex.state = ex._reset_slot(ex.state, np.int32(ow.slot))
        ex._no_close.discard(s)
    return rows


def run_pair(aggs, window, *, n=500, seed=1, having=None, post=None):
    """Drive a fused executor and a per-slot-patched twin through the
    same stream; return (fused rows, reference rows)."""
    fused = make_exec(aggs, window, having=having, post=post)
    ref = make_exec(aggs, window, having=having, post=post)
    ref._close_windows = lambda starts: close_per_slot(ref, starts)
    rows, ts = gen(n, seed=seed)
    out_f, out_r = [], []
    for i in range(0, n, 200):
        out_f.extend(fused.process(rows[i:i + 200], ts[i:i + 200]))
        out_r.extend(ref.process(rows[i:i + 200], ts[i:i + 200]))
    closer = [{"device": "d0", "temp": 0.0}], [BASE + 200_000]
    out_f.extend(fused.process(*closer))
    out_r.extend(ref.process(*closer))
    return out_f, out_r


def assert_rows_equal(out_f, out_r):
    assert len(out_f) == len(out_r) > 0
    kf, kr = by_key(out_f), by_key(out_r)
    assert set(kf) == set(kr)
    for key, want in kr.items():
        got = kf[key]
        assert set(got) == set(want), key
        for name, v in want.items():
            if isinstance(v, float):
                assert got[name] == pytest.approx(v, rel=1e-6), (key, name)
            else:
                assert got[name] == v, (key, name)


# ---- equivalence vs per-slot close -----------------------------------------

def test_batched_close_matches_per_slot_tumbling():
    out_f, out_r = run_pair([COUNT, SUM_T, MIN_T, AVG_T],
                            TumblingWindow(10_000, grace_ms=0))
    assert_rows_equal(out_f, out_r)


def test_batched_close_matches_per_slot_hopping_multi_due():
    # HOP(20s, 5s): a watermark jump closes SEVERAL windows in one
    # cycle — the case the fused kernel exists for
    out_f, out_r = run_pair([COUNT, SUM_T, UNIQ_T],
                            HoppingWindow(20_000, 5_000, grace_ms=0),
                            n=800, seed=2)
    assert_rows_equal(out_f, out_r)
    # the row-ordering contract also holds (window-major, key-ascending)
    assert [r.get("winStart") for r in out_f] == \
        [r.get("winStart") for r in out_r]


def test_batched_close_matches_with_having_and_projection():
    having = BinOp(">=", Col("cnt"), Lit(2))
    post = [("device", Col("device")),
            ("doubled", BinOp("*", Col("cnt"), Lit(2)))]
    out_f, out_r = run_pair([COUNT], TumblingWindow(10_000, grace_ms=0),
                            having=having, post=post, n=300, seed=3)
    assert_rows_equal(out_f, out_r)
    assert all("doubled" in r and "winStart" in r for r in out_f)


def test_host_only_projection_falls_back_per_row():
    # TO_UPPER is not vectorizable -> the columnwise path must fall
    # back to the per-row interpreter with identical results
    post = [("dev", UnOp("TO_UPPER", Col("device"))),
            ("cnt", Col("cnt"))]
    out_f, out_r = run_pair([COUNT], TumblingWindow(10_000, grace_ms=0),
                            post=post, n=200, seed=4)
    assert len(out_f) == len(out_r) > 0
    assert sorted((r["dev"], r["cnt"], r["winStart"]) for r in out_f) \
        == sorted((r["dev"], r["cnt"], r["winStart"]) for r in out_r)
    assert all(r["dev"].startswith("D") for r in out_f)


def test_topk_close_matches_per_slot():
    aggs = [COUNT, AggSpec(AggKind.TOPK, "top3", input=Col("temp"), k=3)]
    out_f, out_r = run_pair(aggs, TumblingWindow(10_000, grace_ms=0),
                            n=400, seed=5)
    assert len(out_f) == len(out_r) > 0
    kf, kr = by_key(out_f), by_key(out_r)
    assert set(kf) == set(kr)
    for key in kr:
        assert kf[key]["top3"] == pytest.approx(kr[key]["top3"]), key


# ---- dispatch accounting ----------------------------------------------------

def test_close_cycle_is_one_dispatch_one_fetch():
    # TUMBLE(10s) GRACE 20s keeps three windows open at once; advancing
    # the watermark makes all three due in ONE close_due_windows cycle —
    # which must cost exactly one kernel dispatch + one fetch
    ex = make_exec([COUNT, SUM_T], TumblingWindow(10_000,
                                                  grace_ms=20_000))
    rows, ts = gen(300, span_ms=25_000, seed=6)
    assert ex.process(rows, ts) == []  # grace holds everything open
    assert len(ex._open) == 3
    before = dict(ex.close_stats)
    ex.watermark_abs = BASE + 100_000
    out = ex.close_due_windows()
    assert len({r["winStart"] for r in out}) == 3
    assert ex.close_stats["close_cycles"] == before["close_cycles"] + 1
    assert ex.close_stats["close_dispatches"] == \
        before["close_dispatches"] + 1
    assert ex.close_stats["close_fetches"] == before["close_fetches"] + 1
    # a processed closer (inside the slot horizon) also costs one
    # dispatch per cycle end-to-end
    before = dict(ex.close_stats)
    ex.process(*rows_of(("d0", 1.0, 101_000)))
    assert ex.close_stats["close_dispatches"] - \
        before["close_dispatches"] == \
        ex.close_stats["close_cycles"] - before["close_cycles"]


def test_deferred_close_fetches_once_per_shape():
    ex = make_exec([COUNT], TumblingWindow(10_000, grace_ms=0))
    ex.defer_close_decode = True
    ex.process(*rows_of(("a", 1.0, 0)))
    assert ex.process(*rows_of(("a", 1.0, 12_000))) == []  # deferred
    assert ex.process(*rows_of(("a", 1.0, 25_000))) == []
    assert len(ex._pending_closes) == 2
    before = ex.close_stats["close_fetches"]
    out = ex.drain_closed()
    got = by_key(out)
    assert got[("a", BASE)]["cnt"] == 1
    assert got[("a", BASE + 10_000)]["cnt"] == 1
    # same buffer shape -> ONE stacked fetch drains both cycles
    assert ex.close_stats["close_fetches"] == before + 1
    assert ex._pending_closes == []


def test_deferred_close_grow_keys_between_closes():
    # grow_keys between two deferred closes changes the packed K dim;
    # the drain must group by shape and decode both correctly
    ex = make_exec([COUNT, SUM_T], TumblingWindow(10_000, grace_ms=0),
                   initial_keys=8)
    ex.defer_close_decode = True
    rows, ts = rows_of(("a", 1.0, 0), ("b", 2.0, 100))
    ex.process(rows, ts)
    ex.process(*rows_of(("c", 1.0, 12_000)))  # closes w0 (deferred)
    grow_rows = [{"device": f"g{i}", "temp": 1.0} for i in range(40)]
    ex.process(grow_rows, [BASE + 13_000 + i for i in range(40)])
    assert ex.spec.n_keys > 8  # grew between the deferred closes
    ex.process(*rows_of(("c", 1.0, 26_000)))  # closes w1 (deferred)
    out = ex.drain_closed()
    got = by_key(out)
    assert got[("a", BASE)]["cnt"] == 1
    assert got[("a", BASE)]["total"] == pytest.approx(1.0)
    assert got[("b", BASE)]["total"] == pytest.approx(2.0)
    assert got[("c", BASE + 10_000)]["cnt"] == 1
    assert sum(1 for r in out if r["winStart"] == BASE + 10_000) == 41


def test_emit_changes_close_resets_without_fetch():
    ex = make_exec([COUNT], TumblingWindow(10_000, grace_ms=0),
                   emit_changes=True)
    out = ex.process(*rows_of(("a", 1.0, 0), ("a", 1.0, 100)))
    assert out[0]["cnt"] == 2
    before = dict(ex.close_stats)
    ex.process(*rows_of(("a", 1.0, 12_000)))  # closes w0 silently
    assert ex.close_stats["close_dispatches"] == \
        before["close_dispatches"] + 1
    assert ex.close_stats["close_fetches"] == before["close_fetches"]
    # the reset really happened: a late-window peek shows only w1
    got = by_key(ex.peek())
    assert ("a", BASE) not in got
    assert got[("a", BASE + 10_000)]["cnt"] == 1


# ---- batched peek -----------------------------------------------------------

def test_peek_all_open_windows_single_dispatch():
    ex = make_exec([COUNT, SUM_T], HoppingWindow(20_000, 5_000,
                                                 grace_ms=0))
    rows, ts = gen(300, span_ms=18_000, seed=7)
    ex.process(rows, ts)
    assert len(ex._open) >= 4
    calls = []
    orig = ex._extract_slots

    def counting(state, slots):
        calls.append(len(slots))
        return orig(state, slots)

    ex._extract_slots = counting
    got = by_key(ex.peek())
    assert len(calls) == 1  # ONE batched dispatch for every open window
    # reference: per-window legacy extract
    want = {}
    for s in sorted(ex._open):
        ow = ex._open[s]
        packed = np.asarray(ex._extract_slot(ex.state, np.int32(ow.slot)))
        count, _sr, outs = lattice.unpack_extract_rows(ex.spec, packed)
        for kid in np.nonzero(count > 0)[0]:
            row = ex._agg_row(int(kid), outs, int(kid), s)
            if row is not None:
                want[(row["device"], row["winStart"])] = row
    assert set(got) == set(want)
    for key, w in want.items():
        assert got[key]["cnt"] == w["cnt"]
        assert got[key]["total"] == pytest.approx(w["total"], rel=1e-6)


def test_windowless_peek_matches_changes():
    ex = make_exec([COUNT, SUM_T], window=None, emit_changes=True)
    ex.process(*rows_of(("a", 1.0, 0), ("b", 2.0, 50), ("a", 3.0, 60)))
    got = {r["device"]: r for r in ex.peek()}
    assert got["a"]["cnt"] == 2 and got["a"]["total"] == pytest.approx(4.0)
    assert got["b"]["cnt"] == 1


# ---- columnar emission ------------------------------------------------------

def test_close_emits_columnar_batch_to_the_wire():
    ex = make_exec([COUNT, SUM_T], TumblingWindow(10_000,
                                                  grace_ms=20_000))
    rows, ts = gen(200, span_ms=25_000, seed=8)
    assert ex.process(rows, ts) == []  # grace holds everything open
    ex.watermark_abs = BASE + 100_000
    closed = ex.close_due_windows()
    assert isinstance(closed, ColumnarEmit)  # stayed columnar
    assert len({r["winStart"] for r in closed}) == 3  # one fused cycle
    # one columnar wire record straight from the columns
    payload = rows_to_payload(closed, 123)
    assert payload is not None
    ts_dec, cols_dec = decode_columnar(payload)
    wire_rows = to_rows(ts_dec, cols_dec)
    assert len(wire_rows) == len(closed)
    legacy = closed.rows()
    for w, l in zip(wire_rows, legacy):
        assert set(w) == set(l)
        assert w["device"] == l["device"]
        assert w["cnt"] == l["cnt"]
        assert w["winStart"] == l["winStart"]
        assert w["total"] == pytest.approx(l["total"], rel=1e-6)
    # Sequence protocol: len / index / iterate / extend into a list
    acc = []
    acc.extend(closed)
    assert acc == legacy and closed[0] == legacy[0]


def test_extend_rows_keeps_lone_batch_columnar():
    ce = ColumnarEmit({"a": np.asarray([1, 2])}, 2)
    assert extend_rows(None, ce) is ce
    assert extend_rows([], ce) is ce
    mixed = extend_rows(ce, [{"a": 3}])
    assert isinstance(mixed, list)
    assert mixed == [{"a": 1}, {"a": 2}, {"a": 3}]
    assert extend_rows(ce, []) is ce


def test_topk_batch_falls_back_to_row_records():
    aggs = [AggSpec(AggKind.TOPK, "top2", input=Col("temp"), k=2)]
    ex = make_exec(aggs, TumblingWindow(10_000, grace_ms=0))
    ex.process(*rows_of(("a", 1.0, 0), ("a", 5.0, 10), ("a", 3.0, 20)))
    closed = ex.process(*rows_of(("a", 0.0, 15_000)))
    assert isinstance(closed, ColumnarEmit)
    assert rows_to_payload(closed, 1) is None  # lists -> per-row
    assert closed[0]["top2"] == [5.0, 3.0]


# ---- session windows stay unaffected ---------------------------------------

def test_session_close_and_peek_unchanged():
    from hstream_tpu.engine.plan import AggregateNode as AN
    from hstream_tpu.engine.session import SessionExecutor
    from hstream_tpu.engine.window import SessionWindow

    node = AN(child=SourceNode("s", SCHEMA), group_keys=[Col("device")],
              window=SessionWindow(5_000, grace_ms=0),
              aggs=[COUNT, SUM_T])
    ex = SessionExecutor(node, SCHEMA, emit_changes=False)
    ex.process(*rows_of(("a", 1.0, 0), ("a", 2.0, 1_000)))
    live = ex.peek()
    assert live and live[0]["cnt"] == 2
    out = ex.process(*rows_of(("a", 7.0, 60_000)))  # closes the session
    assert len(out) == 1
    assert out[0]["cnt"] == 2 and out[0]["total"] == pytest.approx(3.0)


# ---- sharded executor -------------------------------------------------------

def test_sharded_batched_close_matches_single_chip():
    from hstream_tpu.parallel import ShardedQueryExecutor, make_mesh

    mesh = make_mesh(n_data=4, n_key=2)
    window = HoppingWindow(20_000, 5_000, grace_ms=0)
    node = AggregateNode(child=SourceNode("s", SCHEMA),
                         group_keys=[Col("device")], window=window,
                         aggs=[COUNT, SUM_T, MIN_T])
    ref = QueryExecutor(node, SCHEMA, emit_changes=False,
                        initial_keys=16, batch_capacity=256)
    sh = ShardedQueryExecutor(node, SCHEMA, mesh=mesh,
                              emit_changes=False, initial_keys=16,
                              batch_capacity=256)
    rows, ts = gen(500, n_keys=13, span_ms=22_000, seed=9)
    out_ref, out_sh = [], []
    for i in range(0, 500, 200):
        out_ref.extend(ref.process(rows[i:i + 200], ts[i:i + 200]))
        out_sh.extend(sh.process(rows[i:i + 200], ts[i:i + 200]))
    before_sh, before_ref = dict(sh.close_stats), dict(ref.close_stats)
    closer = [{"device": "d0", "temp": 0.0}], [BASE + 200_000]
    out_ref.extend(ref.process(*closer))
    out_sh.extend(sh.process(*closer))
    # every close cycle is ONE dispatch + ONE fetch on the mesh too,
    # and the mesh takes exactly the cycles the single chip takes (the
    # far-future closer costs both two: the gap guard first closes the
    # windows its slots collide with, then the due ones)
    delta_sh = {k: sh.close_stats[k] - before_sh[k] for k in before_sh}
    delta_ref = {k: ref.close_stats[k] - before_ref[k]
                 for k in before_ref}
    assert delta_sh["close_cycles"] == delta_sh["close_dispatches"] \
        == delta_sh["close_fetches"] >= 1, delta_sh
    assert delta_sh == delta_ref
    assert_rows_equal(out_sh, out_ref)
    # batched peek parity (both should be empty after the big closer,
    # bar the closer's own window)
    assert {(r["device"], r["winStart"]) for r in sh.peek()} == \
        {(r["device"], r["winStart"]) for r in ref.peek()}
