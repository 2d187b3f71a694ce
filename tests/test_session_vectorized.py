"""Vectorized session segmentation must be record-for-record equivalent
to the per-record merge path: batch process() vs one-row-at-a-time
process() over randomized, out-of-order, late-record workloads."""
from __future__ import annotations

import numpy as np
import pytest
from helpers import assert_code_columns_fresh

from hstream_tpu.engine import ColumnType, Schema
from hstream_tpu.engine.expr import Col
from hstream_tpu.engine.plan import AggKind, AggregateNode, AggSpec, SourceNode
from hstream_tpu.engine.session import SessionExecutor
from hstream_tpu.engine.window import SessionWindow

BASE = 1_700_000_000_000


def make_ex(aggs, gap=1000, grace=500, emit_changes=False):
    schema = Schema.of(k=ColumnType.STRING, v=ColumnType.FLOAT)
    node = AggregateNode(
        child=SourceNode("s", schema), group_keys=[Col("k")],
        window=SessionWindow(gap, grace_ms=grace), aggs=aggs)
    ex = SessionExecutor(node, schema, emit_changes=emit_changes)
    # this file validates the HOST reference engine against the
    # per-record oracle (it inspects ex.sessions directly); device/host
    # equivalence has its own suite (tests/test_session_device.py)
    ex.use_device_sessions = False
    return ex


def gen(seed, n_batches=8, batch=300, keys=12, late_frac=0.15):
    rng = np.random.default_rng(seed)
    batches = []
    t = BASE
    for _ in range(n_batches):
        ks = rng.integers(0, keys, batch)
        # mostly-forward timestamps with jitter; some records far behind
        # the watermark to exercise the late policy
        ts = t + rng.integers(0, 4000, batch)
        late = rng.random(batch) < late_frac
        ts = np.where(late, ts - rng.integers(3000, 20_000, batch), ts)
        vs = np.abs(rng.normal(50, 20, batch))
        rows = [{"k": f"u{int(k)}", "v": float(v)}
                for k, v in zip(ks, vs)]
        batches.append((rows, ts.tolist()))
        t += 2500
    return batches


def canon_state(ex):
    out = {}
    for key, sess_list in ex.sessions.items():
        out[key] = [(s.start, s.end, _canon_accs(s.accs))
                    for s in sorted(sess_list, key=lambda s: s.start)]
    return out


def _canon_accs(accs):
    c = {}
    for k, v in accs.items():
        if isinstance(v, np.ndarray):
            c[k] = v.tolist()
        elif isinstance(v, tuple):
            c[k] = tuple(round(float(x), 9) for x in v)
        elif isinstance(v, float):
            c[k] = round(v, 9)
        elif isinstance(v, list):
            c[k] = [round(float(x), 9) for x in v]
        else:
            c[k] = v
    return c


def canon_rows(rows):
    return sorted(
        (tuple(sorted((k, round(v, 6) if isinstance(v, float) else
                       tuple(v) if isinstance(v, list) else v)
                      for k, v in r.items())))
        for r in rows)


AGG_SETS = [
    [AggSpec(AggKind.COUNT_ALL, "c"),
     AggSpec(AggKind.SUM, "s", input=Col("v")),
     AggSpec(AggKind.AVG, "a", input=Col("v"))],
    [AggSpec(AggKind.MIN, "lo", input=Col("v")),
     AggSpec(AggKind.MAX, "hi", input=Col("v")),
     AggSpec(AggKind.COUNT, "n", input=Col("v"))],
    [AggSpec(AggKind.APPROX_QUANTILE, "p50", input=Col("v"), quantile=0.5),
     AggSpec(AggKind.APPROX_COUNT_DISTINCT, "d", input=Col("v"))],
    [AggSpec(AggKind.TOPK, "top", input=Col("v"), k=3)],
]


def oracle_process(ex, rows, ts):
    """The pre-vectorization batch semantics, verbatim: every record
    walks the per-record merge path in ts order under the pre-batch
    watermark; watermark advances and sessions close at batch end."""
    order = sorted(range(len(rows)), key=lambda i: ts[i])
    for i in order:
        ex._ingest_row(rows[i], int(ts[i]))
    new_wm = max(int(t) for t in ts)
    if new_wm > ex.watermark:
        ex.watermark = new_wm
    return ex.close_due_sessions()


@pytest.mark.parametrize("aggset", range(len(AGG_SETS)))
@pytest.mark.parametrize("seed", [0, 1])
def test_batch_matches_per_record_oracle(aggset, seed):
    aggs = AGG_SETS[aggset]
    ex_batch = make_ex(aggs)
    ex_oracle = make_ex(aggs)
    out_b, out_r = [], []
    for rows, ts in gen(seed):
        out_b.extend(ex_batch.process(rows, ts))
        out_r.extend(oracle_process(ex_oracle, rows, ts))
    assert canon_state(ex_batch) == canon_state(ex_oracle)
    assert canon_rows(out_b) == canon_rows(out_r)


def test_emit_changes_touched_keys():
    aggs = [AggSpec(AggKind.COUNT_ALL, "c")]
    ex = make_ex(aggs, emit_changes=True)
    rows = [{"k": "a", "v": 1.0}, {"k": "b", "v": 2.0}]
    out = ex.process(rows, [BASE, BASE + 100])
    assert {r["k"] for r in out} == {"a", "b"}
    assert all(r["c"] == 1 for r in out)


def test_multi_column_group_key():
    schema = Schema.of(k=ColumnType.STRING, r=ColumnType.INT,
                       v=ColumnType.FLOAT)
    node = AggregateNode(
        child=SourceNode("s", schema),
        group_keys=[Col("k"), Col("r")],
        window=SessionWindow(1000, grace_ms=0),
        aggs=[AggSpec(AggKind.SUM, "s", input=Col("v"))])
    ex = SessionExecutor(node, schema)
    ex.use_device_sessions = False  # host engine: inspects ex.sessions
    rows = [{"k": "a", "r": 1, "v": 1.0}, {"k": "a", "r": 2, "v": 2.0},
            {"k": "a", "r": 1, "v": 3.0}]
    ex.process(rows, [BASE, BASE, BASE + 10])
    assert len(ex.sessions) == 2
    got = ex.process([{"k": "z", "v": 0.0}], [BASE + 100_000])
    # both (a,1) and (a,2) sessions closed with correct sums
    sums = {(r["k"], r["r"]): r["s"] for r in got}
    assert sums == {("a", 1): 4.0, ("a", 2): 2.0}


def test_non_numeric_input_skipped_both_paths():
    """A malformed value must be NULLed identically on the vectorized
    and late-segment per-record paths (not crash on one of them)."""
    aggs = [AggSpec(AggKind.SUM, "s", input=Col("v")),
            AggSpec(AggKind.COUNT_ALL, "c")]
    ex = make_ex(aggs, gap=1000, grace=0)
    ex.process([{"k": "a", "v": 1.0}], [BASE + 50_000])  # wm forward
    # late batch (seg_t0 + gap <= wm) with a junk value -> per-record
    # fallback; on-time junk -> vectorized path. Neither may raise.
    out = ex.process(
        [{"k": "a", "v": "junk"}, {"k": "a", "v": 2.0},
         {"k": "b", "v": "junk"}],
        [BASE + 49_900, BASE + 49_950, BASE + 51_000])
    rows = ex.process([{"k": "z", "v": 0.0}], [BASE + 200_000])
    got = {r["k"]: (r["c"], r["s"]) for r in rows if r["k"] in "ab"}
    assert got["a"] == (3, 3.0), got   # junk counted, not summed
    assert got["b"] == (1, 0.0), got


def test_numeric_strings_null_both_paths():
    """NUMERIC strings ("42") must be NULLed exactly like junk strings
    on BOTH engines: np.asarray silently coerced an all-numeric-string
    batch to floats on the vectorized path while the per-record slow
    path NULLed it — the same record then aggregated differently
    depending on lateness (ISSUE 1 satellite, session.py NULL rule)."""
    aggs = [AggSpec(AggKind.SUM, "s", input=Col("v")),
            AggSpec(AggKind.COUNT, "n", input=Col("v")),
            AggSpec(AggKind.COUNT_ALL, "c")]
    ex = make_ex(aggs, gap=1000, grace=0)
    ex.process([{"k": "a", "v": 1.0}], [BASE + 50_000])  # wm forward
    # same shape as the junk test: the late rows walk the per-record
    # fallback, the on-time row the vectorized path — but every string
    # here PARSES as a number, the case np.asarray used to coerce
    out = ex.process(
        [{"k": "a", "v": "7.5"}, {"k": "a", "v": "3"},
         {"k": "b", "v": "42"}],
        [BASE + 49_900, BASE + 49_950, BASE + 51_000])
    assert out == []
    rows = ex.process([{"k": "z", "v": 0.0}], [BASE + 200_000])
    got = {r["k"]: (r["c"], r["n"], r["s"])
           for r in rows if r["k"] in "ab"}
    assert got["a"] == (3, 1, 1.0), got  # strings counted, never summed
    assert got["b"] == (1, 0, 0.0), got


def test_ragged_sequence_values_nulled_not_crash():
    """List-valued (ragged) column cells must be NULLed on the
    vectorized path — np.asarray raises on inhomogeneous shapes and
    that must not kill the query."""
    aggs = [AggSpec(AggKind.SUM, "s", input=Col("v")),
            AggSpec(AggKind.COUNT_ALL, "c")]
    ex = make_ex(aggs, gap=1000, grace=0)
    ex.process([{"k": "a", "v": [1.0, 2.0]}, {"k": "a", "v": [3.0]},
                {"k": "a", "v": 5.0}], [BASE, BASE + 10, BASE + 20])
    rows = ex.process([{"k": "z", "v": 0.0}], [BASE + 200_000])
    got = {r["k"]: (r["c"], r["s"]) for r in rows if r["k"] == "a"}
    assert got["a"] == (3, 5.0), got


@pytest.mark.parametrize("groups", [("k",), ("k", "r"), ("k", "r", "f"), ()],
                         ids=["one", "two", "three", "none"])
def test_host_reset_sets_the_decode_columns_with_the_dictionary(groups):
    """The host engine drops its code dictionary wholesale above the
    cache bound; the decode columns (ISSUE 30) are set with it, counted
    as a build, and what is appended after equals a from-scratch build
    of `_code_rev`: int, string, float and absent (None) key cells."""
    schema = Schema.of(k=ColumnType.STRING, r=ColumnType.INT,
                       f=ColumnType.FLOAT, v=ColumnType.FLOAT)
    node = AggregateNode(
        child=SourceNode("s", schema),
        group_keys=[Col(g) for g in groups],
        window=SessionWindow(1000, grace_ms=0),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c")])
    ex = SessionExecutor(node, schema)
    ex.use_device_sessions = False
    ex._KEY_CACHE_MAX = 40
    rng = np.random.default_rng(4)
    resets = 0
    for b in range(10):
        rows = []
        for i in rng.integers(b * 20, b * 20 + 30, 50).tolist():
            row = {"k": f"k{i}", "r": i % 3, "f": (i % 4) * 0.25, "v": 1.0}
            if i % 11 == 0:
                del row["k"]  # an absent cell is the key None
            rows.append(row)
        size = len(ex._code_of)
        ex.process(rows, [BASE + b * 5000 + j for j in range(50)])
        if size > 40:
            resets += 1
            assert len(ex._code_rev) <= 50  # only this batch's keys
        assert ex.session_stats["code_cols_builds"] == resets
        assert_code_columns_fresh(ex)
    assert ex._dev is None
    if groups:
        assert resets >= 2
    else:  # one key (): nothing to bound
        assert resets == 0 and ex._code_rev == [()]
    assert ex._code_cols_filled == len(ex._code_rev)
