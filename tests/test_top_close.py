"""The top across groups at a window's close, on the device: the fused
close of a plan with a `WindowTop` against a numpy argmax over seeded
planes, with ties, with an empty window and with more ties than the
survivors' buffer holds; and the executor's close against the unfiltered
statement's rows."""

import numpy as np
import pytest

import jax.numpy as jnp

from hstream_tpu.engine import lattice
from hstream_tpu.engine.plan import AggKind, AggSpec
from hstream_tpu.engine.expr import Col
from hstream_tpu.engine.window import HoppingWindow
from hstream_tpu.server.tasks import _columnar_key_ids
from hstream_tpu.sql.codegen import make_executor, stream_codegen

WIN = HoppingWindow(size_ms=10_000, advance_ms=2_000, grace_ms=0)
BASE = 1_700_000_000_000


def _spec(n_keys, aggs):
    return lattice.LatticeSpec(n_keys=n_keys, window=WIN, aggs=aggs,
                               track_touched=False)


def _planes(spec, rng, *, ties=0, empty_slot=None):
    """A seeded state: counts in every slot, `ties` extra keys lifted to
    slot 0's maximum, `empty_slot` without a group."""
    K, W = spec.n_keys, spec.n_slots
    count = rng.integers(0, 40, (K, W)).astype(np.int32)
    count[rng.random((K, W)) < 0.3] = 0
    if ties:
        best = count[:, 0].max()
        count[rng.choice(K, ties, replace=False), 0] = best
    if empty_slot is not None:
        count[:, empty_slot] = 0
    state = {k: np.asarray(v).copy()
             for k, v in lattice.init_state(spec).items()}
    state["count"] = count
    state["slot_start"] = (np.arange(W, dtype=np.int32) * 2000)
    for i, agg in enumerate(spec.aggs):
        if agg.kind == AggKind.SUM:
            state[lattice._plane_name(i, agg)] = np.where(
                count > 0, rng.integers(-50, 50, (K, W)), 0
            ).astype(np.float32)
    return {k: jnp.asarray(v) for k, v in state.items()}, count


COUNT = (AggSpec(AggKind.COUNT_ALL, "n"),)
COUNT_SUM = (AggSpec(AggKind.COUNT_ALL, "n"),
             AggSpec(AggKind.SUM, "s", input=Col("x")))


@pytest.mark.parametrize("seed,ties,extreme", [
    (0, 0, "max"), (1, 3, "max"), (2, 7, "max"), (3, 0, "min"),
    (4, 2, "min"),
])
def test_top_close_is_the_numpy_argmax(seed, ties, extreme):
    rng = np.random.default_rng(seed)
    spec = _spec(64, COUNT)
    state, count = _planes(spec, rng, ties=ties if extreme == "max" else 0)
    if extreme == "min" and ties:
        live = np.flatnonzero(count[:, 0] > 0)
        count[live[:ties], 0] = count[live, 0].min()
        state["count"] = jnp.asarray(count)
    close = lattice.build_extract_top_reset_slots(spec, "n", extreme)
    slots = lattice.pad_slots([0, 3, 5])
    after, top, full = close(state, slots)
    top, full = np.asarray(top), np.asarray(full)
    assert top.shape == (4, 3, lattice.top_rows(64))
    assert lattice.top_rows(1 << 20) == lattice.TOP_ROWS
    for p, slot in enumerate([0, 3, 5]):
        col = count[:, slot]
        live = col > 0
        best = col[live].max() if extreme == "max" else col[live].min()
        want = np.flatnonzero(live & (col == best))
        n_keep, n_groups, kids, outs = lattice.unpack_top_rows(spec, top[p])
        assert (n_keep, n_groups) == (len(want), int(live.sum()))
        assert kids.tolist() == want.tolist()
        assert outs["n"].tolist() == [float(best)] * len(want)
        assert top[p, 0, 2] == slot * 2000
        # the full column, kept on the device for a tie past the buffer
        assert np.flatnonzero(full[p, 0] > 0).tolist() == want.tolist()
    assert not top[3].any() and not full[3].any()   # the padding slot
    left = np.asarray(after["count"])
    assert not left[:, [0, 3, 5]].any()
    assert (left[:, [1, 2, 4, 6]] == count[:, [1, 2, 4, 6]]).all()
    assert np.asarray(after["slot_start"])[[0, 3, 5]].tolist() == [
        lattice.EMPTY_START] * 3


@pytest.mark.parametrize("n_keys,where,width", [
    (64, [3, 9, 63], 8),                       # one block
    (1024, [0, 255, 256, 700, 1023], 8),       # four blocks
    (1024, list(range(100, 140)), 8),          # more than the width
    (256 * 80, [256 * b + b for b in range(70)], 256),   # > 64 blocks
    (256 * 80, [], 16),
    (100, [99], 4),                            # no multiple of a block
])
def test_first_true_is_numpys_nonzero_up_to_its_bounds(n_keys, where,
                                                       width):
    mask = np.zeros(n_keys, np.bool_)
    mask[where] = True
    pos, shown = lattice._first_true(jnp.asarray(mask), n_keys, width)
    pos, shown = np.asarray(pos), int(shown)
    blocks = sorted({w // 256 for w in where})[:64] \
        if n_keys % 256 == 0 else [0]
    seen = [w for w in where
            if (w // 256 if n_keys % 256 == 0 else 0) in blocks][:width]
    assert shown == len(seen) and pos[:shown].tolist() == seen
    assert not pos[shown:].any()
    if len(seen) < len(where):     # the caller sees that some are hidden
        assert shown < int(mask.sum())


def test_an_empty_window_keeps_nothing():
    spec = _spec(32, COUNT)
    state, _count = _planes(spec, np.random.default_rng(5), empty_slot=2)
    close = lattice.build_extract_top_reset_slots(spec, "n", "max")
    _after, top, full = close(state, lattice.pad_slots([2]))
    n_keep, n_groups, kids, _outs = lattice.unpack_top_rows(
        spec, np.asarray(top)[0])
    assert (n_keep, n_groups, len(kids)) == (0, 0, 0)
    assert not np.asarray(full)[0, 0].any()


def test_the_other_aggregates_ride_with_the_survivors():
    rng = np.random.default_rng(6)
    spec = _spec(64, COUNT_SUM)
    state, count = _planes(spec, rng, ties=4)
    sums = np.asarray(state["a1_sum"])
    for name, vals in (("n", count[:, 0]), ("s", sums[:, 0])):
        close = lattice.build_extract_top_reset_slots(spec, name, "max")
        _after, top, _full = close(state, lattice.pad_slots([0]))
        live = count[:, 0] > 0
        want = np.flatnonzero(live & (vals == vals[live].max()))
        _k, _g, kids, outs = lattice.unpack_top_rows(
            spec, np.asarray(top)[0])
        assert kids.tolist() == want.tolist()
        assert outs["n"].tolist() == count[want, 0].astype(float).tolist()
        assert outs["s"].tolist() == sums[want, 0].tolist()


def test_a_width_k_aggregate_has_no_extreme():
    spec = _spec(8, (AggSpec(AggKind.TOPK, "t", input=Col("x"), k=3),))
    with pytest.raises(ValueError, match="no single value"):
        lattice.build_extract_top_reset_slots(spec, "t", "max")


# ---- the executor's close -------------------------------------------------

Q = ("CREATE VIEW v AS SELECT auction, COUNT(*) AS num FROM bid GROUP BY "
     "auction, HOPPING (INTERVAL 10 SECOND, INTERVAL 2 SECOND) GRACE BY "
     "INTERVAL 0 SECOND{};")
QUALIFY = (" QUALIFY COUNT(*) >= MAX(COUNT(*)) OVER (PARTITION BY winStart,"
           " winEnd)")


def _run(top: bool, seed: int, *, flat: bool = False, keys: int = 30):
    """The statement over a seeded churn of integer keys: rows emitted.
    `flat`: every auction of a window bids equally often (all tie)."""
    plan = stream_codegen(Q.format(QUALIFY if top else "")).select
    ex = make_executor(plan, sample_rows=[{"auction": 1}],
                       initial_keys=64, batch_capacity=4096)
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(30):
        n = 400
        ts = BASE + i * 1000 + np.sort(rng.integers(0, 1000, n))
        if flat:
            auction = (1 << 25) + np.arange(n) % keys
        else:
            auction = (1 << 25) + i * 5 + rng.integers(0, keys, n)
        cols = {"auction": ("i64", auction.astype(np.int64), None)}
        kids = _columnar_key_ids(ex, cols, n, ts_hi=int(ts.max()))
        rows.extend(ex.process_columnar(kids, ts, {}))
    return ex, rows


def _top_of(rows):
    best: dict = {}
    for r in rows:
        best[r["winStart"]] = max(best.get(r["winStart"], 0), r["num"])
    return sorted((r["winStart"], r["auction"], r["num"], r["winEnd"])
                  for r in rows if r["num"] == best[r["winStart"]])


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 7])
def test_the_executors_close_keeps_the_unfiltered_rows_maxima(seed):
    ex, got = _run(True, seed)
    _plain, every = _run(False, seed)
    assert sorted((r["winStart"], r["auction"], r["num"], r["winEnd"])
                  for r in got) == _top_of(every)
    st = ex.close_stats
    assert st["close_rows_kept"] == len(got) > 0
    assert st["close_groups"] == len(every)
    assert st["close_tie_refetches"] == 0
    assert st["close_fetches"] == st["close_cycles"]
    assert st["close_dispatches"] == st["close_cycles"]


def test_more_ties_than_the_buffer_holds_are_fetched_not_cut(monkeypatch):
    monkeypatch.setattr(lattice, "TOP_ROWS", 8)
    lattice.compiled_top_close.cache_clear()
    try:
        ex, got = _run(True, 3, flat=True)
        _plain, every = _run(False, 3, flat=True)
    finally:
        lattice.compiled_top_close.cache_clear()
    want = _top_of(every)
    assert max(sum(1 for w in want if w[0] == ws)
               for ws in {w[0] for w in want}) > 8
    assert sorted((r["winStart"], r["auction"], r["num"], r["winEnd"])
                  for r in got) == want
    st = ex.close_stats
    assert st["close_tie_refetches"] > 0
    assert st["close_fetches"] == (st["close_cycles"]
                                   + st["close_tie_refetches"])
    assert st["close_rows_kept"] == len(got)


def test_the_reference_close_of_a_degraded_executor_filters_too():
    from hstream_tpu.common.faultinject import FAULTS

    FAULTS.arm("device.activate", "fail:1")
    try:
        ex, got = _run(True, 4)
    finally:
        FAULTS.disarm()
    assert ex.device_fallbacks == 1
    _plain, every = _run(False, 4)
    assert sorted((r["winStart"], r["auction"], r["num"], r["winEnd"])
                  for r in got) == _top_of(every)


def test_an_open_window_has_no_row_to_peek():
    """The extreme is known when the window closes: the live half of a
    pull is empty for such a plan, where the same statement without the
    filter shows every group of its open windows so far."""
    ex, _rows = _run(True, 3)
    assert len(ex._open) == 5 and ex.peek() == []
    plain, _rows = _run(False, 3)
    assert len(plain.peek()) > len(plain._open) == 5


def test_a_plan_without_the_filter_runs_the_close_it_ran():
    ex, _rows = _run(False, 5)
    assert not hasattr(ex, "_extract_top_reset")
    assert lattice.CLOSE_PROGRAM == "jit_extract_and_reset"
    assert lattice.TOP_CLOSE_PROGRAM == "jit_extract_top_and_reset"
    top = lattice.build_extract_top_reset_slots(_spec(8, COUNT), "n", "max")
    assert "jit_" + top.__wrapped__.__name__ == lattice.TOP_CLOSE_PROGRAM


def test_emit_changes_is_refused_at_the_executor_too():
    from hstream_tpu.common.errors import SQLCodegenError
    from hstream_tpu.engine.executor import QueryExecutor

    plan = stream_codegen(Q.format(QUALIFY)).select
    from hstream_tpu.sql.codegen import bind_schema

    with pytest.raises(SQLCodegenError, match="EMIT CHANGES"):
        QueryExecutor(plan.node, bind_schema(plan, [{"auction": 1}]),
                      emit_changes=True)
