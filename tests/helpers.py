"""Shared test helpers: readiness waits instead of sleeps.

SURVEY §4 flags the reference's sleep-based test sync ("FIXME: requires
a notification mechanism", RunSQLSpec.hs:54); QueryTask.attached is
that mechanism — set once the reader is attached to every source at its
start LSN (tasks.attached_lsns)."""

from __future__ import annotations

import time

import numpy as np


def wait_attached(ctx, query_id: str, timeout: float = 10.0):
    """Block until the query's task is registered AND attached to its
    source streams; returns the task."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        task = ctx.running_queries.get(query_id)
        if task is not None and task.attached.wait(0.05):
            return task
        time.sleep(0.01)
    raise TimeoutError(f"query {query_id!r} never attached "
                       f"(running: {list(ctx.running_queries)})")


def wait_any_attached(ctx, timeout: float = 10.0, *, exclude=()):
    """Block until a running query task OUTSIDE `exclude` is attached
    (push queries have generated ids the test cannot predict; pass the
    pre-existing query ids so a stale attached task cannot satisfy the
    wait)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        for qid, task in list(ctx.running_queries.items()):
            if qid in exclude:
                continue
            if getattr(task, "attached", None) is not None \
                    and task.attached.is_set():
                return task
        time.sleep(0.01)
    raise TimeoutError("no (new) query task attached")


def wait_watermark(task, target: int, timeout: float = 60.0) -> None:
    """Block until the task's executor has taken every event up to
    `target` (absolute ms): the batch that ended there is absorbed."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        ex = task.executor
        if ex is not None and ex.watermark_abs >= target:
            return
        time.sleep(0.005)
    raise TimeoutError(f"the task did not drain to {target}")


def fresh_code_columns(ex) -> list:
    """A session executor's decode columns built from scratch, the plain
    way: one walk of the WHOLE code dictionary a group column, a
    sharded compaction's holes left None."""
    out = []
    for g in range(len(ex.group_cols)):
        arr = np.empty(len(ex._code_rev), object)
        for i, key in enumerate(ex._code_rev):
            if key is not None:
                arr[i] = key[g]
        out.append(arr)
    return out


def typed(values) -> list:
    """Values with their types: 1, 1.0 and True are three keys."""
    return [(type(v).__name__, v) for v in values]


def assert_code_columns_fresh(ex) -> None:
    """The incrementally kept decode columns equal a from-scratch build
    of `_code_rev`, value for value and type for type."""
    got, want = ex._code_rev_columns(), fresh_code_columns(ex)
    assert len(got) == len(want) == len(ex.group_cols)
    for g, w in zip(got, want):
        assert g.dtype == object and g.shape == w.shape
        assert typed(g.tolist()) == typed(w.tolist())


def assert_mirror_is_the_arena(ex) -> None:
    """Row i of a session executor's interval mirror is slot i of its
    device arena (per shard: its rank within its residue class): the
    arena's code / t0 / t1 fetched at the live rows' slots equal the
    mirror's, and the arena holds no more sessions than the mirror has
    rows (a closed row keeps its slot until the next step)."""
    import jax

    from hstream_tpu.engine.lattice import SESSION_SENT_CODE

    dev = ex._dev
    arena = jax.device_get({k: dev["arena"][k] for k in ("code", "t0", "t1")})
    held = int((arena["code"] != SESSION_SENT_CODE).sum())
    if dev.get("ssl") is not None:
        cls, slot = ex._shard_slots()
        arena = {k: v[cls, slot] for k, v in arena.items()}
    live = np.nonzero(dev["mir_live"])[0]
    assert len(live) <= held <= len(dev["mir_live"])
    np.testing.assert_array_equal(arena["code"][live], dev["mir_code"][live])
    for t in ("t0", "t1"):
        np.testing.assert_array_equal(
            arena[t][live].astype(np.int64) + ex.epoch, dev["mir_" + t][live])


def mirror_run(big: int, seed: int = 11, n_batches: int = 14):
    """(rows, ts) batches of a session stream (gap 500 ms, grace 0) that
    holds closes with every batch, ONE batch of `big` distinct keys (an
    arena growth) and, with `_KEY_CACHE_MAX` low, code compactions
    before and after it; some keys hold several open sessions."""
    rng = np.random.default_rng(seed)
    for b in range(n_batches):
        n = big if b == 6 else 160
        ids = b * 31 + (np.arange(n) if b == 6 else rng.integers(0, 70, n))
        # two bursts a batch, more than a gap apart: a key in both has
        # two sessions open until the close
        ts = SMOKE_BASE + b * 2000 + rng.integers(0, 300, n) \
            + 900 * rng.integers(0, 2, n)
        yield ([{"k": f"u{int(i)}", "v": float(i % 9)} for i in ids],
               ts.tolist())


def assert_mirror_tracks_the_arena(ex, batches) -> dict:
    """Drive `ex` through `batches`: the mirror equals the arena after
    every step, and between a code compaction and the step behind it;
    `mirror_full_merges` rises with the first batch (activation) and
    with the step behind a key-sharded compaction, never else. Returns
    the executor's `session_stats`."""
    compact = ex._compact_codes_device

    def compacting():
        compact()
        assert_mirror_is_the_arena(ex)

    ex._compact_codes_device = compacting
    st = ex.session_stats
    for i, (rows, ts) in enumerate(batches):
        remaps, full = st["remap_dispatches"], st["mirror_full_merges"]
        ex.process(rows, ts)
        assert ex._dev is not None and ex.device_fallbacks == 0
        assert_mirror_is_the_arena(ex)
        sharded = ex._dev.get("ssl") is not None
        compacted = st["remap_dispatches"] - remaps
        assert st["mirror_full_merges"] - full == (
            1 if i == 0 or (sharded and compacted) else 0), i
        assert ex._dev["mir_ordered"]
    return st


# ---- retrace-gate configurations (zero compiles in steady state) ------------


SMOKE_BASE = 1_700_000_000_000


def _tumbling_uniq(n: int = 512) -> list:
    rng = np.random.default_rng(0)
    return [(rng.integers(0, 100, n).astype(np.int32),
             (np.rint(rng.normal(20, 5, n) * 10).astype(np.float32)
              * np.float32(0.1)))
            for _ in range(4)]


_TUMBLING_UNIQ = _tumbling_uniq()
_TUMBLING_TS = (np.arange(512, dtype=np.int64) * 200) // 512


def smoke_tumbling_batch(i: int) -> tuple:
    """(key ids, temps, ts) of batch i of the fused-close retrace
    gate's stream: 512 rows over 100 keys, 200 ms of stream a batch.
    Four pre-generated batches cycled on a FIXED ts template: the
    adaptive wire codec's combo — and so the compiled step — is
    identical batch to batch; fresh random data per batch would
    legitimately grow a new combo mid-run."""
    kids, temps = _TUMBLING_UNIQ[i % 4]
    return kids, temps, SMOKE_BASE + i * 200 + _TUMBLING_TS


def smoke_tumbling_config():
    """(executor, feed(i), warm_batches) for the fused-close retrace
    gate — shared by the tier-1 RetraceGuard tests."""
    from hstream_tpu.engine import (
        AggKind, AggSpec, AggregateNode, ColumnType, QueryExecutor,
        Schema, SourceNode, TumblingWindow,
    )
    from hstream_tpu.engine.expr import Col

    schema = Schema.of(device=ColumnType.STRING, temp=ColumnType.FLOAT)
    node = AggregateNode(
        child=SourceNode("s", schema), group_keys=[Col("device")],
        window=TumblingWindow(1_000, grace_ms=0),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c"),
              AggSpec(AggKind.SUM, "t", input=Col("temp"))])
    ex = QueryExecutor(node, schema, emit_changes=False,
                       initial_keys=256, batch_capacity=1024)
    for k in range(100):
        ex.key_id_for((f"d{k}",))

    def feed(i):
        kids, temps, ts = smoke_tumbling_batch(i)
        return ex.process_columnar(kids, ts, {"temp": temps})

    # warmup spans >= 2 close cycles at 1s windows / 200ms batches
    return ex, feed, 15


def smoke_join_config(mesh=None):
    """(executor, feed(b), warm_batches) for the device-join retrace
    gate — shared by the tier-1 RetraceGuard tests. With `mesh`, the
    join runs key-sharded (ISSUE 16) and the feed asserts the sharded
    stores actually activated (no silent degrade)."""
    from hstream_tpu.sql.codegen import make_executor, stream_codegen

    plan = stream_codegen(
        "SELECT l.k, COUNT(*) AS c FROM l INNER JOIN r "
        "WITHIN (INTERVAL 1 SECOND) ON l.k = r.k "
        "GROUP BY l.k, TUMBLING (INTERVAL 2 SECOND) "
        "GRACE BY INTERVAL 0 SECOND EMIT CHANGES;")
    ex = make_executor(plan, sample_rows=[{"k": "k0", "x": 1.0}],
                       batch_capacity=4096, mesh=mesh)
    rng = np.random.default_rng(1)
    keys = np.array([f"k{i}" for i in range(500)], object)
    n = 256
    xcol = np.ones(n, np.float32)
    kcols = [keys[rng.integers(0, 500, n)] for _ in range(4)]
    ts_template = (np.arange(n, dtype=np.int64) * 200) // n

    def feed(b):
        ex.process_columnar(
            SMOKE_BASE + b * 200 + ts_template,
            {"k": kcols[b % 4], "x": xcol},
            stream="l" if b % 2 else "r")
        if mesh is not None and b == 5:
            assert ex._dev is not None and \
                ex._dev.get("sjl") is not None, \
                f"join did not shard: {ex._device_refusal}"

    # warmup must reach the FIRST real eviction (stores half full at
    # ~32 batches) so the evict kernel's shape compiles before the
    # guarded region, alongside activation, fused-probe and close
    return ex, feed, 40


def smoke_session_config(mesh=None):
    """(executor, feed(b), warm_batches) for the device-session retrace
    gate — shared by the tier-1 RetraceGuard tests. With `mesh`, the
    session arena runs key-sharded (ISSUE 16) and the feed asserts the
    sharded arena actually activated."""
    from hstream_tpu.engine import ColumnType, Schema
    from hstream_tpu.engine.expr import Col
    from hstream_tpu.engine.plan import AggKind, AggregateNode, AggSpec, \
        SourceNode
    from hstream_tpu.engine.session import SessionExecutor
    from hstream_tpu.engine.window import SessionWindow

    schema = Schema.of(user=ColumnType.STRING, lat=ColumnType.FLOAT)
    node = AggregateNode(
        child=SourceNode("s", schema), group_keys=[Col("user")],
        window=SessionWindow(2_000, grace_ms=0),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c"),
              AggSpec(AggKind.APPROX_QUANTILE, "p50", input=Col("lat"),
                      quantile=0.5)])
    kw = {} if mesh is None else {"mesh": mesh}
    ex = SessionExecutor(node, schema, emit_changes=False, **kw)
    ex.defer_close_decode = True
    rng = np.random.default_rng(2)
    n = 512
    users = np.array([f"u{i}" for i in range(64)])
    # cycled pre-generated batches with a FIXED ts template so shapes
    # and segment counts are stable
    kcols = [users[rng.integers(0, 64, n)] for _ in range(4)]
    vcols = [np.abs(rng.normal(50, 20, n)) for _ in range(4)]
    ts_template = (np.arange(n, dtype=np.int64) % 500)
    stride = 10_000  # > 2*gap: prior sessions close every batch

    def feed(b):
        ex.process_columnar(SMOKE_BASE + b * stride + ts_template,
                            {"user": kcols[b % 4], "lat": vcols[b % 4]})
        if b % 8 == 7:
            ex.drain_closed()  # stacked-drain shapes compile in warmup
        if mesh is not None and b == 5:
            assert ex._dev is not None and \
                ex._dev.get("ssl") is not None, \
                f"sessions did not shard: {ex._device_refusal}"

    # warmup spans activation, the first grow, close cycles, and every
    # stacked-drain depth the steady state uses
    return ex, feed, 20
