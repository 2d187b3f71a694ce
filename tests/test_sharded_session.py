"""Sharded session lattice vs single-chip equivalence (ISSUE 16).

The key-sharded session arena (ShardedSessionLattice under shard_map
on the 8-virtual-device CPU mesh) must produce byte-identical rows to
the single-chip session kernels for BOTH device kernel modes (record
and segment), through every stateful edge: out-of-order records, late
drops, code compaction (device remap), arena growth, deferred stacked
close drains, the degrade-to-host view, and snapshot migration across
mesh sizes (1 chip <-> 8-device mesh, re-shard on restore).
"""

import numpy as np
import pytest
from helpers import (
    assert_code_columns_fresh,
    assert_mirror_tracks_the_arena,
    mirror_run,
)

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

BASE = 1_700_000_000_000
SCHEMA = Schema.of(k=ColumnType.STRING, v=ColumnType.FLOAT)
AGGS = [AggSpec(AggKind.COUNT_ALL, "c"),
        AggSpec(AggKind.SUM, "sv", input=Col("v")),
        AggSpec(AggKind.MIN, "mn", input=Col("v")),
        AggSpec(AggKind.MAX, "mx", input=Col("v"))]


@pytest.fixture(scope="module")
def mesh():
    import jax
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 (virtual) devices")
    return Mesh(np.array(devs[:8]).reshape(1, 8), ("data", "key"))


def node_of(gap_ms, grace_ms, aggs=AGGS):
    return AggregateNode(
        child=SourceNode("s", SCHEMA), group_keys=[Col("k")],
        window=SessionWindow(gap_ms, grace_ms=grace_ms), aggs=aggs,
        having=None, post_projections=[])


def to_rows(out):
    if out is None:
        return []
    return out if isinstance(out, list) else out.rows()


def canon(rows, names=("c", "sv", "mn", "mx")):
    return sorted(
        (r["k"], int(r["winStart"]), int(r["winEnd"]))
        + tuple(round(float(r[n]), 4) for n in names)
        for r in rows)


def gen_ooo(seed, n_batches=10, batch=300, keys=40, late_frac=0.15):
    """Out-of-order traffic with a late tail reaching past grace."""
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


@pytest.mark.parametrize("mode", ["record", "segment"])
def test_sharded_sessions_match_single_chip(mesh, mode):
    """Baseline equivalence: out-of-order + late drops, both kernel
    modes, zero device fallbacks on either side."""
    def run(m):
        kw = {} if m is None else {"mesh": m}
        ex = SessionExecutor(node_of(1000, 500), SCHEMA, **kw)
        ex.device_session_mode = mode
        out = []
        for rows, ts in gen_ooo(3):
            out.extend(to_rows(ex.process(rows, ts)))
        out.extend(to_rows(ex.drain_closed()))
        out.extend(to_rows(ex.peek()))
        assert ex.device_fallbacks == 0, ex._device_refusal
        return out, ex

    ref, _ = run(None)
    got, ex = run(mesh)
    assert ex._dev is not None and ex._dev.get("ssl") is not None, \
        ex._device_refusal
    assert ex.sharded_dispatches > 0
    assert canon(got) == canon(ref)


@pytest.mark.parametrize("mode", ["record", "segment"])
@pytest.mark.parametrize("defer", [False, True])
def test_sharded_sessions_compaction_and_deferred(mesh, mode, defer):
    """Rotating key population forces code compaction (device remap
    with the residue-class-preserving LUT) mid-run; with deferral on,
    several close cycles stack before each drain so the deferred
    extract buffers cross a compaction epoch."""
    aggs = AGGS[:2] + [AggSpec(AggKind.MAX, "mx", input=Col("v"))]

    def gen(seed, n_batches=14, batch=250):
        rng = np.random.default_rng(seed)
        batches, t = [], BASE
        for b in range(n_batches):
            ks = rng.integers(b * 37, b * 37 + 90, batch)
            ts = t + rng.integers(0, 3000, batch)
            late = rng.random(batch) < 0.1
            ts = np.where(late, ts - rng.integers(3000, 15_000, batch),
                          ts)
            vs = rng.integers(0, 1000, batch)
            rows = [{"k": f"u{int(k)}", "v": float(v)}
                    for k, v in zip(ks, vs)]
            batches.append((rows, ts.tolist()))
            t += 2000
        return batches

    def run(m):
        kw = {} if m is None else {"mesh": m}
        ex = SessionExecutor(node_of(800, 400, aggs), SCHEMA, **kw)
        ex.device_session_mode = mode
        ex.defer_close_decode = defer
        ex._KEY_CACHE_MAX = 128   # force code compaction mid-run
        out = []
        for i, (rows, ts) in enumerate(gen(11)):
            out.extend(to_rows(ex.process(rows, ts)))
            if defer and i % 5 == 4:
                out.extend(to_rows(ex.drain_closed()))
        out.extend(to_rows(ex.drain_closed()))
        # degrade path: the gathered host view of the (sharded) arena
        # must round-trip into the host reference state
        if ex._dev is not None:
            ex._degrade_to_host("test: host view check")
        out.extend(to_rows(ex.peek()))
        return out, ex

    names = ("c", "sv", "mx")
    ref, _ = run(None)
    got, ex = run(mesh)
    assert ex.session_stats["remap_dispatches"] > 0, "no remap fired"
    assert canon(got, names) == canon(ref, names)


@pytest.mark.parametrize("mode", ["record", "segment"])
def test_sharded_sessions_arena_growth(mesh, mode):
    """A live key population past the initial arena capacity grows
    the per-shard arenas (doubling under put_arena) on both paths."""
    aggs = AGGS[:2]

    def gen(seed, n_batches=8, batch=900, keys=3000):
        rng = np.random.default_rng(seed)
        batches, t = [], BASE
        for _ in range(n_batches):
            ks = rng.integers(0, keys, batch)
            ts = t + rng.integers(0, 1500, batch)
            vs = rng.integers(0, 100, batch)
            rows = [{"k": f"u{int(k)}", "v": float(v)}
                    for k, v in zip(ks, vs)]
            batches.append((rows, ts.tolist()))
            t += 1200
        return batches

    def run(m):
        kw = {} if m is None else {"mesh": m}
        # gap >> span: nothing closes, the arena only accretes
        ex = SessionExecutor(node_of(60_000, 100, aggs), SCHEMA, **kw)
        ex.device_session_mode = mode
        out = []
        for rows, ts in gen(5):
            out.extend(to_rows(ex.process(rows, ts)))
        out.extend(to_rows(ex.peek()))
        assert ex.device_fallbacks == 0, ex._device_refusal
        return out, ex

    names = ("c", "sv")
    ref, exa = run(None)
    got, exb = run(mesh)
    assert exa.session_stats["grows"] > 0, "single-chip never grew"
    assert exb.session_stats["grows"] > 0, "sharded never grew"
    assert canon(got, names) == canon(ref, names)


@pytest.mark.parametrize("mode", ["record", "segment"])
def test_session_mesh_size_migration(mesh, mode):
    """Snapshot on one mesh size, restore on another (1 chip <-> 8):
    the snapshot serializes the gathered host view, the restore
    re-shards (or un-shards) on activation, rows stay identical."""
    from hstream_tpu.engine.snapshot import (
        restore_executor,
        snapshot_executor,
    )

    aggs = AGGS[:2]
    node = node_of(1000, 500, aggs)

    class P:  # restore_executor only reads .node off the plan
        pass

    P.node = node

    def gen(seed=2, n_batches=10, batch=250, keys=30):
        rng = np.random.default_rng(seed)
        out, t = [], BASE
        for _ in range(n_batches):
            ks = rng.integers(0, keys, batch)
            ts = t + rng.integers(0, 3000, batch)
            vs = rng.integers(0, 500, batch)
            rows = [{"k": f"u{int(k)}", "v": float(v)}
                    for k, v in zip(ks, vs)]
            out.append((rows, ts.tolist()))
            t += 2200
        return out

    def run(mesh_a, mesh_b, cut=5):
        kw = {} if mesh_a is None else {"mesh": mesh_a}
        ex = SessionExecutor(node, SCHEMA, **kw)
        ex.device_session_mode = mode
        out, bs = [], gen()
        for rows, ts in bs[:cut]:
            out.extend(to_rows(ex.process(rows, ts)))
        blob = snapshot_executor(ex)
        ex2, _ = restore_executor(P(), blob, mesh=mesh_b)
        ex2.device_session_mode = mode
        for rows, ts in bs[cut:]:
            out.extend(to_rows(ex2.process(rows, ts)))
        out.extend(to_rows(ex2.peek()))
        return canon(out, ("c", "sv")), ex2

    base, _ = run(None, None)
    up, sx = run(None, mesh)
    assert sx._dev is not None and sx._dev.get("ssl") is not None, \
        ("restore onto mesh did not shard", sx._device_refusal)
    down, dx = run(mesh, None)
    assert dx._dev is None or dx._dev.get("ssl") is None
    assert base == up
    assert base == down


@pytest.mark.parametrize("mode", ["record", "segment"])
@pytest.mark.parametrize("defer", [False, True])
def test_sharded_decode_columns_keep_holes_and_live_keys(mesh, mode,
                                                         defer):
    """The residue-preserving compaction leaves holes in `_code_rev`:
    the decode columns it sets with it (ISSUE 30) hold None there and
    the live codes' keys, equal to a from-scratch build after every
    batch; later codes append behind them; closed, deferred and open
    rows are the host engine's."""
    aggs = AGGS[:2]

    def make(m):
        kw = {} if m is None else {"mesh": m}
        ex = SessionExecutor(node_of(500, 0, aggs), SCHEMA, **kw)
        ex.device_session_mode = mode
        return ex

    exs, exh = make(mesh), make(None)
    exh.use_device_sessions = False
    exs.defer_close_decode = defer
    exs._KEY_CACHE_MAX = 96
    rng = np.random.default_rng(17)
    got, want, holes = [], [], 0
    for b in range(12):
        ids = rng.integers(b * 31, b * 31 + 70, 160)
        ts = (BASE + b * 2000 + rng.integers(0, 400, 160)).tolist()
        rows = [{"k": f"u{int(i)}", "v": float(i % 9)} for i in ids]
        got.extend(to_rows(exs.process(rows, ts)))
        want.extend(to_rows(exh.process(rows, ts)))
        assert_code_columns_fresh(exs)
        holes += sum(k is None for k in exs._code_rev)
        if defer and b % 4 == 3:
            got.extend(to_rows(exs.drain_closed()))
    got.extend(to_rows(exs.drain_closed()))
    assert exs._dev is not None and exs._dev.get("ssl") is not None
    assert exs.device_fallbacks == 0
    remaps = exs.session_stats["remap_dispatches"]
    assert remaps >= 2 and holes > 0
    assert exs.session_stats["code_cols_builds"] == 1 + remaps
    names = ("c", "sv")
    assert canon(got, names) == canon(want, names)
    assert canon(to_rows(exs.peek()), names) \
        == canon(to_rows(exh.peek()), names)
    # the degrade path reads the (holed) dictionary itself
    exs._degrade_to_host("test: host view of a holed dictionary")
    assert_code_columns_fresh(exs)
    assert canon(to_rows(exs.peek()), names) \
        == canon(to_rows(exh.peek()), names)


@pytest.mark.parametrize("mode", ["record", "segment"])
def test_sharded_mirror_is_the_arena_after_every_step(mesh, mode):
    """[1x8]: each shard's arena holds its residue class's mirror rows
    in mirror order (`_shard_slots`), batch after batch, through closes,
    a growth and compactions. A key-sharded compaction renumbers within
    each class, so the mirror's global order is unknown until the step
    behind it, which hands the chain merge every row (ISSUE 32):
    `mirror_full_merges` counts those steps and the first batch."""
    exs = SessionExecutor(node_of(500, 0, AGGS[:1]), SCHEMA, mesh=mesh)
    exs.device_session_mode = mode
    exs._KEY_CACHE_MAX = 64
    st = assert_mirror_tracks_the_arena(exs, mirror_run(2600))
    assert exs._dev.get("ssl") is not None
    assert st["grows"] >= 1 and st["close_cycles"] >= 10
    assert st["mirror_full_merges"] == 1 + st["remap_dispatches"] >= 3
