"""Key retirement on the window lattice: a group key whose every window
has closed gives its id back, the next new key takes it, the planes keep
their capacity and nothing recompiles. Held exact as a property: a
seeded churn of integer keys (ids past 2^24) over hopping windows, every
group's count of the UNFILTERED statement against a plain numpy count in
every closed window while ids are reused many times over; on one device
and key-sharded over the 8 virtual devices; through a snapshot taken
between two retirements."""

import json
import time

import numpy as np
import pytest

from hstream_tpu.engine import snapshot
from hstream_tpu.engine.executor import _KEY_FREE, _KEY_PINNED
from hstream_tpu.server.tasks import _columnar_key_ids
from hstream_tpu.sql.codegen import make_executor, stream_codegen

BASE = 1_700_000_000_000
SIZE, ADV = 10_000, 2_000
SQL = ("CREATE VIEW v AS SELECT auction, COUNT(*) AS num FROM bid GROUP BY "
       "auction, HOPPING (INTERVAL 10 SECOND, INTERVAL 2 SECOND) GRACE BY "
       "INTERVAL 0 SECOND;")
PLAN = stream_codegen(SQL).select


def _mesh(shape):
    if shape is None:
        return None
    import jax

    from hstream_tpu.parallel import make_mesh

    assert jax.device_count() >= 8, f"{jax.device_count()} devices"
    return make_mesh(n_data=1, n_key=8)


def _executor(mesh=None, initial_keys=64):
    return make_executor(PLAN, sample_rows=[{"auction": 1}], mesh=mesh,
                         initial_keys=initial_keys, batch_capacity=1024)


def _batch(seed: int, i: int, n: int = 300, fresh: int = 9):
    """Batch `i`: one second of event time, its keys a sliding set of
    integer ids past 2^24 (`fresh` new ones a batch, for ever)."""
    rng = np.random.default_rng([seed, i])
    ts = BASE + i * 1000 + np.sort(rng.integers(0, 1000, n))
    auction = (1 << 25) + 3 + i * fresh + rng.integers(0, 2 * fresh, n)
    return ts.astype(np.int64), auction.astype(np.int64)


def _feed(ex, ts, auction, dated=True):
    cols = {"auction": ("i64", auction, None)}
    kids = _columnar_key_ids(ex, cols, len(ts),
                             ts_hi=int(ts.max()) if dated else None)
    return list(ex.process_columnar(kids, ts, {}))


def _reference(batches, watermark):
    """{(winStart, auction): count} of every window closed at
    `watermark`, by a plain count."""
    out: dict = {}
    for ts, auction in batches:
        latest = ts - ts % ADV
        for back in range(SIZE // ADV):
            for ws, a in zip((latest - back * ADV).tolist(),
                             auction.tolist()):
                if ws + SIZE <= watermark:
                    out[(ws, a)] = out.get((ws, a), 0) + 1
    return out


def _as_counts(rows):
    out = {(r["winStart"], r["auction"]): r["num"] for r in rows}
    assert len(out) == len(rows), "a (window, key) pair emitted twice"
    assert all(r["winEnd"] == r["winStart"] + SIZE for r in rows)
    return out


def _free_rows_are_zero(ex):
    """The host's rule against the device's truth: an id no key holds
    has no count in any slot."""
    count = np.asarray(ex.state["count"])
    if count.ndim == 3:          # [data shards, K, W] on a mesh
        count = count.sum(axis=0)
    free = np.ones(ex.spec.n_keys, np.bool_)
    free[list(ex._key_ids.values())] = False
    assert not count[free].any()
    assert (ex._key_last[free] == _KEY_FREE).all()
    assert sorted(ex._free, reverse=True) == ex._free
    holes = [i for i, k in enumerate(ex._key_rev) if k is None]
    assert sorted(ex._free) == holes


@pytest.mark.parametrize("mesh", [None, "1x8"])
@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_counts_stay_exact_while_ids_are_reused(seed, mesh):
    ex = _executor(_mesh(mesh))
    batches, rows = [], []
    for i in range(60):
        batches.append(_batch(seed, i))
        rows.extend(_feed(ex, *batches[-1]))
        if i % 7 == 0:
            _free_rows_are_zero(ex)
    assert _as_counts(rows) == _reference(batches, ex.watermark_abs)
    st = ex.key_gauges()
    named = len({a for _ts, auction in batches for a in auction.tolist()})
    assert st["key_ids_reused"] > 2 * ex.spec.n_keys, st   # many times over
    assert st["keys_retired"] >= st["key_ids_reused"] > 0
    assert st["key_retirements"] >= 3
    assert st["keys_live"] + st["keys_retired"] == named
    assert st["keys_live"] < st["key_capacity"] == ex.spec.n_keys <= 512
    assert ex.late_drops == 0 and ex.device_fallbacks == 0
    _free_rows_are_zero(ex)


@pytest.mark.parametrize("mesh", [None, "1x8"])
def test_nothing_compiles_after_the_first_retirement(retrace_guard, mesh):
    ex = _executor(_mesh(mesh))
    i = 0
    while ex.key_stats["keys_retired"] == 0 or i < 25:
        _feed(ex, *_batch(5, i))
        i += 1
    ex.block_until_ready()
    capacity = ex.spec.n_keys
    retirements = ex.key_stats["key_retirements"]
    with retrace_guard():
        for j in range(i, i + 40):
            _feed(ex, *_batch(5, j))
        ex.block_until_ready()
    assert ex.spec.n_keys == capacity
    assert ex.key_stats["key_retirements"] > retirements


@pytest.mark.parametrize("mesh", [None, "1x8"])
def test_a_snapshot_after_a_growth_compiles_nothing(retrace_guard, mesh):
    """The table grows in set-up; the snapshot that first meets the new
    capacity may fall anywhere after it. The task builds the device
    copy that pins a capture when it sees the capacity move
    (`QueryTask._build_pin`), so that snapshot compiles nothing."""
    import threading
    import types

    import jax

    from hstream_tpu.server import tasks

    ex = _executor(_mesh(mesh))
    task = types.SimpleNamespace(state_lock=threading.RLock(),
                                 executor=ex, _pin_keys_built=0)
    i = 0
    while ex.key_stats["keys_retired"] == 0:
        _feed(ex, *_batch(7, i))
        tasks.QueryTask._build_pin(task)    # as `_maybe_snapshot` does
        i += 1
    assert ex.spec.n_keys > 64 and task._pin_keys_built == ex.spec.n_keys
    ex.block_until_ready()
    with retrace_guard():
        tasks.QueryTask._build_pin(task)    # the capacity stands: nothing
        _meta, arrays = snapshot.capture_executor(ex, {})
        pinned = [v for v in tasks._pin(arrays).values()
                  if isinstance(v, jax.Array)]
        jax.block_until_ready(pinned)
    assert len(pinned) >= 2


@pytest.mark.parametrize("mesh", [None, "1x8"])
def test_a_snapshot_between_retirements_restores_and_continues(mesh):
    seed = 9
    ex = _executor(_mesh(mesh))
    batches, rows = [], []
    while len(batches) < 30 or not ex._free:   # between two retirements
        batches.append(_batch(seed, len(batches)))
        rows.extend(_feed(ex, *batches[-1]))
    assert ex.key_stats["keys_retired"] > 0 and ex._free
    cut = len(batches)
    blob = snapshot.snapshot_executor(ex)
    back, _extra = snapshot.restore_executor(PLAN, blob,
                                             mesh=_mesh(mesh))
    assert back._key_rev == ex._key_rev          # the same ids, holes too
    assert back._key_ids == ex._key_ids
    assert back._free == ex._free
    held = list(ex._key_ids.values())
    assert (back._key_last[held] == ex._key_last[held]).all()
    assert back._named_hi == ex._named_hi
    assert [c.tolist() for c in back._key_cols] == [
        c.tolist() for c in ex._key_cols]
    before = ex.key_stats["keys_retired"]
    tail_a, tail_b = [], []
    for i in range(cut, cut + 40):
        batches.append(_batch(seed, i))
        tail_a.extend(_feed(ex, *batches[-1]))
        tail_b.extend(_feed(back, *batches[-1]))
    assert tail_a == tail_b                      # row for row, in order
    assert back._key_rev == ex._key_rev
    assert ex.key_stats["keys_retired"] > before
    assert _as_counts(rows + tail_b) == _reference(batches,
                                                   back.watermark_abs)
    _free_rows_are_zero(back)


def test_integer_keys_snapshot_as_arrays_other_keys_as_json():
    ex = _executor()
    for i in range(20):
        _feed(ex, *_batch(8, i))
    meta, arrays = snapshot._unpack(snapshot.snapshot_executor(ex))
    assert meta["key_rev"] is None
    held = arrays["k/held"]
    assert held.dtype == np.bool_ and len(held) == len(ex._key_rev)
    assert held.tolist() == [k is not None for k in ex._key_rev]
    assert arrays["k/c0"].dtype == np.int64
    assert arrays["k/c0"].tolist() == [k[0] for k in ex._key_rev
                                       if k is not None]
    mixed = _executor()
    for key in [(5,), ("five",), (None,), (2.5,)]:
        mixed.key_id_for(key)
    meta, arrays = snapshot._unpack(snapshot.snapshot_executor(mixed))
    assert "k/held" not in arrays and len(meta["key_rev"]) == 4
    back, _extra = snapshot.restore_executor(
        PLAN, snapshot.snapshot_executor(mixed))
    assert back._key_rev == mixed._key_rev


def test_a_snapshot_from_before_ids_had_dates_restores_pinned():
    ex = _executor()
    for i in range(12):
        _feed(ex, *_batch(1, i))
    meta, arrays = snapshot._unpack(snapshot.snapshot_executor(ex))
    arrays.pop("k/last")
    meta["key_rev"] = [snapshot._enc(k) for k in ex._key_rev]  # as then
    arrays.pop("k/held"), arrays.pop("k/c0")
    back, _extra = snapshot.restore_executor(
        PLAN, snapshot._pack(meta, arrays))
    assert back._key_rev == ex._key_rev
    held = list(back._key_ids.values())
    assert (back._key_last[held] == _KEY_PINNED).all()
    assert back._retire_keys() == 0


def test_a_key_set_that_stands_still_retires_nothing_then_churn_is_exact():
    """Keys that every batch names are dated by every batch and never
    die, and their table never grows; once keys churn, through a
    snapshot too, ids are reused and the counts stay exact."""
    ex = _executor()
    batches, rows = [], []

    def still(i):                      # the same 40 keys, for ever
        rng = np.random.default_rng([3, i])
        ts = BASE + i * 1000 + np.sort(rng.integers(0, 1000, 200))
        return ts.astype(np.int64), (1 << 25) + rng.integers(0, 40, 200)

    for i in range(36):
        batches.append(still(i))
        rows.extend(_feed(ex, *batches[-1]))
    assert ex.key_stats["keys_retired"] == 0 and ex.spec.n_keys == 64
    assert ex.key_stats["key_retirements"] == 0   # never asked for room
    back, _extra = snapshot.restore_executor(
        PLAN, snapshot.snapshot_executor(ex))
    assert back._named_hi == ex._named_hi == int(batches[-1][0].max())
    tail = []
    for i in range(36, 100):           # now the keys churn
        batches.append(_batch(12, i))
        rows.extend(_feed(ex, *batches[-1]))
        tail.extend(_feed(back, *batches[-1]))
    assert ex.key_stats["key_ids_reused"] > 100
    assert _as_counts(rows) == _reference(batches, ex.watermark_abs)
    assert tail == rows[len(rows) - len(tail):]
    _free_rows_are_zero(ex)
    _free_rows_are_zero(back)


def test_ids_that_are_never_dated_are_never_retired():
    """A caller that keeps ids across batches (a join's code -> id
    table) never dates them: the table then grows, as it always did."""
    ex = _executor()
    batches, rows = [], []
    for i in range(40):
        batches.append(_batch(2, i))
        rows.extend(_feed(ex, *batches[-1], dated=False))
    assert ex.key_stats["keys_retired"] == 0
    assert len(ex._key_rev) == len(ex._key_ids) > 256
    assert ex.spec.n_keys >= len(ex._key_rev)
    assert _as_counts(rows) == _reference(batches, ex.watermark_abs)


def test_an_id_named_by_the_batch_in_hand_is_not_retired_under_it():
    """The batch that fills the table names an old key among its first
    lookups: the retirement its new keys set off must not hand that id
    to one of them."""
    ex = _executor(initial_keys=16)
    old = np.full(4, (1 << 25) + 1, np.int64)
    _feed(ex, np.full(4, BASE, np.int64), old)
    t = BASE
    while len(ex._key_rev) < 16:        # fill the table, nothing dead
        t += 100
        new = (1 << 26) + len(ex._key_rev) + np.arange(2, dtype=np.int64)
        _feed(ex, np.full(2, t, np.int64), new)
    kid = ex._key_ids[((1 << 25) + 1,)]
    # far later: every window of every key above has closed
    t += 60_000
    _feed(ex, np.full(1, t, np.int64), np.array([7], np.int64))
    t += 60_000
    auction = np.concatenate([old[:1], (1 << 27) + np.arange(20)])
    ts = np.full(len(auction), t, np.int64)
    cols = {"auction": ("i64", auction, None)}
    kids = _columnar_key_ids(ex, cols, len(ts), ts_hi=t)
    assert ex.key_stats["keys_retired"] > 0
    assert kids[0] == kid == ex._key_ids[((1 << 25) + 1,)]
    assert len(set(kids.tolist())) == len(auction)
    assert [ex._key_rev[k][0] for k in kids.tolist()] == auction.tolist()


def test_the_steps_key_width_follows_the_capacity_not_the_batch():
    """With retirement a batch's ids may span the whole table where the
    last one's spanned a corner of it: the wire's key width is taken
    from the capacity, so the step's program is the same for both."""
    from hstream_tpu.engine import transport

    ex = _executor(initial_keys=4096)
    assert ex._transport._bits["__kid"] == transport._bits_for(4095) == 12
    ts = np.arange(8, dtype=np.int64)
    narrow = ex._transport.encode(256, 8, np.arange(8, dtype=np.int32),
                                  ts, {}, ())[0]
    wide = ex._transport.encode(
        256, 8, np.linspace(0, 4095, 8).astype(np.int32), ts, {}, ())[0]
    assert narrow == wide
    ex._grow_keys()
    assert ex._transport._bits["__kid"] == transport._bits_for(8191)


def test_dates_only_move_forward():
    ex = _executor()
    ts, auction = _batch(3, 5)
    _feed(ex, ts, auction)
    hi = ex._named_hi
    early_ts, early = _batch(3, 1)
    cols = {"auction": ("i64", early, None)}
    kids = _columnar_key_ids(ex, cols, len(early_ts),
                             ts_hi=int(early_ts.max()))
    assert ex._named_hi == hi
    assert (ex._key_last[kids] == hi).all()


def test_string_keys_retire_through_the_native_table():
    plan = stream_codegen(
        "CREATE VIEW v AS SELECT k, COUNT(*) AS c FROM s GROUP BY k, "
        "TUMBLING (INTERVAL 2 SECOND) GRACE BY INTERVAL 0 SECOND;").select
    ex = make_executor(plan, sample_rows=[{"k": "a"}], initial_keys=32,
                       batch_capacity=1024)
    got, want = {}, {}
    for i in range(40):
        names = [f"dev-{i * 5 + j}" for j in range(10)]
        codes = np.arange(200, dtype=np.int32) % 10
        ts = BASE + i * 1000 + (np.arange(200, dtype=np.int64) * 5)
        kids = _columnar_key_ids(ex, {"k": ("str", codes, names)}, 200,
                                 ts_hi=int(ts.max()))
        for r in ex.process_columnar(kids, ts, {}):
            got[(r["winStart"], r["k"])] = r["c"]
        for name in names:
            ws = (BASE + i * 1000) - (BASE + i * 1000) % 2000
            want[(ws, name)] = want.get((ws, name), 0) + 20
    closed = {k: v for k, v in want.items()
              if k[0] + 2000 <= ex.watermark_abs}
    assert got == closed
    assert ex.key_stats["key_ids_reused"] > 32
    assert ex.spec.n_keys <= 64
    assert len(ex._key_table) == len(ex._key_ids)


@pytest.mark.parametrize("kind", ["str", "i64"])
def test_a_retired_key_that_returns_is_a_new_key(kind):
    """The key table forgets what the executor retires: a key that comes
    back after its id has passed to another gets an id of its own."""
    plan = stream_codegen(
        "CREATE VIEW v AS SELECT k, COUNT(*) AS c FROM s GROUP BY k, "
        "TUMBLING (INTERVAL 2 SECOND) GRACE BY INTERVAL 0 SECOND;").select
    ex = make_executor(plan, sample_rows=[{"k": "a"}], initial_keys=16,
                       batch_capacity=1024)

    def feed(values, t):
        if kind == "str":
            ent = ("str", np.arange(len(values), dtype=np.int32),
                   [f"k{v}" for v in values])
        else:
            ent = ("i64", np.asarray(values, np.int64) + (1 << 25), None)
        ts = np.full(len(values), t, np.int64)
        kids = _columnar_key_ids(ex, {"k": ent}, len(values), ts_hi=t)
        rows = list(ex.process_columnar(kids, ts, {}))
        return kids.tolist(), rows

    first, _rows = feed([0], BASE)
    t, n = BASE, 1
    while ex.key_stats["key_ids_reused"] == 0:
        t += 3000
        feed(list(range(n, n + 5)), t)
        n += 5
    name = "k0" if kind == "str" else (1 << 25)
    assert (name,) not in ex._key_ids
    assert ex._key_rev[first[0]] != (name,)     # its id went to another
    again, _rows = feed([0, n], t + 3000)
    assert ex._key_rev[again[0]] == (name,) and again[0] != again[1]
    _kids, rows = feed([n + 1], t + 9000)
    assert {(r["k"], r["c"]) for r in rows
            if r["winStart"] == (t + 3000) - (t + 3000) % 2000} == {
        (name, 1), (f"k{n}" if kind == "str" else n + (1 << 25), 1)}


def test_no_retirement_with_emit_changes_or_a_deferred_close():
    plan = stream_codegen(SQL.replace("CREATE VIEW v AS ", "")
                          .replace(";", " EMIT CHANGES;"))
    ex = make_executor(plan, sample_rows=[{"auction": 1}], initial_keys=64,
                       batch_capacity=1024)
    for i in range(30):
        _feed(ex, *_batch(4, i))
    assert ex.key_stats["keys_retired"] == 0 and ex.spec.n_keys >= 256
    ex = _executor()
    ex.defer_close_decode = True
    for i in range(30):
        _feed(ex, *_batch(4, i))
    assert ex._pending_closes and ex.key_stats["keys_retired"] == 0
    ex.drain_closed()
    assert ex._retire_keys() > 0


def test_key_ids_for_is_the_per_key_walk():
    a, b = _executor(), _executor()
    keys = [(5,), (20.1,), (float(np.float32(20.1)),), ("x",), (5,),
            (None,), (7.5,)]
    one = [a.key_id_for(k) for k in keys]
    many = b.key_ids_for(keys).tolist()
    assert one == many == [0, 1, 1, 2, 0, 3, 4]
    assert a._key_rev == b._key_rev and a._key_ids == b._key_ids
    assert (b._key_last[:5] == _KEY_PINNED).all()


# ---- the served path ------------------------------------------------------


def test_served_view_retires_keys_and_says_so():
    """The statement over gRPC with server defaults: keys churn, ids are
    reused, the view holds every closed window exactly, `key_retire`
    lies inside `key_encode`, and `admin stats queries` and /metrics
    carry the counts."""
    import grpc

    from hstream_tpu.client.producer import ColumnarProducer, encode_batch
    from hstream_tpu.common import records as rec
    from hstream_tpu.proto import api_pb2 as pb
    from hstream_tpu.proto.rpc import HStreamApiStub
    from hstream_tpu.server.main import serve
    from hstream_tpu.stats.prometheus import render_metrics

    from helpers import wait_attached

    server, ctx = serve("127.0.0.1", 0, "mem://", trace_sample=1.0)
    ch = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    try:
        stub = HStreamApiStub(ch)
        stub.CreateStream(pb.Stream(stream_name="bid"))
        stub.ExecuteQuery(
            pb.CommandQuery(stmt_text=SQL),
            metadata=(("x-request-id", "key-retire-test"),))
        task = wait_attached(ctx, "view-v")
        producer = ColumnarProducer(ch, "bid")
        batches = []
        for i in range(130):
            ts, auction = _batch(6, i, n=200, fresh=11)
            batches.append((ts, auction))
            producer.append_stream_frames(
                [encode_batch(ts, {"auction": auction})])
        deadline = time.monotonic() + 120
        while ctx.stats.stat_ladder("consumed_events",
                                    "view-v")["total"] < 130 * 200:
            assert time.monotonic() < deadline, "batches never stepped"
            assert task.error is None, task.error
            time.sleep(0.02)
        task._drain_pipe()
        wm = int(batches[-1][0].max())
        out = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text=f"SELECT * FROM v WHERE winEnd <= {wm};"))
        rows = [rec.struct_to_dict(s) for s in out.result_set]
        assert _as_counts(rows) == _reference(batches, wm)
        gauges = task.engine_gauges()
        assert gauges["keys_retired"] > 0 and gauges["key_ids_reused"] > 0
        assert gauges["keys_live"] < gauges["key_capacity"] <= 2048
        admin = stub.SendAdminCommand(pb.AdminCommandRequest(
            command="stats", args=rec.dict_to_struct(
                {"entity": "queries", "interval": "1min"})))
        row = json.loads(admin.result)["view-v"]
        for k in ("keys_live", "key_capacity", "keys_retired",
                  "key_ids_reused", "key_retirements", "close_groups"):
            assert row[k] == gauges[k], k
        task._note_device_fallbacks()
        text = render_metrics(ctx)
        for name in ("keys_retired", "key_ids_reused", "key_retirements"):
            assert f'hstream_{name}_total{{stream="view-v"}} ' \
                f'{gauges[name]}' in text, name
        assert 'hstream_keys_live{query="view-v"}' in text \
            or 'hstream_keys_live{' in text
        spans = ctx.tracing.spans("view-v")
        retire = [s for s in spans if s["stage"] == "key_retire"]
        encode = [s for s in spans if s["stage"] == "key_encode"]
        assert retire, "no key_retire span in the query's ring"
        for s in retire:   # times are rounded to the microsecond
            assert any(e["t0_ms"] - 0.002 <= s["t0_ms"]
                       and s["t0_ms"] + s["dur_ms"]
                       <= e["t0_ms"] + e["dur_ms"] + 0.002
                       for e in encode), "key_retire outside key_encode"
        h = ctx.stats.histograms_snapshot().get(
            ("stage_latency_ms", "key_retire"))
        assert h is not None and h.snapshot()[2] >= len(retire)
    finally:
        ch.close()
        server.stop(grace=1)
        ctx.shutdown()
