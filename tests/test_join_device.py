"""Device-resident interval join: equivalence against the retained
host reference path (_FlatIntervalStore batch probing), dispatch/fetch
contracts (join_stats), epoch rebase, store growth, match-buffer
overflow redo, columnar changelog decode, and the key-sharded mirror.

The host path IS the reference: every scenario runs twice — once with
`use_device_join=False` (host), once on the device path — and the
FINAL change per (key, window) must agree exactly (coalescing/deferred
drains only change emission cadence, never final values)."""

import numpy as np
import pytest

from hstream_tpu.engine.join import JoinExecutor
from hstream_tpu.sql import stream_codegen
from hstream_tpu.sql.codegen import make_executor

BASE = 1_700_000_000_000

SQL = ("SELECT l.k, COUNT(*) AS c, SUM(l.x) AS s FROM l INNER JOIN r "
       "WITHIN (INTERVAL 1 SECOND) ON l.k = r.k "
       "GROUP BY l.k, TUMBLING (INTERVAL 10 SECOND) "
       "GRACE BY INTERVAL 0 SECOND EMIT CHANGES;")


def make_join(sql=SQL, **tune):
    ex = make_executor(stream_codegen(sql),
                       sample_rows=[{"k": "k0", "x": 1.0}])
    assert isinstance(ex, JoinExecutor)
    for k, v in tune.items():
        setattr(ex, k, v)
    return ex


def gen_batches(seed=11, n_batches=12, n=256, n_keys=50, stride=500,
                jitter=500, shuffle=False):
    rng = np.random.default_rng(seed)
    batches = []
    for b in range(n_batches):
        rows = [{"k": f"k{int(i)}", "x": float(v)}
                for i, v in zip(rng.integers(0, n_keys, n),
                                rng.normal(1, 1, n))]
        ts = (BASE + b * stride
              + rng.integers(0, jitter, n).astype(np.int64))
        if shuffle:
            rng.shuffle(ts)
        batches.append((rows, ts.tolist(), "l" if b % 2 else "r"))
    return batches


def run_batches(ex, batches):
    out = []
    for rows, ts, side in batches:
        out.extend(ex.process(rows, ts, stream=side))
    out.extend(ex.flush_changes())
    assert not ex.has_pending_changes()
    return out


def final_changes(rows):
    """Changelog mode: the LAST change per (key, window) is the value."""
    last = {}
    for r in rows:
        last[(r["l.k"], r["winStart"])] = (r["c"], round(r["s"], 3))
    return last


def assert_equivalent(batches, **device_tune):
    host = make_join(use_device_join=False)
    href = final_changes(run_batches(host, batches))
    dev = make_join(**device_tune)
    dref = final_changes(run_batches(dev, batches))
    assert dev._dev is not None, "device path did not activate"
    assert href == dref
    return host, dev


# ---- equivalence -----------------------------------------------------------


def test_device_join_equivalence_basic():
    _, dev = assert_equivalent(gen_batches())
    assert dev.join_stats["probe_batches"] > 0


def test_device_join_out_of_order_arrivals():
    # unsorted timestamps within each batch, including cross-batch
    # overlap: the probe must see identical store states either way
    _, dev = assert_equivalent(gen_batches(seed=7, jitter=1500,
                                           shuffle=True))
    assert dev.join_stats["probe_dispatches"] == \
        dev.join_stats["probe_batches"]


def test_device_join_watermark_eviction():
    # long stream under capacity pressure: retention (within + grace =
    # 1s) far behind the watermark forces two-sided evictions; late
    # records near the cutoff must match exactly what the pruned host
    # stores produce (the probe's retention mask)
    batches = gen_batches(seed=3, n_batches=30, stride=700, jitter=900)
    host = make_join(use_device_join=False)
    href = final_changes(run_batches(host, batches))
    dev = make_join()
    dev.DEVICE_STORE_CAPACITY = 1 << 9
    assert final_changes(run_batches(dev, batches)) == href
    assert dev._dev is not None
    assert dev.join_stats["evict_dispatches"] > 0
    counts = dev.device_store_counts()
    # eviction keeps the stores near the live window, not the stream
    assert counts["l"] + counts["r"] < 30 * 256


def test_device_join_key_growth_and_remap():
    # more distinct keys than the inner executor's initial capacity:
    # the code->kid LUT grows and inner grow_keys reshapes mid-run
    batches = gen_batches(seed=5, n_batches=16, n_keys=3000, n=512)
    host = make_join(use_device_join=False)
    href = final_changes(run_batches(host, batches))
    dev = make_join()
    dref = final_changes(run_batches(dev, batches))
    assert dev._dev is not None
    assert href == dref
    assert dev._inner.spec.n_keys > 1024  # actually grew


def test_device_join_deferred_and_coalesced():
    assert_equivalent(gen_batches(seed=13), match_drain_depth=4,
                      coalesce_rows=2048, defer_change_decode=True,
                      change_drain_depth=3, async_change_drain=True)


def test_device_join_columnar_input():
    batches = gen_batches(seed=17)
    host = make_join(use_device_join=False)
    href = final_changes(run_batches(host, batches))
    dev = make_join()
    out = []
    for rows, ts, side in batches:
        kk = np.asarray([r["k"] for r in rows], object)
        xx = np.asarray([r["x"] for r in rows], np.float64)
        out.extend(dev.process_columnar(
            np.asarray(ts, np.int64), {"k": kk, "x": xx}, stream=side))
    out.extend(dev.flush_changes())
    assert dev._dev is not None
    assert final_changes(out) == href


def test_device_join_columnar_null_keys_dropped():
    # null-masked key cells drop the record, like a row missing the key
    dev = make_join()
    host = make_join(use_device_join=False)
    for ex in (dev, host):
        # activate via a plain matched pair first
        ex.process([{"k": "a", "x": 1.0}], [BASE], stream="r")
        ex.process([{"k": "a", "x": 2.0}], [BASE + 10], stream="l")
    kk = np.asarray(["a", "a", "a"], object)
    xx = np.asarray([5.0, 7.0, 9.0], np.float64)
    nm = np.asarray([False, True, False])
    out_d = list(dev.process_columnar(
        np.asarray([BASE + 20] * 3, np.int64), {"k": kk, "x": xx},
        {"k": nm}, stream="l"))
    out_d.extend(dev.flush_changes())
    rows = [{"k": "a", "x": 5.0}, {"x": 7.0}, {"k": "a", "x": 9.0}]
    out_h = list(host.process(rows, [BASE + 20] * 3, stream="l"))
    out_h.extend(host.flush_changes())
    assert final_changes(out_d) == final_changes(out_h)


# ---- contracts -------------------------------------------------------------


def test_join_stats_one_dispatch_per_batch():
    dev = make_join(match_drain_depth=8)
    run_batches(dev, gen_batches(seed=19, n_batches=16))
    js = dev.join_stats
    assert js["probe_batches"] > 4
    # THE contract: one fused probe+insert dispatch per micro-batch
    assert js["probe_dispatches"] == js["probe_batches"]
    assert js["match_redispatches"] == 0
    # the aggregate fuses into the probe kernel: matches never leave
    # the device, so the per-batch fetch count is ZERO
    assert js["fused_batches"] == js["probe_batches"]
    assert js["probe_fetches"] == 0


def test_join_stats_fetch_path_stacks_buffers():
    # with fusion disabled (stateless-style fallback), deferred drains
    # stack match buffers: strictly fewer fetches than batches
    dev = make_join(match_drain_depth=8)
    batches = gen_batches(seed=43, n_batches=16)
    for rows, ts, side in batches[:3]:
        dev.process(rows, ts, stream=side)
    assert dev._dev is not None
    dev._dev["feed"] = None  # force the match-fetch path
    for rows, ts, side in batches[3:]:
        dev.process(rows, ts, stream=side)
    dev.flush_changes()
    js = dev.join_stats
    assert js["probe_dispatches"] == js["probe_batches"]
    assert 0 < js["probe_fetches"] < js["probe_batches"]


def test_device_join_match_width_self_sizing():
    # one hot key, both sides dense: per-batch match totals exceed the
    # forced-tiny match width, but the host shadow sizes the buffer
    # EXACTLY before every dispatch — no overflow, no redo, exact
    # values
    def hot(n_batches=5, n=120):
        out = []
        for b in range(n_batches):
            rows = [{"k": "hot", "x": 1.0} for _ in range(n)]
            ts = [BASE + b * 200 + i for i in range(n)]
            out.append((rows, ts, "l" if b % 2 else "r"))
        return out

    host = make_join(use_device_join=False)
    href = final_changes(run_batches(host, hot()))
    dev = make_join()
    dev.DEVICE_STORE_CAPACITY = 1 << 10
    batches = hot()
    out = []
    for rows, ts, side in batches[:3]:  # activate the device path
        out.extend(dev.process(rows, ts, stream=side))
    assert dev._dev is not None
    dev._dev["match_cap"] = 64  # shadow must grow it back, exactly
    for rows, ts, side in batches[3:]:
        out.extend(dev.process(rows, ts, stream=side))
    out.extend(dev.flush_changes())
    assert dev.join_stats["match_redispatches"] == 0
    assert dev._dev["match_cap"] >= 120  # self-sized past the force
    assert final_changes(out) == href


def test_probe_kernel_reports_match_overflow():
    # kernel-level overflow contract: a too-narrow match buffer
    # reports the TRUE total in its header, and the probe-only redo at
    # a wider width (same store — the fused kernel never mutates the
    # probed side) recovers every match
    from hstream_tpu.engine import lattice as L

    cap, bcap = 64, 16
    store = L.init_join_store(cap, 0)
    empty = L.init_join_store(cap, 0)
    kern = L.join_probe_insert(cap, bcap, 8, 0, 0)
    batch = np.zeros((4, bcap), np.int32)
    batch[0, :10] = 0
    batch[0, 10:] = L.JOIN_SENT_CODE
    batch[1, :10] = np.arange(10)
    store2, _ = kern(store, empty, batch, np.int32(10), np.int32(5),
                     np.int32(-1000))
    # probe the now-populated store with the same batch: 10 records x
    # ~10 in-window entries >> match_cap 8
    _, pk = kern(empty, store2, batch, np.int32(10), np.int32(100),
                 np.int32(-1000))
    total = int(np.asarray(pk)[0, 0])
    assert total == 100 and total > 8
    wide = L.join_probe_only(cap, bcap, 128, 0, 0)
    pk2 = np.asarray(wide(store2, batch, np.int32(10), np.int32(100),
                          np.int32(-1000)))
    t2, kid, jts, mf, of, mc, oc = L.unpack_join_matches(pk2, 0)
    assert t2 == 100 and len(kid) == 100


def test_device_join_store_grow():
    dev = make_join()
    dev.DEVICE_STORE_CAPACITY = 256
    batches = gen_batches(seed=23, n_batches=10, n=512, stride=100)
    host = make_join(use_device_join=False)
    href = final_changes(run_batches(host, batches))
    assert final_changes(run_batches(dev, batches)) == href
    assert dev.join_stats["store_grows"] >= 1
    assert dev._dev["cap"] > 256


def test_device_join_epoch_rebase_boundary():
    """The device ring buffers REBASE on the shared epoch instead of
    aborting like the host flat store's 2^41 span guard: crossing the
    (artificially lowered) relative-time threshold mid-stream must
    dispatch a rebase and keep results exact across the boundary."""
    batches = gen_batches(seed=29, n_batches=60, stride=400,
                          jitter=600)  # spans 24s of stream time
    host = make_join(use_device_join=False)
    href = final_changes(run_batches(host, batches))
    dev = make_join()
    dev.REBASE_REL_MS = 1 << 14  # 16s: at least one rebase mid-run
    assert final_changes(run_batches(dev, batches)) == href
    assert dev._dev is not None
    assert dev.join_stats["rebase_dispatches"] >= 1
    # t0 moved forward past the original anchor
    assert dev._dev["t0"] > int(batches[0][1][0]) - dev.retention_ms


def test_device_join_rebase_down_for_late_batch():
    # a batch older than the join epoch rebases t0 DOWN (negative
    # delta) instead of corrupting relative time
    dev = make_join()
    host = make_join(use_device_join=False)
    warm = gen_batches(seed=31, n_batches=4)
    out_d = list(run_batches(dev, warm))
    out_h = list(run_batches(host, warm))
    assert dev._dev is not None
    t0_before = dev._dev["t0"]
    late_rows = [{"k": "k1", "x": 4.0}]
    late_ts = [t0_before - 5000]
    out_d.extend(dev.process(late_rows, late_ts, stream="l"))
    out_d.extend(dev.flush_changes())
    out_h.extend(host.process(late_rows, late_ts, stream="l"))
    out_h.extend(host.flush_changes())
    assert dev._dev["t0"] < t0_before
    assert final_changes(out_d) == final_changes(out_h)


def test_device_join_snapshot_roundtrip():
    from hstream_tpu.engine.snapshot import (restore_executor,
                                             snapshot_executor)

    batches = gen_batches(seed=37, n_batches=12)
    host = make_join(use_device_join=False)
    href = final_changes(run_batches(host, batches))

    plan = stream_codegen(SQL)
    dev = make_executor(plan, sample_rows=[{"k": "k0", "x": 1.0}])
    out = []
    for rows, ts, side in batches[:6]:
        out.extend(dev.process(rows, ts, stream=side))
    out.extend(dev.flush_changes())
    assert dev._dev is not None  # snapshot taken in DEVICE mode
    blob = snapshot_executor(dev)
    resumed, _ = restore_executor(plan, blob)
    for rows, ts, side in batches[6:]:
        out.extend(resumed.process(rows, ts, stream=side))
    out.extend(resumed.flush_changes())
    assert resumed._dev is not None  # device path re-activated
    assert final_changes(out) == href


def test_host_store_view_matches_reference_store():
    batches = gen_batches(seed=41, n_batches=6)
    host = make_join(use_device_join=False)
    run_batches(host, batches)
    dev = make_join()
    run_batches(dev, batches)
    hv = dev._host_store_view()
    for side in ("l", "r"):
        ref, got = host._stores[side], hv[side]
        assert len(ref) == len(got)
        ref_keys = {k: tss for k, (tss, _r) in ref.by_key.items()}
        got_keys = {k: tss for k, (tss, _r) in got.by_key.items()}
        assert ref_keys == got_keys


# ---- columnar changelog decode ---------------------------------------------


def _changelog_executor():
    from hstream_tpu.engine import (AggKind, AggSpec, AggregateNode,
                                    ColumnType, QueryExecutor, Schema,
                                    SourceNode, TumblingWindow)
    from hstream_tpu.engine.expr import BinOp, Col, Lit

    schema = Schema.of(device=ColumnType.STRING,
                       temp=ColumnType.FLOAT)
    node = AggregateNode(
        child=SourceNode("s", schema), group_keys=[Col("device")],
        window=TumblingWindow(10_000, grace_ms=0),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c"),
              AggSpec(AggKind.SUM, "s", input=Col("temp")),
              AggSpec(AggKind.TOPK, "t2", input=Col("temp"), k=2)],
        having=BinOp(">", Col("c"), Lit(1)),
        post_projections=[("device", Col("device")),
                          ("c", Col("c")),
                          ("s2", BinOp("*", Col("s"), Lit(2)))])
    ex = QueryExecutor(node, schema, emit_changes=True,
                       initial_keys=256, batch_capacity=4096)
    ex.defer_change_decode = True
    for k in range(100):
        ex.key_id_for((f"d{k}",))
    return ex


def test_columnar_changelog_decode_matches_perrow_reference():
    ex = _changelog_executor()
    rng = np.random.default_rng(2)
    kids = rng.integers(0, 100, 2048).astype(np.int32)
    temps = rng.normal(20, 5, 2048).astype(np.float32)
    ts = BASE + np.arange(2048, dtype=np.int64) % 500
    ex.process_columnar(kids, ts, {"temp": temps})
    epoch, buf = ex._pending_changes[0]
    pk = np.asarray(buf)
    cols = list(ex._decode_changes(pk, epoch))
    rows = ex._decode_changes_rows(pk, epoch)
    assert len(cols) == len(rows) > 0
    for ra, rb in zip(cols, rows):
        assert set(ra) == set(rb)
        for k in rb:
            va, vb = ra[k], rb[k]
            if isinstance(vb, float):
                assert va == pytest.approx(vb)
            elif isinstance(vb, list):
                assert va == pytest.approx(vb)
            else:
                assert va == vb


def test_changelog_drain_stays_columnar():
    from hstream_tpu.common.columnar import ColumnarEmit

    ex = _changelog_executor()
    ex.defer_change_decode = False
    rng = np.random.default_rng(4)
    kids = rng.integers(0, 100, 1024).astype(np.int32)
    temps = rng.normal(20, 5, 1024).astype(np.float32)
    ts = BASE + np.arange(1024, dtype=np.int64) % 500
    out = ex.process_columnar(kids, ts, {"temp": temps})
    # a lone change batch reaches the caller as ONE columnar batch
    assert isinstance(out, ColumnarEmit)
    assert len(out) > 0
    # and its wire encoding round-trips straight from the columns
    payload = out.to_payload(123)
    assert payload is not None


def test_changelog_decode_no_rows_on_empty():
    ex = _changelog_executor()
    pk = np.zeros((3 + 4, 64), np.int32)  # header n = 0
    assert list(ex._decode_changes(pk, BASE)) == []


# ---- eval_host_vec widening ------------------------------------------------


def test_eval_host_vec_string_and_ifnull_ops():
    from hstream_tpu.engine.expr import (BinOp, Col, Lit, UnOp,
                                         eval_host, eval_host_vec)

    cols = {
        "name": np.asarray(["Ada", " bob ", "Eve", None], object),
        "tags": np.asarray([["a", "b"], ["c"], [], ["d", "e"]],
                           object),
        "x": np.asarray([1.5, -2.0, 0.0, 3.0]),
    }
    # reference rows carry plain Python scalars, like decoded records
    rows = [{"name": cols["name"][i], "tags": cols["tags"][i],
             "x": float(cols["x"][i])} for i in range(4)]

    exprs = [
        UnOp("TO_UPPER", BinOp("IFNULL", Col("name"), Lit("?"))),
        UnOp("TRIM", BinOp("IFNULL", Col("name"), Lit(""))),
        UnOp("STRLEN", BinOp("IFNULL", Col("name"), Lit(""))),
        UnOp("ARR_LENGTH", Col("tags")),
        BinOp("ARR_CONTAINS", Col("tags"), Lit("a")),
        BinOp("ARR_JOIN", Col("tags"), Lit("-")),
        UnOp("IS_STR", BinOp("IFNULL", Col("name"), Lit(0))),
        UnOp("SIGN", Col("x")),
    ]
    for e in exprs:
        vec = eval_host_vec(e, cols)
        ref = [eval_host(e, r) for r in rows]
        assert list(np.asarray(vec)) == ref, e


def test_join_projection_stays_columnar():
    """A joined HAVING + string projection decodes through the
    columnar pass (no per-row fallback): the emitted batch is a
    ColumnarEmit."""
    from hstream_tpu.common.columnar import ColumnarEmit

    sql = ("SELECT TO_UPPER(l.k) AS kk, COUNT(*) AS c "
           "FROM l INNER JOIN r WITHIN (INTERVAL 1 SECOND) "
           "ON l.k = r.k GROUP BY l.k, TUMBLING (INTERVAL 10 SECOND) "
           "GRACE BY INTERVAL 0 SECOND EMIT CHANGES;")
    ex = make_join(sql)
    ex.process([{"k": "a"}, {"k": "b"}], [BASE, BASE + 1], stream="r")
    out = ex.process([{"k": "a"}, {"k": "b"}], [BASE + 10, BASE + 11],
                     stream="l")
    out = list(out) + list(ex.flush_changes())
    assert any(r.get("kk") in ("A", "B") for r in out)
    # the inner drain produced a columnar batch at least once
    ex2 = make_join(sql)
    ex2.process([{"k": "a"}], [BASE], stream="r")
    inner_out = ex2.process([{"k": "a"}], [BASE + 5], stream="l")
    assert isinstance(inner_out, (list, ColumnarEmit))


# ---- sharded mirror --------------------------------------------------------


def test_sharded_join_kernels_match_single_chip():
    """Key-sharded probe/insert/evict vs the single-chip kernels: same
    batches, same matches (order within the concat may differ by
    shard, so compare as multisets) and same surviving entries."""
    import jax
    from jax.sharding import Mesh

    from hstream_tpu.engine import lattice as L
    from hstream_tpu.parallel.lattice import ShardedJoinLattice

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = Mesh(np.array(devs[:2]), ("key",))
    cap, bcap, mcap = 64, 16, 64
    sj = ShardedJoinLattice(mesh, "key", cap, bcap, mcap, 1, 1)
    sl = sj.init_store("l")
    sr = sj.init_store("r")
    ref_l = L.init_join_store(cap, 1)
    ref_r = L.init_join_store(cap, 1)
    kern = L.join_probe_insert(cap, bcap, mcap, 1, 1)
    rng = np.random.default_rng(8)
    within = np.int32(100)
    cutoff = np.int32(-(1 << 31))
    ref_matches, sh_matches = [], []
    for b in range(6):
        n = 12
        batch = np.zeros((5, bcap), np.int32)
        codes = np.sort(rng.integers(0, 6, n)).astype(np.int32)
        ts = (b * 50 + np.arange(n)).astype(np.int32)
        order = np.lexsort((ts, codes))
        batch[0, :n] = codes[order]
        batch[0, n:] = L.JOIN_SENT_CODE
        batch[1, :n] = ts[order]
        batch[2, :n] = codes[order] + 100          # kid
        batch[4, :n] = rng.integers(0, 99, n)      # one payload col
        side = "l" if b % 2 else "r"
        if side == "l":
            ref_l, pk = kern(ref_l, ref_r, batch, np.int32(n), within,
                             cutoff)
            sl, spk = sj.probe_insert("l", sl, sr, batch, np.int32(n),
                                      within, cutoff)
        else:
            ref_r, pk = kern(ref_r, ref_l, batch, np.int32(n), within,
                             cutoff)
            sr, spk = sj.probe_insert("r", sr, sl, batch, np.int32(n),
                                      within, cutoff)
        t, kid, jts, mf, of, mc, oc = L.unpack_join_matches(
            np.asarray(pk), 1)
        ref_matches += list(zip(kid.tolist(), jts.tolist(),
                                mc[0].tolist(), oc[0].tolist()))
        st, skid, sjts, smf, sof, smc, soc = sj.unpack_matches(
            np.asarray(spk), side)
        assert st == t
        sh_matches += list(zip(skid.tolist(), sjts.tolist(),
                               smc[0].tolist(), soc[0].tolist()))
    assert sorted(ref_matches) == sorted(sh_matches)
    # two-sided eviction parity
    ev = L.join_evict(cap, 1, 1)
    rl, rr, nref = ev(ref_l, ref_r, np.int32(120), np.int32(0))
    sl2, sr2, nsh = sj.evict(sl, sr, np.int32(120), np.int32(0))
    assert int(np.asarray(nref).sum()) == int(np.asarray(nsh).sum())
    got = np.asarray(sl2["code"])
    ref = np.asarray(rl["code"])
    live_ref = sorted(ref[ref < L.JOIN_SENT_CODE].tolist())
    live_got = sorted(got[got < L.JOIN_SENT_CODE].flatten().tolist())
    assert live_ref == live_got
