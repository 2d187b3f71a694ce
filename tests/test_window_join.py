"""The window join (`JOIN ... WITHIN WINDOW`) on the device path, held
to its host twin (`_FlatIntervalStore`, `use_device_join=False`) and to
a brute-force numpy join on seeded streams: pairs astride a boundary, a
seller named before its Person event, a window with no pair, integer
ids past 2^24, the row path beside the columnar one, an eviction and a
code reclamation at every close with nothing compiled after the warm
windows, a mesh refused by name, inner key ids retired through the
join, the dependency the GROUP BY rests on held by name, and a snapshot
taken and restored mid-stream."""

import numpy as np
import pytest

from hstream_tpu.common.errors import SQLCodegenError
from hstream_tpu.common.tracing import RetraceGuard
from hstream_tpu.engine import lattice
from hstream_tpu.engine.join import JoinExecutor
from hstream_tpu.engine.plan import single_chip_reason
from hstream_tpu.engine.snapshot import restore_executor, snapshot_executor
from hstream_tpu.sql import stream_codegen
from hstream_tpu.sql.codegen import make_executor

BASE = 1_700_000_000_000
W = 10_000
FIRST_ID = 20_000_000          # past 2^24: float32 cannot tell neighbours
SQL = ("CREATE VIEW new_users AS SELECT person.id, person.name, "
       "COUNT(*) AS auctions FROM person INNER JOIN auction WITHIN WINDOW "
       "ON person.id = auction.seller GROUP BY person.id, person.name, "
       "TUMBLING (INTERVAL 10 SECOND) GRACE BY INTERVAL 0 SECOND;")


def make_join(mesh=None, **tune):
    plan = stream_codegen(SQL).select
    ex = make_executor(plan, mesh=mesh, **{
        k: tune.pop(k) for k in ("initial_keys",) if k in tune})
    assert isinstance(ex, JoinExecutor) and ex.window_join
    for k, v in tune.items():
        setattr(ex, k, v)
    return ex


def streams(seed, n_spans=40, span_ms=1_000, persons=50, auctions=150,
            back=3, quiet=()):
    """Seeded batches, persons then auctions a span: ids ascend, a
    seller is one of the persons of the last `back` spans or of the next
    few (an auction may name a person whose event comes later); the
    spans in `quiet` send auctions of sellers no person is (a window
    with no pair, where `quiet` covers one)."""
    rng = np.random.default_rng(seed)
    out = []
    for k in range(n_spans):
        t0 = BASE + k * span_ms
        ids = np.arange(FIRST_ID + k * persons,
                        FIRST_ID + (k + 1) * persons, dtype=np.int64)
        pts = np.sort(t0 + rng.integers(0, span_ms, persons))
        names = np.array([f"n{i % 97}" for i in ids], object)
        lo = FIRST_ID + max(0, k - back) * persons
        sellers = rng.integers(lo, FIRST_ID + (k + 1) * persons + 5,
                               auctions).astype(np.int64)
        if k in quiet:
            sellers = sellers - FIRST_ID  # no person's id
        ats = np.sort(t0 + rng.integers(0, span_ms, auctions))
        out.append(("person", pts, {"id": ids, "name": names}))
        out.append(("auction", ats, {"seller": sellers}))
    return out


def closers(batches):
    end = max(int(ts.max()) for _s, ts, _c in batches) + 2 * W
    one = np.array([end], np.int64)
    return [("person", one, {"id": np.array([1]),
                             "name": np.array(["x"], object)}),
            ("auction", one, {"seller": np.array([2])})]


def brute(batches):
    """{(id, name, winStart): auctions} by a loop a record."""
    people = {}
    for stream, ts, cols in batches:
        if stream == "person":
            for i, t, n in zip(cols["id"].tolist(), ts.tolist(),
                               cols["name"].tolist()):
                people[i] = (t, n)
    want = {}
    for stream, ts, cols in batches:
        if stream == "auction":
            for s, t in zip(cols["seller"].tolist(), ts.tolist()):
                p = people.get(s)
                if p is not None and p[0] // W == t // W:
                    key = (s, p[1], t // W * W)
                    want[key] = want.get(key, 0) + 1
    return want


def rows_of(out):
    return out.to_rows() if hasattr(out, "to_rows") else list(out)


def answers(rows):
    got = {}
    for r in rows:
        if r["person.id"] < FIRST_ID:
            continue  # the closers' own
        key = (int(r["person.id"]), r["person.name"], int(r["winStart"]))
        assert key not in got, f"{key} given twice"
        assert r["winEnd"] == r["winStart"] + W
        got[key] = int(r["auctions"])
    return got


def run(ex, batches, *, rows_path=False):
    out = []
    for stream, ts, cols in batches + closers(batches):
        if rows_path:
            rows = [dict(zip(cols, vals))
                    for vals in zip(*(c.tolist() for c in cols.values()))]
            out += rows_of(ex.process(rows, ts.tolist(), stream=stream))
        else:
            out += rows_of(ex.process_columnar(ts, cols, None,
                                               stream=stream))
    return answers(out)


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_device_path_equals_its_host_twin_and_a_brute_force_join(seed):
    batches = streams(seed, quiet=range(20, 31))
    want = brute(batches)
    assert len(want) > 500
    # the streams hold what the cases name: pairs astride a boundary
    # (an interval join's, not a window join's), sellers named before
    # their Person event, a window with no pair
    people = {int(i): int(t) for s, ts, c in batches if s == "person"
              for i, t in zip(c["id"], ts)}
    pairs = [(people[int(x)], int(t)) for s, ts, c in batches
             if s == "auction" for x, t in zip(c["seller"], ts)
             if int(x) in people]
    assert sum(1 for p, a in pairs if p // W != a // W) > 20
    assert sum(1 for p, a in pairs if a < p and p // W == a // W) > 20
    assert not [k for k in want if k[2] == BASE + 2 * W]  # no pair there
    host = make_join(use_device_join=False)
    assert run(host, batches) == want
    dev = make_join()
    assert run(dev, batches) == want
    assert dev._dev is not None and dev.device_fallbacks == 0
    assert dev.join_stats["fused_batches"] > 60
    assert dev.join_stats["matches"] == host.join_stats["matches"]
    assert dev.join_stats["rows_past_retention"] == 0
    assert dev._inner.late_drops == host._inner.late_drops == 0


def test_pairs_astride_a_boundary_join_nowhere():
    """A person 1 ms before a boundary and its auction 1 ms after it: an
    interval join's pair, no pair here; the same two inside one window
    are one."""
    ids = np.array([FIRST_ID + 1, FIRST_ID + 2], np.int64)
    names = np.array(["a", "b"], object)
    batches = [
        ("person", np.array([BASE + W - 1, BASE + W + 5]),
         {"id": ids, "name": names}),
        ("auction", np.array([BASE + W, BASE + W + 1, BASE + W + 2]),
         {"seller": np.array([FIRST_ID + 1, FIRST_ID + 2,
                              FIRST_ID + 2])}),
    ]
    for device in (False, True):
        # two rounds so that the device path is active for the second
        ex = make_join(use_device_join=device)
        got = run(ex, batches)
        assert got == {(FIRST_ID + 2, "b", BASE + W): 2}, (device, got)


def test_a_seller_named_before_its_person_event_joins():
    batches = [
        ("auction", np.array([BASE + 10, BASE + 20]),
         {"seller": np.array([FIRST_ID + 7, FIRST_ID + 7])}),
        ("person", np.array([BASE + 30]),
         {"id": np.array([FIRST_ID + 7]), "name": np.array(["late"],
                                                           object)}),
        ("auction", np.array([BASE + 40]),
         {"seller": np.array([FIRST_ID + 7])}),
    ]
    for device in (False, True):
        got = run(make_join(use_device_join=device), batches)
        assert got == {(FIRST_ID + 7, "late", BASE): 3}, (device, got)


def test_neighbouring_ids_past_2_24_stay_apart():
    ids = np.array([FIRST_ID + 1, FIRST_ID + 2, FIRST_ID + 3], np.int64)
    assert ids.astype(np.float32).astype(np.int64).tolist() != ids.tolist()
    batches = streams(5, n_spans=4) + [
        ("person", BASE + 4_000 + np.arange(3),
         {"id": ids + 10**6, "name": np.array(["p", "q", "r"], object)}),
        ("auction", BASE + 4_100 + np.arange(6),
         {"seller": np.repeat(ids + 10**6, [1, 2, 3])})]
    want = brute(batches)
    got = run(make_join(), batches)
    assert got == want
    assert [got[(int(i) + 10**6, n, BASE)] for i, n in zip(
        ids, "pqr")] == [1, 2, 3]


def test_the_row_path_equals_the_columnar_path():
    batches = streams(17, n_spans=24)
    want = brute(batches)
    assert run(make_join(), batches, rows_path=True) == want
    assert run(make_join(use_device_join=False), batches,
               rows_path=True) == want


def test_every_close_evicts_and_reclaims_and_nothing_compiles_after():
    """Shapes pinned by the warm windows: one eviction and one code
    reclamation with every window after them, the dictionary and the
    stores bounded by the open window, no program built."""
    batches = streams(23, n_spans=80)   # 8 windows
    want = brute(batches)
    ex = make_join()
    out = []
    feed = batches + closers(batches)
    warm = 2 * 30                        # three windows
    for stream, ts, cols in feed[:warm]:
        out += rows_of(ex.process_columnar(ts, cols, None, stream=stream))
    assert ex._dev is not None
    cap = ex._dev["cap"]
    seen = []
    with RetraceGuard() as guard:
        for i, (stream, ts, cols) in enumerate(feed[warm:]):
            before = dict(ex.join_stats)
            out += rows_of(ex.process_columnar(ts, cols, None,
                                               stream=stream))
            if ex.join_stats["evict_dispatches"] > before[
                    "evict_dispatches"]:
                seen.append((ex.join_stats["codes_reclaimed"]
                             - before["codes_reclaimed"],
                             ex.join_gauges()["codes_live"],
                             len(ex._jcode_rev)))
    assert guard.count == 0, f"{guard.count} programs built"
    assert answers(out) == want
    assert len(seen) == 6                # windows 3..7 and the closers'
    assert all(freed > 400 for freed, _live, _n in seen[:-1]), seen
    # a window holds 500 persons and the sellers of 1 500 auctions
    # (what is live after a close: the rows the sources had already
    # put past the boundary, a span of each)
    assert all(live <= 250 for _f, live, _n in seen), seen
    assert max(n for _f, _l, n in seen) <= 1_300, seen
    assert ex._dev["cap"] == cap and ex.join_stats["store_grows"] <= 2
    counts = ex.device_store_counts()
    assert counts["l"] <= 2 and counts["r"] <= 2  # the closers' own


def test_the_programs_are_named():
    ex = make_join()
    run(ex, streams(29, n_spans=24))
    dev = ex._dev
    step = lattice.join_probe_insert_step.cache_info()
    assert step.currsize >= 1
    kern = lattice.join_evict(dev["cap"], 0, 0, True)
    assert kern.__name__ == "window_join_evict"
    assert lattice.WINDOW_JOIN_EVICT_PROGRAM == "jit_" + kern.__name__
    assert lattice.join_evict(dev["cap"], 0, 0).__name__ == "evict"
    assert lattice.WINDOW_JOIN_STEP_PROGRAM == "jit_window_join_step"


@pytest.mark.parametrize("shape", ["single", "1x8"])
def test_a_mesh_is_refused_by_name_and_the_answer_is_the_same(shape):
    mesh = None
    if shape == "1x8":
        from hstream_tpu.parallel import make_mesh

        mesh = make_mesh(1, 8)
        plan = stream_codegen(SQL).select
        reason = single_chip_reason(plan.node, plan.join)
        assert reason is not None and "single-chip" in reason
    batches = streams(31, n_spans=24)
    ex = make_join(mesh=mesh)
    assert ex.mesh is None
    assert run(ex, batches) == brute(batches)
    assert ex._dev is not None and ex._dev.get("sjl") is None
    assert ex.sharded_dispatches == 0


def test_inner_key_ids_are_retired_through_the_join():
    """Ids reused, a free id's row holds no count, capacity steady over
    8 windows: the join dates the ids it hands out, so the inner
    executor retires them once their window has closed."""
    batches = streams(37, n_spans=90)   # 9 windows of 500 persons
    ex = make_join(initial_keys=1024, _inner_keys_floor=0)
    want = brute(batches)
    out = []
    capacity = []
    for i, (stream, ts, cols) in enumerate(batches + closers(batches)):
        out += rows_of(ex.process_columnar(ts, cols, None, stream=stream))
        if i % 20 == 19 and ex._inner is not None:
            capacity.append(ex._inner.spec.n_keys)
    assert answers(out) == want
    inner = ex._inner
    stats = inner.key_stats
    assert stats["key_ids_reused"] > 2_000 and stats["keys_retired"] > 3_000
    assert len(set(capacity[1:])) == 1, capacity   # steady after window 1
    assert inner.spec.n_keys <= 2048
    inner._retire_keys()                 # every window has closed
    free = np.asarray(sorted(inner._free), np.int64)
    assert len(free) > 400
    count = np.asarray(inner.state["count"])
    assert count.shape[0] == inner.spec.n_keys
    assert not count[free].any(), "a free id's row holds a count"


def test_a_group_column_the_join_key_does_not_determine_is_refused():
    batches = streams(41, n_spans=6)
    ex = make_join()
    for stream, ts, cols in batches:
        ex.process_columnar(ts, cols, None, stream=stream)
    assert ex._fast and ex._fast["det"] == ("l", ["id", "name"])
    twice = ("person", np.array([BASE + 6_000, BASE + 6_001]),
             {"id": np.array([FIRST_ID + 9_000, FIRST_ID + 9_000]),
              "name": np.array(["one", "other"], object)})
    with pytest.raises(SQLCodegenError) as e:
        ex.process_columnar(twice[1], twice[2], None, stream="person")
    assert "'name' is not determined by the join key" in str(e.value)


@pytest.mark.parametrize("device", [True, False])
def test_a_snapshot_mid_stream_restores_to_the_same_answers(device):
    batches = streams(43, n_spans=50)
    want = brute(batches)
    plan = stream_codegen(SQL).select
    ex = make_join(use_device_join=device)
    out = []
    cut = 46                             # inside the third window
    for stream, ts, cols in batches[:cut]:
        out += rows_of(ex.process_columnar(ts, cols, None, stream=stream))
    out += rows_of(ex.flush_changes())
    blob = snapshot_executor(ex)
    ex2, _extra = restore_executor(plan, blob)
    assert isinstance(ex2, JoinExecutor) and ex2.window_join
    assert ex2.watermark == ex.watermark and ex2._src_hi == ex._src_hi
    assert (len(ex2._stores["l"]), len(ex2._stores["r"])) == tuple(
        ex.join_gauges()[k] for k in ("store_rows_left",
                                      "store_rows_right"))
    ex2.use_device_join = device
    for stream, ts, cols in batches[cut:] + closers(batches):
        out += rows_of(ex2.process_columnar(ts, cols, None,
                                            stream=stream))
    assert answers(out) == want
    assert (ex2._dev is not None) == device
