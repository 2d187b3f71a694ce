"""Shared test helpers: readiness waits instead of sleeps.

SURVEY §4 flags the reference's sleep-based test sync ("FIXME: requires
a notification mechanism", RunSQLSpec.hs:54); QueryTask.attached is
that mechanism — set once the reader is attached to every source at its
start LSN (tasks.attached_lsns)."""

from __future__ import annotations

import time

import numpy as np
import pytest


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


# ---- columnar headers, read two ways (ISSUE 34) ----------------------------

HEADER_BASE = 1_700_000_000_000


def columnar_block(header: bytes, *arrays, n: int = 2,
                   tail: bytes = b"") -> bytes:
    """An HSCB1 payload whose header is `header`, byte for byte, over `n`
    timestamps and `arrays` as the column (and mask) bytes."""
    from hstream_tpu.common import columnar

    ts = HEADER_BASE + np.arange(n, dtype=np.int64)
    return (columnar.MAGIC + np.uint32(len(header)).tobytes() + header
            + ts.tobytes()
            + b"".join(np.ascontiguousarray(a).tobytes() for a in arrays)
            + tail)


def header_cases() -> list[tuple[str, bytes, bool]]:
    """Odd and forged headers: (name, payload, whether the native scan
    recognises the header). Most hold one string column `k` of two rows
    with ids (0, 1); what each means is for the whole-header parse to
    say: the decode must agree with it either way."""
    ids = np.array([0, 1], np.int32)
    k = b'"cols":[["k","str"]]'

    def one(entries: bytes) -> bytes:
        return b'{"n":2,' + k + b',"dicts":{"k":' + entries + b'}}'

    plain = one(b'["a","b"]')
    two_cols = b'"cols":[["j","str"],["k","str"]]'
    many = [f"c{i}" for i in range(70)]
    many_header = (
        b'{"n":2,"cols":['
        + b",".join(b'["%s","str"]' % c.encode() for c in many)
        + b'],"dicts":{'
        + b",".join(b'"%s":["a","b"]' % c.encode() for c in many) + b"}}")
    cases = [
        ("plain", columnar_block(plain, ids), True),
        ("dicts_first", columnar_block(
            b'{"dicts":{"k":["a","b"]},"n":2,' + k + b"}", ids), True),
        ("one_entry", columnar_block(one(b'["a"]'), ids * 0), True),
        ("escape_newline", columnar_block(one(b'["a\\n","b"]'), ids),
         False),
        ("escaped_quote", columnar_block(one(b'["a\\"b","c"]'), ids),
         False),
        ("u_pair", columnar_block(one(b'["\\ud83d\\ude00","b"]'), ids),
         False),
        ("lone_surrogate", columnar_block(one(b'["\\ud800","b"]'), ids),
         False),
        ("utf8", columnar_block(one('["é","b"]'.encode()), ids),
         False),
        ("invalid_utf8", columnar_block(one(b'["\xff","b"]'), ids),
         False),
        ("control_byte", columnar_block(one(b'["a\x01","b"]'), ids),
         False),
        ("del_byte", columnar_block(one(b'["a\x7f","b"]'), ids), False),
        ("non_string_entry", columnar_block(one(b'["a",1]'), ids), False),
        ("nested_array", columnar_block(one(b'["a",["b"]]'), ids), False),
        ("space_in_array", columnar_block(one(b'["a", "b"]'), ids),
         False),
        ("default_separators", columnar_block(
            b'{"n": 2, "cols": [["k", "str"]], "dicts": {"k": ["a", "b"]}}',
            ids), False),
        ("repeated_dicts_key", columnar_block(
            b'{"n":2,' + k + b',"dicts":{"k":["x","y"]},'
            b'"dicts":{"k":["a","b"]}}', ids), False),
        ("spelled_dicts_key", columnar_block(
            b'{"n":2,' + k + b',"d\\u0069cts":{"k":["a","b"]}}', ids),
         False),
        ("columns_named_like_header_keys", columnar_block(
            b'{"n":2,"cols":[["dicts","str"],["n","str"]],'
            b'"dicts":{"dicts":["a","b"],"n":["c","d"]}}', ids, ids), True),
        ("brackets_in_a_name", columnar_block(
            b'{"n":2,"cols":[["a]}","str"]],"dicts":{"a]}":["x","y"]}}',
            ids), True),
        ("backslash_in_a_name", columnar_block(
            b'{"n":2,' + k + b',"dicts":{"k\\"x":["q"],"k":["a","b"]}}',
            ids), False),
        ("repeated_name", columnar_block(
            b'{"n":2,' + k + b',"dicts":{"k":["x"],"k":["a","b"]}}', ids),
         True),
        ("truncated_array", columnar_block(
            b'{"n":2,' + k + b',"dicts":{"k":["a","b"', ids), False),
        ("truncated_string", columnar_block(
            b'{"n":2,' + k + b',"dicts":{"k":["a","b', ids), False),
        ("count_below_the_ids", columnar_block(one(b'["a"]'), ids), True),
        ("empty_dictionary", columnar_block(one(b"[]"), ids), True),
        ("trailing_payload_bytes", columnar_block(plain, ids, tail=b"x"),
         True),
        ("payload_cut_short", columnar_block(plain, ids)[:-1], True),
        ("trailing_header_space", columnar_block(plain + b" ", ids),
         False),
        ("trailing_header_junk", columnar_block(plain + b"x", ids),
         False),
        ("missing_dict", columnar_block(
            b'{"n":2,' + k + b',"dicts":{}}', ids), True),
        ("no_dicts_key", columnar_block(b'{"n":2,' + k + b"}", ids),
         True),
        ("a_number_for_a_dictionary", columnar_block(
            b'{"n":2,' + two_cols + b',"dicts":{"j":["a","b"],"k":0}}',
            ids, ids), False),
        ("header_not_an_object", columnar_block(b"[1,2]", ids), False),
        ("empty_header", columnar_block(b"", ids), False),
        ("utf16_header", columnar_block(
            plain.decode().encode("utf-16"), ids), False),
        ("utf8_bom", columnar_block(b"\xef\xbb\xbf" + plain, ids), False),
        ("seventy_string_columns", columnar_block(
            many_header, *([ids] * len(many))), False),
        ("null_mask", columnar_block(
            b'{"n":2,' + k + b',"dicts":{"k":["a","b"]},"nulls":["k"]}',
            ids, np.array([1, 0], np.uint8)), True),
        ("null_mask_of_no_column", columnar_block(
            b'{"n":2,' + k + b',"dicts":{"k":["a","b"]},"nulls":["z"]}',
            ids, np.array([1, 0], np.uint8)), True),
        ("negative_n", columnar_block(
            b'{"n":-1,' + k + b',"dicts":{"k":["a","b"]}}', ids), True),
        ("float_n", columnar_block(
            b'{"n":2.0,' + k + b',"dicts":{"k":["a","b"]}}', ids), True),
        ("nan_n", columnar_block(
            b'{"n":NaN,' + k + b',"dicts":{"k":["a","b"]}}', ids), True),
        ("huge_n", columnar_block(
            b'{"n":1000000000000,' + k + b',"dicts":{"k":["a","b"]}}',
            ids), True),
        ("unknown_kind", columnar_block(
            b'{"n":2,"cols":[["k","u64"]],"dicts":{"k":["a","b"]}}', ids),
         True),
        ("dictionary_of_a_float_column", columnar_block(
            b'{"n":2,"cols":[["v","f32"]],"dicts":{"v":["a"]}}',
            np.array([1.5, 2.5], np.float32)), True),
        ("ids_below_zero", columnar_block(plain, ids - 1), True),
    ]
    return cases


def decode_outcome(payload) -> tuple:
    """What `columnar._decode` makes of `payload`, in a form two runs
    can be compared by: the values read (dictionaries as lists), or the
    error's type and message (a JSON error's position left out: the
    scan parses a shorter text); then whether the header's dictionaries
    were checked natively (None where it raised)."""
    from hstream_tpu.common import columnar

    try:
        ts, cols, nulls, native = columnar._decode(payload)
    except Exception as e:  # noqa: BLE001: the outcome IS the error
        msg = str(e)
        if msg.startswith("bad columnar header JSON"):
            msg = "bad columnar header JSON"
        return ("raises", type(e).__name__, msg), None
    return ("reads", ts.tolist(),
            {name: (kind, arr.tolist(), None if d is None else list(d))
             for name, (kind, arr, d) in cols.items()},
            None if nulls is None else {name: m.tolist()
                                        for name, m in nulls.items()}
            ), native


def whole_header_outcome(payload) -> tuple:
    """`decode_outcome` with the native scan out of the way: the
    whole-header parse, which defines what is right."""
    from hstream_tpu.common import columnar

    patch = pytest.MonkeyPatch()
    patch.setattr(columnar, "load_native", lambda: None)
    try:
        return decode_outcome(payload)[0]
    finally:
        patch.undo()
