"""The key_encode stage's key table (ISSUE 27): a batch's string
dictionary resolves in one call against a table that persists across
batches, and every key still gets the id the per-value loop gave it.

The loop this replaced is kept here as the plain reference
(`_reference_key_ids`, the single-group-column part of the old
`_columnar_key_ids` word for word); each case runs the same batches
through both on twin executors and compares ids, `_key_rev` order and
key capacity, in the table's two forms (native library / plain dict).
"""

import logging
import time
import types

import grpc
import numpy as np
import pytest

from hstream_tpu.engine import (
    AggKind, AggSpec, AggregateNode, ColumnType, QueryExecutor, Schema,
    SourceNode, TumblingWindow, codec_native, keytable, snapshot)
from hstream_tpu.engine.expr import Col
from hstream_tpu.engine.keytable import KeyTable
from hstream_tpu.server.tasks import _columnar_key_ids

FORMS = ("native", "dict")


@pytest.fixture(params=FORMS)
def form(request, monkeypatch):
    if request.param == "dict":
        monkeypatch.setattr(keytable.codec_native, "load", lambda: None)
    elif codec_native.load() is None:
        pytest.skip("no toolchain for the native library")
    return request.param


def _node(kind=ColumnType.STRING):
    schema = Schema.of(device=kind, v=ColumnType.FLOAT)
    return schema, AggregateNode(
        child=SourceNode("s", schema), group_keys=[Col("device")],
        window=TumblingWindow(10_000, grace_ms=0),
        aggs=[AggSpec(AggKind.COUNT_ALL, "c")])


def _executor(initial_keys=1024, kind=ColumnType.STRING):
    schema, node = _node(kind)
    return QueryExecutor(node, schema, emit_changes=False,
                         initial_keys=initial_keys, batch_capacity=64)


def _reference_key_ids(ex, cols, n, nulls=None):
    """The single-group-column path as it was before the key table."""
    (c,) = ex.group_cols
    kind, arr, d = cols[c]
    if kind == "str" and len(d) <= n:
        vals = list(d)
        codes = arr.astype(np.int64)
    elif kind == "str":
        uniq, inv = np.unique(arr, return_inverse=True)
        vals = [d[int(u)] for u in uniq]
        codes = inv.astype(np.int64)
    else:
        uniq, inv = np.unique(arr, return_inverse=True)
        vals = [int(u) if float(u).is_integer() else float(u)
                for u in uniq]
        codes = inv.astype(np.int64)
    nm = nulls.get(c) if nulls else None
    if nm is not None and nm.any():
        vals = [None] + vals
        codes = np.where(nm, 0, codes + 1)
    memo = getattr(ex, "_kid_vmemo", None)
    if memo is None:
        memo = ex._kid_vmemo = {}
    kid_lut = np.zeros(len(vals), np.int32)
    for p in np.unique(codes).tolist():
        v = vals[p]
        kid = memo.get(v)
        if kid is None:
            kid = ex.key_id_for((v,))
            memo[v] = kid
        kid_lut[p] = kid
    return kid_lut[codes]


def _batch(d, codes, nulls=None):
    codes = np.asarray(codes, np.int32)
    cols = {"device": ("str", codes, list(d))}
    if nulls is not None:
        nulls = {"device": np.asarray(nulls, np.bool_)}
    return cols, len(codes), nulls


def _seeded(seed, keys, n):
    """A batch as `encode_columnar` makes it: a sorted dictionary of the
    distinct keys drawn, dense codes."""
    rng = np.random.default_rng(seed)
    drawn = np.asarray(keys)[rng.integers(0, len(keys), n)]
    d, codes = np.unique(drawn, return_inverse=True)
    return _batch(d.tolist(), codes)


KEYS = [f"dev-{i:05d}" for i in range(400)]
ODD = ["", "é", "日本語", "á", "\ud800", "x" * 300, "dev-1",
       "\U0001f600", " ", "dev-1 "]


def _cases():
    yield "all_misses", [_seeded(1, KEYS, 256)]
    yield "all_hits", [_seeded(1, KEYS, 256), _seeded(1, KEYS, 256)]
    yield "misses_mixed_in", [_seeded(2, KEYS[::3], 256),
                              _seeded(3, KEYS, 256),
                              _seeded(4, KEYS, 256)]
    # "ghost" is in the dictionary and in no row: never registered
    yield "absent_entry", [_batch(["a", "ghost", "z"], [2, 0, 2, 0]),
                           _batch(["a", "ghost", "z"], [0, 2, 2, 0])]
    # the masked cells' placeholder code points at "ghost"
    yield "null_masked", [
        _batch(["a", "ghost", "z"], [2, 1, 0, 1, 2],
               [False, True, False, True, False]),
        _batch(["a", "b"], [0, 1, 1], [False, False, True])]
    yield "all_rows_null", [_batch([], [0, 0, 0], [True, True, True]),
                            _batch(["a"], [0, 0], [False, False])]
    yield "forged_dict_larger_than_batch", [
        _batch(["k%03d" % i for i in range(50)], [7, 3, 7, 49]),
        _batch(["k007", "k003"], [0, 1])]
    yield "non_ascii_and_empty", [_batch(ODD, list(range(len(ODD))) * 2),
                                  _batch(ODD[::-1], [0, 3, 4, 9])]
    # a key holding the separator cannot go through in one piece
    yield "embedded_nul", [_batch(["a", "a\0b", "b"], [1, 0, 2, 1]),
                           _batch(["a", "a\0b", "b"], [0, 1, 2, 1])]
    yield "non_string_entry", [_batch(["a", 7, None], [0, 1, 2, 1]),
                               _batch(["a", 7], [1, 0])]
    yield "one_entry", [_batch(["solo"], [0]), _batch(["solo"], [0, 0])]
    yield "grow_keys_inside_one_batch", [_seeded(5, KEYS, 512)]


CASES = dict(_cases())


@pytest.mark.parametrize("case", list(CASES))
def test_same_ids_as_the_loop(case, form):
    initial = 4 if case == "grow_keys_inside_one_batch" else 1024
    ex, ref = _executor(initial), _executor(initial)
    assert (ex._key_table._h is not None) == (form == "native")
    for cols, n, nulls in CASES[case]:
        got = _columnar_key_ids(ex, cols, n, nulls=nulls)
        want = _reference_key_ids(ref, cols, n, nulls)
        assert got.dtype == np.int32
        assert got.tolist() == want.tolist()
    assert ex._key_rev == ref._key_rev
    assert ex._key_ids == ref._key_ids
    assert ex.spec.n_keys == ref.spec.n_keys
    assert snapshot.snapshot_executor(ex) == snapshot.snapshot_executor(ref)
    if case == "grow_keys_inside_one_batch":
        assert ex.spec.n_keys > initial


def test_absent_string_entry_is_never_registered(form):
    """The bool phantom-key test of test_regressions.py, for strings: an
    unknown dictionary entry no row points at gets no id, a needless
    grow of the key capacity included."""
    ex = _executor(initial_keys=2)
    cols, n, nulls = _batch(["a", "ghost", "z"], [0, 2, 0, 2])
    kids = _columnar_key_ids(ex, cols, n, nulls=nulls)
    assert kids.tolist() == [0, 1, 0, 1]
    assert ex._key_rev == [("a",), ("z",)]
    assert ex.spec.n_keys == 2
    assert len(ex._key_table) == 2


def test_counts_say_what_they_count(form):
    ex = _executor()
    table = ex._key_table
    cols, n, nulls = _seeded(1, KEYS, 256)
    distinct = len(cols["device"][2])
    _columnar_key_ids(ex, cols, n, nulls=nulls)
    assert table.take_counts() == (distinct, distinct)
    _columnar_key_ids(ex, cols, n, nulls=nulls)
    assert table.take_counts() == (distinct, 0)
    # an entry absent from the rows is looked up and is no miss; a
    # null-masked cell is one more lookup, and a miss the first time
    seen = cols["device"][2][0]
    cols, n, nulls = _batch(["ghost", "new", seen], [2, 1, 0],
                            [False, False, True])
    _columnar_key_ids(ex, cols, n, nulls=nulls)
    assert table.take_counts() == (4, 2)
    _columnar_key_ids(ex, cols, n, nulls=nulls)
    assert table.take_counts() == (4, 0)
    assert table.take_counts() == (0, 0)


def test_restored_executor_resolves_to_the_restored_ids(form):
    ex = _executor()
    for seed in (6, 7):
        cols, n, nulls = _seeded(seed, KEYS, 256)
        _columnar_key_ids(ex, cols, n, nulls=nulls)
    blob = snapshot.snapshot_executor(ex)
    _schema, node = _node()
    back, _extra = snapshot.restore_executor(
        types.SimpleNamespace(node=node), blob, batch_capacity=64)
    assert back._key_rev == ex._key_rev
    assert len(back._key_table) == 0
    cols, n, nulls = _seeded(8, KEYS, 256)
    want = _columnar_key_ids(ex, cols, n, nulls=nulls)
    ex._key_table.take_counts()
    got = _columnar_key_ids(back, cols, n, nulls=nulls)
    assert got.tolist() == want.tolist()
    assert back._key_rev == ex._key_rev
    # rebuilt from _key_rev: what was known before the snapshot is no miss
    assert len(back._key_table) == len(back._key_rev)
    _lookups, misses = back._key_table.take_counts()
    assert misses == len(back._key_rev) - len(
        snapshot._unpack(blob)[0]["key_rev"])
    assert snapshot.snapshot_executor(back) == snapshot.snapshot_executor(ex)


def test_keys_registered_on_another_path_rebuild_the_table(form):
    ex = _executor()
    cols, n, nulls = _batch(["a", "b"], [0, 1])
    _columnar_key_ids(ex, cols, n, nulls=nulls)
    assert ex.key_id_for(("row-path",)) == 2   # e.g. a JSON row batch
    assert ex._key_table.covered == 2
    cols, n, nulls = _batch(["b", "c", "row-path"], [0, 1, 2])
    assert _columnar_key_ids(ex, cols, n, nulls=nulls).tolist() == [1, 3, 2]
    assert ex._key_table.covered == len(ex._key_rev) == 4
    assert ex._key_table.take_counts()[1] == 3   # a, b at first; then c


def test_numeric_keys_keep_their_ids_and_the_table_stays_bounded(form):
    """Numeric group values take the per-value path through the same
    table: only canonical values are remembered, so a float the executor
    canonicalises through float32 cannot fill it."""
    ex, ref = _executor(kind=ColumnType.FLOAT), _executor(
        kind=ColumnType.FLOAT)
    rng = np.random.default_rng(9)
    for _ in range(3):
        arr = np.concatenate([rng.integers(0, 40, 200).astype(np.float64),
                              rng.random(56) + 0.1])
        cols = {"device": ("f64", arr, None)}
        got = _columnar_key_ids(ex, cols, len(arr))
        want = _reference_key_ids(ref, cols, len(arr))
        assert got.tolist() == want.tolist()
    assert ex._key_rev == ref._key_rev
    assert len(ex._key_table) <= len(ex._key_rev)
    assert len(ex._key_table) >= 40


def test_fallback_is_said_once_in_the_log(monkeypatch):
    monkeypatch.setattr(keytable.codec_native, "load", lambda: None)
    monkeypatch.setattr(keytable, "_warned", False)
    said = []
    handler = logging.Handler(logging.WARNING)
    handler.emit = said.append
    keytable.log.addHandler(handler)
    try:
        KeyTable()
        KeyTable()
    finally:
        keytable.log.removeHandler(handler)
    assert [r.levelname for r in said] == ["WARNING"]
    assert "key table" in said[0].getMessage()


def test_native_refuses_a_buffer_that_is_not_n_keys():
    lib = codec_native.load()
    if lib is None:
        pytest.skip("no toolchain for the native library")
    table = KeyTable()
    table._insert(["a", "b"], np.array([0, 1], np.int32))
    assert table.resolve(["b", "a", "c", ""]).tolist() == [1, 0, -1, -1]
    assert table.resolve([]).tolist() == []
    assert table.resolve(["a\0b"]) is None
    assert table.resolve(["a", 3]) is None
    out = np.full(2, 7, np.int32)
    ptr = out.ctypes.data_as(codec_native._p_i32)
    assert lib.kt_resolve(table._h, b"a\0b\0c", 5, 2, ptr) == -1
    assert lib.kt_insert(table._h, b"x\0y\0z", 5, 2, ptr) == -1
    assert out.tolist() == [7, 7] and lib.kt_size(table._h) == 2


def test_native_table_at_scale_matches_a_dict():
    """Growth, probing and the blocked resolve at a size past the first
    few doublings: 50 000 keys in, every one found, strangers not."""
    if codec_native.load() is None:
        pytest.skip("no toolchain for the native library")
    keys = [f"k{i * 7919 % 1000003:x}" for i in range(50_000)]
    assert len(set(keys)) == len(keys)
    table = KeyTable()
    for lo in range(0, len(keys), 12_345):
        part = keys[lo:lo + 12_345]
        table._insert(part, np.arange(lo, lo + len(part), dtype=np.int32))
    table._insert(keys[:100], np.full(100, 9, np.int32))  # ids are kept
    assert len(table) == len(keys)
    rng = np.random.default_rng(10)
    pick = rng.permutation(len(keys))[:20_000]
    ask = [keys[i] for i in pick] + ["stranger", "k", ""]
    assert table.resolve(ask).tolist() == pick.tolist() + [-1, -1, -1]


# ---- the served path: the two counters beside consumed_events --------------


def test_served_query_counts_lookups_and_misses():
    from hstream_tpu.client.producer import ColumnarProducer, encode_batch
    from hstream_tpu.proto import api_pb2 as pb
    from hstream_tpu.proto.rpc import HStreamApiStub
    from hstream_tpu.server.main import serve

    from helpers import wait_attached

    base, rows, keys, batches = 1_700_000_000_000, 512, 24, 4

    def frame(i):
        ts = base + i * 100 + np.arange(rows, dtype=np.int64) % 100
        return ts, {"k": np.array([f"dev{j % keys}" for j in range(rows)]),
                    "v": np.ones(rows)}

    server, ctx = serve("127.0.0.1", 0, "mem://")
    ch = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    try:
        stub = HStreamApiStub(ch)
        stub.CreateStream(pb.Stream(stream_name="ktsrc"))
        stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="CREATE VIEW ktview AS SELECT k, COUNT(*) AS c "
                      "FROM ktsrc GROUP BY k, TUMBLING (INTERVAL 10 "
                      "SECOND) GRACE BY INTERVAL 0 SECOND;"))
        task = wait_attached(ctx, "view-ktview")
        producer = ColumnarProducer(ch, "ktsrc")

        def total(family):
            return ctx.stats.stat_ladder(family, "view-ktview")["total"]

        for i in range(batches):
            producer.append_stream_frames([encode_batch(*frame(i))])
            deadline = time.monotonic() + 60
            while total("consumed_events") < (i + 1) * rows:
                assert time.monotonic() < deadline, "batch never stepped"
                time.sleep(0.01)
            assert total("key_lookups") == (i + 1) * keys
            assert total("key_misses") == keys   # stands still once warm
        if task.error is not None:
            raise task.error
        assert len(task.executor._key_rev) == keys
    finally:
        ch.close()
        server.stop(grace=1)
        ctx.shutdown()
