"""Columnar producer path: one RAW record carries a whole column batch
(common/columnar.py); query tasks feed it straight into the lattice
(tasks._run_columnar) — the server-side product fast path."""

import json
import logging
import os
import sys
import time

import grpc
import numpy as np
import pytest

from hstream_tpu.common import colframe, columnar, jsondec
from hstream_tpu.common import records as rec
from hstream_tpu.common.errors import InvalidFrame
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.server.main import serve

from helpers import (columnar_block, decode_outcome, header_cases,
                     wait_attached, whole_header_outcome)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

BASE = 1_700_000_000_000


def test_codec_roundtrip():
    ts = np.arange(10, dtype=np.int64) + BASE
    cols = {"device": [f"d{i % 3}" for i in range(10)],
            "temp": np.arange(10, dtype=np.float32) * 0.5,
            "n": np.arange(10), "ok": np.arange(10) % 2 == 0}
    blob = columnar.encode_columnar(ts, cols)
    assert columnar.is_columnar(blob)
    ts2, dec = columnar.decode_columnar(blob)
    np.testing.assert_array_equal(ts2, ts)
    kind, arr, d = dec["device"]
    assert kind == "str" and [d[i] for i in arr] == cols["device"]
    np.testing.assert_array_equal(dec["temp"][1], cols["temp"])
    np.testing.assert_array_equal(dec["n"][1], cols["n"])
    np.testing.assert_array_equal(dec["ok"][1], cols["ok"])


@pytest.fixture(scope="module")
def server_stub():
    server, ctx = serve("127.0.0.1", 0, "mem://")
    channel = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    stub = HStreamApiStub(channel)
    yield stub, ctx
    channel.close()
    server.stop(grace=1)
    ctx.shutdown()


def _append_columnar(stub, stream, ts, cols):
    req = pb.AppendRequest(stream_name=stream)
    req.records.append(rec.build_columnar_record(ts, cols))
    stub.Append(req)


def _view_rows(stub, view, pred, timeout=30):
    rows = []
    deadline = time.time() + timeout
    while time.time() < deadline:
        resp = stub.ExecuteQuery(pb.CommandQuery(
            stmt_text=f"SELECT * FROM {view};"))
        rows = [rec.struct_to_dict(s) for s in resp.result_set]
        if pred(rows):
            break
        time.sleep(0.2)
    return rows


def test_columnar_append_through_view(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="colsrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW colview AS SELECT device, COUNT(*) AS c, "
                  "SUM(temp) AS s FROM colsrc WHERE temp > 0 "
                  "GROUP BY device, TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-colview")
    n = 1000
    ts = BASE + np.arange(n, dtype=np.int64) % 5000
    ts.sort()
    devs = [f"d{i % 4}" for i in range(n)]
    temps = np.where(np.arange(n) % 10 == 0, -1.0,
                     1.0).astype(np.float32)  # 100 filtered out
    _append_columnar(stub, "colsrc", ts, {"device": devs, "temp": temps})
    _append_columnar(stub, "colsrc", np.array([BASE + 30_000]),
                     {"device": ["zz"], "temp": np.array([1.0], np.float32)})
    rows = _view_rows(
        stub, "colview",
        lambda rs: len([r for r in rs if r.get("winStart") == BASE]) >= 4)
    closed = {r["device"]: r for r in rows if r.get("winStart") == BASE}
    # per device: 250 records, minus the temp<0 ones (i%10==0 hits d0's
    # residue class i%4==0 in i%20==0... compute exactly instead)
    exp = {f"d{k}": sum(1 for i in range(n)
                        if i % 4 == k and i % 10 != 0)
           for k in range(4)}
    got = {d: r["c"] for d, r in closed.items()}
    assert got == exp, (got, exp)
    for k in range(4):
        assert closed[f"d{k}"]["s"] == pytest.approx(exp[f"d{k}"] * 1.0)


def test_columnar_mixed_with_json_records(server_stub):
    """JSON per-record appends and columnar batches interleave on one
    stream; both feed the same aggregation."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="mixsrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW mixview AS SELECT k, COUNT(*) AS c "
                  "FROM mixsrc GROUP BY k, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-mixview")
    req = pb.AppendRequest(stream_name="mixsrc")
    req.records.append(rec.build_record({"k": "a"}, publish_time_ms=BASE))
    stub.Append(req)
    _append_columnar(stub, "mixsrc", np.array([BASE + 1, BASE + 2]),
                     {"k": ["a", "b"]})
    req = pb.AppendRequest(stream_name="mixsrc")
    req.records.append(rec.build_record({"k": "b"},
                                        publish_time_ms=BASE + 3))
    stub.Append(req)
    _append_columnar(stub, "mixsrc", np.array([BASE + 30_000]),
                     {"k": ["zz"]})
    rows = _view_rows(
        stub, "mixview",
        lambda rs: {(r.get("k"), r.get("c")) for r in rs
                    if r.get("winStart") == BASE} >= {("a", 2), ("b", 2)})
    got = {r["k"]: r["c"] for r in rows if r.get("winStart") == BASE}
    assert got.get("a") == 2 and got.get("b") == 2, rows


def test_malformed_columnar_record_is_skipped(server_stub):
    """A forged/corrupt columnar payload must not kill the query task
    (pre-fix: decode raised and the task died CONNECTION_ABORT)."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="badsrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW badview AS SELECT k, COUNT(*) AS c "
                  "FROM badsrc GROUP BY k, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-badview")
    req = pb.AppendRequest(stream_name="badsrc")
    req.records.append(rec.build_record(columnar.MAGIC))  # truncated
    req.records.append(rec.build_record(
        columnar.MAGIC + b"\xff\xff\xff\xff garbage"))
    stub.Append(req)
    _append_columnar(stub, "badsrc", np.array([BASE, BASE + 30_000]),
                     {"k": ["a", "zz"]})
    rows = _view_rows(
        stub, "badview",
        lambda rs: any(r.get("k") == "a" and r.get("c") == 1
                       for r in rs if r.get("winStart") == BASE))
    assert any(r.get("k") == "a" and r.get("c") == 1 for r in rows), rows
    task = ctx.running_queries.get("view-badview")
    assert task is not None and task.is_alive()


def test_columnar_records_reach_connector_sink(server_stub, tmp_path):
    """Connector sinks must consume columnar batches too, not silently
    drop them while advancing the checkpoint."""
    import sqlite3

    stub, ctx = server_stub
    db = tmp_path / "colsink.db"
    conn = sqlite3.connect(db)
    conn.execute("CREATE TABLE t (a INTEGER, b TEXT)")
    conn.commit()
    conn.close()
    stub.CreateStream(pb.Stream(stream_name="colcsrc"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text=f"CREATE SINK CONNECTOR colsc WITH "
                  f"(type = 'sqlite', stream = 'colcsrc', "
                  f"path = '{db}', table = 't');"))
    _append_columnar(stub, "colcsrc", np.array([BASE, BASE + 1]),
                     {"a": np.array([1, 2]), "b": ["x", "y"]})
    deadline = time.time() + 15
    rows = []
    while time.time() < deadline:
        conn = sqlite3.connect(db)
        rows = conn.execute("SELECT a, b FROM t ORDER BY a").fetchall()
        conn.close()
        if len(rows) == 2:
            break
        time.sleep(0.2)
    assert rows == [(1, "x"), (2, "y")]
    stub.DeleteConnector(pb.DeleteConnectorRequest(id="colsc"))


def test_float_group_key_consistent_across_formats(server_stub):
    """A float GROUP BY value must land in ONE group whether it arrived
    as a JSON python float or a columnar f32 (canon_key)."""
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="fkey"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW fkeyv AS SELECT g, COUNT(*) AS c "
                  "FROM fkey GROUP BY g, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-fkeyv")
    req = pb.AppendRequest(stream_name="fkey")
    req.records.append(rec.build_record({"g": 20.1},
                                        publish_time_ms=BASE))
    stub.Append(req)
    _append_columnar(stub, "fkey", np.array([BASE + 1]),
                     {"g": np.array([20.1], np.float32)})
    _append_columnar(stub, "fkey", np.array([BASE + 30_000]),
                     {"g": np.array([0.0], np.float32)})
    rows = _view_rows(
        stub, "fkeyv",
        lambda rs: any(r.get("c") == 2 for r in rs
                       if r.get("winStart") == BASE))
    closed = [r for r in rows if r.get("winStart") == BASE]
    assert len(closed) == 1 and closed[0]["c"] == 2, rows


def test_columnar_numeric_group_key(server_stub):
    stub, ctx = server_stub
    stub.CreateStream(pb.Stream(stream_name="numcol"))
    stub.ExecuteQuery(pb.CommandQuery(
        stmt_text="CREATE VIEW numcolv AS SELECT sensor, COUNT(*) AS c "
                  "FROM numcol GROUP BY sensor, "
                  "TUMBLING (INTERVAL 10 SECOND) "
                  "GRACE BY INTERVAL 0 SECOND;"))
    wait_attached(ctx, "view-numcolv")
    _append_columnar(stub, "numcol", BASE + np.arange(6, dtype=np.int64),
                     {"sensor": np.array([1, 2, 1, 3, 2, 1])})
    _append_columnar(stub, "numcol", np.array([BASE + 30_000]),
                     {"sensor": np.array([9])})
    rows = _view_rows(
        stub, "numcolv",
        lambda rs: len([r for r in rs if r.get("winStart") == BASE]) >= 3)
    got = {r["sensor"]: r["c"] for r in rows if r.get("winStart") == BASE}
    assert got == {1: 3, 2: 2, 3: 1}, rows


# ---- a header's dictionaries: the native scan against the whole-header
# ---- parse, which defines what is right (ISSUE 34) -------------------------

@pytest.fixture
def native():
    if jsondec.load() is None:
        pytest.skip("no toolchain for the native library")


def _scan_recognises(payload: bytes) -> bool:
    """Whether `jd_header_dicts` itself takes the payload's header."""
    off = len(columnar.MAGIC) + 4
    hlen = int(np.frombuffer(payload, np.uint32, 1, off - 4)[0])
    return columnar._dictionary_spans(
        memoryview(payload)[off: off + hlen]) is not None


def _generator_frame(config: str) -> bytes:
    """Frame 3 of a benchmark configuration at its dry sizes, as the
    benchmark's producer encodes it; the block inside the frame."""
    from benchmarks.harness import manifest, producer

    cfg = manifest.load_json("configs", config + ".json")
    size = manifest.size_of(cfg, True)
    _stream, ts, cols, _n = manifest.generator_of(cfg).frame(size, 7, 3)
    return bytes(colframe.open_frame(producer.encode_frame(ts, cols)))


def _shapes() -> dict:
    n = 6
    ts = BASE + np.arange(n, dtype=np.int64)
    names = [f"d{i % 3}" for i in range(n)]
    return {
        "nexmark_bids": lambda: _generator_frame("nexmark_q5"),
        "sensor": lambda: _generator_frame("sensor_hll_100k"),
        "null_masks": lambda: columnar.encode_columnar(
            ts, {"k": names, "v": np.arange(n, dtype=np.float32)},
            nulls={"k": np.arange(n) % 2 == 0, "v": np.arange(n) == 1}),
        "no_rows_no_entries": lambda: columnar.encode_columnar(
            ts[:0], {"k": np.array([], "U1")}),
        "one_entry": lambda: columnar.encode_columnar(
            ts, {"k": ["only"] * n}),
        "three_string_columns": lambda: columnar.encode_columnar(
            ts, {"a": names, "x": np.arange(n), "b": names[::-1],
                 "c": [f"{i} {{[,]}} :" for i in range(n)]}),
        "empty_strings": lambda: columnar.encode_columnar(
            ts, {"k": ["", "a", ""] * 2, "e": [""] * n}),
        "no_string_column": lambda: columnar.encode_columnar(
            ts, {"x": np.arange(n), "ok": np.arange(n) % 2 == 0}),
    }


@pytest.mark.parametrize("shape", list(_shapes()))
def test_the_scan_takes_the_encoders_frames_and_builds_no_string(
        native, shape):
    payload = _shapes()[shape]()
    ts, cols, nulls, took = columnar._decode(payload)
    assert took
    dicts = [d for kind, _arr, d in cols.values() if kind == "str"]
    assert all(isinstance(d, columnar.LazyDictionary) and not d.built
               for d in dicts)
    want = whole_header_outcome(payload)
    assert want[0] == "reads"
    # lengths are known without a string being built
    assert [len(d) for d in dicts] == [
        len(d) for _k, _a, d in want[2].values() if d is not None]
    assert not any(d.built for d in dicts)
    assert decode_outcome(payload)[0] == want
    assert columnar.decode_columnar_nulls(payload)[1].keys() \
        == want[2].keys()


CASES = header_cases()


@pytest.mark.parametrize("name, payload, recognised", CASES,
                         ids=[c[0] for c in CASES])
def test_an_odd_header_reads_or_fails_as_the_whole_parse_says(
        native, name, payload, recognised):
    got, took = decode_outcome(payload)
    assert got == whole_header_outcome(payload)
    assert _scan_recognises(payload) == recognised
    if took is not None:
        assert took == recognised


@pytest.mark.parametrize("name, payload, recognised", CASES,
                         ids=[c[0] for c in CASES])
def test_the_door_refuses_what_the_whole_parse_refuses(
        native, name, payload, recognised):
    """`check_block` is the door: a block the whole-header parse reads
    passes with its rows and last timestamp, every other one is the
    typed refusal, whichever way its header was read."""
    want = whole_header_outcome(payload)
    frame = colframe.encode_frame(payload)
    if want[0] == "reads":
        _view, n, last_ts, took = colframe.check_block(frame)
        assert (n, last_ts) == (len(want[1]), want[1][-1])
        assert took == recognised
        assert colframe.open_block(frame)[1:] == (n, last_ts)
    else:
        with pytest.raises(InvalidFrame, match="bad columnar block"):
            colframe.check_block(frame)


@pytest.mark.parametrize("seed", range(8))
def test_mutated_headers_read_or_fail_as_the_whole_parse_says(native,
                                                              seed):
    """Headers of the client's encoder with a few bytes changed, dropped
    or put in from the bytes JSON is made of: whatever comes of it, both
    ways of reading the header say the same."""
    rng = np.random.default_rng(seed)
    ids = np.array([0, 1], np.int32)
    base = (b'{"n":2,"cols":[["k","str"],["j","str"]],'
            b'"dicts":{"k":["ab","c d"],"j":["e","f","g"]},"nulls":[]}')
    alphabet = b'"\\[]{},: a0\x00\xc3\x7f'
    took_both = 0
    for _ in range(400):
        hdr = bytearray(base)
        for _ in range(int(rng.integers(1, 4))):
            at = int(rng.integers(0, len(hdr)))
            how = int(rng.integers(0, 3))
            byte = alphabet[int(rng.integers(0, len(alphabet)))]
            if how == 0:
                hdr[at] = byte
            elif how == 1:
                del hdr[at]
            else:
                hdr.insert(at, byte)
        payload = columnar_block(bytes(hdr), ids, ids)
        got, took = decode_outcome(payload)
        assert got == whole_header_outcome(payload), bytes(hdr)
        took_both += bool(took)
    assert took_both  # some mutants are still the encoder's form


def test_without_the_library_the_whole_header_is_parsed_and_said_once(
        monkeypatch):
    monkeypatch.setattr(jsondec, "load", lambda: None)
    monkeypatch.setattr(columnar, "_warned", False)
    said = []
    handler = logging.Handler(logging.WARNING)
    handler.emit = said.append
    columnar.log.addHandler(handler)
    payload = columnar.encode_columnar(
        BASE + np.arange(4, dtype=np.int64),
        {"k": ["a", "b", "a", "c"], "v": np.arange(4, dtype=np.float32)},
        nulls={"v": np.arange(4) == 2})
    try:
        first = columnar._decode(payload)
        second = columnar._decode(payload)
        n, last_ts, took = columnar.validate_block(payload)
    finally:
        columnar.log.removeHandler(handler)
    assert [r.levelname for r in said] == ["WARNING"]
    assert "header scan" in said[0].getMessage()
    assert not first[3] and not second[3] and not took
    assert (n, last_ts) == (4, BASE + 3)
    assert first[1]["k"][2] == ["a", "b", "c"]
    assert type(first[1]["k"][2]) is list
    assert first[2]["v"].tolist() == [False, False, True, False]


def test_a_lazy_dictionary_is_a_sequence_that_parses_once(native):
    payload = columnar.encode_columnar(
        BASE + np.arange(3, dtype=np.int64), {"k": ["b", "a", "c"]})
    d = columnar.decode_columnar_nulls(payload)[1]["k"][2]
    assert isinstance(d, columnar.LazyDictionary)
    assert (len(d), bool(d), d.built) == (3, True, False)
    assert d[1] == "b" and d.built
    assert d.strings() is d.strings()
    assert list(d) == ["a", "b", "c"] == list(reversed(list(d)[::-1]))
    assert d == ["a", "b", "c"] and d != ["a", "b"] and "c" in d
    assert d.index("c") == 2 and d[-1] == "c" and d[1:] == ["b", "c"]
    assert np.asarray(d).tolist() == ["a", "b", "c"]
    assert np.asarray(d, object)[np.array([2, 0])].tolist() == ["c", "a"]
    assert json.dumps(list(d)) == '["a", "b", "c"]'


def _consumers() -> dict:
    from hstream_tpu.server import subscriptions, tasks
    from test_key_table import _executor

    def key_ids(ts, cols, nulls):
        ex = _executor()
        ids, named = tasks._dictionary_key_ids(ex, cols, len(ts), nulls)
        return ids.tolist(), named.tolist(), list(ex._key_rev)

    def general_key_ids(ts, cols, nulls):
        ex = _executor()
        # a dictionary larger than the batch takes the general path
        ids = tasks._columnar_key_ids(ex, cols, len(ts), nulls=nulls)
        return ids.tolist(), list(ex._key_rev)

    return {
        "to_rows": lambda ts, cols, nulls: columnar.to_rows(
            ts, cols, nulls, drop_null=True),
        "to_rows_nones": lambda ts, cols, nulls: columnar.to_rows(
            ts, cols, nulls),
        "sample_rows": lambda ts, cols, nulls: tasks._sample_rows(
            ts, cols, nulls),
        "plain_columns": lambda ts, cols, nulls: {
            k: v.tolist() for k, v in tasks._plain_columns(cols).items()},
        "session_columns": lambda ts, cols, nulls: {
            k: v.tolist() for k, v in tasks._session_columns(
                cols, frozenset({"device"})).items()},
        "dictionary_key_ids": key_ids,
        "columnar_key_ids": general_key_ids,
    }


@pytest.mark.parametrize("consumer", list(_consumers()))
def test_every_consumer_reads_a_lazy_dictionary_as_it_read_the_list(
        native, monkeypatch, consumer):
    n = 12
    ts = BASE + np.arange(n, dtype=np.int64)
    payload = columnar.encode_columnar(
        ts, {"device": [f"dev-{i % 5}" for i in range(n)],
             "v": np.arange(n, dtype=np.float32),
             "note": [f"n{i}" for i in range(n)]},
        nulls={"device": np.arange(n) == 4})
    fn = _consumers()[consumer]
    lazy = columnar.decode_columnar_nulls(payload)
    assert isinstance(lazy[1]["device"][2], columnar.LazyDictionary)
    got = fn(*lazy)
    monkeypatch.setattr(columnar, "load_native", lambda: None)
    whole = columnar.decode_columnar_nulls(payload)
    assert type(whole[1]["device"][2]) is list
    assert got == fn(*whole)
    if consumer == "session_columns":
        # the plan names `device` alone: `note` is never built
        assert lazy[1]["device"][2].built
        assert not lazy[1]["note"][2].built


def test_a_subscription_expands_a_lazily_read_record_as_before(
        native, monkeypatch):
    from hstream_tpu.server.subscriptions import _expand_columnar

    ts = BASE + np.arange(3, dtype=np.int64)
    record = rec.build_columnar_record(
        ts, {"k": ["a", "b", "c"], "v": np.arange(3, dtype=np.float32)}
    ).SerializeToString()
    got = _expand_columnar(record)
    monkeypatch.setattr(columnar, "load_native", lambda: None)
    assert got == _expand_columnar(record)
    assert [rec.record_to_dict(rec.parse_record(r))["k"] for r in got] \
        == ["a", "b", "c"]
    assert columnar.payload_rows(
        rec.parse_record(record).payload)[1] == {"k": "b", "v": 1}
