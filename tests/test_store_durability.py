"""Native store durability: reopen, torn-tail recovery, corrupt-frame
truncation, meta-WAL compaction replay, and the async append path.

The recovery machinery (nstore.cpp: CRC-validated frames, truncate at
first bad frame on open, meta.wal replay + compaction) is the point of
having a native store — these tests kill/corrupt and reopen it.
Reference: the checkpointed-store durability the LogDevice layer gives
the reference for free (hs_checkpoint.cpp, hs_writer.cpp:29-51).
"""

import os

import pytest

from hstream_tpu.store.api import (
    Compression,
    DataBatch,
    GapRecord,
    LogAttrs,
    LSN_MIN,
)
from hstream_tpu.store.native import NativeLogStore


def read_all(store, logid):
    r = store.new_reader()
    r.set_timeout(0)
    r.start_reading(logid, LSN_MIN)
    out = []
    while True:
        got = r.read(256)
        if not got:
            return out
        out.extend(got)


def payloads_of(items):
    return [p for it in items if isinstance(it, DataBatch)
            for p in it.payloads]


def seg_files(root, logid):
    d = os.path.join(root, "logs", str(logid))
    return sorted(f for f in os.listdir(d) if f.startswith("seg."))


def test_reopen_preserves_everything(tmp_path):
    root = str(tmp_path / "st")
    store = NativeLogStore(root)
    store.create_log(7, LogAttrs(replication_factor=3,
                                 extras={"kind": "stream"}))
    lsns = [store.append_batch(7, [f"r{i}".encode(), b"x"])
            for i in range(10)]
    store.append_batch(7, [b"zlib" * 100], compression=Compression.ZLIB)
    store.meta_put("cfg/a", b"v1")
    store.meta_put("cfg/b", b"v2")
    store.meta_delete("cfg/b")
    tail = store.tail_lsn(7)
    store.close()

    re = NativeLogStore(root)
    assert re.log_exists(7) and re.tail_lsn(7) == tail
    attrs = re.log_attrs(7)
    assert attrs.replication_factor == 3
    assert attrs.extras == {"kind": "stream"}
    got = payloads_of(read_all(re, 7))
    assert got[:2] == [b"r0", b"x"] and got[-1] == b"zlib" * 100
    assert len(got) == 21
    assert re.meta_get("cfg/a") == b"v1"
    assert re.meta_get("cfg/b") is None
    # appends continue with increasing LSNs after reopen
    assert re.append_batch(7, [b"after"]) > tail
    re.close()


def test_torn_tail_truncated_on_open(tmp_path):
    """A crash mid-write leaves a partial frame at the segment tail; open
    must truncate it and keep every complete frame (nstore.cpp torn-tail
    validation)."""
    root = str(tmp_path / "st")
    store = NativeLogStore(root)
    store.create_log(9)
    for i in range(5):
        store.append_batch(9, [f"ok{i}".encode()])
    store.close()

    seg = os.path.join(root, "logs", "9", seg_files(root, 9)[-1])
    with open(seg, "ab") as f:  # torn frame: valid magic, then garbage
        f.write(b"NSBK" + b"\x01\x02\x03")

    re = NativeLogStore(root)
    got = payloads_of(read_all(re, 9))
    assert got == [f"ok{i}".encode() for i in range(5)]
    # the torn bytes are gone; new appends land cleanly and survive
    lsn = re.append_batch(9, [b"new"])
    assert lsn == re.tail_lsn(9)
    re.close()
    re2 = NativeLogStore(root)
    assert payloads_of(read_all(re2, 9))[-1] == b"new"
    re2.close()


def test_corrupt_frame_truncates_to_last_good(tmp_path):
    """Bit-rot inside the LAST frame fails its CRC; open truncates back
    to the previous good frame instead of serving corrupt data."""
    root = str(tmp_path / "st")
    store = NativeLogStore(root)
    store.create_log(11)
    for i in range(4):
        store.append_batch(11, [f"keep{i}".encode()])
    store.append_batch(11, [b"doomed-payload-xxxx"])
    store.close()

    seg = os.path.join(root, "logs", "11", seg_files(root, 11)[-1])
    size = os.path.getsize(seg)
    with open(seg, "r+b") as f:  # flip a byte near the end (payload/CRC)
        f.seek(size - 5)
        b = f.read(1)
        f.seek(size - 5)
        f.write(bytes([b[0] ^ 0xFF]))

    re = NativeLogStore(root)
    got = payloads_of(read_all(re, 11))
    assert got == [f"keep{i}".encode() for i in range(4)]
    re.close()


def test_meta_wal_compaction_replay(tmp_path):
    """Overwrites + deletes force the meta WAL through compaction; the
    replayed state after reopen is exactly the final KV contents."""
    root = str(tmp_path / "st")
    store = NativeLogStore(root)
    big = b"v" * 4096
    # ~16MB of WAL traffic, live set ~2MB: without compaction the WAL
    # ends ~16MB, with it well under the 4MB trigger + one round's worth
    for round_ in range(8):
        for i in range(500):
            store.meta_put(f"k{i}", big)
    for i in range(0, 500, 2):
        store.meta_delete(f"k{i}")
    store.meta_put("last", b"final")
    wal = os.path.getsize(os.path.join(root, "meta.wal"))
    assert wal < (4 << 20) + 3 * (1 << 20), \
        f"compaction never ran (wal={wal})"
    store.close()

    re = NativeLogStore(root)
    assert re.meta_get("last") == b"final"
    assert re.meta_get("k0") is None and re.meta_get("k2") is None
    assert re.meta_get("k1") == big
    assert len(re.meta_list("k")) == 250
    re.close()


def test_meta_value_over_64mib_survives_reopen(tmp_path):
    """An operator-state snapshot at a real key count is one meta
    value of hundreds of MB (an HLL plane alone is 1 KiB per key and
    window slot). The replay used to treat any value over 64 MiB as
    corruption and stop there — losing that snapshot AND every key the
    compacted WAL orders after it."""
    root = str(tmp_path / "st")
    store = NativeLogStore(root)
    big = os.urandom(1 << 20) * 65  # 65 MiB
    store.meta_put("qsnap/q@0", big)
    store.meta_put("streams/after", b"still here")
    store.close()

    re = NativeLogStore(root)
    assert re.meta_get("qsnap/q@0") == big
    assert re.meta_get("streams/after") == b"still here"
    re.close()


def test_async_append_concurrent_first_use(tmp_path):
    """Many threads racing the FIRST append_async must share one
    appender (pre-fix: unlocked lazy init could build two appenders with
    colliding token counters on the one completion queue)."""
    import threading

    store = NativeLogStore(str(tmp_path / "st"))
    store.create_log(21)
    results: list[list[int]] = [[] for _ in range(8)]
    errs: list[BaseException] = []
    start = threading.Barrier(8)

    def work(t):
        try:
            start.wait(5)
            futs = [store.append_async(21, [f"t{t}b{i}".encode()])
                    for i in range(25)]
            results[t] = [f.result(timeout=15) for f in futs]
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=work, args=(t,)) for t in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30)
    assert not errs, errs
    all_lsns = [lsn for r in results for lsn in r]
    assert len(all_lsns) == 200 and len(set(all_lsns)) == 200
    assert store.tail_lsn(21) == max(all_lsns)
    store.close()


def test_trim_survives_reopen(tmp_path):
    root = str(tmp_path / "st")
    store = NativeLogStore(root)
    store.create_log(13)
    lsns = [store.append_batch(13, [f"p{i}".encode()]) for i in range(6)]
    store.trim(13, lsns[2])
    store.close()
    re = NativeLogStore(root)
    assert re.trim_point(13) == lsns[2]
    items = read_all(re, 13)
    assert isinstance(items[0], GapRecord)
    assert payloads_of(items) == [b"p3", b"p4", b"p5"]
    re.close()


def test_async_append_durable_and_ordered(tmp_path):
    """append_async futures resolve to increasing LSNs once durable; a
    reopen sees every completed append (the reference's async writer
    path, hs_writer.cpp:29-51)."""
    root = str(tmp_path / "st")
    store = NativeLogStore(root)
    store.create_log(15)
    futs = [store.append_async(15, [f"a{i}".encode()]) for i in range(50)]
    lsns = [f.result(timeout=10) for f in futs]
    assert lsns == sorted(lsns) and len(set(lsns)) == 50
    assert store.tail_lsn(15) == lsns[-1]
    store.close()
    re = NativeLogStore(root)
    assert payloads_of(read_all(re, 15)) == [f"a{i}".encode()
                                             for i in range(50)]
    re.close()


def test_push_query_uses_async_sink_on_native_store(tmp_path):
    """End-to-end push query on the native store: emitted rows flow
    through the async append sink (stream_sink pending futures) and
    reach the subscriber."""
    import threading
    import time

    import grpc

    from hstream_tpu.common import records as rec
    from hstream_tpu.proto import api_pb2 as pb
    from hstream_tpu.proto.rpc import HStreamApiStub
    from hstream_tpu.server.main import serve

    BASE = 1_700_000_000_000
    server, ctx = serve("127.0.0.1", 0, str(tmp_path / "store"))
    ch = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    stub = HStreamApiStub(ch)
    try:
        stub.CreateStream(pb.Stream(stream_name="asink"))
        got = []
        started = threading.Event()

        def consume():
            call = stub.ExecutePushQuery(pb.CommandPushQuery(
                query_text="SELECT k, COUNT(*) AS c FROM asink "
                           "GROUP BY k, TUMBLING (INTERVAL 10 SECOND) "
                           "GRACE BY INTERVAL 0 SECOND EMIT CHANGES;"))
            started.set()
            try:
                for s in call:
                    got.append(rec.struct_to_dict(s))
            except grpc.RpcError:
                pass

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        started.wait(5)
        from helpers import wait_any_attached
        wait_any_attached(ctx)  # fresh server: no pre-existing tasks
        req = pb.AppendRequest(stream_name="asink")
        for i in range(4):
            req.records.append(rec.build_record(
                {"k": "a" if i % 2 else "b"}, publish_time_ms=BASE + i))
        stub.Append(req)
        deadline = time.time() + 30
        while time.time() < deadline:
            if any(r.get("c") == 2 for r in got):
                break
            time.sleep(0.2)
        assert any(r.get("c") == 2 for r in got), got
        stub.TerminateQueries(pb.TerminateQueriesRequest(all=True))
        t.join(10)
    finally:
        ch.close()
        server.stop(grace=1)
        ctx.shutdown()


def test_async_append_unknown_log_fails_future(tmp_path):
    store = NativeLogStore(str(tmp_path / "st"))
    fut = store.append_async(999, [b"x"])
    with pytest.raises(Exception):
        fut.result(timeout=10)
    store.close()
