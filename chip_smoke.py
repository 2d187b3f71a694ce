"""Chip smoke: the served path, once, on one TPU chip, at a state size a
user would call real — the quickest proof that the system still starts
on the chip.

One process, no network beyond the loopback socket, data from `--seed`.
An in-process `serve()` with server defaults over the native store in a
fresh directory; a gRPC client on the loopback socket drives three
phases:

  served_tumbling  BASELINE config 1/3 (COUNT / SUM / APPROX_COUNT_DISTINCT
                   per device, TUMBLING 10 s) at 100 000 device keys:
                   4 windows x 16 framed appends of 2^19 rows, a reader
                   thread pulling the view beside ingest, every window
                   checked against a numpy reference as it closes.
  session_device   BASELINE config 4 (APPROX_QUANTILE p50/p99 per user,
                   SESSION 5 s) — must run the fused device kernel and
                   equal the host engine on the same input.
  join_device      BASELINE config 5 (l JOIN r WITHIN 1 s, GROUP BY l.k,
                   TUMBLING 10 s) — the same, for the device join.

Exit 0 only if every phase ran on the TPU and every check held. The
last stdout line is the verdict, `{"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}}` with the device as JAX reports it and
no other key; the line before it is the report (versions, sizes,
per-phase results, compile and cache counts), one JSON object too. A
failed run prints neither. `--dry-run` runs the same phases
at toy size on whatever backend is there and is the only way to exit 0
without a TPU. `--mesh 1x4` additionally shards the tumbling view over
four chips. Wall times printed here are set-up facts, not speeds.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import math
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

BASE = 1_700_000_000_000
WINDOW_MS = 10_000
HLL_STDERR = 0.0325  # HLL at p=10: 1.04 / sqrt(1024) (engine/sketches.py)

# sizes: `frames_per_window` is the only knob the time limit may cut —
# never keys or widths — and a cut is listed under "reduced"
FULL = {"keys": 100_000, "windows": 4, "frames_per_window": 16,
        "frame_rows": 1 << 19,
        "session_users": 200, "session_frames": 64,
        "session_rows": 1 << 14,
        "join_keys": 4000, "join_frames": 128, "join_rows": 1 << 13}
DRY = {"keys": 2_000, "windows": 4, "frames_per_window": 1,
       "frame_rows": 4096,
       "session_users": 50, "session_frames": 12, "session_rows": 512,
       "join_keys": 200, "join_frames": 24, "join_rows": 256}
# cuts of FULL from the sizes ISSUE 21 states, each with its reason:
# none was needed (the cold run takes 456 s of the 1200 s allowed)
REDUCED: list[dict] = []

TUMBLING_SQL = (
    "CREATE VIEW per_device AS SELECT device, COUNT(*) AS cnt, "
    "SUM(temp) AS total, APPROX_COUNT_DISTINCT(temp) AS uniq "
    "FROM sensors GROUP BY device, TUMBLING (INTERVAL 10 SECOND) "
    "GRACE BY INTERVAL 0 SECOND;")
SESSION_SELECT = (
    "SELECT user, APPROX_QUANTILE(lat, 0.5) AS p50, "
    "APPROX_QUANTILE(lat, 0.99) AS p99 FROM clicks GROUP BY user, "
    "SESSION (INTERVAL 5 SECOND) GRACE BY INTERVAL 0 SECOND")
JOIN_SELECT = (
    "SELECT l.k, COUNT(*) AS c FROM l INNER JOIN r "
    "WITHIN (INTERVAL 1 SECOND) ON l.k = r.k "
    "GROUP BY l.k, TUMBLING (INTERVAL 10 SECOND) "
    "GRACE BY INTERVAL 0 SECOND")


def say(msg: str) -> None:
    print(f"# {msg}", flush=True)


def check(cond: bool, what: str) -> None:
    """A failed check ends the run: no phase is allowed to continue
    past a wrong answer."""
    if not cond:
        raise SystemExit(f"CHECK FAILED: {what}")


class CompileLedger:
    """Compile accounting: programs built (or loaded from the
    persistent cache — the inventory wraps compile_or_get_cached) and
    their seconds from the server's program inventory, plus the cache's
    own hit / miss events (a miss is a program written to it)."""

    def __init__(self):
        import jax.monitoring

        self.cache_hits = 0
        self.cache_misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.cache_misses += 1

    def snapshot(self) -> dict:
        from hstream_tpu.stats.devicecost import PROGRAMS

        built = PROGRAMS.summary()
        with self._lock:
            return {"compiles": built["total_compiles"],
                    "compile_s": round(built["total_compile_ms"] / 1e3,
                                       3),
                    "cache_hits": self.cache_hits,
                    "cache_misses": self.cache_misses}

    def since(self, before: dict) -> dict:
        now = self.snapshot()
        return {k: round(now[k] - before[k], 3) for k in now}


# ---- client side ------------------------------------------------------------


class Client:
    """The loopback gRPC client every phase drives the server through."""

    def __init__(self, port: int):
        import grpc

        from hstream_tpu.client.retry import RetryPolicy
        from hstream_tpu.proto.rpc import HStreamApiStub

        # a 100k-key window pulls as one response: raise the client's
        # receive cap to the server's send cap
        self.channel = grpc.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_receive_message_length", 64 << 20)])
        self.stub = HStreamApiStub(self.channel)
        # the server sheds appends (RESOURCE_EXHAUSTED + retry-after)
        # while its overload ladder reads REJECT; the client library's
        # policy honors the hint. Each append call carries ONE request
        # message, which the server admits or refuses atomically, so a
        # retry can never duplicate a frame.
        self.retry = RetryPolicy(attempts=120)
        self.acked: dict[str, list[int]] = {}  # stream -> [frames, rows]

    def close(self) -> None:
        self.channel.close()

    def sql(self, text: str) -> list[dict]:
        from hstream_tpu.common import records as rec
        from hstream_tpu.proto import api_pb2 as pb

        resp = self.stub.ExecuteQuery(pb.CommandQuery(stmt_text=text))
        return [rec.struct_to_dict(s) for s in resp.result_set]

    def create_stream(self, name: str) -> None:
        self.sql(f"CREATE STREAM {name};")

    def append(self, stream: str, batches: list[tuple]) -> None:
        """Framed streaming append of (ts, cols) micro-batches, one
        AppendColumnarStream call per request message."""
        from hstream_tpu.client.producer import (
            STREAM_BLOCKS_PER_MSG,
            ColumnarProducer,
            encode_batch,
        )

        producer = ColumnarProducer(self.channel, stream)
        for i in range(0, len(batches), STREAM_BLOCKS_PER_MSG):
            group = batches[i:i + STREAM_BLOCKS_PER_MSG]
            frames = [encode_batch(ts, cols) for ts, cols in group]
            resp = self.retry.call(producer.append_stream_frames, frames)
            rows = sum(len(ts) for ts, _cols in group)
            check(resp.rows == rows and len(resp.record_ids) == len(group),
                  f"append to {stream}: acked {resp.rows} rows / "
                  f"{len(resp.record_ids)} frames, sent {rows} / "
                  f"{len(group)}")
            acked = self.acked.setdefault(stream, [0, 0])
            acked[0] += len(group)
            acked[1] += rows


def wait_for(pred, what: str, timeout: float, poll: float = 0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(poll)
    raise SystemExit(f"TIMEOUT after {timeout:.0f}s waiting for {what}")


def wait_task(ctx, qid: str):
    def attached():
        task = ctx.running_queries.get(qid)
        return task if task is not None and task.attached.is_set() \
            else None

    return wait_for(attached, f"query {qid} to attach", 30)


def wait_consumed(ctx, task, timeout: float) -> None:
    """Every appended batch read by the task AND through its ingest
    pipeline (the idle tick drains the staged tail)."""
    from hstream_tpu.server.health import _source_backlog

    def done():
        with task.state_lock:
            pipe = task._pipe
        return (_source_backlog(ctx, task) == 0
                and (pipe is None or pipe.pending == 0))

    wait_for(done, f"{task.info.query_id} to consume its sources",
             timeout, poll=0.005)
    check(task.error is None, f"{task.info.query_id} died: {task.error}")


def log_batches(ctx, stream: str) -> tuple[int, int]:
    """(store batches, payloads) on a stream's log, read back from the
    store itself — what an acknowledged append must have left there."""
    reader = ctx.store.new_reader()
    reader.start_reading(ctx.streams.get_logid(stream), 0)
    reader.set_timeout(0)
    batches = payloads = 0
    while True:
        got = reader.read(2)
        if not got:
            return batches, payloads
        for b in got:
            if hasattr(b, "payloads"):
                batches += 1
                payloads += len(b.payloads)


def assert_on_device(ctx, task, kind: str, dry_run: bool) -> dict:
    """The device did the work: right executor class, no degradation
    to a host twin, query RUNNING and healthy. A dry run reports
    DEGRADED and does not fail on it: a toy run on a CPU it shares
    cannot hold the health plane to OK (`pipeline_occupancy` counts a
    cold compile on a loaded host as busy)."""
    from hstream_tpu.server.health import evaluate_query
    from hstream_tpu.server.persistence import TaskStatus
    from hstream_tpu.stats.prometheus import render_metrics

    qid = task.info.query_id
    with task.state_lock:
        ex = task.executor
    check(ex is not None, f"{qid}: no executor")
    check(type(ex).__name__ == kind,
          f"{qid}: executor is {type(ex).__name__}, want {kind}")
    fallbacks = task.engine_total("device_fallbacks")
    check(fallbacks == 0, f"{qid}: device_fallbacks == {fallbacks}")
    task._note_device_fallbacks()  # mirror engine counters to /metrics
    for line in render_metrics(ctx).splitlines():
        if line.startswith("hstream_device_path_fallbacks_total"):
            check(float(line.rsplit(" ", 1)[1]) == 0,
                  f"/metrics reports a device fallback: {line}")
    status = ctx.persistence.get_query(qid).status
    check(status == TaskStatus.RUNNING, f"{qid}: status {status}")
    health = evaluate_query(ctx, qid)
    check(health["verdict"] == "OK"
          or (dry_run and health["verdict"] == "DEGRADED"),
          f"{qid}: health {health['verdict']} {health['reasons']}")
    return {"executor": kind, "device_fallbacks": fallbacks,
            "health": health["verdict"],
            "health_reasons": health["reasons"]}


# ---- phase: served_tumbling -------------------------------------------------


def tumbling_frame(seed: int, w: int, f: int, size: dict) -> tuple:
    """Frame f of window w: uniform keys, one-decimal f32 temps (the
    codec-canonical form), event times shuffled inside the window."""
    rng = np.random.default_rng([seed, 1, w, f])
    n = size["frame_rows"]
    kids = rng.integers(0, size["keys"], n).astype(np.int32)
    temps = (np.rint(rng.normal(20.0, 5.0, n) * 10).astype(np.float32)
             * np.float32(0.1))
    ts = BASE + w * WINDOW_MS + rng.integers(0, WINDOW_MS, n)
    return kids, temps, ts.astype(np.int64)


def hll_reference(kids: np.ndarray, temps: np.ndarray, n_keys: int,
                  p: int = 10) -> np.ndarray:
    """Plain numpy HyperLogLog over (key, f32 value) pairs: murmur3
    finalizer of the value's bits, register = top p bits, rank =
    leading zeros of the rest + 1, bias-corrected harmonic mean with the
    linear-counting small-range correction — the sketch
    APPROX_COUNT_DISTINCT documents (engine/sketches.py), written
    independently of it. f64 estimate per key."""
    m = 1 << p
    h = np.where(temps == 0.0, np.float32(0.0), temps).view(np.uint32)
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    h = h ^ (h >> np.uint32(16))
    reg = (h >> np.uint32(32 - p)).astype(np.int64)
    rest = h << np.uint32(p)
    # bit_length via the f64 exponent (exact for 32-bit integers)
    clz = 32 - np.frexp(rest.astype(np.float64))[1]
    rank = np.minimum(clz + 1, 32 - p + 1).astype(np.int8)
    regs = np.zeros(n_keys * m, np.int8)
    np.maximum.at(regs, kids.astype(np.int64) * m + reg, rank)
    regs = regs.reshape(n_keys, m)
    inv = np.exp2(-np.arange(64, dtype=np.float64))
    est = np.empty(n_keys, np.float64)
    alpha = 0.7213 / (1 + 1.079 / m)
    for lo in range(0, n_keys, 8192):
        r = regs[lo:lo + 8192]
        raw = alpha * m * m / inv[r].sum(axis=1)
        zeros = (r == 0).sum(axis=1)
        linear = m * np.log(m / np.maximum(zeros, 1))
        est[lo:lo + 8192] = np.where((raw <= 2.5 * m) & (zeros > 0),
                                     linear, raw)
    return est


def tumbling_reference(frames: list[tuple], n_keys: int) -> dict:
    """Per key over one window's frames: cnt, the f64 total and sum|x|,
    the exact distinct count, and the reference HLL estimate."""
    kids = np.concatenate([k for k, _t, _ts in frames])
    temps = np.concatenate([t for _k, t, _ts in frames])
    t64 = temps.astype(np.float64)
    q = np.rint(t64 * 10).astype(np.int64)
    q -= q.min()
    span = int(q.max()) + 1
    pairs = np.unique(kids.astype(np.int64) * span + q)
    return {
        "cnt": np.bincount(kids, minlength=n_keys),
        "total": np.bincount(kids, weights=t64, minlength=n_keys),
        "abs": np.bincount(kids, weights=np.abs(t64), minlength=n_keys),
        "distinct": np.bincount(pairs // span, minlength=n_keys),
        "hll": hll_reference(kids, temps, n_keys),
    }


def check_window(client: Client, w: int, ref: dict,
                 names: np.ndarray) -> dict:
    """Pull window w from the view and hold it to the reference: cnt
    exact for every key; total within the f32 recursive-summation bound
    cnt * 2^-23 * sum|x| of the f64 sum; uniq against both the
    reference sketch and the exact distinct count."""
    start = BASE + w * WINDOW_MS
    want = int(np.count_nonzero(ref["cnt"]))
    lookup = {name: i for i, name in enumerate(names.tolist())}

    def pulled():
        rows = client.sql(
            f"SELECT * FROM per_device WHERE winStart = {start};")
        return rows if len(rows) == want else None

    rows = wait_for(pulled, f"window {w} to close in the view", 600,
                    poll=0.5)
    idx = np.fromiter((lookup[r["device"]] for r in rows), np.int64,
                      len(rows))
    check(len(set(idx.tolist())) == want, f"window {w}: duplicate keys")
    cnt = np.array([r["cnt"] for r in rows], np.int64)
    total = np.array([r["total"] for r in rows], np.float64)
    uniq = np.array([r["uniq"] for r in rows], np.float64)
    check(all(r["winEnd"] == start + WINDOW_MS for r in rows),
          f"window {w}: wrong winEnd")
    bad = np.flatnonzero(cnt != ref["cnt"][idx])
    check(len(bad) == 0, f"window {w}: cnt differs for {len(bad)} keys, "
          f"first {names[idx[bad[:3]]].tolist()}")
    tol = ref["cnt"][idx] * 2.0 ** -23 * ref["abs"][idx] + 1e-6
    err = np.abs(total - ref["total"][idx])
    check(bool((err <= tol).all()),
          f"window {w}: total off by up to {err.max():.6g} "
          f"(tolerance {tol[np.argmax(err)]:.6g})")
    # uniq IS the sketch: equal to the reference HLL on the same
    # pairs, to the rounding of the last unit (the device estimates in
    # f32) — which also holds the wire codec's decoded f32 values to
    # the producer's bit for bit, since the sketch hashes those bits
    hll = ref["hll"][idx]
    herr = np.abs(uniq - hll)
    htol = 0.5 + 1e-4 * hll
    check(bool((herr <= htol).all()),
          f"window {w}: uniq differs from the reference HLL by up to "
          f"{herr.max():.2f} ({int((herr > htol).sum())} keys)")
    # ... and the sketch is as accurate as p=10 promises: one standard
    # error bounds the RMS relative error against the exact count (a
    # per-key bound would fail by chance alone over 400 000 estimates)
    true_u = ref["distinct"][idx].astype(np.float64)
    rel = (uniq - true_u) / true_u
    rms = float(np.sqrt(np.mean(rel ** 2)))
    check(rms <= HLL_STDERR,
          f"window {w}: uniq RMS relative error {rms:.4f} exceeds "
          f"{HLL_STDERR}")
    return {"window": w, "rows": len(rows), "events": int(cnt.sum()),
            "total_max_abs_err": round(float(err.max()), 6),
            "uniq_max_diff_vs_reference_hll": round(float(herr.max()), 4),
            "uniq_rms_rel_err_vs_exact": round(rms, 5),
            "uniq_max_rel_err_vs_exact": round(float(np.abs(rel).max()),
                                               4)}


def phase_served_tumbling(ctx, client: Client, ledger: CompileLedger,
                          seed: int, size: dict, platform: str,
                          mesh: str | None, dry_run: bool) -> dict:
    from hstream_tpu.common.tracing import RetraceGuard
    from hstream_tpu.stats.devicecost import backend_hbm_bytes

    n_keys, n_win = size["keys"], size["windows"]
    names = np.array([f"dev{k:06d}" for k in range(n_keys)])
    client.create_stream("sensors")
    client.sql(TUMBLING_SQL)
    task = wait_task(ctx, "view-per_device")

    # the second client: pull one key's rows in a loop beside ingest —
    # reader-thread peeks and the 1 s snapshot copy run against a
    # donating step
    stop_reader = threading.Event()
    reads = {"n": 0, "error": None}

    def reader():
        rng = np.random.default_rng([seed, 2])
        while not stop_reader.is_set():
            k = names[int(rng.integers(0, n_keys))]
            try:
                client.sql("SELECT * FROM per_device "
                           f"WHERE device = '{k}';")
            except Exception as e:  # noqa: BLE001 — reported, fatal
                reads["error"] = f"{type(e).__name__}: {e}"
                return
            reads["n"] += 1
            stop_reader.wait(0.2)

    reader_thread = threading.Thread(target=reader, name="smoke-reader")
    reader_thread.start()
    before = ledger.snapshot()
    t0 = time.monotonic()
    windows = []
    last_guard = None
    refs: dict[int, dict] = {}
    try:
        for w in range(n_win + 1):
            if w == n_win:
                # the closer: one record past the last window's end
                batches = [(np.array([BASE + n_win * WINDOW_MS],
                                     np.int64),
                            {"device": names[:1],
                             "temp": np.array([20.0], np.float32)})]
            else:
                frames = [tumbling_frame(seed, w, f, size)
                          for f in range(size["frames_per_window"])]
                refs[w] = tumbling_reference(frames, n_keys)
                batches = [(ts, {"device": names[kids], "temp": temps})
                           for kids, temps, ts in frames]
            last = w == n_win - 1
            t_w = time.monotonic()
            with (RetraceGuard() if last
                  else contextlib.nullcontext()) as guard:
                client.append("sensors", batches)
                if w > 0:
                    # window w-1 closes once the first frame of w
                    # lands; it must be checked before w+1 evicts it
                    # from the view's 100 000-row closed store
                    wait_closed(task, BASE + w * WINDOW_MS)
                    windows.append(check_window(
                        client, w - 1, refs.pop(w - 1), names))
                if last:
                    # the last data window end to end: its appends, its
                    # steps, the close of the window before it
                    wait_consumed(ctx, task, 600)
            if last:
                last_guard = guard.count
            say(("closer" if w == n_win else f"window {w}") + " sent"
                + (f", window {w - 1} exact: {windows[-1]}" if w else "")
                + f" ({time.monotonic() - t_w:.1f}s)")
            check(reads["error"] is None,
                  f"reader thread: {reads['error']}")
    finally:
        stop_reader.set()
        reader_thread.join(60)
    check(not reader_thread.is_alive(), "reader thread did not stop")
    check(reads["error"] is None, f"reader thread: {reads['error']}")
    check(reads["n"] > 0, "reader thread completed no pull")
    wall = time.monotonic() - t0
    compiles = ledger.since(before)

    # every acknowledged frame is in the store
    frames_acked, rows_acked = client.acked["sensors"]
    batches, payloads = log_batches(ctx, "sensors")
    check(payloads == frames_acked,
          f"store holds {payloads} frames, {frames_acked} acked")
    front = ctx.append_front.stats()
    check(front["in_flight"] == 0, f"append front in flight: {front}")

    out = assert_on_device(
        ctx, task,
        "ShardedQueryExecutor" if mesh else "QueryExecutor", dry_run)
    ex = task.executor
    check(bool(ex._fused_close_ok), "fused close degraded")
    check(last_guard == 0,
          f"last window compiled {last_guard} programs")
    if mesh:
        devs = {d for arr in ex.state.values()
                for d in arr.sharding.device_set}
        want = math.prod(int(d) for d in mesh.lower().split("x"))
        check(len(devs) == want,
              f"planes lie on {len(devs)} devices, want {want}")
        out["plane_devices"] = len(devs)

    planes = task.device_plane_bytes()
    plane_total = int(sum(planes.values()))
    backend = backend_hbm_bytes()
    mem = {"plane_bytes": plane_total, "backend_bytes": backend,
           "planes": {k: int(v) for k, v in sorted(planes.items())}}
    if backend is not None:
        mem["backend_over_planes"] = round(backend / plane_total, 3)
    if platform != "cpu":
        check(backend is not None, "backend reports no memory stats")
        if not mesh:  # backend_hbm_bytes reads device 0 only
            check(backend >= plane_total,
                  f"backend {backend} B < planes {plane_total} B")
    out.update({
        "events": rows_acked, "frames": frames_acked,
        "store_batches": batches, "keys": n_keys,
        "key_capacity": int(ex.spec.n_keys),
        "windows": windows, "reader_pulls": reads["n"],
        "append_retries": client.retry.retries,
        "last_window_compiles": last_guard,
        "cold": compiles, "wall_s": round(wall, 1), "memory": mem,
        "close_stats": dict(ex.close_stats),
    })
    return out


def wait_closed(task, watermark: int) -> None:
    """The engine has seen event time `watermark`, so every window
    ending at or before it is closed."""
    def seen():
        wm = task._event_watermark()
        return wm is not None and wm >= watermark

    wait_for(seen, f"the engine to reach event time {watermark}", 900)


# ---- phases: session_device / join_device -----------------------------------


def canon_rows(rows, key_cols: tuple[str, ...]) -> dict:
    return {tuple(r[c] for c in key_cols) + (r["winStart"], r["winEnd"]):
            {k: v for k, v in r.items()
             if k not in key_cols + ("winStart", "winEnd")}
            for r in rows}


def host_reference(select_sql: str, feed: list[tuple], attr: str,
                   sample: list[dict]) -> list[dict]:
    """The same plan on the repo's host engine, fed the same batches in
    the same order."""
    from hstream_tpu.sql.codegen import make_executor, stream_codegen

    # the plan a CREATE VIEW runs: closed windows only, no changelog
    plan = stream_codegen(f"CREATE VIEW ref AS {select_sql};").select
    ex = make_executor(plan, sample_rows=sample)
    setattr(ex, attr, False)
    rows: list[dict] = []
    for stream, ts, cols in feed:
        kw = {} if stream is None else {"stream": stream}
        rows.extend(ex.process_columnar(ts, cols, None, **kw))
    flush = getattr(ex, "flush_changes", None)
    if flush is not None:
        rows.extend(flush())
    check(getattr(ex, "_dev", None) is None,
          "host reference activated a device engine")
    return [dict(r) for r in rows]


def phase_session_device(ctx, client: Client, ledger: CompileLedger,
                         seed: int, size: dict, platform: str,
                         dry_run: bool) -> dict:
    from hstream_tpu.engine.sketches import QuantileConfig

    rng = np.random.default_rng([seed, 3])
    n, frames = size["session_rows"], size["session_frames"]
    users = np.array([f"u{i:04d}" for i in range(size["session_users"])])
    stride = 20_000  # > 2 x gap: the prior batch's sessions close
    feed = []
    for b in range(frames):
        ts = BASE + b * stride + rng.integers(0, 1000, n)
        feed.append((None, ts.astype(np.int64), {
            "user": users[rng.integers(0, len(users), n)],
            "lat": rng.integers(1, 200, n).astype(np.float32)}))
    closer_ts = BASE + (frames + 10) * stride
    feed.append((None, np.array([closer_ts], np.int64),
                 {"user": np.array(["closer"]),
                  "lat": np.array([1.0], np.float32)}))

    before = ledger.snapshot()
    t0 = time.monotonic()
    client.create_stream("clicks")
    client.sql(f"CREATE VIEW sess AS {SESSION_SELECT};")
    task = wait_task(ctx, "view-sess")
    client.append("clicks", [(ts, cols) for _s, ts, cols in feed[:4]])
    wait_consumed(ctx, task, 600)
    with task.state_lock:
        ex = task.executor
    check(ex._dev is not None and ex._device_refusal is None,
          f"sessions stayed on the host: {ex._device_refusal}")
    mode = ex._dev.get("mode")
    if platform != "cpu":
        check(mode == "record", f"session plan mode is {mode!r}")
    client.append("clicks", [(ts, cols) for _s, ts, cols in feed[4:]])
    wait_consumed(ctx, task, 600)

    want = canon_rows(
        [r for r in host_reference(
            SESSION_SELECT, feed, "use_device_sessions",
            [{"user": "u", "lat": 1.0}]) if r["winEnd"] < closer_ts],
        ("user",))
    check(len(want) > 0, "host reference closed no session")

    def view_rows():
        rows = [r for r in client.sql("SELECT * FROM sess;")
                if r["winEnd"] < closer_ts]
        return rows if len(rows) >= len(want) else None

    got = canon_rows(wait_for(view_rows, "closed sessions in the view",
                              120, poll=0.2), ("user",))
    check(set(got) == set(want),
          f"session sets differ: {len(got)} served, {len(want)} on the "
          f"host engine")
    # quantiles may land one DDSketch bucket apart between engines
    rel = float(np.expm1(QuantileConfig().gamma_log)) * (1 + 1e-6)
    worst = 0.0
    for key, ref in want.items():
        for col in ("p50", "p99"):
            d = abs(got[key][col] - ref[col]) / abs(ref[col])
            worst = max(worst, d)
            check(d <= rel, f"session {key} {col}: {got[key][col]} vs "
                  f"host {ref[col]}")
    out = assert_on_device(ctx, task, "SessionExecutor", dry_run)
    check(ex._dev is not None, "sessions left the device mid-run")
    out.update({"events": sum(len(ts) for _s, ts, _c in feed),
                "sessions": len(want), "mode": mode,
                "quantile_max_rel_diff": round(worst, 6),
                "quantile_tolerance": round(rel, 6),
                "cold": ledger.since(before),
                "wall_s": round(time.monotonic() - t0, 1)})
    return out


def phase_join_device(ctx, client: Client, ledger: CompileLedger,
                      seed: int, size: dict, dry_run: bool) -> dict:
    rng = np.random.default_rng([seed, 4])
    n, frames = size["join_rows"], size["join_frames"]
    keys = np.array([f"k{i:05d}" for i in range(size["join_keys"])])
    feed = []
    for b in range(frames):
        ts = BASE + b * 500 + np.sort(rng.integers(0, 500, n))
        feed.append(("l" if b % 2 else "r", ts.astype(np.int64), {
            "k": keys[rng.integers(0, len(keys), n)],
            "x": np.ones(n, np.float32)}))
    last_ts = int(feed[-1][1][-1])
    closer_ts = (last_ts // WINDOW_MS + 3) * WINDOW_MS
    for side in ("r", "l"):
        feed.append((side, np.array([closer_ts], np.int64),
                     {"k": np.array(["closer"]),
                      "x": np.ones(1, np.float32)}))

    before = ledger.snapshot()
    t0 = time.monotonic()
    client.create_stream("l")
    client.create_stream("r")
    client.sql(f"CREATE VIEW joined AS {JOIN_SELECT};")
    task = wait_task(ctx, "view-joined")
    # the task reads both logs through one reader, so the order the two
    # sides interleave in is set by when each append lands: feed in
    # lock-step so the served run and the host reference see the same
    # sequence (an interval join's evictions depend on it)
    for i, (side, ts, cols) in enumerate(feed):
        client.append(side, [(ts, cols)])
        wait_consumed(ctx, task, 600)
        if i == 8:  # the device stores activate two batches late
            with task.state_lock:
                ex = task.executor
            check(ex._dev is not None and ex.use_device_join,
                  "join stayed on the host engine")

    def closed_only(rows):
        return [r for r in rows if r["winEnd"] <= closer_ts
                and r.get("l.k", r.get("k")) != "closer"]

    ref_feed = [(s, ts, {k: np.asarray(v, object) if v.dtype.kind == "U"
                         else v for k, v in cols.items()})
                for s, ts, cols in feed]
    ref_rows = closed_only(host_reference(
        JOIN_SELECT, ref_feed, "use_device_join",
        [{"k": "k", "x": 1.0}]))
    key_col = "l.k" if ref_rows and "l.k" in ref_rows[0] else "k"
    want = canon_rows(ref_rows, (key_col,))
    check(len(want) > 0, "host reference closed no join window")

    def view_rows():
        rows = closed_only(client.sql("SELECT * FROM joined;"))
        return rows if len(rows) >= len(want) else None

    got = canon_rows(wait_for(view_rows, "closed join windows in the view",
                              120, poll=0.2), (key_col,))
    diff = [(k, got.get(k), want.get(k)) for k in set(got) | set(want)
            if got.get(k) != want.get(k)]
    check(not diff, f"join rows differ from the host engine's in "
          f"{len(diff)} of {len(want)} rows, first {diff[:1]}")
    out = assert_on_device(ctx, task, "JoinExecutor", dry_run)
    with task.state_lock:
        ex = task.executor
    check(ex._dev is not None and ex.use_device_join,
          "join left the device mid-run")
    out.update({"events": sum(len(ts) for _s, ts, _c in feed),
                "rows": len(want),
                "matches": int(sum(v["c"] for v in want.values())),
                "join_stats": dict(ex.join_stats),
                "cold": ledger.since(before),
                "wall_s": round(time.monotonic() - t0, 1)})
    return out


# ---- shutdown ---------------------------------------------------------------


def check_final_snapshot(store_dir: str, tail_lsn: int, logid: int) -> dict:
    """After shutdown: reopen the store and hold the tumbling view's
    last snapshot to the log — sealed, and paired with the read
    position of the last acknowledged frame."""
    from hstream_tpu.engine.snapshot import open_blob
    from hstream_tpu.server.tasks import (
        parse_snapshot_pointer,
        snapshot_key,
        snapshot_slot_key,
    )
    from hstream_tpu.store import open_store

    qid = "view-per_device"
    store = open_store(store_dir)
    try:
        slot = parse_snapshot_pointer(store.meta_get(snapshot_key(qid)))
        check(slot is not None, "no snapshot pointer after shutdown")
        sealed = store.meta_get(snapshot_slot_key(qid, slot))
        blob = open_blob(sealed)  # raises on a torn or corrupt write
        with np.load(io.BytesIO(blob)) as z:
            meta = json.loads(bytes(z["__meta__"].tobytes()))
        ckps = {int(k): int(v)
                for k, v in meta["extra"]["ckps"].items()}
        check(ckps.get(logid) == tail_lsn,
              f"final snapshot is paired with LSN {ckps.get(logid)}, "
              f"the log's tail is {tail_lsn}")
        return {"snapshot_bytes": len(sealed), "paired_lsn": tail_lsn}
    finally:
        store.close()


def slowest_programs(k: int = 8) -> list[dict]:
    """The k programs that took longest to build (or to load from the
    cache), from the server's program inventory."""
    from hstream_tpu.stats.devicecost import PROGRAMS

    rows = sorted(PROGRAMS.rows(), key=lambda r: -r["compile_ms"])[:k]
    return [{"name": r["name"], "family": r["family"],
             "compile_s": round(r["compile_ms"] / 1e3, 2)} for r in rows]


# ---- main -------------------------------------------------------------------


def build_natives() -> None:
    """Rebuild the three native libraries from source: the run uses
    what git would commit, not a .so left on disk — and fails rather
    than fall back to the numpy packer or the Python decoder."""
    from hstream_tpu.common import jsondec
    from hstream_tpu.engine import codec_native
    from hstream_tpu.store import build as store_build

    store_build.build(force=True)
    codec_native.build(force=True)
    jsondec.build(force=True)
    check(codec_native.load() is not None, "native wire codec not loaded")
    check(jsondec.load() is not None, "native JSON decoder not loaded")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="toy sizes on whatever backend is there")
    ap.add_argument("--mesh", default=None, metavar="DxK",
                    help="shard the tumbling view over a device mesh "
                         "(1x4 on a four-chip host)")
    args = ap.parse_args(argv)
    t_start = time.monotonic()

    import jax
    import jaxlib

    from hstream_tpu.common.jaxenv import (
        device_summary,
        place_compile_cache,
        require_tpu,
    )

    device = device_summary() if args.dry_run else require_tpu()
    cache_dir = place_compile_cache()
    say(f"device {device}; compile cache at {cache_dir}")
    ledger = CompileLedger()
    build_natives()
    size = dict(DRY if args.dry_run else FULL)

    from hstream_tpu.server.main import serve

    store_dir = tempfile.mkdtemp(prefix="chip_smoke_store_")
    phases: dict[str, dict] = {}
    try:
        server, ctx = serve("127.0.0.1", 0, store_dir,
                            mesh_shape=args.mesh)
        client = Client(ctx.port)
        try:
            phases["served_tumbling"] = phase_served_tumbling(
                ctx, client, ledger, args.seed, size,
                device["platform"], args.mesh, args.dry_run)
            say(f"served_tumbling passed: "
                f"{json.dumps(phases['served_tumbling'])}")
            if args.mesh is None:  # the mesh run is the tumbling view's
                phases["session_device"] = phase_session_device(
                    ctx, client, ledger, args.seed, size,
                    device["platform"], args.dry_run)
                say(f"session_device passed: "
                    f"{json.dumps(phases['session_device'])}")
                phases["join_device"] = phase_join_device(
                    ctx, client, ledger, args.seed, size, args.dry_run)
                say(f"join_device passed: "
                    f"{json.dumps(phases['join_device'])}")
            logid = ctx.streams.get_logid("sensors")
            tail = ctx.store.tail_lsn(logid)
        finally:
            client.close()
            t_stop = time.monotonic()
            server.stop(grace=1)
            ctx.shutdown()
        phases["shutdown"] = check_final_snapshot(store_dir, tail, logid)
        phases["shutdown"]["wall_s"] = round(
            time.monotonic() - t_stop, 1)
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    report = {
        "ok": True,
        "platform": device["platform"],
        "device_kind": device["kind"],
        "n_devices": device["count"],
        "dry_run": args.dry_run,
        "mesh": args.mesh,
        "versions": {"jax": jax.__version__,
                     "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version,
                     "python": sys.version.split()[0]},
        "seed": args.seed,
        "cache_dir": cache_dir,
        "compile": ledger.snapshot(),
        "slowest_programs": slowest_programs(),
        "phases": phases,
        "sizes": size,
        "reduced": REDUCED,
        "wall_s": round(time.monotonic() - t_start, 1),
    }
    # two lines, in this order: the report, then the verdict — the chip
    # check reads the LAST line and takes exactly these two keys
    print(json.dumps(report), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
