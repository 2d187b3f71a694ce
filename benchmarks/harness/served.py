"""The system under test, in this process: an in-process `serve()` with
server defaults over the native store on disk, a loopback gRPC client,
and the waits and counter snapshots the window needs. The client, the
waits and the device check are copied from `chip_smoke.py` (sound, ran
on the chip); nothing is imported from it, so a later PR may change it.

From the program this takes only the system and its spans and counters:
`stage_latency_ms` and `kernel_dispatch_ms` histograms, `pipe.stats()`,
`close_stats`, `read_extracts`, and the query's public count of what it
has consumed, the `consumed_events` stat. A task whose executor has no
ingest pipeline reads `pipe` as `{}`; one that never counts
`consumed_events` reads 0.
"""

from __future__ import annotations

import threading
import time

import numpy as np


class Client:
    """Loopback gRPC client: SQL, and framed appends for the warm phase
    and the closer (the window's appends come from the producer
    process)."""

    def __init__(self, port: int):
        import grpc

        from hstream_tpu.client.retry import RetryPolicy
        from hstream_tpu.proto.rpc import HStreamApiStub

        self.channel = grpc.insecure_channel(
            f"127.0.0.1:{port}",
            options=[("grpc.max_receive_message_length", 64 << 20),
                     ("grpc.max_send_message_length", 64 << 20)])
        self.stub = HStreamApiStub(self.channel)
        self.retry = RetryPolicy(attempts=120)
        self.frames_acked: dict[str, int] = {}  # by stream

    def close(self) -> None:
        self.channel.close()

    def sql(self, text: str) -> list[dict]:
        from hstream_tpu.common import records as rec
        from hstream_tpu.proto import api_pb2 as pb

        resp = self.stub.ExecuteQuery(pb.CommandQuery(stmt_text=text))
        return [rec.struct_to_dict(s) for s in resp.result_set]

    def append_call(self, frames: list[tuple]) -> None:
        """One AppendColumnarStream call of one request message: frames
        of one stream, each (stream, ts, cols, events) as the
        generator makes them."""
        from hstream_tpu.client.producer import ColumnarProducer, encode_batch

        stream = frames[0][0]
        if any(f[0] != stream for f in frames):
            raise RuntimeError("a call holds frames of one stream, not of "
                               f"{sorted({f[0] for f in frames})}")
        producer = ColumnarProducer(self.channel, stream)
        data = [encode_batch(ts, cols) for _s, ts, cols, _n in frames]
        resp = self.retry.call(producer.append_stream_frames, data)
        rows = sum(n for _s, _ts, _cols, n in frames)
        if resp.rows != rows or len(resp.record_ids) != len(frames):
            raise RuntimeError(
                f"append to {stream}: acked {resp.rows} rows / "
                f"{len(resp.record_ids)} frames, sent {rows} / "
                f"{len(frames)}")
        self.frames_acked[stream] = (self.frames_acked.get(stream, 0)
                                     + len(frames))


def wait_for(pred, what: str, timeout: float, poll: float = 0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(poll)
    raise RuntimeError(f"timeout after {timeout:.0f}s waiting for {what}")


def wait_task(ctx, qid: str):
    def attached():
        task = ctx.running_queries.get(qid)
        return task if task is not None and task.attached.is_set() \
            else None

    return wait_for(attached, f"query {qid} to attach", 60)


def pipe_of(task):
    with task.state_lock:
        return task._pipe


def consumed_events(ctx, task) -> int:
    """Events the query has consumed, by the program's public count: the
    `consumed_events` stat (`admin stats queries`), which a batch joins
    when the task's ingest pipeline takes it to its step. 0 for a query
    that never counted."""
    return int(ctx.stats.stat_ladder("consumed_events",
                                     task.info.query_id)["total"])


def wait_consumed(ctx, task, timeout: float) -> None:
    from hstream_tpu.server.health import _source_backlog

    def done():
        pipe = pipe_of(task)
        return (_source_backlog(ctx, task) == 0
                and (pipe is None or pipe.pending == 0))

    wait_for(done, f"{task.info.query_id} to consume its sources",
             timeout, poll=0.005)
    if task.error is not None:
        raise RuntimeError(f"{task.info.query_id} died: {task.error}")


def log_payloads(ctx, stream: str) -> int:
    """Payloads (frames) on the stream's log, read back from the store:
    what an acknowledged append must have left there."""
    reader = ctx.store.new_reader()
    reader.start_reading(ctx.streams.get_logid(stream), 0)
    reader.set_timeout(0)
    payloads = 0
    while True:
        got = reader.read(64)
        if not got:
            return payloads
        for b in got:
            if hasattr(b, "payloads"):
                payloads += len(b.payloads)


def acked_not_stored(ctx, streams: list[str], client_acked: dict,
                     calls: list) -> int:
    """Over every stream: the distance between the frames acknowledged
    (to this process's client, and to the producer in its log's `calls`)
    and the payloads on the stream's log."""
    acked = dict(client_acked)
    for c in calls:
        if c[6]:
            acked[c[7]] = acked.get(c[7], 0) + c[1]
    return sum(abs(log_payloads(ctx, stream) - acked.get(stream, 0))
               for stream in streams)


def on_device(ctx, task, kind: str) -> dict:
    """Guarantees a run can show beside the answers: the right executor
    class, no activation degraded to a host twin, the query RUNNING."""
    from hstream_tpu.server.persistence import TaskStatus

    with task.state_lock:
        ex = task.executor
    status = ctx.persistence.get_query(task.info.query_id).status
    return {
        "executor_wrong": int(type(ex).__name__ != kind),
        "device_fallbacks": int(task.engine_total("device_fallbacks")),
        "late_drops": int(task.engine_total("late_drops")),
        "query_not_running": int(status != TaskStatus.RUNNING
                                 or task.error is not None),
    }


def stage_count(ctx, stage: str) -> int:
    """How often `stage_latency_ms{stage}` has observed a span."""
    h = ctx.stats.histograms_snapshot().get(("stage_latency_ms", stage))
    return 0 if h is None else h.snapshot()[2]


def counters(ctx, task, view: str) -> dict:
    """One reading of every span and counter the per-layer readers use;
    taken at the window's start and end, differenced by the readers."""
    hists: dict[str, dict] = {"stage_latency_ms": {},
                              "kernel_dispatch_ms": {}}
    for (metric, label), h in ctx.stats.histograms_snapshot().items():
        if metric in hists:
            cum, total, count = h.snapshot()
            hists[metric][label] = {"bounds": list(h.bounds), "cum": cum,
                                    "sum_ms": total, "count": count}
    pipe = pipe_of(task)
    with task.state_lock:
        ex = task.executor
    return {
        "t": time.monotonic(),
        "consumed_events": consumed_events(ctx, task),
        "histograms": hists,
        "pipe": dict(pipe.stats()) if pipe is not None else {},
        "close_stats": dict(getattr(ex, "close_stats", {})),
        "read_extracts": int(ctx.stats.stream_stat_get("read_extracts",
                                                       view)),
    }


class Reader(threading.Thread):
    """One closed-loop reader: pull the statement of one seeded draw of
    the generator's (`reader_pull`), think, pull again. Every pull is
    kept, with its times and what the draw says of it, for the
    comparison after the window."""

    def __init__(self, client: Client, gen, size: dict, seed: int,
                 think_s: float):
        super().__init__(name="bench-reader", daemon=True)
        self.client, self.gen, self.size = client, gen, size
        self.think_s = think_s
        self.rng = np.random.default_rng([int(seed), 2])
        self.stop_ev = threading.Event()
        self.pulls: list[dict] = []
        self.error: str | None = None

    def pull(self) -> dict:
        draw = dict(self.gen.reader_pull(self.size, self.rng))
        text = draw.pop("sql")
        t0 = time.monotonic()
        rows = self.client.sql(text)
        return {**draw, "t0": t0, "t1": time.monotonic(), "rows": rows}

    def run(self) -> None:
        while not self.stop_ev.is_set():
            try:
                self.pulls.append(self.pull())
            except Exception as e:  # noqa: BLE001 — reported, fatal
                self.error = f"{type(e).__name__}: {e}"
                self.pulls.append({"t0": time.monotonic(),
                                   "t1": time.monotonic(), "rows": None})
                return
            self.stop_ev.wait(self.think_s)

    def stop(self) -> None:
        self.stop_ev.set()
        self.join(120)
