"""The load generator: a process of its own, started by `run.py` with
`JAX_PLATFORMS=cpu` (the client library imports the SQL codegen and with
it JAX; nothing here touches the chip).

ONE ordered producer, closed loop: one `AppendColumnarStream` call
outstanding at a time, and (where the traffic sets `max_lead_events`) no
event sent further than that ahead of what the query has consumed, which
`run.py` relays as `consumed <events>` lines (the program's public
`consumed_events` count); each call is one request message of the
configuration's `frames_per_call` frames, all of one stream, sent in
frame order (with GRACE 0 a second, unordered sender would turn good
frames into late ones). Which stream a frame goes to, and so how several
streams interleave, is the generator's: the module the configuration
names, the only thing here that knows a stream or a column. Frames are
generated from the seed and encoded as the client library's
`encode_batch` encodes them by `encoders` helper processes, each making
every n-th frame into a queue of two, so the next calls' frames are
ready while the last is in flight; the sender takes them in order.
`RESOURCE_EXHAUSTED` is honoured with the client library's
`RetryPolicy`; a refused call that is retried is one sample, timed from
its first send.

Protocol: started before the server is up, it reads `port <n>` on stdin,
prints `ready` once every encoder has a frame waiting and it is
connected, then reads `go`, `consumed <events>` and `stop` lines; after
`stop` (or end of input) it finishes the call in flight, writes its log
to `--log` and exits. The log is one JSON object:
  calls        [first frame, frames, t_send, t_ack, retries, lsn, ok,
               stream, events] per call, times on CLOCK_MONOTONIC
               (shared with run.py)
  t_go, t_stop when the sender started and saw the stop
  encode_wait_s  time the sender sat waiting for an encoder (generator
               too slow), apart from time waiting for acks
  lead_wait_s  time it sat with frames in hand, waiting for the query to
               come within `--max-lead-events` of what was sent
  error        the first failure, or null
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import queue
import sys
import threading
import time

QUEUE_DEPTH = 2


def encode_frame(ts, cols) -> bytes:
    """The bytes the client library's `encode_batch` makes, by the two
    calls it makes. Imported from `hstream_tpu.common`, so that an
    encoder process starts without the client package's SQL codegen and
    JAX (seconds of import, four times over, beside the chip's start)."""
    from hstream_tpu.common import colframe, columnar

    return colframe.encode_frame(
        columnar.encode_columnar(ts, cols, float_kind="f32"))


def encoder_main(size: dict, home: str | None, seed: int, worker: int,
                 n_workers: int, first: int, out_q, stop) -> None:
    from benchmarks.harness import manifest

    manifest.use(home)
    gen = manifest.generator_of(size)
    i = first + worker
    parent = os.getppid()

    def stopped() -> bool:  # also when the producer was killed outright
        return stop.is_set() or os.getppid() != parent

    while not stopped():
        stream, ts, cols, events = gen.frame(size, seed, i)
        data = encode_frame(ts, cols)
        while not stopped():
            try:
                out_q.put((i, stream, events, data), timeout=0.1)
                break
            except queue.Full:
                continue
        i += n_workers


class Credit:
    """What `run.py` relays: the server's port, and the measured events
    the query has consumed."""

    def __init__(self):
        self.consumed = 0
        self.port = 0
        self.has_port = threading.Event()


def _stdin_watch(go: threading.Event, stop: threading.Event,
                 credit: Credit) -> None:
    for line in sys.stdin:
        word, _, arg = line.strip().partition(" ")
        if word == "consumed":
            credit.consumed = int(arg)
        elif word == "port":
            credit.port = int(arg)
            credit.has_port.set()
        elif word == "go":
            go.set()
        elif word == "stop":
            break
    stop.set()
    go.set()
    credit.has_port.set()


def _take(q, stop: threading.Event):
    """The next frame of one encoder's queue, or None once stopped."""
    while not stop.is_set():
        try:
            return q.get(timeout=0.25)
        except queue.Empty:
            continue
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--size", required=True,
                    help="JSON file of the sizes run.py resolved")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--first-frame", type=int, required=True)
    ap.add_argument("--encoders", type=int, required=True)
    ap.add_argument("--max-lead-events", type=int, default=0,
                    help="send no event further than this ahead of the "
                         "query's consumption (0: as fast as acks allow; "
                         "never under two calls)")
    ap.add_argument("--manifest", default=None,
                    help="a manifest in BENCHMARK.json's place (tests)")
    ap.add_argument("--log", required=True)
    args = ap.parse_args(argv)
    with open(args.size) as f:
        size = json.load(f)

    mp = multiprocessing.get_context("spawn")
    stop_workers = mp.Event()
    n = max(int(args.encoders), 1)
    queues = [mp.Queue(maxsize=QUEUE_DEPTH) for _ in range(n)]
    workers = [mp.Process(
        target=encoder_main, name=f"bench-encoder-{w}", daemon=True,
        args=(size, args.manifest, args.seed, w, n, args.first_frame,
              queues[w], stop_workers)) for w in range(n)]
    for p in workers:
        p.start()

    # after the encoders are off: the client package imports JAX, which
    # takes longer than an encoder needs to make its first frame
    import grpc

    from benchmarks.harness import manifest
    from hstream_tpu.client.producer import ColumnarProducer
    from hstream_tpu.client.retry import RetryPolicy

    manifest.use(args.manifest)
    gen = manifest.generator_of(size)
    retry = RetryPolicy(attempts=120)
    go, stop = threading.Event(), threading.Event()
    credit = Credit()
    threading.Thread(target=_stdin_watch, args=(go, stop, credit),
                     daemon=True, name="bench-stdin").start()
    channel = None

    log = {"calls": [], "t_go": None, "t_stop": None,
           "encode_wait_s": 0.0, "lead_wait_s": 0.0, "error": None,
           "frames_per_call": size["frames_per_call"]}
    try:
        # ready: every encoder has made its first frame
        deadline = time.monotonic() + 120
        while (any(q.empty() for q in queues)
               and time.monotonic() < deadline and not stop.is_set()):
            if not all(p.is_alive() for p in workers):
                raise RuntimeError("an encoder process died")
            time.sleep(0.02)
        credit.has_port.wait(600)
        if not credit.port:
            raise RuntimeError("no server port arrived on stdin")
        channel = grpc.insecure_channel(
            f"127.0.0.1:{credit.port}",
            options=[("grpc.max_send_message_length", 64 << 20)])
        producers = {st["name"]: ColumnarProducer(channel, st["name"])
                     for st in gen.streams(size)}
        print("ready", flush=True)
        go.wait()
        log["t_go"] = time.monotonic()
        nxt = args.first_frame
        per_call = size["frames_per_call"]
        sent = 0  # measured events sent, this call's included
        while not stop.is_set():
            t_w = time.monotonic()
            frames, streams, events = [], set(), 0
            for _ in range(per_call):
                got = _take(queues[(nxt - args.first_frame) % n], stop)
                if got is None:
                    break
                i, stream, n_events, data = got
                if i != nxt:
                    raise RuntimeError(f"frame {i} arrived, {nxt} due")
                frames.append(data)
                streams.add(stream)
                events += n_events
                nxt += 1
            if len(frames) < per_call:
                break
            if len(streams) != 1:
                raise RuntimeError(
                    f"the call of frames {nxt - per_call}..{nxt - 1} holds "
                    f"frames of {sorted(streams)}: a call is of one stream")
            stream = streams.pop()
            sent += events
            t_e = time.monotonic()
            log["encode_wait_s"] += t_e - t_w
            if args.max_lead_events:
                # closed loop on the query: wait for it to come within
                # the lead before sending further
                lead = max(args.max_lead_events, 2 * events)
                while (sent > credit.consumed + lead
                       and not stop.is_set()):
                    time.sleep(0.002)
            t_send = time.monotonic()
            log["lead_wait_s"] += t_send - t_e
            before = retry.retries
            ok, lsn = True, None
            try:
                resp = retry.call(producers[stream].append_stream_frames,
                                  frames)
                ok = (resp.rows == events
                      and len(resp.record_ids) == per_call)
                lsn = int(resp.record_ids[-1].batch_id)
            except grpc.RpcError as e:
                ok = False
                log["error"] = f"{e.code()}: {e.details()}"
            t_ack = time.monotonic()
            log["calls"].append([nxt - per_call, per_call, t_send, t_ack,
                                 retry.retries - before, lsn, ok, stream,
                                 events])
            if not ok:
                log["error"] = log["error"] or "short ack"
                break
    except Exception as e:  # noqa: BLE001 — reported in the log, fatal
        log["error"] = f"{type(e).__name__}: {e}"
    finally:
        log["t_stop"] = time.monotonic()
        stop_workers.set()
        # drain before joining: a writer blocked on a full pipe never ends
        end = time.monotonic() + 10
        while any(p.is_alive() for p in workers) and time.monotonic() < end:
            for q in queues:
                try:
                    q.get_nowait()
                except queue.Empty:
                    pass
            time.sleep(0.01)
        for p in workers:
            if p.is_alive():
                p.terminate()
            p.join(5)
        for q in queues:
            q.cancel_join_thread()
            q.close()
        if channel is not None:
            channel.close()
        tmp = args.log + ".tmp"
        with open(tmp, "w") as f:
            json.dump(log, f)
        os.replace(tmp, args.log)
    return 0 if log["error"] is None else 1


if __name__ == "__main__":
    sys.exit(main())
