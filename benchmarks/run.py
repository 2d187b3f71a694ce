"""One run of one benchmark cell on the served path.

  python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: an in-process `serve()` with server defaults
over the native store on disk in a fresh temporary directory. The
window drives gRPC `AppendColumnarStream` on the loopback socket from a
producer process of its own (`harness/producer.py`) and, where the
traffic has a reader, `ExecuteQuery` pulls of the view. Phases:

  set-up   (`setup_s`, process start -> window start) device check,
           compile cache placed, natives built if missing, streams and
           view created, the generator's warm frames consumed (for the
           sensor generator: every key named, one close cycle), one
           snapshot landed, one pull
  window   `--seconds` of load from an event-time window boundary; no
           program may compile inside it
  after    producer stopped, closer sent, task drained, answers pulled,
           guarantees read, server shut down, THEN the plain reference
           runs and the comparison decides `correct`

Nothing here names a stream, a column or a window: the configuration
names its generator (`benchmarks/generators/<name>.py`), which gives the
streams to create, every frame, the closers, the statements to pull and
the windows that must be complete; its `server` block gives the options
`serve()` is started with; and what the query has consumed is the
program's public `consumed_events` count.

The last stdout line is the result (`harness/result.py` holds it to the
driver's contract before printing it). `--dry 1` runs the
configuration's `dry` sizes on whatever backend is there; it prints
`platform: cpu` and is for the tests only, never a result.
"""

from __future__ import annotations

import time

T_PROC = time.monotonic()  # process start, as near as Python can tell

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_SLICE_S = 4.0


def say(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


class CompileLedger:
    """Persistent-cache hits and misses, from JAX's own events (copied
    from `chip_smoke.py`)."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            with self._lock:
                self.misses += 1

    def snapshot(self) -> dict:
        from hstream_tpu.stats.devicecost import PROGRAMS

        built = PROGRAMS.summary()
        return {"programs": built["total_compiles"],
                "build_s": built["total_compile_ms"] / 1e3,
                "cache_hits": self.hits, "cache_misses": self.misses}


def build_natives() -> None:
    """The three native libraries, built only where the .so is missing
    or stale; a run never falls back to the numpy packer or the Python
    decoder."""
    from hstream_tpu.common import jsondec
    from hstream_tpu.engine import codec_native
    from hstream_tpu.store import build as store_build

    store_build.build()
    codec_native.build()
    jsondec.build()
    if codec_native.load() is None or jsondec.load() is None:
        raise SystemExit("a native library did not load")


def start_producer(size: dict, traffic: dict, seed: int, tmp: str,
                   first_frame: int, home: str | None) -> subprocess.Popen:
    """Started once this process holds the chip (beside JAX's own start
    its five processes' imports cost more than they save), so that its
    encoders spawn and fill their queues while the server boots and
    warms; it is told the server's port on stdin once there is one."""
    size_path = os.path.join(tmp, "size.json")
    with open(size_path, "w") as f:
        json.dump(size, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    return subprocess.Popen(
        [sys.executable, "-m", "benchmarks.harness.producer",
         "--size", size_path, "--seed", str(seed),
         "--first-frame", str(first_frame),
         "--encoders", str(traffic["encoders"]),
         "--max-lead-events", str(traffic.get("max_lead_events") or 0),
         "--log", os.path.join(tmp, "producer.json"),
         *(["--manifest", home] if home else [])],
        cwd=ROOT, env=env, stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        text=True)


def stop_producer(proc: subprocess.Popen) -> None:
    """End of input stops the producer and, through it, its encoders;
    killed only if it does not go."""
    if proc.poll() is not None:
        return
    try:
        proc.stdin.close()
    except OSError:
        pass
    try:
        proc.wait(20)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait(30)


def relay_consumed(proc: subprocess.Popen, consumed, base: int,
                   stop: threading.Event) -> None:
    """Tell the producer how many measured events the query has
    consumed (`consumed()`, the program's public count), some fifty
    times a second."""
    last = -1
    while not stop.is_set():
        n = consumed() - base
        if n != last:
            try:
                proc.stdin.write(f"consumed {n}\n")
                proc.stdin.flush()
            except (BrokenPipeError, ValueError):
                return
            last = n
        stop.wait(0.02)


def wait_ready(proc: subprocess.Popen, timeout: float) -> None:
    got: list[str] = []
    t = threading.Thread(target=lambda: got.append(proc.stdout.readline()),
                         daemon=True)
    t.start()
    t.join(timeout)
    if not got or got[0].strip() != "ready":
        raise RuntimeError(f"producer not ready after {timeout:.0f}s: "
                           f"{got!r}, exit {proc.poll()}")


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats()
        if stats:
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(args) -> int:
    from benchmarks.harness import manifest

    manifest.use(args.manifest)
    cell = manifest.cell(args.workload)
    size = manifest.size_of(cell["config"], bool(args.dry))
    tmp = tempfile.mkdtemp(prefix="bench_run_")
    started: list[subprocess.Popen] = []  # the producer, once it runs
    try:
        return measure(args, cell, size, tmp, started)
    finally:
        for proc in started:
            stop_producer(proc)
        shutil.rmtree(tmp, ignore_errors=True)


def measure(args, cell: dict, size: dict, tmp: str,
            started: list) -> int:
    from benchmarks.harness import manifest, result, served
    from benchmarks.harness import trace as tr

    config, traffic, man = cell["config"], cell["traffic"], cell["manifest"]
    gen = manifest.generator_of(config)
    dry = bool(args.dry)
    traced = bool(args.trace)
    seconds = float(args.seconds)

    import jax

    from hstream_tpu.common.jaxenv import (
        device_summary,
        place_compile_cache,
        require_tpu,
    )

    device = device_summary() if dry else require_tpu()
    if not dry and device["count"] < cell["chips"]:
        raise SystemExit(f"the cell asks for {cell['chips']} chips, JAX "
                         f"reports {device['count']}")
    chips = device["count"] if dry else cell["chips"]
    mesh = manifest.mesh_devices(config)
    if not dry and mesh is not None and mesh != cell["chips"]:
        raise SystemExit(f"the cell asks for {cell['chips']} chips, its "
                         f"configuration's mesh for {mesh}")
    producer = start_producer(size, traffic, args.seed, tmp,
                              gen.warm_frames(size), args.manifest)
    started.append(producer)
    cache_dir = place_compile_cache()
    # keep every program: a warm run must load all of them (JAX's
    # defaults keep only those that took a second to build)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    ledger = CompileLedger()
    build_natives()
    say(f"{args.workload} seed {args.seed} on {device}; compile cache "
        f"at {cache_dir}")

    from hstream_tpu.common.tracing import RetraceGuard
    from hstream_tpu.server.main import serve

    options = manifest.server_options(config, serve)
    streams = [st["name"] for st in gen.streams(size)]
    view = size["view"]
    n_warm = gen.warm_frames(size)
    per_call = size["frames_per_call"]
    info: dict = {"workload": args.workload, "seed": args.seed,
                  "dry": dry, "traced": traced}
    marks = {"device_and_natives": time.monotonic() - T_PROC}
    info["setup_marks_s"] = marks  # seconds since process start
    server = ctx = client = reader = None
    try:
        server, ctx = serve("127.0.0.1", 0, os.path.join(tmp, "store"),
                            **options)
        producer.stdin.write(f"port {ctx.port}\n")
        producer.stdin.flush()
        client = served.Client(ctx.port)
        for stream in streams:
            client.sql(f"CREATE STREAM {stream};")
        client.sql(size["sql"])
        task = served.wait_task(ctx, f"view-{view}")
        marks["served_and_view"] = time.monotonic() - T_PROC

        # ---- warm phase: the cell's own shapes and no others ----------
        biggest = 0  # events of the largest warm frame
        for lo in range(0, n_warm, per_call):
            call = [gen.frame(size, args.seed, i)
                    for i in range(lo, lo + per_call)]
            biggest = max(biggest, *(f[3] for f in call))
            client.append_call(call)
        marks["warm_sent"] = time.monotonic() - T_PROC
        served.wait_consumed(ctx, task, 900)
        marks["warm_consumed"] = time.monotonic() - T_PROC
        with task.state_lock:
            ex = task.executor
        capacity = getattr(ex, "batch_capacity", None)
        if capacity is not None and capacity < biggest:
            raise RuntimeError(
                f"executor batch capacity {capacity} is under the frame's "
                f"{biggest} rows: a frame is not one step")
        served.wait_for(
            lambda: served.stage_count(ctx, "snapshot") >= 1
            and not getattr(task, "_persist_busy", False),
            "the first snapshot to land", 300)
        marks["snapshot_landed"] = time.monotonic() - T_PROC
        if traffic.get("readers"):
            reader = served.Reader(client, gen, size, args.seed,
                                   traffic["reader_think_ms"] / 1e3)
            reader.pull()
        wait_ready(producer, 180)
        marks["producer_ready"] = time.monotonic() - T_PROC
        # compilation in the warm phase reads as load to the server's
        # overload ladder: start the window only once it admits again
        served.wait_for(lambda: ctx.flow.overload.effective_level() == 0,
                        "the overload ladder to admit", 300)
        info["flow_start"] = ctx.flow.overload.status()
        gc.collect()
        info["setup_compile"] = ledger.snapshot()

        # ---- the window ------------------------------------------------
        guard = RetraceGuard()
        guard.__enter__()
        start = served.counters(ctx, task, view)
        relay_stop = threading.Event()
        relay = threading.Thread(
            target=relay_consumed, name="bench-relay", daemon=True,
            args=(producer, lambda: served.consumed_events(ctx, task),
                  start["consumed_events"], relay_stop))
        producer.stdin.write("go\n")
        producer.stdin.flush()
        relay.start()
        t0 = start["t"]
        setup_s = t0 - T_PROC
        if reader is not None:
            reader.start()
        trace_dir = os.path.join(tmp, "trace")
        if traced:
            slice_s = min(TRACE_SLICE_S, seconds / 2)
            time.sleep(max(0.0, t0 + (seconds - slice_s) / 2
                           - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench_slice"):
                    time.sleep(slice_s)
            finally:
                jax.profiler.stop_trace()
        time.sleep(max(0.0, t0 + seconds - time.monotonic()))
        end = served.counters(ctx, task, view)
        guard.__exit__(None, None, None)
        info["flow_end"] = ctx.flow.overload.status()
        after: dict = {}
        info["after_marks_s"] = after  # seconds since the window's end
        relay_stop.set()
        relay.join(10)
        producer.stdin.write("stop\n")
        producer.stdin.flush()
        if reader is not None:
            reader.stop()
        try:
            producer.wait(180)
        except subprocess.TimeoutExpired:
            producer.kill()
            raise RuntimeError("the producer did not stop") from None
        after["producer_stopped"] = time.monotonic() - end["t"]
        with open(os.path.join(tmp, "producer.json")) as f:
            plog = json.load(f)
        peak = memory_peak_bytes()

        # ---- after the window: drain, pull the answers ----------------
        n_frames = n_warm + sum(c[1] for c in plog["calls"] if c[6])
        served.wait_consumed(ctx, task, 900)
        after["drained"] = time.monotonic() - end["t"]
        plan = gen.pulls(size, n_frames)
        final: list[dict] = []
        complete: list = []
        seen: set = set()  # rows an earlier statement gave

        def pull_answers(statements: list) -> None:
            # a later statement may repeat an earlier one's window: a row
            # both give alike counts once, one they give differently
            # twice (and so does a row one statement gives twice)
            for st in statements:
                rows = client.sql(st["sql"])
                whole = [tuple(sorted(r.items())) for r in rows]
                final.extend(r for r, w in zip(rows, whole)
                             if w not in seen)
                seen.update(whole)
                if rows or not st.get("if_rows"):
                    complete.extend(m for m in st["complete"]
                                    if m not in complete)

        pull_answers(plan["before"])
        for closer in gen.closers(size, n_frames):
            client.append_call([closer])
        served.wait_consumed(ctx, task, 900)
        after["closer_consumed"] = time.monotonic() - end["t"]
        pull_answers(plan["after"])
        after["answers_pulled"] = time.monotonic() - end["t"]
        guarantees = served.on_device(ctx, task, size["executor"])
        guarantees["acked_not_stored"] = served.acked_not_stored(
            ctx, streams, client.frames_acked, plog["calls"])
        guarantees["compiles_in_window"] = int(guard.count)
        after["guarantees_read"] = time.monotonic() - end["t"]
        info["compile"] = ledger.snapshot()
        info["plane_bytes"] = int(sum(task.device_plane_bytes().values()))
    finally:
        stop_producer(producer)
        if reader is not None and reader.is_alive():
            reader.stop()
        if client is not None:
            client.close()
        if server is not None:
            server.stop(grace=1)
        if ctx is not None:
            ctx.shutdown()
            if "after_marks_s" in info:
                info["after_marks_s"]["shut_down"] = (
                    time.monotonic() - end["t"])
        trace_red = None
        try:
            if traced and os.path.isdir(os.path.join(tmp, "trace")):
                xplane = tr.find_xplane(os.path.join(tmp, "trace"))
                info["trace_bytes"] = os.path.getsize(xplane)
                if args.out:
                    os.makedirs(args.out, exist_ok=True)
                    with open(os.path.join(args.out, "trace_lines.json"),
                              "w") as f:
                        json.dump(tr.describe(xplane), f)
                events = tr.events_from_xplane(xplane)
                if args.out:
                    with open(os.path.join(args.out, "trace_events.json"),
                              "w") as f:
                        json.dump(tr.pack(events), f)
                trace_red = tr.reduce(events)
        finally:
            if args.out and os.path.exists(os.path.join(tmp,
                                                        "producer.json")):
                os.makedirs(args.out, exist_ok=True)
                shutil.copy(os.path.join(tmp, "producer.json"), args.out)

    # ---- the plain reference, once the program's state is freed -------
    t_ref = time.monotonic()
    pulls = reader.pulls if reader is not None else []
    numbers = manifest.reference_of(config).compare(
        size, args.seed, n_frames,
        {"final": final, "complete": complete,
         "pulls": [p for p in pulls if p["rows"] is not None],
         "horizon": plan["horizon"]})
    numbers.update(guarantees)
    info["reference_s"] = time.monotonic() - t_ref
    limits = size["limits"]
    compared = {k: {"value": v, "limit": limits[k]}
                for k, v in numbers.items()}

    calls = [c for c in plog["calls"] if start["t"] <= c[2] <= end["t"]]
    in_window = [p for p in pulls if start["t"] <= p["t0"] <= end["t"]]
    failed = (sum(1 for c in calls if not c[6])
              + sum(1 for p in in_window if p["rows"] is None))
    attempted = len(calls) + len(in_window)
    errors = [e for e in (plog["error"],
                          reader.error if reader is not None else None)
              if e]
    for e in errors:
        say(f"error: {e}")
    correct = (all(c["value"] <= c["limit"] for c in compared.values())
               and failed == 0 and not errors and attempted > 0)

    run_ctx = {"start": start, "end": end, "window_s": end["t"] - start["t"],
               "producer": plog, "calls": calls, "pulls": in_window,
               "size": size, "config": config, "device": device,
               "trace": trace_red, "setup_s": setup_s}
    kind = "per_layer" if traced else "end_to_end"
    due = manifest.metrics_of(args.workload, man, kind)
    metrics = {}
    for m in due:
        spec, read = manifest.reader_of(m["name"])
        value = read(run_ctx, spec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": spec["unit"]}
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": chips, "memory_peak_bytes": peak}
    breakdown = None
    if traced and trace_red is not None:
        dev["busy_s"] = trace_red["busy_s"]
        dev["window_s"] = trace_red["window_s"]
        breakdown = tr.breakdown(trace_red)
        info["programs"] = trace_red["programs"]
    stages = {}
    for label, e in end["histograms"]["stage_latency_ms"].items():
        b = start["histograms"]["stage_latency_ms"].get(
            label, {"sum_ms": 0.0, "count": 0})
        stages[label] = [round(e["sum_ms"] - b["sum_ms"], 1),
                         e["count"] - b["count"]]
    info["stage_ms_and_count"] = stages  # host spans over the window
    info.update({"setup_s": setup_s, "window_s": run_ctx["window_s"],
                 "calls": len(calls), "pulls": len(in_window),
                 "frames": n_frames, "retries": sum(c[4] for c in calls),
                 "encode_wait_s": plog["encode_wait_s"],
                 "lead_wait_s": plog["lead_wait_s"],
                 "total_s": time.monotonic() - T_PROC})
    say("info " + json.dumps(info))
    if args.out:
        with open(os.path.join(args.out, "info.json"), "w") as f:
            json.dump(info, f)
    line = result.build(correct=correct, attempted=attempted, failed=failed,
                        metrics=metrics, device=dev, compared=compared,
                        breakdown=breakdown)
    return result.emit(line, due, traced=traced, chips=chips,
                       platform="cpu" if dry else "tpu")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry", type=int, choices=(0, 1), default=0,
                    help="the configuration's toy sizes on any backend "
                         "(tests only)")
    ap.add_argument("--manifest", default=None,
                    help="a manifest in BENCHMARK.json's place, relative "
                         "to the checkout's root: files are looked for "
                         "beside it first (tests only)")
    ap.add_argument("--out", default=None,
                    help="directory to keep the producer's log, run "
                         "info and the trace's description in")
    return run(ap.parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
