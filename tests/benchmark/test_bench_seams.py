"""The harness's three seams, on the CPU: the generator a configuration
names, its `server` block, and consumption by the program's public
`consumed_events` count. The toy generators and the fixture manifest
live in `tests/benchmark/toy/`; nothing in BENCHMARK.json names them.
"""

import hashlib
import io
import json
import os
import subprocess
import sys
import threading
import time
import types

import numpy as np
import pytest

from bench_drive import ROOT, drive
from benchmarks.harness import manifest, served

TOY = "tests/benchmark/toy/BENCHMARK.json"
TOY_DIR = os.path.join(ROOT, "tests", "benchmark", "toy")
MAN = manifest.manifest()

# sha256 over the frame as the generator hands it on (ts, then each
# column's name, dtype and bytes), taken from the PARENT's
# generator module (commit 870c8a8, under `harness/`) at full size, before
# it moved to `generators/sensor.py`: [frame index, digest]
PINNED = {
    "sensor_hll_100k/7/warm": [0, "d3e4d859fc8ff59dd75789594886eb600b04cb434bfe8f6e59f0eb4988f44fe7"],
    "sensor_hll_100k/7/warm_last": [3, "1025bac5e5ff0dd2288f1f999ac5b3b0c9377d85933b0667634edb4bd78850fd"],
    "sensor_hll_100k/7/first": [4, "c2e7a085e140e7b19b4c7d42fd85d5615a4e8c3f3764699c07a0cdd9bfa9e0b1"],
    "sensor_hll_100k/7/late": [1004, "59d6b2c31c3a62102218caa567696416161bbf1e21cec70a295e135162aa949b"],
    "sensor_hll_100k/2147483659/warm": [0, "84d020211493eb40790838ee58efdad44dd827ea3ffe31f2d11bbfb19a5ff3eb"],
    "sensor_hll_100k/2147483659/warm_last": [3, "ddf8c937f1d735c06cdc4d7656d12fbe2a71ac14adc38559189c1003c6b52412"],
    "sensor_hll_100k/2147483659/first": [4, "db4c3cc986d413072da1a1ea97054b3f5ac28f303edd5d3085ff5e5be2171bd2"],
    "sensor_hll_100k/2147483659/late": [1004, "af757cd1e8136342c99957d008b3d3f8f0efb910bc3d2df3fde949e0fa353902"],
    "sensor_hll_100k/closer9": [9, "89f56bb4dd5ffde027b439d044c415cfc739a0425504cc600513ee49d0c5ca37"],
    "sensor_hop_1k/7/warm": [0, "209561c76e52aea3bbf02dc05e73d26b63a5b7fc67f2ad56f60c0790a7ca01fd"],
    "sensor_hop_1k/7/warm_last": [1, "b544639133713e85d7bb225de4f9409e66cdd4d7104d65fe7160e3a90444437a"],
    "sensor_hop_1k/7/first": [2, "5d10f6d8799e71e42ea6e8728d988f9b260af574ee47bb02e8df1ff2dd950a58"],
    "sensor_hop_1k/7/late": [1002, "d41a05c186feee1c876b7e997343d209d3310ce3dc9b020a8e4177f29f582464"],
    "sensor_hop_1k/2147483659/warm": [0, "d9c60a5a8942bfc1a782276ac59877b61f6a20fe364240a9afb7d21e6aa2aa78"],
    "sensor_hop_1k/2147483659/warm_last": [1, "2bd039973ddad4fe41c4f0aa12789cce614d6b0dc4b30e1ad7b51050a328c12f"],
    "sensor_hop_1k/2147483659/first": [2, "4cb11cf9faf255234c5ed2a615019cbe982566597c82b2d22d51c72b543547e3"],
    "sensor_hop_1k/2147483659/late": [1002, "2f594a6a065ffd063e6052311640fcb0cd99e2720a3e2bb3f958036af1934890"],
    "sensor_hop_1k/closer9": [9, "8823be4a5d2afad9f6ce1e6a1c020d7e665fca92e4f9b0cc35d607c762eb4cbf"],
}


def digest(ts, cols) -> str:
    h = hashlib.sha256()
    h.update(ts.dtype.str.encode())
    h.update(ts.tobytes())
    for k, v in cols.items():
        h.update(k.encode())
        h.update(v.dtype.str.encode())
        h.update(v.tobytes())
    return h.hexdigest()


def full_size(config_name: str) -> tuple:
    config = manifest.load_json("configs", config_name + ".json")
    return manifest.generator_of(config), manifest.size_of(config, False)


# ---- (a) the sensor generator's frames are the parent's, byte for byte ----

@pytest.mark.parametrize("key", sorted(PINNED))
def test_sensor_frames_are_the_parents_byte_for_byte(key):
    config_name, *rest = key.split("/")
    gen, size = full_size(config_name)
    index, want = PINNED[key]
    if rest[0].startswith("closer"):
        # the parent's closer(size, names, last_pane=9): the closers after
        # a stream whose last frame lies in pane 9
        n_frames = gen.warm_frames(size) + 7 * gen.frames_per_pane(size) + 1
        assert gen.pane_of(size, n_frames - 1) == index
        [(stream, ts, cols, events)] = gen.closers(size, n_frames)
        assert events == 1
    else:
        stream, ts, cols, events = gen.frame(size, int(rest[0]), index)
        assert events == size["frame_rows"] == len(ts)
    assert stream == size["stream"]
    assert digest(ts, cols) == want


@pytest.mark.parametrize("config_name", ["sensor_hll_100k", "sensor_hop_1k"])
def test_sensor_pulls_are_the_parents_statements_and_windows(config_name):
    """`run.py:351-372` of the parent, moved into the generator: the
    statement before the closer, the one after, the windows due."""
    gen, size = full_size(config_name)
    n_frames = gen.warm_frames(size) + 3 * gen.frames_per_pane(size) + 5
    plan = gen.pulls(size, n_frames)
    width = size["size_ms"] // size["advance_ms"]
    last_pane = gen.pane_of(size, n_frames - 1)
    assert last_pane == 5
    newest_closed = last_pane - width
    view = size["view"]
    assert plan["before"] == [{
        "sql": f"SELECT * FROM {view} WHERE winStart = "
               f"{gen.BASE + newest_closed * size['advance_ms']};",
        "complete": [newest_closed], "if_rows": True}]
    n_windows = last_pane + width
    fit = max(1, size["view_rows_kept"] // size["keys"] - 1)
    assert plan["after"] == [{
        "sql": f"SELECT * FROM {view};",
        "complete": list(range(last_pane - min(n_windows, fit) + 1,
                               last_pane + 1))}]
    assert plan["horizon"] == (gen.BASE + (last_pane + 1)
                               * size["advance_ms"] + size["size_ms"])
    # the reader's draw: the parent's rng call and statement
    a = gen.reader_pull(size, np.random.default_rng([3, 2]))
    k = int(np.random.default_rng([3, 2]).integers(0, size["keys"]))
    assert a == {"key": k, "sql": f"SELECT * FROM {view} WHERE "
                 f"{size['columns'][0]} = '{gen.key_names(size)[k]}';"}


def test_a_configuration_without_a_generator_is_an_error():
    with pytest.raises(SystemExit, match="names no generator"):
        manifest.generator_of({"name": "x"})
    for entry in MAN["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            assert json.load(f)["generator"] == "sensor"


def test_nothing_forwards_to_the_old_generator_module():
    """No module left under `harness/` for the generator, and the three
    general files name no stream, column, window shape or private
    count of the pipeline's (the words are spelt in halves so that a
    search of this directory finds them nowhere)."""
    old = "gener" + "ator"
    assert not os.path.exists(os.path.join(
        manifest.BENCH_DIR, "harness", old + ".py"))
    for name in ("run.py", "harness/producer.py", "harness/served.py"):
        with open(os.path.join(manifest.BENCH_DIR, name)) as f:
            text = f.read()
        for word in ("_take" + "_seq", "winStart", "winEnd", "sensors",
                     "'temp'", '"temp"', "per_device", "size_ms",
                     "harness." + old, "harness import " + old):
            assert word not in text, (name, word)


# ---- (b) a toy generator through the whole of run.py --------------------

def toy_drive(cell: str, seed: int, **kw):
    return drive(cell, seed, manifest=TOY, **kw)


def test_toy_generator_runs_correct_through_run_py():
    rc, line, err = toy_drive("toy_clicks.replay", 2**31 + 77)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"events_per_s", "append_ack_p95_ms",
                                    "setup_s"}
    assert line["metrics"]["events_per_s"]["value"] > 0
    assert set(line["compared"]) >= {"rows_missing", "hits_mismatch",
                                     "bytes_mismatch", "late_drops"}
    info = json.loads(next(ln for ln in err.splitlines()
                           if ln.startswith("# info "))[len("# info "):])
    # events out of pane order reached the query and none was late
    assert info["frames"] > 20 and info["calls"] > 5


def test_toy_generator_with_an_altered_answer_reads_incorrect():
    rc, line, err = toy_drive("toy_clicks.replay", 31,
                              fault="answer_altered")
    assert rc == 0, err[-3000:]
    assert line["correct"] is False, line["compared"]
    over = {k for k, p in line["compared"].items()
            if p["value"] > p["limit"]}
    assert over & {"hits_mismatch", "bytes_mismatch", "rows_missing"}


def test_toy_frames_are_zipf_and_out_of_order_inside_the_grace():
    config = manifest_of_toy("toy_clicks")
    gen = toy_module("generators", "clicks")
    size = manifest.size_of(config, True)
    n_warm = gen.warm_frames(size)
    back = total = 0
    hist = np.zeros(size["keys"], np.int64)
    wm = -1
    for i in range(n_warm + 3 * gen.frames_per_pane(size)):
        kids, _nbytes, ts = gen.draw(size, 9, i)
        lo = gen.T0 + gen.pane_of(size, i) * size["advance_ms"]
        back += int((ts < lo).sum())
        total += len(ts)
        hist += np.bincount(kids, minlength=size["keys"])
        # no event's window had closed when its frame arrived
        ends = (ts - gen.T0) // size["advance_ms"] * size["advance_ms"] \
            + gen.T0 + size["advance_ms"]
        assert (ends + size["grace_ms"] > wm).all(), i
        wm = max(wm, int(ts.max()))
    assert 0.03 < back / total < 0.2
    assert hist[0] > 4 * hist[size["keys"] // 2] > 0


# ---- (c) two streams, no query: every frame on its own stream's log -----

def manifest_of_toy(name: str) -> dict:
    with open(os.path.join(TOY_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def toy_module(kind: str, name: str):
    manifest.use(TOY)
    try:
        return manifest.module_of(kind, name)
    finally:
        manifest.use(None)


def log_frames(ctx, stream: str) -> list[int]:
    """The frame index every payload on the stream's log carries."""
    from hstream_tpu.common import columnar
    from hstream_tpu.common.records import parse_record

    reader = ctx.store.new_reader()
    reader.start_reading(ctx.streams.get_logid(stream), 0)
    reader.set_timeout(0)
    seqs = []
    while True:
        got = reader.read(64)
        if not got:
            return seqs
        for b in got:
            for payload in getattr(b, "payloads", []):
                _ts, cols = columnar.decode_columnar(
                    parse_record(payload).payload)
                seq = np.asarray(cols["seq"][1])
                assert (seq == seq[0]).all()
                seqs.append(int(seq[0]))


def test_two_stream_generator_every_frame_on_its_own_log_in_send_order(
        tmp_path):
    from hstream_tpu.server.main import serve

    config = manifest_of_toy("toy_two_streams")
    gen = toy_module("generators", "two_streams")
    size = manifest.size_of(config, False)
    size_path = tmp_path / "size.json"
    size_path.write_text(json.dumps(size))
    log_path = tmp_path / "producer.json"
    n_warm = gen.warm_frames(size)
    server, ctx = serve("127.0.0.1", 0, "mem://")
    client = proc = None
    try:
        client = served.Client(ctx.port)
        names = [st["name"] for st in gen.streams(size)]
        for name in names:
            client.sql(f"CREATE STREAM {name};")
        assert all(ctx.streams.get_logid(n) is not None for n in names)
        for lo in range(0, n_warm, size["frames_per_call"]):
            client.append_call([gen.frame(size, 4, i) for i in range(
                lo, lo + size["frames_per_call"])])
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
        proc = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.harness.producer",
             "--size", str(size_path), "--seed", "4", "--first-frame",
             str(n_warm), "--encoders", "3", "--manifest", TOY,
             "--log", str(log_path)],
            cwd=ROOT, env=env, stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        proc.stdin.write(f"port {ctx.port}\ngo\n")
        proc.stdin.flush()
        assert proc.stdout.readline().strip() == "ready"
        time.sleep(1.0)
        proc.stdin.write("stop\n")
        proc.stdin.flush()
        assert proc.wait(60) == 0
        plog = json.loads(log_path.read_text())
        assert plog["error"] is None and len(plog["calls"]) >= 6
        n_frames = n_warm + sum(c[1] for c in plog["calls"] if c[6])
        for closer in gen.closers(size, n_frames):
            client.append_call([closer])
        # a call is of one stream, and says which and how many events
        for first, frames, *_rest, ok, stream, events in plog["calls"]:
            assert ok and stream == gen.stream_of(size, first)
            assert events == frames * gen.ROWS[stream]
        on_log = {name: log_frames(ctx, name) for name in names}
        for name in names:
            mine = [i for i in range(n_frames)
                    if gen.stream_of(size, i) == name]
            assert on_log[name] == mine + [-1], name  # send order, closer
        assert served.acked_not_stored(ctx, names, client.frames_acked,
                                       plog["calls"]) == 0
        # one acknowledged frame too many reads as a loss
        assert served.acked_not_stored(
            ctx, names, {**client.frames_acked, "rhs": 99},
            plog["calls"]) > 0
    finally:
        if proc is not None and proc.poll() is None:
            proc.kill()
            proc.wait(30)
        if client is not None:
            client.close()
        server.stop(grace=1)
        ctx.shutdown()


def test_a_call_of_two_streams_is_refused():
    gen = toy_module("generators", "two_streams")
    size = manifest.size_of(manifest_of_toy("toy_two_streams"), False)
    client = served.Client.__new__(served.Client)
    with pytest.raises(RuntimeError, match="one stream"):
        client.append_call([gen.frame(size, 1, 3), gen.frame(size, 1, 4)])


# ---- (d) the server block ------------------------------------------------

def test_server_block_goes_to_serve_and_an_unknown_key_is_refused():
    from hstream_tpu.server.main import serve

    assert manifest.server_options({"name": "c"}, serve) == {}
    assert manifest.server_options({"name": "c", "server": {}}, serve) == {}
    block = {"mesh_shape": "1x4", "snapshot_interval_ms": 500}
    assert manifest.server_options({"name": "c", "server": block},
                                   serve) == block
    for bad in ({"mesh": "1x4"}, {"port": 1}, {"store_uri": "mem://"}):
        with pytest.raises(SystemExit, match="does not take"):
            manifest.server_options({"name": "c", "server": bad}, serve)


def test_a_cells_chips_are_the_devices_its_mesh_asks_for():
    assert manifest.mesh_devices({}) is None
    assert manifest.mesh_devices({"server": {"mesh_shape": "1x4"}}) == 4
    assert manifest.mesh_devices({"server": {"mesh_shape": "2x2"}}) == 4
    assert manifest.mesh_devices({"server": {"mesh_shape": "4"}}) == 4
    for w in MAN["workloads"]:
        config = manifest.cell(w["name"])["config"]
        want = manifest.mesh_devices(config)
        assert want is None or want == w["chips"], w["name"]
    # the two accepted configurations state no server option
    for entry in MAN["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            assert "server" not in json.load(f)


def test_mesh_shape_in_the_server_block_gives_a_sharded_executor():
    """`executor_wrong` holds the run to `ShardedQueryExecutor`: the
    block reached `serve()`. On the suite's virtual CPU devices."""
    rc, line, err = toy_drive("toy_clicks_mesh.replay", 41, devices=8)
    assert rc == 0, err[-3000:]
    assert line["correct"] is True, err[-3000:]
    assert line["compared"]["executor_wrong"] == {"value": 0, "limit": 0}
    assert line["device"]["count"] == 8


def test_an_unknown_server_key_stops_the_run():
    """The fixture's `toy_bad_server` configuration misspells
    `mesh_shape`: the run stops before it serves, and says why."""
    rc, line, err = toy_drive("toy_bad_server.replay", 1)
    assert rc != 0 and line is None
    assert "mesh_shap" in err and "does not take" in err


# ---- (e) a task without a pipeline ---------------------------------------

class FakeTask:
    """What `served` touches of a task, for an executor that has no
    ingest pipeline and a query that never counted `consumed_events`."""

    def __init__(self, pipe=None):
        self.state_lock = threading.Lock()
        self._pipe = pipe
        self.executor = object()
        self.info = types.SimpleNamespace(query_id="view-none")


def fake_ctx():
    from hstream_tpu.stats import StatsHolder

    return types.SimpleNamespace(stats=StatsHolder())


def test_a_task_without_a_pipeline_reads_zero_and_nones():
    from benchmarks.readers import events_per_s, pipe_step_occupancy_pct
    from benchmarks.run import relay_consumed

    ctx, task = fake_ctx(), FakeTask()
    assert served.pipe_of(task) is None
    assert served.consumed_events(ctx, task) == 0
    start = served.counters(ctx, task, "some_view")
    end = served.counters(ctx, task, "some_view")
    assert start["pipe"] == {} and start["close_stats"] == {}
    assert start["consumed_events"] == 0 and start["read_extracts"] == 0
    run = {"start": start, "end": end, "window_s": 2.0}
    assert events_per_s.read(run, {}) == 0.0
    assert pipe_step_occupancy_pct.read(run, {}) is None
    for name in ("pipe_stage_wait_pct", "task_state_wait_pct",
                 "close_cycle_p50_ms"):
        spec, read = manifest.reader_of(name)
        assert read(run, spec) is None, name  # not due, not a crash
    # the relay tells the producer 0 once and stops when told to
    proc = types.SimpleNamespace(stdin=io.StringIO())
    stop = threading.Event()
    t = threading.Thread(target=relay_consumed, args=(
        proc, lambda: served.consumed_events(ctx, task), 0, stop))
    t.start()
    time.sleep(0.1)
    stop.set()
    t.join(5)
    assert not t.is_alive()
    assert proc.stdin.getvalue() == "consumed 0\n"


def test_consumption_is_the_public_count_and_the_relay_follows_it():
    from benchmarks.readers import events_per_s
    from benchmarks.run import relay_consumed

    ctx, task = fake_ctx(), FakeTask()
    ctx.stats.stat_add("consumed_events", "view-none", 4096.0)
    ctx.stats.stat_add("consumed_events", "view-other", 7.0)
    start = served.counters(ctx, task, "v")
    assert start["consumed_events"] == 4096
    proc = types.SimpleNamespace(stdin=io.StringIO())
    stop = threading.Event()
    t = threading.Thread(target=relay_consumed, args=(
        proc, lambda: served.consumed_events(ctx, task),
        start["consumed_events"], stop))
    t.start()
    for _ in range(3):
        ctx.stats.stat_add("consumed_events", "view-none", 1024.0)
        time.sleep(0.08)
    stop.set()
    t.join(5)
    end = served.counters(ctx, task, "v")
    lines = proc.stdin.getvalue().split()
    assert lines[-2:] == ["consumed", "3072"]
    assert events_per_s.read({"start": start, "end": end,
                              "window_s": 1.5}, {}) == 2048.0


def test_the_reader_pulls_the_generators_statement():
    """`served.Reader` knows no view and no column: the draw is the
    generator's, and what it says of itself is kept beside the rows."""
    gen = toy_module("generators", "clicks")
    size = manifest.size_of(manifest_of_toy("toy_clicks"), True)
    asked = []
    client = types.SimpleNamespace(
        sql=lambda text: asked.append(text) or [{"site": "x"}])
    reader = served.Reader(client, gen, size, 11, 0.0)
    got = [reader.pull() for _ in range(3)]
    rng = np.random.default_rng([11, 2])
    want = [gen.reader_pull(size, rng) for _ in range(3)]
    assert asked == [w["sql"] for w in want]
    for g, w in zip(got, want):
        assert g["site"] == w["site"] and g["rows"] == [{"site": "x"}]
        assert g["t1"] >= g["t0"] and "sql" not in g
