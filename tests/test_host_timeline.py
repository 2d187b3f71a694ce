"""One host timeline (ISSUE 25): every stage span is a profiler
annotation on its own thread's line, the waits and lock holds are
spans, and the served path's program names are pinned.

One served toy query is driven once for the whole module: one warm
batch (the executor is built on the first batch it sees: set-up, and
without a span), then, under a `jax.profiler` session on the CPU
backend, 50 framed batches through the ingest pipeline, a few close
cycles, one pull. The tests read what that run left in the stage
histograms and in the trace's host plane.
"""

import glob
import os
import time

import grpc
import numpy as np
import pytest

from hstream_tpu.common.tracing import (
    KERNEL_FAMILIES,
    TRACE_PARENT,
    TRACE_STAGES,
    QueryTracer,
    begin_span,
    trace_span,
)
from hstream_tpu.engine import lattice
from hstream_tpu.proto import api_pb2 as pb
from hstream_tpu.proto.rpc import HStreamApiStub
from hstream_tpu.server.main import serve

from helpers import wait_attached

BASE = 1_700_000_000_000
WARM = 1
BATCHES = 50
ROWS = 2048
KEYS = 16
SESSION = "timeline_session"

# the table of ISSUE 25 §2: stage -> the thread it is observed on
PER_BATCH = ("state_wait", "key_encode", "ring_wait", "stage_wait",
             "step", "encode")
PER_PULL = ("pull_state_wait", "pull_hold", "pull_serve")


def _frame(i: int):
    """Batch i: ROWS events over KEYS string keys, event times walking
    forward 1 s a batch, so a 10 s window closes every ten batches."""
    rng = np.random.default_rng(i)
    ts = BASE + i * 1000 + np.sort(rng.integers(0, 1000, ROWS))
    cols = {"k": np.array([f"dev{j % KEYS}" for j in range(ROWS)]),
            "v": rng.integers(0, 100, ROWS).astype(np.float64)}
    return ts.astype(np.int64), cols


def _wait(pred, what: str, timeout: float = 60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(0.01)
    raise TimeoutError(what)


def _histograms(ctx) -> dict:
    out: dict = {"stage_latency_ms": {}, "kernel_dispatch_ms": {}}
    for (metric, label), h in ctx.stats.histograms_snapshot().items():
        if metric in out:
            _cum, total, count = h.snapshot()
            out[metric][label] = (count, total)
    return out


def _host_lines(xplane: str) -> list:
    """[(line name, [(event name, start ns, end ns)])] of the host
    plane, one entry per thread."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane)
    plane = next(p for p in data.planes if p.name == "/host:CPU")
    return [(line.name,
             [(ev.name, int(ev.start_ns),
               int(ev.start_ns) + int(ev.duration_ns))
              for ev in line.events])
            for line in plane.lines]


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    import jax

    from hstream_tpu.client.producer import ColumnarProducer, encode_batch

    trace_dir = str(tmp_path_factory.mktemp("timeline"))
    server, ctx = serve("127.0.0.1", 0, "mem://")
    ch = grpc.insecure_channel(f"127.0.0.1:{ctx.port}")
    stub = HStreamApiStub(ch)
    out: dict = {}
    try:
        stub.CreateStream(pb.Stream(stream_name="tlsrc"))
        stub.ExecuteQuery(pb.CommandQuery(
            stmt_text="CREATE VIEW tlview AS SELECT k, COUNT(*) AS c, "
                      "SUM(v) AS s FROM tlsrc GROUP BY k, "
                      "TUMBLING (INTERVAL 10 SECOND) "
                      "GRACE BY INTERVAL 0 SECOND;"))
        task = wait_attached(ctx, "view-tlview")
        producer = ColumnarProducer(ch, "tlsrc")

        def stepped(n):
            def done():
                with task.state_lock:
                    pipe = task._pipe
                return (pipe is not None and pipe.pending == 0
                        and pipe.stats()["batches_stepped"] >= n)
            return done

        producer.append_stream_frames([encode_batch(*_frame(0))])
        _wait(stepped(WARM), "the warm batch to be stepped")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(SESSION):
                for i in range(WARM, WARM + BATCHES):
                    producer.append_stream_frames(
                        [encode_batch(*_frame(i))])
                _wait(stepped(WARM + BATCHES),
                      "the task to step every batch")
                resp = stub.ExecuteQuery(pb.CommandQuery(
                    stmt_text="SELECT * FROM tlview WHERE k = 'dev3';"))
                out["pull_rows"] = len(resp.result_set)
        finally:
            jax.profiler.stop_trace()
        if task.error is not None:
            raise task.error
        with task.state_lock:
            out["pipe_stats"] = dict(task._pipe.stats())
            out["take_seq"] = task._pipe._take_seq
            out["executor"] = task.executor
        out["hist"] = _histograms(ctx)
        out["consumed_events"] = ctx.stats.stat_ladder(
            "consumed_events", "view-tlview")["total"]
        out["read_extracts"] = int(ctx.stats.stream_stat_get(
            "read_extracts", "tlview"))
        xplane = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        out["lines"] = _host_lines(xplane)
    finally:
        ch.close()
        server.stop(grace=1)
        ctx.shutdown()
    return out


# ---- the spans, in the histograms the benchmark's readers difference ------


@pytest.mark.parametrize("stage", PER_BATCH)
def test_stage_observed_once_per_batch(run, stage):
    count, _total = run["hist"]["stage_latency_ms"][stage]
    assert count >= BATCHES, (stage, count)


@pytest.mark.parametrize("stage", ("read_wait", "store_read", "decode",
                                   "emit"))
def test_stage_observed(run, stage):
    assert run["hist"]["stage_latency_ms"][stage][0] >= 1


def test_stage_wait_and_encode_count_the_batches_exactly(run):
    """A batch taken is one `stage_wait`, a batch staged one `encode`,
    whether or not anything waited; the public count agrees with the
    pipeline's own."""
    stages = run["hist"]["stage_latency_ms"]
    n = WARM + BATCHES
    assert stages["stage_wait"][0] == n
    assert stages["encode"][0] == n
    assert run["pipe_stats"]["batches_stepped"] == n
    assert run["take_seq"] == n
    assert run["consumed_events"] == n * ROWS


def test_close_cycle_and_its_halves(run):
    """`stage_latency_ms{close}` is recorded on the fused-close path,
    once per cycle, with the D2H sync and the decode split out."""
    stages = run["hist"]["stage_latency_ms"]
    cycles = run["executor"].close_stats["close_cycles"]
    assert cycles >= 3
    assert stages["close"][0] == cycles
    assert stages["close_fetch"][0] == cycles
    assert stages["close_decode"][0] == cycles
    assert stages["close"][1] >= (stages["close_fetch"][1]
                                  + stages["close_decode"][1])


@pytest.mark.parametrize("stage", PER_PULL)
def test_stage_observed_once_per_pull(run, stage):
    assert run["pull_rows"] >= 1
    assert run["hist"]["stage_latency_ms"][stage][0] == 1


def test_peek_family_counts_the_peeks(run):
    assert run["read_extracts"] == 1
    assert run["hist"]["kernel_dispatch_ms"]["peek"][0] == 1


def test_every_name_used_is_declared(run):
    assert set(run["hist"]["stage_latency_ms"]) <= TRACE_STAGES
    assert set(run["hist"]["kernel_dispatch_ms"]) <= KERNEL_FAMILIES
    assert "peek" in KERNEL_FAMILIES
    assert set(TRACE_PARENT) | set(TRACE_PARENT.values()) <= TRACE_STAGES
    for stage in TRACE_PARENT:  # a forest: every chain ends
        seen = set()
        while stage in TRACE_PARENT:
            assert stage not in seen
            seen.add(stage)
            stage = TRACE_PARENT[stage]


def test_summary_has_a_lifetime_max():
    tr = QueryTracer(capacity=2)
    for ms in (4500, 1, 2, 3):  # the stall has left the ring
        tr.record("step", ms / 1e3)
    s = tr.summary()["step"]
    assert s["max_ms"] == pytest.approx(4500.0)
    assert s["p95_ms"] < 10


def test_begin_span_ends_once_and_is_free_without_a_tracer():
    tr = QueryTracer()
    wait = begin_span(tr, "state_wait")
    time.sleep(0.002)
    wait.end()
    wait.end()
    assert tr.summary()["state_wait"]["count"] == 1
    assert tr.summary()["state_wait"]["total_ms"] >= 1.5
    begin_span(None, "state_wait").end()


def test_sampled_spans_carry_their_declared_parent():
    from hstream_tpu.common.tracing import SpanCollector

    spans = SpanCollector(1.0)
    tr = QueryTracer()
    tr.bind_trace(spans, scope="q", trace_id="t")
    with trace_span(tr, "step"):
        with trace_span(tr, "close"):
            pass
    got = {s["stage"]: s for s in spans.spans("q")}
    assert got["close"]["attrs"] == {"parent_stage": "step"}
    assert "attrs" not in got["step"]


# ---- the same spans, on the profiler's clock -------------------------------


def _named(lines, prefix):
    return [(name, evs) for name, evs in lines if name.startswith(prefix)]


def test_each_thread_has_its_own_named_line(run):
    """The task, each encode worker, the prefetch thread and the pull's
    handler thread each have a line of the host plane, named by the
    thread, holding that thread's stages and no other's."""
    lines = run["lines"]
    (_n, task), = _named(lines, "query-view-tlv")
    (_n, read), = _named(lines, "read-view-tlvi")
    encs = _named(lines, "ingest-enc-")
    pulls = [evs for _n, evs in _named(lines, "rpc_")
             if any(e[0] == "pull_hold" for e in evs)]
    assert len(encs) >= 1 and len(pulls) == 1

    def names(evs):
        return {e[0] for e in evs}

    assert {"read_wait", "state_wait", "key_encode", "step", "stage_wait",
            "ring_wait", "close", "close_fetch", "close_decode", "emit",
            "dispatch:step", "dispatch:close"} <= names(task)
    assert "store_read" in names(read)
    for _n, evs in encs:
        assert "encode" in names(evs)
        assert not names(evs) & {"step", "key_encode", "pull_hold"}
    assert {"pull_state_wait", "pull_hold", "dispatch:peek",
            "pull_serve"} <= names(pulls[0])
    assert not names(task) & {"encode", "store_read", "pull_hold"}
    with_spans = [n for n, evs in lines
                  if names(evs) & {"key_encode", "step", "encode",
                                   "pull_hold"}]
    assert len(set(with_spans)) >= 3


def test_every_span_lies_inside_the_session(run):
    lines = run["lines"]
    (lo, hi), = [(a, b) for _n, evs in lines for n, a, b in evs
                 if n == SESSION]
    seen = 0
    for _name, evs in lines:
        for n, a, b in evs:
            if n in TRACE_STAGES or n.startswith("dispatch:"):
                assert lo <= a <= b <= hi, (n, a, b)
                seen += 1
    assert seen >= 6 * BATCHES


def test_each_child_lies_inside_its_parent(run):
    checked = set()
    parents = dict(TRACE_PARENT)
    parents.update({"dispatch:step": "step", "dispatch:close": "close",
                    "dispatch:peek": "pull_hold"})
    for _name, evs in run["lines"]:
        by_name: dict = {}
        for n, a, b in evs:
            by_name.setdefault(n, []).append((a, b))
        for child, parent in parents.items():
            for a, b in by_name.get(child, ()):
                assert any(pa <= a and b <= pb
                           for pa, pb in by_name.get(parent, ())), \
                    (child, parent)
                checked.add(child)
    # a session query alone shows the session path's stages
    # (tests/test_session_served.py holds their nesting), a join query
    # alone the join's (tests/test_new_users_served.py), and a query
    # whose keys churn alone retires any (tests/test_key_retire.py)
    assert checked == {c for c in parents
                       if not c.startswith(("session_", "join_"))
                       and c != "key_retire"}
    assert not [n for _name, evs in run["lines"] for n, _a, _b in evs
                if n.startswith(("join_", "dispatch:join"))]


def test_top_level_stages_cover_the_task_threads_wall(run):
    """The task thread's top-level stages do not overlap and sum to
    within 5% of its wall over the run's 50 batches: nothing it does
    for long is without a span."""
    (_n, evs), = _named(run["lines"], "query-view-tlv")
    top = sorted((a, b) for n, a, b in evs
                 if n in TRACE_STAGES and n not in TRACE_PARENT)
    for (_a0, b0), (a1, _b1) in zip(top, top[1:]):
        assert a1 >= b0  # non-overlapping, by declaration
    wall = top[-1][1] - top[0][0]
    covered = sum(b - a for a, b in top)
    assert covered >= 0.95 * wall, (covered, wall)


# ---- device names that survive a refactor ----------------------------------


def test_pinned_program_names_are_what_lattice_says(run):
    """A trace names a program `jit_<function>`: the constants a
    reduction matches on are the names of the functions that run."""
    ex = run["executor"]
    fns = lattice.compiled(ex.spec, ex.schema, ex._filter_expr,
                           min(ex.batch_capacity
                               * ex.spec.windows_per_record,
                               ex.spec.n_keys * ex.spec.n_slots),
                           ex._layout)
    assert ex._extract_slots is fns.extract_slots
    assert "jit_" + fns.extract_slots.__name__ == lattice.PEEK_PROGRAM
    assert lattice.PEEK_PROGRAM == "jit_peek_slots"
    assert ("jit_" + fns.extract_reset_slots.__name__
            == lattice.CLOSE_PROGRAM == "jit_extract_and_reset")
    assert "jit_" + fns.step.__name__ == lattice.STEP_PROGRAM == "jit_step"
    # it alone: the other extracts keep their own name
    assert fns.extract_slot.__name__ == "extract"
    assert fns.extract_touched.__name__ == "extract"
    # and the trace of the run shows them, each under its family
    inside = {"PjitFunction(step)": "dispatch:step",
              "PjitFunction(extract_and_reset)": "dispatch:close",
              "PjitFunction(peek_slots)": "dispatch:peek"}
    found = set()
    for _name, evs in run["lines"]:
        for n, a, b in evs:
            if n in inside:
                assert any(m == inside[n] and pa <= a and b <= pb
                           for m, pa, pb in evs), n
                found.add(n)
    assert found == set(inside)
