"""First-class step tracing (SURVEY §5.1).

The reference has no tracing at all — its closest artifact is a
logDebug inside the poll loop (Processor.hs:131-133). Here every query
task records per-batch stage timings (decode, key-encode, device step,
emission, snapshot) into a bounded ring per query, cheap enough to stay
always-on: one perf_counter pair per stage, no allocation beyond the
ring slot.

`trace_span(tracer, stage)` is the instrumentation point;
`QueryTracer.summary()` aggregates count/total/mean/p50/p95/max per
stage for the admin surface (admin CLI `trace` command, HTTP
/queries/<id>).

One host timeline (ISSUE 25): every span also holds a
`jax.profiler.TraceAnnotation` (a TraceMe) open for its life, named by
the stage (`dispatch:<family>` for a kernel family). It is inert unless
a profiler session is live; when one is, the span lands on its own
thread's line of the trace's host plane, on the profiler's clock — the
device planes' clock. The program keeps no clock and no ring of its
own for this. `name_os_thread()` gives that line its thread's name.

ISSUE 13 grows the request-id correlation into cross-component trace
spans: `SpanCollector` keeps bounded per-scope rings of completed spans
(trace id + span id + parent), exported as Chrome trace-event JSON via
`GET /queries/<id>/trace` / `admin trace --spans`. The trace id IS the
request id (already propagated client -> gateway -> handler), so one
sampled request's journey — RPC handler, append-front stages, the
query task's pipeline stages, subscription delivery — shares one id.
Disarmed cost is ONE attribute read + one branch (`collector.active`,
the FlowGovernor / FAULTS discipline); the sampling decision is a
deterministic hash of the trace id so every component agrees without
coordination.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import time
import uuid
import zlib
from collections import defaultdict, deque, OrderedDict

from hstream_tpu.stats.devicecost import DEVICE_TIME as _DEVICE_TIME


class QueryTracer:
    """Bounded per-stage duration rings for one query.

    `observer(stage, seconds)` (optional) is invoked on every record —
    the hook the stats holder's stage-latency histograms ride, so the
    rings stay self-contained while /metrics sees every span.
    `request_id` carries the correlation id of the request that created
    the query (ISSUE 3), surfaced by summary() / admin trace."""

    def __init__(self, capacity: int = 512, *, observer=None):
        self._cap = capacity
        self._rings: dict[str, deque[float]] = defaultdict(
            lambda: deque(maxlen=capacity))
        self._counts: dict[str, int] = defaultdict(int)
        self._totals: dict[str, float] = defaultdict(float)
        self._maxes: dict[str, float] = defaultdict(float)
        self._lock = threading.Lock()
        self._observer = observer
        self.request_id: str | None = None
        # cross-component trace binding (ISSUE 13): when the request
        # that created this query was SAMPLED, every completed stage
        # timing also lands as a span in the collector's per-query
        # ring, under the creating request's trace id. Unbound cost:
        # one attribute read + one branch per record().
        self._spans: "SpanCollector | None" = None
        self._span_scope: str | None = None
        self._trace_id: str | None = None
        self._parent_span: str = ""

    def bind_trace(self, collector: "SpanCollector", *, scope: str,
                   trace_id: str, parent_id: str = "") -> None:
        """Attach this tracer's stage timings to a sampled trace: spans
        land in `collector` under `scope` (the query id), parented on
        the creating request's handler span."""
        self._span_scope = scope
        self._trace_id = trace_id
        self._parent_span = parent_id
        self._spans = collector

    def record(self, stage: str, seconds: float) -> None:
        with self._lock:
            self._rings[stage].append(seconds)
            self._counts[stage] += 1
            self._totals[stage] += seconds
            if seconds > self._maxes[stage]:
                self._maxes[stage] = seconds
        if self._observer is not None:
            try:
                self._observer(stage, seconds)
            except Exception:  # noqa: BLE001 — observers are metrics
                pass           # plumbing; never fail the traced stage
        spans = self._spans
        if spans is not None:
            try:
                dur_ms = seconds * 1e3
                parent = TRACE_PARENT.get(stage)
                spans.record_span(
                    self._span_scope, stage,
                    trace_id=self._trace_id, span_id=new_span_id(),
                    parent_id=self._parent_span,
                    t0_ms=time.time() * 1e3 - dur_ms, dur_ms=dur_ms,
                    **({"parent_stage": parent} if parent else {}))
            except Exception:  # noqa: BLE001 — span plumbing must
                pass           # never fail the traced stage

    def summary(self) -> dict[str, dict[str, float]]:
        """stage -> {count, total_ms, mean_ms, p50_ms, p95_ms, max_ms}
        over the ring (percentiles) and lifetime (count/total/max: one
        4.5 s stall among a million 40 ms batches still shows)."""
        out: dict[str, dict[str, float]] = {}
        with self._lock:
            for stage, ring in self._rings.items():
                if not ring:
                    continue
                xs = sorted(ring)
                n = len(xs)
                out[stage] = {
                    "count": self._counts[stage],
                    "total_ms": round(self._totals[stage] * 1e3, 3),
                    "mean_ms": round(
                        self._totals[stage] / self._counts[stage] * 1e3,
                        3),
                    "p50_ms": round(xs[n // 2] * 1e3, 3),
                    "p95_ms": round(xs[min(n - 1, (n * 95) // 100)] * 1e3,
                                    3),
                    "max_ms": round(self._maxes[stage] * 1e3, 3),
                }
        if self.request_id:
            out["request"] = {"id": self.request_id}
        return out


_TraceMe = None  # jax.profiler.TraceAnnotation, imported at first use


def _annotation(name: str):
    """A profiler annotation named `name`: a TraceMe, inert (under half
    a microsecond) unless a profiler session is live. Imported lazily
    so the SQL client, which shares this module's header keys, does
    not pay JAX's import."""
    global _TraceMe
    if _TraceMe is None:
        from jax.profiler import TraceAnnotation

        _TraceMe = TraceAnnotation
    return _TraceMe(name)


def name_os_thread() -> None:
    """Give the calling thread's OS thread its Python name (cut to the
    kernel's 15 bytes). The profiler's host plane, `top -H` and perf
    name a thread's line by it; CPython sets it only from 3.14 on, so
    every thread that records spans calls this first. Linux only; a
    no-op where `/proc/thread-self/comm` is not there."""
    try:
        with open("/proc/thread-self/comm", "w") as f:
            f.write(threading.current_thread().name[:15])
    except OSError:
        pass


@contextlib.contextmanager
def trace_span(tracer: QueryTracer | None, stage: str):
    """Time a stage into the tracer, under a profiler annotation of the
    same name; no-op when tracer is None."""
    if tracer is None:
        yield
        return
    t0 = time.perf_counter()
    try:
        with _annotation(stage):
            yield
    finally:
        tracer.record(stage, time.perf_counter() - t0)


class begin_span:  # noqa: N801 — reads as the verb it is, like trace_span
    """A span ended by hand: for a wait that ends INSIDE the `with` of
    the lock it waited for, where no block can hold it —

        wait = begin_span(self.tracer, "state_wait")
        with self.state_lock:
            wait.end()

    so the wait is named at the call site by who waited (the lock's own
    `lock_wait_ms` cannot tell the task from a pull) and the analyzer
    still sees a plain `with self.<lock>`. `end()` runs on the thread
    that began the span; a second call is a no-op."""

    __slots__ = ("_tracer", "_stage", "_t0", "_ann")

    def __init__(self, tracer: QueryTracer | None, stage: str):
        self._tracer = tracer
        if tracer is None:
            return
        self._stage = stage
        self._ann = _annotation(stage)
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def end(self) -> None:
        tracer = self._tracer
        if tracer is None:
            return
        self._tracer = None
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(None, None, None)
        tracer.record(self._stage, dt)


# ---- cross-component trace spans (ISSUE 13) --------------------------------

# gRPC metadata / HTTP header keys the trace context travels under.
# The trace id itself rides the existing x-request-id; only the parent
# span id needs a new key.
TRACE_ID_KEY = "x-trace-id"
PARENT_SPAN_KEY = "x-parent-span"

# THE declared stage vocabulary: every span name / trace_span stage /
# append-stage literal must come from this set. The analyzer registry
# pass cross-checks call sites against it (a renamed stage would
# otherwise silently orphan its stage_latency_ms series and its spans).
TRACE_STAGES = frozenset({
    # query-task pipeline stages (QueryTracer rings + stage_latency_ms)
    "decode", "key_encode", "step", "emit", "snapshot", "close",
    # the waits of the task thread and the halves of a close cycle
    # (ISSUE 25): observed once per batch whether or not anything
    # waited, so a label that is absent means a renamed stage
    "read_wait", "state_wait", "ring_wait", "stage_wait",
    "close_fetch", "close_decode",
    # a window-lattice query's full key table freeing the ids of dead
    # group keys (engine/executor.py _retire_keys), on the task thread
    # inside key_encode
    "key_retire",
    # the task's helper threads: encode workers, store prefetch
    "encode", "store_read",
    # the device session path inside `step` (engine/session.py): the
    # key-code dictionary, the host interval mirror (late walk,
    # segmentation, chain merge), packing the batch, one close cycle
    # and its halves, a code-space compaction
    "session_key_codes", "session_mirror", "session_pack",
    "session_close", "session_close_fetch", "session_close_decode",
    "session_remap",
    # the device join inside `step` (engine/join.py): the join-key
    # dictionary and the batch's sort, the host shadow that sizes the
    # match buffer, packing the batch, the wait for the device's match
    # count (fused window join) or match buffer, decoding that buffer,
    # and a window join's eviction of closed windows with the codes'
    # reclamation (`dispatch:join`, `dispatch:join_evict` nest there)
    "join_key_codes", "join_shadow", "join_pack", "join_fetch",
    "join_decode", "join_evict",
    # a pull, on its gRPC thread: asking for tasks.state -> holding it
    # -> released, then filter/project/sort outside the lock
    "pull_state_wait", "pull_hold", "pull_serve",
    # framed-append stages (handlers.APPEND_STAGES)
    "append_decode", "append_admit", "append_handoff", "append_store",
    # RPC entry span + the freshness lag taxonomy (freshness_lag_ms
    # stage labels double as span names where a span exists)
    "rpc", "ingest", "engine", "delivery",
})

# The stage a nested stage runs inside, on the same thread. A stage
# without an entry is top-level: the top-level stages of one thread do
# not overlap, so they sum to (at most) its wall, and a stage's self
# time is its duration minus its children's. `close` is top-level at
# ONE site, the deferred-changes flush of an EMIT CHANGES query, whose
# steps run no close cycle of their own.
TRACE_PARENT = {
    "ring_wait": "step", "stage_wait": "step", "close": "step",
    "close_fetch": "close", "close_decode": "close",
    "key_retire": "key_encode",
    "session_key_codes": "step", "session_mirror": "step",
    "session_pack": "step", "session_close": "step",
    "session_close_fetch": "session_close",
    "session_close_decode": "session_close",
    "session_remap": "session_key_codes",
    "join_key_codes": "step", "join_shadow": "step", "join_pack": "step",
    "join_fetch": "step", "join_decode": "step", "join_evict": "step",
}

# kernel dispatch families (per-family dispatch histograms + recompile
# attribution) — also cross-checked by the analyzer registry pass.
# `peek` is the read plane's batched extract, on a pull's thread.
# `join` / `join_evict`: a window join's fused step and its eviction.
KERNEL_FAMILIES = frozenset({"step", "close", "probe", "session", "peek",
                             "join", "join_evict"})


def new_span_id() -> str:
    return uuid.uuid4().hex[:12]


# the active span (trace_id, span_id) of the current request, bound by
# the handler wrapper so nested instrumentation (append stages,
# subscription delivery) can parent its spans without plumbing
_span_ctx: "contextvars.ContextVar[tuple[str, str] | None]" = \
    contextvars.ContextVar("hstream_span", default=None)


def current_span() -> tuple[str, str] | None:
    """(trace_id, span_id) of the active sampled request, or None."""
    return _span_ctx.get()


@contextlib.contextmanager
def span_scope(trace_id: str, span_id: str):
    token = _span_ctx.set((trace_id, span_id))
    try:
        yield
    finally:
        _span_ctx.reset(token)


class SpanCollector:
    """Bounded per-scope rings of completed spans + the sampling knob.

    A scope is the unit of export: a query id (`GET
    /queries/<id>/trace`), a stream name (append-path spans), or a
    subscription id (delivery spans). Rings are bounded per scope AND
    the scope set itself is LRU-bounded, so a client looping over
    random stream names cannot grow the collector without bound.

    `active` is a plain attribute (False at sample rate 0) — the
    disarmed hot-path cost is one attribute read + one branch, the
    FlowGovernor / FAULTS discipline;
    `tests/test_append_framed.py::test_served_steady_state_compiles_nothing`
    gates that arming the collector compiles nothing."""

    def __init__(self, sample_rate: float = 0.0, *,
                 ring_capacity: int = 512, max_scopes: int = 256):
        self.sample_rate = max(0.0, min(float(sample_rate), 1.0))
        self.active = self.sample_rate > 0.0
        self._cap = int(ring_capacity)
        self._max_scopes = int(max_scopes)
        self._rings: "OrderedDict[str, deque]" = OrderedDict()
        self._lock = threading.Lock()

    def sampled(self, trace_id: str) -> bool:
        """Deterministic per-trace sampling decision: every component
        hashing the same trace id reaches the same verdict, so a trace
        is recorded whole or not at all."""
        if not self.active or not trace_id:
            return False
        if self.sample_rate >= 1.0:
            return True
        return (zlib.crc32(trace_id.encode()) % 10_000
                < self.sample_rate * 10_000)

    def record_span(self, scope: str, stage: str, *, trace_id: str,
                    span_id: str, parent_id: str = "",
                    t0_ms: float, dur_ms: float, **attrs) -> None:
        """Append one completed span to the scope's ring. `t0_ms` is
        wall epoch milliseconds; attrs must be JSON-serializable."""
        span = {"stage": stage, "trace_id": trace_id,
                "span_id": span_id, "parent_id": parent_id,
                "t0_ms": round(float(t0_ms), 3),
                "dur_ms": round(float(dur_ms), 3)}
        if attrs:
            span["attrs"] = attrs
        with self._lock:
            ring = self._rings.get(scope)
            if ring is None:
                while len(self._rings) >= self._max_scopes:
                    self._rings.popitem(last=False)  # LRU scope bound
                ring = deque(maxlen=self._cap)
                self._rings[scope] = ring
            else:
                self._rings.move_to_end(scope)
            ring.append(span)

    def spans(self, scope: str) -> list[dict]:
        with self._lock:
            ring = self._rings.get(scope)
            return list(ring) if ring is not None else []

    def scopes(self) -> list[str]:
        with self._lock:
            return sorted(self._rings)

    def export_chrome(self, scope: str) -> dict:
        """The scope's ring as Chrome trace-event JSON (load in
        chrome://tracing or Perfetto): complete ("ph": "X") events,
        microsecond timestamps, trace/span ids in args."""
        events = []
        for s in self.spans(scope):
            events.append({
                "name": s["stage"],
                "cat": "hstream",
                "ph": "X",
                "ts": round(s["t0_ms"] * 1000.0, 1),   # us
                "dur": max(round(s["dur_ms"] * 1000.0, 1), 1),
                "pid": 1,
                "tid": scope,
                "args": {"trace_id": s["trace_id"],
                         "span_id": s["span_id"],
                         "parent_id": s["parent_id"],
                         **s.get("attrs", {})},
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}


# ---- kernel dispatch families (ISSUE 13 tentpole c) ------------------------
#
# One thread-local scope names the kernel family currently being
# dispatched on this thread. jit compiles synchronously inside the
# first call, so the process-wide compile listener reads the scope to
# attribute a recompile to the factory family that triggered it —
# RetraceGuard's listener otherwise collapses everything into one
# undifferentiated count.

_family_tls = threading.local()


def current_kernel_family() -> str | None:
    return getattr(_family_tls, "name", None)


@contextlib.contextmanager
def kernel_family(family: str, observer=None, *, ready=None):
    """Scope a kernel dispatch under a family name. When `observer`
    (a callable (family, seconds)) is set, the dispatch's host time
    lands there — the per-family dispatch-time histograms ride this.
    Cost with no observer: two thread-local attribute writes and an
    inert profiler annotation `dispatch:<family>`.

    `ready` (ISSUE 18) — a zero-arg callable returning the dispatch's
    live device values — opts the site into the device-time sampler:
    on a deterministically sampled dispatch the values are fenced
    (block-until-ready BEFORE the body drains in-flight work), the
    body runs, and a second block-until-ready bounds the device
    execution time into `kernel_device_ms{family}`. Disarmed cost is
    one attribute read + one branch (the FAULTS / FlowGovernor
    discipline); the disarmed sampler records zero state."""
    prev = getattr(_family_tls, "name", None)
    _family_tls.name = family
    sampled = (ready is not None and _DEVICE_TIME.active
               and _DEVICE_TIME.tick(family))
    if sampled:
        try:
            _DEVICE_TIME.fence(ready)
        except Exception:  # noqa: BLE001 — sampling must never fail
            sampled = False    # a dispatch
    t0 = time.perf_counter() \
        if (observer is not None or sampled) else 0.0
    try:
        with _annotation("dispatch:" + family):
            yield
    finally:
        _family_tls.name = prev
        if sampled:
            try:
                _DEVICE_TIME.measure(family, ready, t0)
            except Exception:  # noqa: BLE001 — sampling must never
                pass           # fail a dispatch
        if observer is not None:
            try:
                observer(family, time.perf_counter() - t0)
            except Exception:  # noqa: BLE001 — observers are metrics
                pass           # plumbing; never fail a dispatch


# ---- recompile guard (ISSUE 7) ----------------------------------------------
#
# The hot-path contracts (one fused dispatch per cycle, pow2-padded
# shapes sharing compiled programs, lru_cache'd kernel factories) all
# cash out as ONE observable: steady-state batches compile ZERO new XLA
# executables. The static passes (tools/analyze: dispatch/retrace)
# check the idioms; RetraceGuard checks the outcome at runtime by
# counting backend compiles via jax.monitoring — the
# '/jax/core/compile/backend_compile_duration' event fires exactly once
# per executable build (incl. the tiny utility jits jnp allocations
# create, which steady loops must also not re-trigger).
#
# One process-wide listener is registered lazily and dispatches to
# every active guard plus the optional stats sink — jax.monitoring has
# no unregister, so guards attach/detach through the module-level set
# instead of the listener itself.

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_active_guards: set["RetraceGuard"] = set()
_guard_lock = threading.Lock()
# weakrefs: a ServerContext torn down mid-process (tests spin up many)
# must not be kept alive by the process-wide listener
_stats_sinks: list[tuple[object, str]] = []  # (weakref to holder, stream)
_listener_installed = False


def _ensure_compile_listener() -> None:
    global _listener_installed
    with _guard_lock:
        if _listener_installed:
            return
        import jax.monitoring

        def _on_event(event: str, duration: float, **kw) -> None:
            if event != _COMPILE_EVENT:
                return
            with _guard_lock:
                guards = list(_active_guards)
                sinks = list(_stats_sinks)
            for g in guards:
                g._bump()
            # stream attribution (ISSUE 13 satellite): a compile seen
            # while a NAMED guard is active counts against that guard's
            # stream (the query/bench scope being driven), not the
            # sink's default "_process" pseudo-stream — previously every
            # recompile collapsed into _process and per-query recompile
            # evidence was unrecoverable
            names = sorted({g.name for g in guards if g.name})
            # factory attribution: jit compiles synchronously inside
            # the triggering call, so the dispatching thread's
            # kernel_family scope names the factory family
            family = current_kernel_family()
            dead = []
            for ref, stream in sinks:
                stats = ref()
                if stats is None:
                    dead.append((ref, stream))
                    continue
                try:
                    for target in (names or [stream]):
                        stats.stream_stat_add("kernel_recompiles",
                                              target)
                    if family:
                        stats.stream_stat_add("factory_recompiles",
                                              family)
                except Exception:  # noqa: BLE001 — monitoring must
                    pass           # never break a compile
            if dead:
                with _guard_lock:
                    for ent in dead:
                        if ent in _stats_sinks:
                            _stats_sinks.remove(ent)

        jax.monitoring.register_event_duration_secs_listener(_on_event)
        _listener_installed = True


def install_recompile_counter(stats, stream: str = "_process") -> None:
    """Bump the `kernel_recompiles` per-stream counter on every XLA
    compile in this process — the /metrics face of the retrace
    contract. Idempotent per (holder, stream)."""
    import weakref

    _ensure_compile_listener()
    with _guard_lock:
        if not any(ref() is stats and s == stream
                   for ref, s in _stats_sinks):
            _stats_sinks.append((weakref.ref(stats), stream))


class RetraceGuard:
    """Counts XLA executable builds while active.

    Usage (tests, bench):

        with RetraceGuard() as g:
            for batch in batches:
                ex.process_columnar(...)
        assert g.count == 0   # steady state must not recompile

    `count` is exact: one per backend compile anywhere in the process
    while the guard is active (guards are process-global, like the
    compiles they observe — do not run two guarded regions
    concurrently and expect per-region attribution).

    `name` (optional) attributes compiles observed while this guard is
    active to that stream in every installed stats sink — the query id
    or bench scope being driven — instead of the sink's default
    `_process` pseudo-stream (ISSUE 13)."""

    def __init__(self, name: str | None = None):
        self.count = 0
        self.name = name
        self._lock = threading.Lock()

    def _bump(self) -> None:
        with self._lock:
            self.count += 1

    def __enter__(self) -> "RetraceGuard":
        _ensure_compile_listener()
        with _guard_lock:
            _active_guards.add(self)
        return self

    def __exit__(self, *exc) -> None:
        with _guard_lock:
            _active_guards.discard(self)
