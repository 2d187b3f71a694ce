"""The device cost plane (ISSUE 18).

Every earlier telemetry plane measures the HOST side of the pipeline;
this module makes the device a first-class subsystem of /metrics:

* **HBM arena accounting** — executors expose `device_plane_bytes()`
  (pure `nbytes` metadata reads over their live arena/store arrays, no
  dispatch, no fetch); `sample_device_gauges` folds them per query and
  per plane into the `device_hbm_bytes` / `device_arena_bytes` gauges
  at scrape time, plus a process total cross-checked against the
  backend's own `memory_stats()` where the platform provides one.

* **Compiled-program inventory** — `PROGRAMS` wraps the single funnel
  every jit/pjit/pmap build passes through
  (`jax._src.compiler.compile_or_get_cached`) and records one row per
  distinct lowered module: kernel family (the dispatching thread's
  `kernel_family` scope — jit compiles synchronously inside the
  triggering call), shape key (crc32 of the MLIR module text), compile
  milliseconds, and `cost_analysis()` flops / bytes-accessed when the
  backend reports them. The seam is private: if an installed JAX moves
  it, `install()` raises at server boot rather than leave an empty
  inventory.

* **Per-dispatch device time** — `DEVICE_TIME` is the deterministic
  1/N sampler `common.tracing.kernel_family` consults: on a sampled
  dispatch the inputs are fenced (block-until-ready before the body),
  then a second block-until-ready bounds the device execution time into
  the `kernel_device_ms{family}` histogram next to the host-wall
  `kernel_dispatch_ms`. Disarmed cost is ONE attribute read + one
  branch (the FAULTS / FlowGovernor / locktrace discipline), and the
  disarmed sampler records ZERO state after a served run
  (`tests/test_append_framed.py::test_served_steady_state_compiles_nothing`);
  armed at rate 1 it records and the steady state still compiles nothing
  (`tests/test_devicecost.py::test_armed_sampler_records_and_compiles_nothing`).
"""

from __future__ import annotations

import threading
import time
import weakref
import zlib
from collections import OrderedDict, deque

# ---- HBM arena accounting ---------------------------------------------------


# contract: dispatches<=0 fetches<=0
def plane_bytes(planes) -> dict[str, int]:
    """Per-plane device bytes of a {name: array} mapping — `nbytes` is
    shape metadata, so the walk costs zero dispatches and zero
    transfers however large the arenas are."""
    out: dict[str, int] = {}
    for name, arr in dict(planes).items():
        nb = getattr(arr, "nbytes", None)
        if nb:
            out[str(name)] = int(nb)
    return out


def backend_hbm_bytes() -> int | None:
    """Bytes-in-use reported by the backend's own allocator
    (`memory_stats()`), or None where the platform gives none (CPU).
    The cross-check axis for the per-plane fold: the two agree up to
    allocator slack and non-arena residents (compiled programs,
    staging buffers)."""
    try:
        import jax

        devs = jax.local_devices()
        if not devs:
            return None
        stats = devs[0].memory_stats()
        if not stats:
            return None
        return int(stats.get("bytes_in_use", 0)) or None
    except Exception:  # noqa: BLE001 — accounting must never throw
        return None


def sample_device_gauges(ctx) -> None:
    """Scrape-time fold of every live query's arena bytes into the
    device gauges (called from prometheus.sample_gauges under the
    scrape lock). Cost is O(live planes) attribute reads — zero device
    work — and stale per-query series are swept like every other
    query-labeled gauge."""
    stats = ctx.stats
    tasks = dict(getattr(ctx, "running_queries", {}))
    live: set[tuple[str, str]] = set()
    total = 0
    for qid, task in tasks.items():
        fn = getattr(task, "device_plane_bytes", None)
        if fn is None:
            continue
        try:
            planes = fn()
        except Exception:  # noqa: BLE001 — a task tearing down mid-
            continue       # scrape must not fail the scrape
        q_total = 0
        for plane, nb in sorted(planes.items()):
            key = f"{qid}/{plane}"
            stats.gauge_set("device_arena_bytes", key, nb)
            live.add(("device_arena_bytes", key))
            q_total += nb
        stats.gauge_set("device_hbm_bytes", qid, q_total)
        live.add(("device_hbm_bytes", qid))
        total += q_total
    from hstream_tpu.stats.prometheus import _drop_stale

    _drop_stale(stats, ("device_arena_bytes", "device_hbm_bytes"), live)
    stats.gauge_set("device_hbm_total_bytes", "", total)
    backend = backend_hbm_bytes()
    if backend is not None:
        stats.gauge_set("device_hbm_backend_bytes", "", backend)


def query_hbm_bytes(ctx, qid: str) -> dict:
    """{total, planes} for one query — the flight recorder's HBM page
    and the admin surface's per-query answer."""
    task = dict(getattr(ctx, "running_queries", {})).get(qid)
    fn = getattr(task, "device_plane_bytes", None) if task else None
    if fn is None:
        return {"total": 0, "planes": {}}
    try:
        planes = {k: int(v) for k, v in sorted(fn().items())}
    except Exception:  # noqa: BLE001
        return {"total": 0, "planes": {}}
    return {"total": sum(planes.values()), "planes": planes}


# ---- compiled-program inventory ---------------------------------------------


class ProgramInventory:
    """Process-wide catalog of every XLA executable built in this
    process, keyed by shape key (crc32 of the lowered MLIR module
    text — two calls over the same shapes share one row; a new shape
    is a new row). Bounded LRU: past MAX_ROWS the oldest row folds
    into the `evicted` count rather than growing without bound."""

    MAX_ROWS = 512

    def __init__(self):
        self._rows: "OrderedDict[str, dict]" = OrderedDict()
        self._lock = threading.Lock()
        self._installed = False
        self.evicted = 0

    def install(self) -> None:
        """Wrap the compile funnel once (idempotent). The seam is the
        private `jax._src.compiler.compile_or_get_cached`; a JAX that
        lacks it is an error at install, not an empty inventory."""
        with self._lock:
            if self._installed:
                return
            from jax._src import compiler as _compiler

            orig = _compiler.compile_or_get_cached
            inv = self

            def _record_and_compile(*args, **kwargs):
                t0 = time.perf_counter()
                exe = orig(*args, **kwargs)
                try:
                    inv._record(exe,
                                (time.perf_counter() - t0) * 1e3, args)
                except Exception:  # noqa: BLE001 — inventory plumbing
                    pass           # must never break a compile
                return exe

            _compiler.compile_or_get_cached = _record_and_compile
            self._installed = True

    def _record(self, exe, compile_ms: float, args) -> None:
        from hstream_tpu.common.tracing import current_kernel_family

        name = None
        try:
            hm = exe.hlo_modules()
            if hm:
                name = hm[0].name
        except Exception:  # noqa: BLE001
            pass
        key = None
        try:
            # args[1] is the lowered MLIR module at every pxla call
            # site; its text embeds every shape, so the crc IS the
            # shape key
            if len(args) > 1 and args[1] is not None:
                key = f"{zlib.crc32(str(args[1]).encode()):08x}"
        except Exception:  # noqa: BLE001
            pass
        if key is None:
            key = f"name:{name or 'unknown'}"
        flops = bytes_accessed = None
        try:
            ca = exe.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            if ca:
                flops = ca.get("flops")
                bytes_accessed = ca.get("bytes accessed")
        except Exception:  # noqa: BLE001 — cost analysis is
            pass           # best-effort per backend
        family = current_kernel_family()
        now_ms = time.time() * 1e3
        with self._lock:
            row = self._rows.get(key)
            if row is None:
                while len(self._rows) >= self.MAX_ROWS:
                    self._rows.popitem(last=False)
                    self.evicted += 1
                row = {"shape_key": key, "name": name or "?",
                       "family": family or "", "compiles": 0,
                       "compile_ms": 0.0, "flops": None,
                       "bytes_accessed": None,
                       "first_unix_ms": round(now_ms, 1)}
                self._rows[key] = row
            else:
                self._rows.move_to_end(key)
            row["compiles"] += 1
            row["compile_ms"] = round(row["compile_ms"] + compile_ms, 3)
            if family:
                row["family"] = family
            if flops is not None:
                row["flops"] = float(flops)
            if bytes_accessed is not None:
                row["bytes_accessed"] = float(bytes_accessed)
            row["last_unix_ms"] = round(now_ms, 1)

    def rows(self) -> list[dict]:
        """Newest-compiled last (the LRU order), each row a plain
        JSON-ready dict."""
        with self._lock:
            return [dict(r) for r in self._rows.values()]

    def summary(self) -> dict:
        with self._lock:
            rows = list(self._rows.values())
            return {
                "programs": len(rows),
                "evicted": self.evicted,
                "installed": self._installed,
                "total_compile_ms": round(
                    sum(r["compile_ms"] for r in rows), 3),
                "total_compiles": sum(r["compiles"] for r in rows),
            }


PROGRAMS = ProgramInventory()


# ---- per-dispatch device time -----------------------------------------------


class DeviceTimeSampler:
    """Deterministic 1/N device-time sampling for kernel_family scopes.

    `active` is a plain attribute (False while disarmed) — the
    disarmed hot-path cost inside `kernel_family` is one attribute
    read + one branch, and the disarmed sampler holds ZERO state (no
    tick counters, no sample rings): the served retrace test of
    `tests/test_append_framed.py` gates both.
    Armed, every Nth dispatch per family is measured as a fenced
    block-until-ready pair; the milliseconds land in the bounded
    per-family rings and in every registered stats sink's
    `kernel_device_ms{family}` histogram."""

    MAX_SAMPLES = 256

    def __init__(self):
        self.active = False
        self.rate = 0
        self._counts: dict[str, int] = {}
        self._samples: dict[str, deque] = {}
        self._sinks: list = []  # weakrefs: torn-down holders must die
        self._lock = threading.Lock()

    def arm(self, rate: int) -> None:
        with self._lock:
            self.rate = max(1, int(rate))
            self.active = True

    def disarm(self) -> None:
        with self._lock:
            self.active = False
            self.rate = 0

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._samples.clear()

    def add_sink(self, stats) -> None:
        with self._lock:
            if not any(ref() is stats for ref in self._sinks):
                self._sinks.append(weakref.ref(stats))

    # contract: dispatches<=0 fetches<=0
    def tick(self, family: str) -> bool:
        """The deterministic sampling decision: true on every Nth
        dispatch of the family. Only ever called armed."""
        with self._lock:
            c = self._counts.get(family, 0) + 1
            self._counts[family] = c
            return self.rate > 0 and c % self.rate == 0

    # contract: dispatches<=0 fetches<=1
    def fence(self, ready) -> None:
        """Drain in-flight device work on the dispatch's values so the
        timed region covers only the sampled dispatch — the sampled
        path's ONE sanctioned pre-body sync."""
        import jax

        jax.block_until_ready(ready())

    # contract: dispatches<=0 fetches<=1
    def measure(self, family: str, ready, t0: float) -> None:
        """Post-body half of a sampled dispatch: block on the results
        and record the fenced wall time as device milliseconds."""
        import jax

        jax.block_until_ready(ready())
        self.record(family, (time.perf_counter() - t0) * 1e3)

    # contract: dispatches<=0 fetches<=0
    def record(self, family: str, ms: float) -> None:
        with self._lock:
            ring = self._samples.get(family)
            if ring is None:
                ring = deque(maxlen=self.MAX_SAMPLES)
                self._samples[family] = ring
            ring.append(float(ms))
            sinks = list(self._sinks)
        dead = []
        for ref in sinks:
            stats = ref()
            if stats is None:
                dead.append(ref)
                continue
            try:
                stats.observe("kernel_device_ms", family, float(ms))
            except Exception:  # noqa: BLE001 — metrics plumbing must
                pass           # never fail a dispatch
        if dead:
            with self._lock:
                for ref in dead:
                    if ref in self._sinks:
                        self._sinks.remove(ref)

    def state(self) -> dict:
        """Everything the sampler remembers — the disarmed-witness
        gate asserts this is empty after a disarmed run."""
        with self._lock:
            return {"counts": dict(self._counts),
                    "samples": {k: len(v)
                                for k, v in self._samples.items()}}


DEVICE_TIME = DeviceTimeSampler()
