"""Host-side query executor: drives the jitted lattice step.

Responsibilities (the reference spreads these across runTask's polling
loop and the aggregate processors — Processor.hs:99-144,
TimeWindowedStream.hs:82-103):

  * columnarize decoded JSON rows into padded HostBatches
  * maintain the group-key dictionary (tuple of group values <-> dense id)
  * maintain the time epoch: device time = int32 ms relative to `epoch`,
    re-anchored (rebase) long before int32 overflow
  * track the watermark (max event time seen = the reference's
    `observedStreamTime`) and the set of open windows ON HOST, so the
    device step never syncs back per batch
  * when the watermark passes win_end + grace: extract + reset that slot
    (window close), finalize, decode keys, apply HAVING + projections
  * EMIT CHANGES mode: additionally extract touched (key, window) pairs
    after each batch (one change per touched pair per micro-batch — the
    batched analogue of the reference's per-record emission)

The executor is single-threaded per query, like the reference's one green
thread per task; concurrency comes from running many executors and from
the device pipelining enqueued steps.
"""

from __future__ import annotations

import itertools
import math
import threading
import time
from collections import deque
from concurrent import futures
from dataclasses import dataclass
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from hstream_tpu.common.columnar import ColumnarEmit, extend_rows
from hstream_tpu.common.errors import SQLCodegenError
from hstream_tpu.common.faultinject import FAULTS
from hstream_tpu.common.logger import get_logger
from hstream_tpu.common.tracing import kernel_family, trace_span
from hstream_tpu.engine import lattice, transport
from hstream_tpu.engine.expr import (
    BinOp,
    Col,
    Expr,
    columns_of,
    encode_strings,
    eval_host,
    eval_host_vec,
)
from hstream_tpu.engine.keytable import KeyTable
from hstream_tpu.engine.plan import (
    AggKind,
    AggregateNode,
    AggSpec,
    WindowTop,
    emitted_group_cols,
)
from hstream_tpu.engine.types import (
    ColumnType,
    HostBatch,
    Schema,
    StringDictionary,
    canon_key,
    round_up_pow2,
)
from hstream_tpu.engine.window import FixedWindow, SessionWindow

log = get_logger("executor")

REBASE_THRESHOLD = 1 << 30  # re-anchor epoch when relative time passes this

EmitFn = Callable[[list[dict[str, Any]]], None]

# `_key_last` of an id no key holds, and of one handed out by
# `key_id_for` and not dated since (`note_key_use`): never retired
_KEY_FREE = -(1 << 62)
_KEY_PINNED = 1 << 62

# Shared device->host change-drain workers: ONE small pool for every
# executor in the process, so N concurrent queries batch their blocking
# D2H fetches onto drain threads instead of each stalling its own task
# loop. 2 workers: one fetch can ride the link while another decodes.
_DRAIN_POOL: futures.ThreadPoolExecutor | None = None
_DRAIN_POOL_LOCK = threading.Lock()


def _change_drain_pool() -> futures.ThreadPoolExecutor:
    global _DRAIN_POOL
    with _DRAIN_POOL_LOCK:
        if _DRAIN_POOL is None:
            _DRAIN_POOL = futures.ThreadPoolExecutor(
                max_workers=2, thread_name_prefix="change-drain")
        return _DRAIN_POOL


def _align_down(ts: int, step: int) -> int:
    return ts - (ts % step)


# Per-instance read-version nonces (ISSUE 20): a restored/rebuilt
# executor must never alias a predecessor's version tuple, so every
# instance draws a process-unique id at construction.
_READ_NONCE = itertools.count(1)


@dataclass
class _OpenWindow:
    start_abs: int  # absolute ms
    slot: int


@dataclass
class StagedBatch:
    """A micro-batch encoded (and optionally uploaded) ahead of its step
    dispatch — the unit of work between the ingest pipeline's encoder
    thread and the executor's ordered step loop. Host copies are kept so
    rare control-flow (gap split, rebase, epoch change) can fall back to
    the synchronous path."""

    n: int
    cap: int
    combo: Any
    bases: Any                      # np.int32 [n_streams] per-stream bases
    words: Any                      # np.ndarray or device array
    epoch: int
    ts_min: int
    ts_max: int
    key_ids: np.ndarray
    ts_ms: np.ndarray
    cols: Mapping[str, np.ndarray]
    nulls: Mapping[str, np.ndarray] | None


class QueryExecutor:
    """Executes one windowed/global GROUP BY aggregation plan."""

    # whether _drain_changes honors defer_change_decode (subclasses with
    # their own drain path override this capability)
    supports_deferred_changes = True

    def __init__(
        self,
        node: AggregateNode,
        schema: Schema,
        *,
        emit_changes: bool = True,
        initial_keys: int = 1024,
        batch_capacity: int = 4096,
    ):
        if isinstance(node.window, SessionWindow):
            raise SQLCodegenError("session windows use SessionExecutor")
        self.node = node
        self.schema = schema
        self.emit_changes = emit_changes
        self.batch_capacity = batch_capacity

        # group keys must be plain columns (validated upstream)
        self.group_cols: list[str] = []
        for k in node.group_keys:
            if not isinstance(k, Col):
                raise SQLCodegenError("GROUP BY supports plain columns")
            self.group_cols.append(k.name)

        # the names a reader may pin the group key by (`peek_key`): an
        # emitted row carries each group column's own value under them,
        # in `group_cols` order. None where a later select item takes
        # one of the names for something else (`SELECT k, COUNT(*) AS
        # k`: the emitted `k` is the count)
        taken = [name for name, _e in node.post_projections or []]
        names = emitted_group_cols(node)
        self.emitted_key_cols: list[str] | None = names if all(
            taken.count(n) <= 1 for n in names) else None

        self.window: FixedWindow | None = node.window
        self.dicts: dict[str, StringDictionary] = {
            name: StringDictionary() for name, t in schema.fields
            if t == ColumnType.STRING
        }

        # the key dictionary: `_key_ids` key -> id, `_key_rev` id -> key
        # (None where the id is free), `_key_cols` the same per group
        # column for the vectorized decode, `_key_last` the newest event
        # time an id was named at (`note_key_use`; _KEY_PINNED until
        # then, _KEY_FREE while no key holds it), `_free` the ids of
        # retired keys, descending, so the smallest is taken first
        self._key_ids: dict[tuple, int] = {}
        self._key_rev: list[tuple | None] = []
        self._key_cols: list[np.ndarray] = [
            np.empty(initial_keys, object) for _ in self.group_cols]
        self._key_last = np.full(initial_keys, _KEY_FREE, np.int64)
        self._free: list[int] = []
        self._named_hi = -1  # newest event time any batch was dated at
        self.key_stats = {"key_retirements": 0, "keys_retired": 0,
                          "key_ids_reused": 0}
        # what `_key_ids` said, kept for the task's key_encode stage to
        # resolve a batch's dictionary in one call; derived, rebuilt
        # from _key_rev, never persisted
        self._key_table = KeyTable()
        self._top: WindowTop | None = node.top
        if self._top is not None:
            if emit_changes:
                raise SQLCodegenError(
                    "QUALIFY ... OVER (a window's top across groups) is "
                    "not supported with EMIT CHANGES: the extreme is "
                    "known when the window closes")
            if self.window is None:
                raise SQLCodegenError(
                    "QUALIFY ... OVER needs a TUMBLING or HOPPING "
                    "window to take the extreme over")

        # Pre-encode string literals (fills the column dictionaries) so the
        # expressions are hashable and compiled functions can be shared.
        encoded_aggs = []
        for agg in node.aggs:
            if agg.input is not None:
                agg = AggSpec(kind=agg.kind, out_name=agg.out_name,
                              input=encode_strings(agg.input, schema, self.dicts),
                              quantile=agg.quantile, k=agg.k)
            encoded_aggs.append(agg)
        self._filter_expr = self._extract_filter()
        if self._filter_expr is not None:
            self._filter_expr = encode_strings(
                self._filter_expr, schema, self.dicts)

        # columns the device step actually needs
        needed = set()
        for agg in encoded_aggs:
            if agg.input is not None:
                needed |= columns_of(agg.input)
        if self._filter_expr is not None:
            needed |= columns_of(self._filter_expr)
        self._needed_cols = sorted(needed)

        self.spec = lattice.LatticeSpec(
            n_keys=initial_keys, window=self.window,
            aggs=tuple(encoded_aggs), track_touched=emit_changes)
        self.state = lattice.init_state(self.spec)
        # sticky adaptive wire codec; survives recompiles (key growth).
        # The lock serializes encode() between an IngestPipeline encoder
        # thread and synchronous fallbacks on the caller thread.
        self._transport = transport.BitpackTransport()
        self._transport_lock = threading.Lock()
        self._null_sticky: set[str] = set()  # null streams once seen
        self._compile()

        self.epoch: int | None = None        # absolute ms anchor, advance-aligned
        self.watermark_abs: int = -1
        self._open: dict[int, _OpenWindow] = {}  # start_abs -> window
        # Window starts whose closure is deferred until the next process()
        # call: populated by the gap-split path so a stream-time jump inside
        # a batch cannot close (and emit) windows that records earlier in
        # the same batch just aggregated into.
        self._no_close: set[int] = set()
        # window starts that received records during the current process()
        # call (populated by _track_windows, cleared per call)
        self._touched_this_call: set[int] = set()
        self.rebase_threshold = REBASE_THRESHOLD
        # Deferred close decode: when True, closing a window dispatches
        # the fused extract+reset on device but keeps the packed result
        # as a device value; drain_closed() decodes them later. This
        # keeps the hot ingest loop free of forced device->host syncs
        # (pull-based emission — the TPU analogue of the reference's
        # sink append). Each entry is (window starts, packed [P,rows,K]).
        self.defer_close_decode = False
        self._pending_closes: list[tuple[list[int], Any]] = []
        # close-path dispatch accounting: the fused close contract is
        # ONE lattice-kernel dispatch and (outside changelog mode) ONE
        # device->host fetch per close cycle, regardless of how many
        # windows are due — tests and bench assert on these
        # a top close adds what the device's own reduce said of each
        # closed window (groups that held a count, rows that reached
        # the extreme) and the times more rows tied than its buffer
        # holds, each a second fetch of the full column
        self.close_stats = {"close_cycles": 0, "close_dispatches": 0,
                            "close_fetches": 0, "close_rows_kept": 0,
                            "close_groups": 0, "close_tie_refetches": 0}
        # fused-close health: a fused kernel failure (activation /
        # compile / injected fault) permanently degrades THIS executor
        # to the retained per-slot reference close; the query task
        # mirrors device_fallbacks into device_path_fallbacks
        self._fused_close_ok = True
        self.device_fallbacks = 0
        # Deferred CHANGE decode (emit_changes mode): keep the touched
        # extract as a device value and decode it one batch later, so
        # the blocking device->host fetch overlaps the next batch's host
        # work instead of stalling the loop (matters on high-RTT links).
        # Changes then lag emission by one micro-batch; flush_changes()
        # drains the tail.
        self._caps_used: set[int] = set()  # compiled staged-step shapes
        self._caps_lock = threading.Lock()
        self.defer_change_decode = False
        # how many change extracts may queue before a batched fetch; >1
        # amortizes the device->host round trip over many micro-batches
        # (changelog rows then lag ingest by up to `depth` batches)
        self.change_drain_depth = 1
        self._pending_changes: list[Any] = []
        # Async change drain: batched change fetches run on the shared
        # drain pool instead of the caller's thread, so the D2H round
        # trip overlaps later batches' encode/step work entirely. Rows
        # are collected strictly in submission order (FIFO head-pop),
        # so emitted change order matches the synchronous path.
        self.async_change_drain = False
        self._drain_futs: deque = deque()
        # double-buffered device staging: at most upload_slots H2D
        # transfers in flight; staging a batch past that waits on the
        # OLDEST outstanding transfer (classic double-buffer handoff)
        self.upload_slots = 2
        self._upload_ring: deque = deque()
        self._upload_lock = threading.Lock()
        # per-stage busy-seconds shared with IngestPipeline.stats()
        self.stage_stats: dict[str, float] = {"upload_wait_s": 0.0,
                                              "drain_s": 0.0}
        self._stats_lock = threading.Lock()
        # observability plane (ISSUE 13), all host-mirror values the
        # owning task mirrors into /metrics: per-family dispatch-time
        # observer (None = one branch per dispatch), late-record drops
        # (the host twin of the device's watermark mask), and H2D/D2H
        # byte totals on the staging and stacked-drain paths
        self.dispatch_observer = None   # callable (family, seconds)
        # the owning task's QueryTracer, set where dispatch_observer
        # is: the close cycle's spans (close / close_fetch /
        # close_decode) land in the query's rings; None = no-op
        self.tracer = None
        self.late_drops = 0
        self.transfer_stats = {"h2d_bytes": 0, "d2h_bytes": 0}
        # read-plane versioning (ISSUE 20): read_epoch bumps at every
        # state-mutating choke point (step dispatch, window close), so
        # (nonce, read_epoch, close_cycles, watermark) is an exact key
        # for "would peek() return the same rows". Plain int writes —
        # readers may sample it lock-free; a torn read can only cause a
        # spurious cache miss, never a stale hit.
        self.read_epoch = 0
        self._read_nonce = next(_READ_NONCE)

    def _extract_filter(self) -> Expr | None:
        # Walk the child chain down to the source, ANDing every FilterNode
        # predicate; reject node types this executor cannot honor so a
        # malformed plan fails loudly instead of silently skipping filters.
        from hstream_tpu.engine.plan import FilterNode, SourceNode

        pred: Expr | None = None
        child = self.node.child
        while not isinstance(child, SourceNode):
            if isinstance(child, FilterNode):
                pred = child.predicate if pred is None else \
                    BinOp("AND", pred, child.predicate)
                child = child.child
            else:
                raise SQLCodegenError(
                    f"aggregate over unsupported child node "
                    f"{type(child).__name__}")
        return pred

    def _compile(self) -> None:
        n_per = self.spec.windows_per_record
        self._transport.reserve_key_ids(self.spec.n_keys)
        self._layout = tuple(
            (name, lattice.layout_tag(self.schema.type_of(name)))
            for name in self._needed_cols)
        # changelog extraction is bounded by the touched-pair space
        # (n_keys * n_slots), usually far below batch capacity — keeps
        # the per-batch device->host extract buffer small
        max_out = min(self.batch_capacity * n_per,
                      self.spec.n_keys * self.spec.n_slots)
        fns = lattice.compiled(self.spec, self.schema, self._filter_expr,
                               max_out, self._layout)
        # close-path kernels are wrapped so close_stats counts ACTUAL
        # device dispatches at the call sites — a reintroduced
        # per-slot close loop shows up as dispatches > cycles
        self._extract_slot = self._count_close_kernel(fns.extract_slot)
        self._reset_slot = self._count_close_kernel(fns.reset_slot)
        self._extract_reset_slots = self._count_close_kernel(
            fns.extract_reset_slots)
        self._extract_slots = fns.extract_slots  # peek: read path
        self._reset_slots = self._count_close_kernel(fns.reset_slots)
        self._extract_touched = fns.extract_touched
        if self._top is not None:
            self._extract_top_reset = self._count_close_kernel(
                lattice.compiled_top_close(self.spec, self._top.agg,
                                           self._top.extreme))
        # (null-flag stream name, referenced columns) per null-tracked agg
        self._null_specs = [
            (key, sorted(columns_of(agg.input)))
            for key, agg in zip(fns.null_keys, self.spec.aggs)
            if key is not None
        ]

    def _count_close_kernel(self, fn):
        """Wrap a close-path lattice kernel so every device dispatch
        bumps close_stats — the accounting the one-dispatch-per-cycle
        contract is asserted against (tests/test_close_batched.py)."""

        def counted(*args):
            self.close_stats["close_dispatches"] += 1
            res = None

            def _ready():  # the kernel result once the body ran
                return self.state if res is None else res

            with kernel_family("close", self.dispatch_observer,
                               ready=_ready):
                res = fn(*args)
            return res

        return counted

    # ---- device cost plane (ISSUE 18) --------------------------------------

    # contract: dispatches<=0 fetches<=0
    def _device_values(self):
        """The executor's live device arrays — the fence/measure target
        of the device-time sampler (a zero-arg late binding: self.state
        is REPLACED by every step/close dispatch)."""
        return self.state

    # contract: dispatches<=0 fetches<=0
    def device_plane_bytes(self) -> dict[str, int]:
        """Exact per-plane device bytes of the live lattice state —
        nbytes metadata reads only, zero dispatches, zero fetches."""
        from hstream_tpu.stats.devicecost import plane_bytes

        return plane_bytes(self.state)

    # contract: dispatches<=1 fetches<=0
    def _run_step(self, cap: int, n: int, key_ids, ts_rel, cols,
                  valid, null_streams, wm_rel) -> None:
        """Encode one micro-batch with the v2 wire codec and dispatch the
        jitted (decode+scatter) step. Null streams, once seen, stay on the
        wire (sticky) so the encoding combo — and the compiled executable
        — is stable batch-to-batch."""
        if FAULTS.active:  # chaos: fail/delay a staged step dispatch
            FAULTS.point("device.dispatch")
        self.read_epoch += 1
        combo, bases, words = self._encode_locked(
            cap, n, key_ids, ts_rel, cols, valid, null_streams)
        step = lattice.compiled_encoded_step(
            self.spec, self.schema, self._filter_expr, combo, cap,
            donate_words=True)
        staged_words = self._device_stage(words)
        with kernel_family("step", self.dispatch_observer,
                           ready=self._device_values):
            self.state = step(self.state, wm_rel, np.int32(n), bases,
                              staged_words)

    def _encode_locked(self, cap, n, key_ids, ts_rel, cols, valid,
                       null_streams):
        """Wire-encode one batch. Only the sticky-null merge holds the
        transport lock (a concurrent add during iteration would throw);
        the encode itself runs UNLOCKED so a pool of pipeline encode
        workers packs batches in parallel — safe because every batch's
        (combo, bases, words) triple is self-consistent and the codec's
        adaptive state tolerates racy updates (transport.BitpackTransport
        thread-safety note). Null streams, once seen, stay on the wire
        (sticky) so the encoding combo — and the compiled executable —
        converges batch-to-batch."""
        with self._transport_lock:
            for nk in null_streams:
                self._null_sticky.add(nk)
            sticky = tuple(self._null_sticky)
        for nk in sticky:
            if nk not in null_streams:
                null_streams[nk] = np.zeros(n, dtype=np.bool_)
        return self._transport.encode(
            cap, n, key_ids, ts_rel, cols, self._layout,
            valid=valid, null_streams=null_streams)

    # ---- keys --------------------------------------------------------------

    def _key_id(self, row: Mapping[str, Any]) -> int:
        return self.key_id_for(tuple(row.get(c) for c in self.group_cols))

    def _grow_key_arrays(self, new_k: int) -> None:
        """The host's per-id arrays at the planes' new capacity."""
        extra = new_k - len(self._key_last)
        self._key_last = np.concatenate(
            [self._key_last, np.full(extra, _KEY_FREE, np.int64)])
        self._key_cols = [np.concatenate([c, np.empty(extra, object)])
                          for c in self._key_cols]

    def _grow_keys(self) -> None:
        new_k = self.spec.n_keys * 2
        self._grow_key_arrays(new_k)
        self.state = lattice.grow_keys(self.state, self.spec, new_k)
        self.spec = lattice.LatticeSpec(
            n_keys=new_k, window=self.spec.window, aggs=self.spec.aggs,
            hll=self.spec.hll, qcfg=self.spec.qcfg,
            track_touched=self.spec.track_touched)
        self._compile()

    # ---- time --------------------------------------------------------------

    def _advance_step(self) -> int:
        return 1 if self.window is None else self.window.advance_ms

    def _ensure_epoch(self, min_ts: int) -> None:
        if self.epoch is None:
            # anchor so every window that can ever legally receive records
            # has a non-negative relative start: hopping windows reach back
            # size - advance before the first record, and out-of-order
            # records within the grace period reach back another
            # size + grace (window valid while start + size + grace > wm,
            # and the watermark only grows from the first batch's max).
            if self.window is None:
                back = 0
            else:
                w = self.window
                adv = w.advance_ms
                back = (w.size_ms - adv) + \
                    ((w.size_ms + w.grace_ms + adv - 1) // adv) * adv
            self.epoch = _align_down(min_ts, self._advance_step()) - back

    def _maybe_rebase(self, max_ts_abs: int) -> None:
        if self.epoch is None:
            return
        if max_ts_abs - self.epoch < self.rebase_threshold:
            return
        # Re-anchor at the oldest still-open window (or the watermark).
        # delta must be a multiple of advance * n_slots so the slot
        # mapping (start // advance) mod W of every open window is
        # preserved across the rebase.
        anchor = min([w.start_abs for w in self._open.values()]
                     + [self.watermark_abs if self.watermark_abs >= 0 else max_ts_abs])
        period = self._advance_step() * self.spec.n_slots
        delta = _align_down(anchor - self.epoch, period)
        if delta <= 0:
            return
        self.state = lattice.rebase(self.state, np.int32(delta))
        self.epoch = self.epoch + delta

    # ---- ingest ------------------------------------------------------------

    def process(self, rows: Sequence[Mapping[str, Any]],
                ts_ms: Sequence[int]) -> list[dict[str, Any]]:
        """Feed one micro-batch of decoded records; returns emitted rows."""
        if not rows:
            return []
        try:
            return self._process_batch(list(rows), list(ts_ms))
        finally:
            # deferred closes apply only within the call that deferred them
            self._no_close.clear()
            self._touched_this_call.clear()

    def _new_window_starts(self, ts_ms: Sequence[int]) -> set[int]:
        """Window starts this batch's records aggregate into (late ones
        — already past end+grace at the current watermark — excluded,
        matching the device mask).

        Fast path: when the batch's aligned time range is small (the
        steady state — a micro-batch spans a handful of advances), the
        candidate starts are simply every aligned value in
        [align(min)-back, align(max)] — O(range/advance), no scan of
        the 100k+ timestamps. Aligned values with no records just open
        empty windows that close without emitting (count>0 filter), so
        the overapproximation is semantics-free. Sparse/jumpy batches
        fall back to the exact np.unique scan."""
        w = self.window
        ts = np.asarray(ts_ms, dtype=np.int64)
        adv = w.advance_ms
        a_lo = int(ts.min())
        a_hi = int(ts.max())
        a_lo -= a_lo % adv
        a_hi -= a_hi % adv
        span = (a_hi - a_lo) // adv + 1
        back = w.windows_per_record - 1
        # tight gate: a sparse/gappy batch (few records over a wide time
        # range) must use the exact scan, or every aligned gap value
        # becomes a phantom open window tracked (and closed) on host
        if span + back <= min(self.spec.n_slots, 64):
            starts = np.arange(a_lo - back * adv, a_hi + adv, adv)
        else:
            latest = np.unique(ts - ts % adv)
            offs = np.arange(w.windows_per_record, dtype=np.int64) * adv
            starts = np.unique((latest[:, None] - offs[None, :]).ravel())
        if self.watermark_abs >= 0:
            starts = starts[starts + w.size_ms + w.grace_ms
                            > self.watermark_abs]
        return set(starts.tolist())

    def _gap_guard(self, ts_arr: np.ndarray, sub):
        """Gap/slot-collision guard, shared by the row and columnar paths.

        Window start s occupies lattice slot (s // advance) mod W, so two
        distinct live windows whose starts are congruent mod W*advance (a
        stream gap / restart jump) would alias the same slot.

        (a) Exact aliasing among (open windows ∪ this batch's windows):
            split the batch in time order at the first aliasing start and
            force-close only the open windows whose slot the suffix
            actually needs — such windows are provably past end+grace,
            since aliasing requires a gap of W*advance > size+grace.
        (b) A stream-time jump past the slot horizon (even without
            aliasing) defers closure of windows this call's records
            aggregated into until the next call: records within a batch
            are concurrent, so a far-future record must not retroactively
            finalize windows its batch-mates just updated. In-horizon
            progress still closes windows at end of batch as usual.

        `sub(idx)` recursively processes the records at positions `idx`
        (an int ndarray). Returns (emitted_rows, None) when the guard
        split the batch (case a), or (None, new_starts) when the caller
        should proceed — possibly after case (b) recorded deferred closes;
        new_starts is this batch's window-start set for _track_windows."""
        w = self.window
        period = w.advance_ms * self.spec.n_slots
        back = w.size_ms - w.advance_ms
        aligned_min = _align_down(int(ts_arr.min()), w.advance_ms) - back
        anchor = min(list(self._open) + [aligned_min])
        horizon = anchor + (self.spec.n_slots - 1) * w.advance_ms
        new_starts = self._new_window_starts(ts_arr)
        by_res: dict[int, list[int]] = {}
        for s in set(self._open) | new_starts:
            by_res.setdefault(s % period, []).append(s)
        colliding = [sorted(g) for g in by_res.values() if len(g) > 1]
        if colliding:
            cut = min(g[1] for g in colliding)  # first aliasing start
            pre = np.nonzero(ts_arr < cut)[0]
            suf = np.nonzero(ts_arr >= cut)[0]
            out = []
            if len(pre):
                out.extend(sub(pre))
            self._no_close |= set(self._open) & self._touched_this_call
            suf_ts = ts_arr[suf]
            suf_starts = self._new_window_starts(suf_ts)
            suf_res = {s % period for s in suf_starts}
            collide = [s for s in self._open
                       if s % period in suf_res and s not in suf_starts]
            if collide:
                # real closes, not early ones — see proof above; the
                # watermark advances to their close boundary so they
                # cannot reopen into a now-occupied slot
                boundary = max(s + w.size_ms + w.grace_ms for s in collide)
                if boundary > int(suf_ts.max()):
                    raise AssertionError(
                        "aliasing window not due — slot layout invariant "
                        "broken")
                self.watermark_abs = max(self.watermark_abs, boundary)
                out.extend(self._close_windows(sorted(collide)))
            out.extend(sub(suf))
            return out, None
        if int(ts_arr.max()) > horizon:
            self._no_close |= (set(self._open) & self._touched_this_call
                               ) | new_starts
        return None, new_starts

    def _process_batch(self, rows: list, ts_ms: list) -> list[dict[str, Any]]:
        if len(rows) > self.batch_capacity:
            out = []
            for i in range(0, len(rows), self.batch_capacity):
                out.extend(self._process_batch(
                    rows[i:i + self.batch_capacity],
                    ts_ms[i:i + self.batch_capacity]))
            return out

        batch_starts = None
        if self.window is not None:
            def sub(idx):
                return self._process_batch([rows[i] for i in idx],
                                           [ts_ms[i] for i in idx])

            guarded, batch_starts = self._gap_guard(
                np.asarray(ts_ms, dtype=np.int64), sub)
            if guarded is not None:
                return guarded

        self._ensure_epoch(min(ts_ms))
        self._maybe_rebase(max(ts_ms))

        n = len(rows)
        cap = round_up_pow2(n)
        key_ids = np.zeros(cap, dtype=np.int32)
        for i, row in enumerate(rows):
            key_ids[i] = self._key_id(row)
        self.note_key_use(key_ids[:n], max(ts_ms))

        batch = HostBatch.from_rows(self.schema, rows, ts_ms, self.dicts,
                                    capacity=cap)
        ts_rel64 = np.asarray(ts_ms, dtype=np.int64) - self.epoch
        if int(ts_rel64.max()) >= (1 << 31):
            # epoch couldn't rebase far enough (an ancient window is still
            # open with an extreme grace) — fail loudly over corrupting.
            raise OverflowError(
                "stream time span exceeds int32 relative range; "
                "reduce grace or close the stalled window")
        ts_rel = np.zeros(cap, dtype=np.int32)
        ts_rel[:n] = ts_rel64

        wm_rel = np.int32(max(self.watermark_abs - self.epoch, -1)
                          if self.watermark_abs >= 0 else -1)

        # SQL NULL handling: a NULL operand makes the WHERE predicate
        # not-true (row excluded) and excludes the row from that aggregate.
        valid, null_streams = self._null_valid_streams(n, batch.nulls)
        self._note_late(np.asarray(ts_ms, dtype=np.int64))
        self._run_step(cap, n, key_ids, ts_rel, batch.cols, valid,
                       null_streams, wm_rel)

        # host window bookkeeping
        out = None
        if self.window is not None:
            self._track_windows(np.asarray(ts_ms, dtype=np.int64),
                                batch_starts)
        new_wm = max(ts_ms)
        if new_wm > self.watermark_abs:
            self.watermark_abs = new_wm

        if self.emit_changes:
            out = extend_rows(out, self._drain_changes())
        # a lone columnar batch (changes or closes) stays columnar all
        # the way to the caller
        out = extend_rows(out, self.close_due_windows())
        return out if out is not None else []

    def _note_late(self, ts_arr: np.ndarray) -> None:
        """Host mirror of the device's late mask (ISSUE 13): a record
        whose NEWEST window is already past close at the pre-batch
        watermark aggregates nowhere — count it so /metrics carries a
        per-query late-drop series. Steady in-order streams pay one
        integer compare (the quick gate); only batches actually
        carrying late rows pay the vector count."""
        w = self.window
        if w is None or self.watermark_abs < 0 or len(ts_arr) == 0:
            return
        cutoff = self.watermark_abs - w.size_ms - w.grace_ms
        lo = int(ts_arr.min())
        if lo - lo % w.advance_ms > cutoff:
            return
        self.late_drops += int(np.count_nonzero(
            ts_arr - ts_arr % w.advance_ms <= cutoff))

    def _track_windows(self, ts_abs: np.ndarray,
                       starts: set[int] | None = None) -> None:
        advance = self.window.advance_ms
        if starts is None:
            starts = self._new_window_starts(ts_abs)
        for s in starts:
            if s < self.epoch:
                continue
            self._touched_this_call.add(s)
            if s not in self._open:
                slot = (((s - self.epoch) // advance) % self.spec.n_slots)
                self._open[s] = _OpenWindow(start_abs=s, slot=slot)

    def process_columnar(self, key_ids: np.ndarray, ts_ms: np.ndarray,
                         cols: Mapping[str, np.ndarray],
                         nulls: Mapping[str, np.ndarray] | None = None,
                         ) -> list[dict[str, Any]]:
        """Columnar ingest fast path: pre-encoded dense key ids + int64
        absolute-ms timestamps + device columns, skipping per-row Python
        decode (the production ingest path stages columnar batches from
        the native layer). Key-dictionary state must have been populated
        by the caller via key_id_for(); string columns must be pre-encoded
        dictionary ids. Gap jumps that would alias lattice slots go
        through the same _gap_guard split as the row path — rare; the
        steady-state path is pure numpy + one jitted step."""
        if len(key_ids) == 0:
            return []
        try:
            return self._process_columnar(np.asarray(key_ids),
                                          np.asarray(ts_ms, dtype=np.int64),
                                          cols, nulls)
        finally:
            self._no_close.clear()
            self._touched_this_call.clear()

    def _stage_cap(self, n: int) -> int:
        """Padded capacity for a columnar micro-batch. Floored at 4096
        (or batch_capacity when smaller) so variable-size coalesced
        batches share compiled step shapes — each distinct cap is a
        separate XLA compile (seconds), and scatter cost on padded rows
        is noise. Sticky: a batch reuses
        the smallest already-chosen cap that fits within 8x padding,
        so varying coalesce sizes converge on a few shapes instead of
        compiling each power of two they happen to hit. (A gap-guard
        fallback can discard a chosen cap before its shape compiles —
        at worst that costs one compile at a nearby size later.)

        Called from both the pipeline's encoder thread and the task
        thread (sync fallbacks): the lock keeps the set iteration and
        the insert from racing."""
        with self._caps_lock:
            for c in sorted(self._caps_used):
                if n <= c <= 8 * max(n, 1):
                    return c
            cap = round_up_pow2(n, lo=min(self.batch_capacity, 4096))
            self._caps_used.add(cap)
            return cap

    def _process_columnar(self, key_ids, ts_ms, cols, nulls
                          ) -> list[dict[str, Any]]:
        n = len(key_ids)
        if n > self.batch_capacity:
            # split BEFORE choosing a staged cap: an oversize batch's
            # cap would be registered but never compiled (the chunks
            # compute their own), corrupting the sticky-cap cache
            out = []
            for i in range(0, n, self.batch_capacity):
                sl = slice(i, i + self.batch_capacity)
                out.extend(self._process_columnar(
                    key_ids[sl], ts_ms[sl],
                    {k: v[sl] for k, v in cols.items()},
                    None if nulls is None else
                    {k: v[sl] for k, v in nulls.items()}))
            return out
        cap = self._stage_cap(n)

        ts_list = np.asarray(ts_ms, dtype=np.int64)
        min_ts, max_ts = int(ts_list.min()), int(ts_list.max())
        batch_starts = None
        if self.window is not None:
            def sub(idx):
                return self._process_columnar(
                    key_ids[idx], ts_list[idx],
                    {k: v[idx] for k, v in cols.items()},
                    None if nulls is None else
                    {k: v[idx] for k, v in nulls.items()})

            guarded, batch_starts = self._gap_guard(ts_list, sub)
            if guarded is not None:
                return guarded

        self._ensure_epoch(min_ts)
        self._maybe_rebase(max_ts)

        ts_rel64 = ts_list - self.epoch
        if int(ts_rel64.max()) >= (1 << 31):
            raise OverflowError(
                "stream time span exceeds int32 relative range")
        # SQL NULL in a WHERE operand makes the predicate not-true: fold
        # filter-column null masks into `valid` exactly like the row path.
        valid, null_streams = self._null_valid_streams(n, nulls)
        wm_rel = np.int32(max(self.watermark_abs - self.epoch, -1)
                          if self.watermark_abs >= 0 else -1)
        self._note_late(ts_list)
        self._run_step(cap, n, key_ids, ts_rel64, cols, valid,
                       null_streams, wm_rel)

        out = None
        if self.window is not None:
            self._track_windows(ts_list, batch_starts)
        if max_ts > self.watermark_abs:
            self.watermark_abs = max_ts
        if self.emit_changes:
            out = extend_rows(out, self._drain_changes())
        out = extend_rows(out, self.close_due_windows())
        return out if out is not None else []

    # ---- pipelined ingest (stage on one thread, step on another) ----------

    def _device_stage(self, words):
        """Double-buffered H2D staging: dispatch the async upload, then
        bound in-flight transfers to `upload_slots` by waiting on the
        OLDEST outstanding one (the classic double-buffer handoff). The
        wait blocks an encode worker, never the step-dispatch thread,
        so upload N+1 rides the link while batch N computes. Buffers
        already consumed (donated) by a step are skipped — donation IS
        the recycling of the staging slot."""
        nbytes = getattr(words, "nbytes", None)
        if nbytes is not None:
            self.transfer_stats["h2d_bytes"] += int(nbytes)
        dev = jax.device_put(words)
        wait = None
        with self._upload_lock:
            self._upload_ring.append(dev)
            if len(self._upload_ring) > max(self.upload_slots, 1):
                wait = self._upload_ring.popleft()
        if wait is not None and not wait.is_deleted():
            t0 = time.perf_counter()
            try:
                # deliberate double-buffer backpressure: bounds in-flight
                # H2D to upload_slots, blocking an encode worker only.
                # analyze: ok dispatch-sync — never the step thread
                wait.block_until_ready()
            except RuntimeError:
                pass  # donated to a step between the check and the wait
            with self._stats_lock:
                self.stage_stats["upload_wait_s"] += \
                    time.perf_counter() - t0
        return dev

    def _null_valid_streams(self, n: int, nulls):
        null_streams: dict[str, np.ndarray] = {}
        if nulls is not None:
            for nk, refs in self._null_specs:
                nm = np.zeros(n, dtype=np.bool_)
                for c in refs:
                    if c in nulls:
                        nm |= nulls[c][:n]
                if nm.any():
                    null_streams[nk] = nm
        valid = None
        if self._filter_expr is not None and nulls is not None:
            fm = np.zeros(n, dtype=np.bool_)
            for c in columns_of(self._filter_expr):
                if c in nulls:
                    fm |= nulls[c][:n]
            if fm.any():
                valid = ~fm
        return valid, null_streams

    # contract: dispatches<=0 fetches<=0
    def stage_columnar(self, key_ids, ts_ms, cols, nulls=None,
                       upload: bool = True) -> StagedBatch | None:
        """Encode (and upload) one micro-batch ahead of its step — safe to
        run on an encoder thread while the main thread dispatches earlier
        batches, as long as stage calls happen in batch order (the wire
        codec's adaptive state is ordered). Staging must stay kernel-
        dispatch- and fetch-FREE (the contract above): it overlaps the
        ordered step loop, and a sync here would serialize the pipeline.
        Rare control flow (epoch rebase, int32 overflow, gap splits)
        falls back to the synchronous path inside process_staged()."""
        key_ids = np.asarray(key_ids, dtype=np.int32)
        n = len(key_ids)
        if n == 0:
            return None
        if n > self.batch_capacity:
            raise ValueError("stage_columnar: batch exceeds capacity; "
                             "split upstream")
        ts = np.asarray(ts_ms, dtype=np.int64)
        self._ensure_epoch(int(ts.min()))
        # single epoch read: a concurrent rebase on the caller thread
        # between here and the stamp below must not split the two (the
        # stamp is what process_staged validates against)
        epoch = self.epoch
        ts_rel64 = ts - epoch
        staged = StagedBatch(
            n=n, cap=self._stage_cap(n),
            combo=None, bases=None, words=None, epoch=epoch,
            ts_min=int(ts.min()), ts_max=int(ts.max()),
            key_ids=key_ids, ts_ms=ts, cols=cols, nulls=nulls)
        if int(ts_rel64.max()) >= (1 << 31):
            return staged  # combo=None -> synchronous fallback (rebases)
        valid, null_streams = self._null_valid_streams(n, nulls)
        combo, bases, words = self._encode_locked(
            staged.cap, n, key_ids, ts_rel64, cols, valid, null_streams)
        staged.combo = combo
        staged.bases = bases
        staged.words = self._device_stage(words) if upload else words
        return staged

    def process_staged(self, staged: StagedBatch | None
                       ) -> list[dict[str, Any]]:
        """Ordered step dispatch for a staged batch (main thread)."""
        if staged is None:
            return []
        if (staged.combo is None or staged.epoch != self.epoch
                or staged.ts_max - self.epoch >= self.rebase_threshold):
            # stale encode (epoch rebased since) or wide time span:
            # synchronous path re-encodes with full handling
            try:
                return self._process_columnar(staged.key_ids, staged.ts_ms,
                                              staged.cols, staged.nulls)
            finally:
                self._no_close.clear()
                self._touched_this_call.clear()
        try:
            return self._process_staged(staged)
        finally:
            self._no_close.clear()
            self._touched_this_call.clear()

    # contract: dispatches<=1 fetches<=0
    def _process_staged(self, staged: StagedBatch) -> list[dict[str, Any]]:
        ts_list = staged.ts_ms
        batch_starts = None
        if self.window is not None:
            def sub(idx):
                return self._process_columnar(
                    staged.key_ids[idx], ts_list[idx],
                    {k: np.asarray(v)[idx] for k, v in staged.cols.items()},
                    None if staged.nulls is None else
                    {k: np.asarray(v)[idx] for k, v in staged.nulls.items()})

            guarded, batch_starts = self._gap_guard(ts_list, sub)
            if guarded is not None:
                return guarded

        # process_staged routes any batch with ts_max - epoch >=
        # rebase_threshold (< 2^31) to the guarded synchronous path.
        # analyze: ok overflow-narrowing — caller-guarded narrow
        wm_rel = np.int32(max(self.watermark_abs - self.epoch, -1)
                          if self.watermark_abs >= 0 else -1)
        self._note_late(ts_list)
        self.read_epoch += 1
        step = lattice.compiled_encoded_step(
            self.spec, self.schema, self._filter_expr, staged.combo,
            staged.cap, donate_words=True)
        with kernel_family("step", self.dispatch_observer,
                           ready=self._device_values):
            self.state = step(self.state, wm_rel, np.int32(staged.n),
                              staged.bases, staged.words)

        out = None
        if self.window is not None:
            self._track_windows(ts_list, batch_starts)
        if staged.ts_max > self.watermark_abs:
            self.watermark_abs = staged.ts_max
        if self.emit_changes:
            out = extend_rows(out, self._drain_changes())
        out = extend_rows(out, self.close_due_windows())
        return out if out is not None else []

    def key_id_for(self, key: tuple) -> int:
        """Dense id for a group-key tuple (columnar-path key dictionary).
        Float key values are canonicalized through float32 so JSON and
        columnar producers agree on group identity. The id is pinned
        (never retired) until a `note_key_use` dates it."""
        key = canon_key(key)
        kid = self._key_ids.get(key)
        if kid is None:
            return int(self._assign_new([key])[0])
        self._key_last[kid] = _KEY_PINNED
        return kid

    def key_ids_for(self, keys: list[tuple], *,
                    canonical: bool = False) -> np.ndarray:
        """`key_id_for` over many keys at once: their ids in the order
        given, the unknown ones registered in one piece and in that
        order (so every key gets the id a per-key walk would give it).
        `canonical`: the caller vouches that no value is a float."""
        if not canonical:
            keys = [canon_key(k) for k in keys]
        kids = np.fromiter(
            map(self._key_ids.get, keys, itertools.repeat(-1)),
            np.int32, len(keys))
        miss = np.flatnonzero(kids < 0)
        # the known ones first: registering the others may set off a
        # retirement, which must not take an id this call hands out
        self.pin_keys(kids[kids >= 0])
        if len(miss):
            new = list(dict.fromkeys(keys[i] for i in miss.tolist()))
            ids = self._assign_new(new)
            if len(new) == len(miss):
                kids[miss] = ids
            else:  # two spellings of one canonical key in one call
                at = dict(zip(new, ids.tolist()))
                kids[miss] = [at[keys[i]] for i in miss.tolist()]
        return kids

    def _assign_new(self, keys: list[tuple]) -> np.ndarray:
        """Ids for canonical keys the dictionary does not hold: the
        free ids of retired keys first (smallest first), then the next
        never used; room is made by `_make_room`. Pinned until dated."""
        n = len(keys)
        self._make_room(n)
        rev = self._key_rev
        kids = np.empty(n, np.int32)
        take = min(n, len(self._free))
        if take:
            reused = self._free[:-take - 1:-1]
            del self._free[-take:]
            kids[:take] = reused
            for kid, key in zip(reused, keys):
                rev[kid] = key
            self.key_stats["key_ids_reused"] += take
        if take < n:
            kids[take:] = np.arange(len(rev), len(rev) + n - take)
            rev.extend(keys[take:])
        self._key_ids.update(zip(keys, kids.tolist()))
        self._key_last[kids] = _KEY_PINNED
        for g, col in enumerate(self._key_cols):
            col[kids] = [k[g] for k in keys]
        return kids

    # contract: dispatches<=0 fetches<=0
    def pin_keys(self, kids: np.ndarray) -> None:
        """Ids resolved outside `key_id_for` (the key table's hits)
        that the batch in hand names: not to be retired before
        `note_key_use` dates them."""
        self._key_last[kids] = _KEY_PINNED

    # contract: dispatches<=0 fetches<=0
    def note_key_use(self, kids: np.ndarray, ts_hi: int) -> None:
        """Date the ids a batch names (each at least once; more do no
        harm; negative entries are no id and are skipped), once all of
        them are resolved: no event of the batch is
        later than `ts_hi` (absolute ms). The date only ever moves
        forward, so batches out of order keep an id alive longer, never
        shorter. Ids that are never dated (a caller that keeps ids
        across batches) stay pinned. A plan that can never retire a key
        (no window, EMIT CHANGES) keeps no dates."""
        if self.window is None or self.emit_changes:
            return
        if ts_hi > self._named_hi:
            self._named_hi = int(ts_hi)
        if len(kids) and kids.min() < 0:
            kids = kids[kids >= 0]
        self._key_last[kids] = self._named_hi

    def _free_ids(self) -> int:
        return len(self._free) + self.spec.n_keys - len(self._key_rev)

    def _make_room(self, need: int) -> None:
        """Room for `need` more ids. Retire before growing: a full
        table first frees the ids of dead keys, and the planes double
        (a recompile) only where that left under a quarter of the
        table free, so that a live set that fits stops growing it and
        one that churns is not retired a few keys at a time."""
        if self._free_ids() >= need:
            return
        self._retire_keys()
        while self._free_ids() < max(need, self.spec.n_keys // 4):
            self._grow_keys()

    # contract: dispatches<=0 fetches<=0
    def _retire_keys(self) -> int:
        """Free the ids of dead group keys; returns how many.

        A key is dead when every window it was named in is closed: the
        newest window an id was dated in (`note_key_use`) is past
        end + grace at the watermark, so the device drops as late
        whatever a batch still in the pipeline holds for it, and is
        older than every window still open, so its slot was reset at
        its close and the id's row is zero in every plane: nothing is
        remapped and no device program runs. Ids handed out by
        `key_id_for` and not dated since stay pinned. Never with EMIT
        CHANGES or a deferred close pending: their extracts name ids
        that are decoded later. A windowless plan's keys never die."""
        w = self.window
        if (w is None or self.emit_changes or self._pending_closes
                or self.watermark_abs < 0):
            return 0
        with trace_span(self.tracer, "key_retire"):
            rev = self._key_rev
            last = self._key_last[:len(rev)]
            horizon = self.watermark_abs - w.size_ms - w.grace_ms
            if self._open:
                horizon = min(horizon, min(self._open) - w.advance_ms)
            dead = np.flatnonzero(
                (last - last % w.advance_ms <= horizon)
                & (last > _KEY_FREE))
            if len(dead) == 0:
                return 0
            self.key_stats["key_retirements"] += 1
            ids = dead.tolist()
            keys = [rev[kid] for kid in ids]
            for kid in ids:
                rev[kid] = None
            deque(map(self._key_ids.__delitem__, keys), maxlen=0)
            self._key_table.forget(keys)
            self._key_last[dead] = _KEY_FREE
            for col in self._key_cols:
                col[dead] = None
            self._free = np.union1d(
                np.asarray(self._free, np.int64), dead)[::-1].tolist()
            self.key_stats["keys_retired"] += len(ids)
            return len(ids)

    # contract: dispatches<=0 fetches<=0
    def key_gauges(self) -> dict[str, int]:
        """The key dictionary's and the top close's counts for `admin
        stats queries` and /metrics: host ints."""
        out = dict(self.key_stats)
        out["keys_live"] = len(self._key_ids)
        out["key_capacity"] = self.spec.n_keys
        for k in ("close_rows_kept", "close_groups",
                  "close_tie_refetches"):
            out[k] = self.close_stats[k]
        return out

    def _load_keys(self, key_rev: list, last=None) -> None:
        """Install a restored dictionary: `key_rev` with None where an
        id is free, `last` its dates (None: every key pinned)."""
        self._key_rev = list(key_rev)
        self._key_ids = {k: i for i, k in enumerate(self._key_rev)
                         if k is not None}
        n = self.spec.n_keys
        self._key_last = np.full(n, _KEY_FREE, np.int64)
        self._key_cols = [np.empty(n, object) for _ in self.group_cols]
        held = np.asarray([i for i, k in enumerate(self._key_rev)
                           if k is not None], np.int64)
        self._key_last[held] = _KEY_PINNED if last is None \
            else np.asarray(last, np.int64)[held]
        for g, col in enumerate(self._key_cols):
            col[held] = [self._key_rev[i][g] for i in held.tolist()]
        self._free = [i for i in range(len(self._key_rev) - 1, -1, -1)
                      if self._key_rev[i] is None]
        dated = self._key_last[held]
        dated = dated[dated < _KEY_PINNED]
        self._named_hi = int(dated.max()) if len(dated) else -1

    # ---- emission ----------------------------------------------------------

    def _decode_key(self, kid: int) -> dict[str, Any]:
        return dict(zip(self.group_cols, self._key_rev[kid]))

    def _postprocess(self, row: dict[str, Any]) -> dict[str, Any] | None:
        if self.node.having is not None:
            if not eval_host(self.node.having, row):
                return None
        if self.node.post_projections:
            projected = {}
            for name, expr in self.node.post_projections:
                projected[name] = eval_host(expr, row)
            # keep window metadata
            for meta in ("winStart", "winEnd"):
                if meta in row:
                    projected[meta] = row[meta]
            return projected
        return row

    def _agg_row(self, kid: int, outs: Mapping[str, np.ndarray], idx: int,
                 win_start_abs: int | None) -> dict[str, Any] | None:
        row = self._decode_key(kid)
        for name, arr in outs.items():
            spec = next(a for a in self.spec.aggs if a.out_name == name)
            if spec.kind in (AggKind.TOPK, AggKind.TOPK_DISTINCT):
                vals = np.asarray(arr[idx])
                row[name] = [float(x) for x in vals
                             if np.isfinite(x)]
                continue
            v = float(arr[idx])
            if spec.kind in (AggKind.COUNT_ALL, AggKind.COUNT,
                             AggKind.APPROX_COUNT_DISTINCT):
                v = int(round(v))
            row[name] = v
        if win_start_abs is not None and self.window is not None:
            row["winStart"] = win_start_abs
            row["winEnd"] = win_start_abs + self.window.size_ms
        return self._postprocess(row)

    # contract: dispatches<=1 fetches<=1
    def _drain_changes(self) -> "ColumnarEmit | list[dict[str, Any]]":
        self.state, packed = self._extract_touched(self.state)
        if not self.defer_change_decode:
            host = np.asarray(packed)
            self.transfer_stats["d2h_bytes"] += host.nbytes
            return self._decode_changes(host, self.epoch)
        # the epoch is captured WITH the extract: a rebase between
        # extract and the deferred decode must not shift window bounds
        self._pending_changes.append((self.epoch, packed))
        out = self._collect_drained(block=False)
        if len(self._pending_changes) <= max(self.change_drain_depth, 1):
            return out if out is not None else []
        # keep the newest extract deferred (it pipelines behind the
        # next batch's work); fetch everything older in one transfer
        keep = self._pending_changes.pop()
        batch = self._pending_changes
        self._pending_changes = [keep]
        if self.async_change_drain:
            # the blocking D2H fetch + columnar decode move to the
            # shared drain pool; batches surface on later calls, in
            # FIFO order
            self._drain_futs.append(
                _change_drain_pool().submit(self._drain_job, batch))
            out = extend_rows(out, self._collect_drained(block=False))
        else:
            out = extend_rows(out, self._decode_pending(batch))
        return out if out is not None else []

    def _drain_job(self, batch: list) -> list[dict[str, Any]]:
        """One async drain unit (drain-pool thread). Reads only
        append-only / immutable executor state: with EMIT CHANGES no
        key is ever retired, so the key columns only gain entries,
        spec.aggs never changes (grow_keys swaps n_keys
        only), and the packed buffers are immutable device values."""
        t0 = time.perf_counter()
        try:
            return self._decode_pending(batch)
        finally:
            with self._stats_lock:
                self.stage_stats["drain_s"] += time.perf_counter() - t0

    def _collect_drained(self, block: bool):
        """Completed async drains, strictly in submission order (head
        pop only — a done future behind an unfinished one waits, so
        change rows never reorder). block=True takes everything. A lone
        columnar batch rides through unmaterialized (extend_rows)."""
        rows = None
        while self._drain_futs:
            f = self._drain_futs[0]
            if not block and not f.done():
                break
            self._drain_futs.popleft()
            rows = extend_rows(rows, f.result())
        return rows

    def flush_changes(self) -> list[dict[str, Any]]:
        """Decode every deferred changelog extract (forces the async
        drain queue, then the still-pending tail)."""
        rows = extend_rows(self._collect_drained(block=True),
                           self._decode_pending(self._pending_changes))
        self._pending_changes = []
        return rows if rows is not None else []

    def has_pending_changes(self) -> bool:
        """True when deferred change extracts (queued or in the async
        drain) still hold undelivered rows."""
        return bool(self._pending_changes or self._drain_futs)

    # contract: dispatches<=0 fetches<=1
    def _decode_pending(self, pending: list
                        ) -> "ColumnarEmit | list[dict[str, Any]]":
        """Decode deferred change extracts, fetching device buffers in
        ONE device->host transfer per buffer shape (fetch count, not
        bytes, dominates on real links — each np.asarray is a full
        round trip). Shapes differ only across grow_keys boundaries.
        A single extract's batch stays columnar (ColumnarEmit)."""
        if not pending:
            return []
        if len(pending) == 1:
            epoch, buf = pending[0]
            host = np.asarray(buf)
            self.transfer_stats["d2h_bytes"] += host.nbytes
            return self._decode_changes(host, epoch)
        rows = None
        by_shape: dict[tuple, list] = {}
        for ep, buf in pending:
            by_shape.setdefault(tuple(buf.shape), []).append((ep, buf))
        for group in by_shape.values():
            stacked = np.asarray(lattice.stack_pow2(
                [b for _, b in group]))
            self.transfer_stats["d2h_bytes"] += stacked.nbytes
            for (ep, _), buf in zip(group, stacked):
                rows = extend_rows(rows, self._decode_changes(buf, ep))
        return rows if rows is not None else []

    def _decode_changes(self, packed: np.ndarray, epoch: int | None
                        ) -> "ColumnarEmit | list[dict[str, Any]]":
        """Batched changelog decode: unpack the touched extract, gather
        group-key columns through the cached reverse index, finalize
        aggregate columns, and hand the whole batch to the columnar
        HAVING/projection pass — a ColumnarEmit, no per-row walk (the
        changelog twin of _decode_extract_batch). The retained per-row
        reference is _decode_changes_rows (equivalence tests)."""
        n, kidx, win_start_rel, outs = lattice.unpack_touched_rows(
            self.spec, packed)
        if n == 0:
            return []
        cols: dict[str, Any] = {}
        kidx = kidx.astype(np.int64)
        for name, arr in zip(self.group_cols, self._key_rev_columns()):
            cols[name] = arr[kidx]
        for agg in self.spec.aggs:
            v = outs[agg.out_name]
            if agg.kind in (AggKind.TOPK, AggKind.TOPK_DISTINCT):
                finite = np.isfinite(v)
                vals = np.empty(len(v), object)
                vals[:] = [[float(x) for x in row[m]]
                           for row, m in zip(v, finite)]
                cols[agg.out_name] = vals
            elif agg.kind in (AggKind.COUNT_ALL, AggKind.COUNT,
                              AggKind.APPROX_COUNT_DISTINCT):
                cols[agg.out_name] = np.rint(v).astype(np.int64)
            else:
                cols[agg.out_name] = v.astype(np.float64)
        if self.window is not None:
            ws = win_start_rel.astype(np.int64) + epoch
            cols["winStart"] = ws
            cols["winEnd"] = ws + self.window.size_ms
        return self._postprocess_cols(cols, n)

    def _decode_changes_rows(self, packed: np.ndarray,
                             epoch: int | None) -> list[dict[str, Any]]:
        """Per-row changelog decode (the pre-columnar reference path,
        kept for equivalence tests)."""
        n, kidx, win_start_rel, outs_np = lattice.unpack_touched_rows(
            self.spec, packed)
        rows = []
        for i in range(n):
            ws = (int(win_start_rel[i]) + epoch
                  if self.window is not None else None)
            row = self._agg_row(int(kidx[i]), outs_np, i, ws)
            if row is not None:
                rows.append(row)
        return rows

    def _pad_slots(self, slots: list[int]) -> np.ndarray:
        """Due-slot vector padded (with -1) to a power of two, so close
        cycles of varying width share a handful of compiled shapes
        instead of one XLA executable per distinct due-count (shared
        with the session extract path via lattice.pad_slots)."""
        return lattice.pad_slots(slots)

    # contract: dispatches<=1 fetches<=1
    def _close_windows(self, starts: list[int]) -> list[dict[str, Any]]:
        """Pop + close every window in `starts` with ONE fused
        extract+reset dispatch (the close-cycle contract: one lattice
        kernel and one device->host fetch regardless of how many
        windows are due). A fused-kernel failure (activation/compile,
        device loss, or an injected ``device.activate`` fault) degrades
        this executor PERMANENTLY to the retained per-slot reference
        close — identical results, counted in device_fallbacks —
        instead of killing the query (ISSUE 8)."""
        if not starts:
            return []
        with trace_span(self.tracer, "close"):
            return self._close_cycle(starts)

    # contract: dispatches<=1 fetches<=1
    def _close_cycle(self, starts: list[int]) -> list[dict[str, Any]]:
        """The cycle itself, inside the `close` span: dispatch, then
        the D2H sync (`close_fetch`: the device's extract time shows
        here, the dispatch being async) and the host decode of the
        fetched rows (`close_decode`)."""
        ows = [(s, self._open.pop(s).slot) for s in starts]
        self.read_epoch += 1
        self.close_stats["close_cycles"] += 1
        if not self._fused_close_ok:
            return self._close_windows_ref(ows)
        slots = self._pad_slots([slot for _s, slot in ows])
        packed = None
        prev_state = self.state  # no donation: stays valid for restore
        try:
            if FAULTS.active:  # chaos: provoke a fused-close failure
                FAULTS.point("device.activate")
            if self.emit_changes:
                # the changelog already carried final values: batched
                # reset only, no extract and no fetch
                self.state = self._reset_slots(self.state, slots)
            elif self._top is not None:
                # the window's extreme is taken on the device: `packed`
                # holds the rows that reach it, `full` stays there
                self.state, packed, full = self._extract_top_reset(
                    self.state, slots)
            else:
                self.state, packed = self._extract_reset_slots(
                    self.state, slots)
        except Exception as e:  # noqa: BLE001 — dispatch failed before
            # any state mutation (functional update): the reference
            # path closes the same windows from unchanged state
            log.warning(
                "fused close failed (%s: %s); degrading to the "
                "per-slot reference close", type(e).__name__, e)
            self._fused_close_ok = False
            self.device_fallbacks += 1
            return self._close_windows_ref(ows)
        if self.emit_changes:
            rows = []
        elif self.defer_close_decode and self._top is None:
            # keep the packed batch as a device value; no host sync
            # (a top close is never deferred: what it fetches is small)
            self._pending_closes.append((list(starts), packed))
            rows = []
        else:
            self.close_stats["close_fetches"] += 1
            try:
                with trace_span(self.tracer, "close_fetch"):
                    packed_host = np.asarray(packed)
                self.transfer_stats["d2h_bytes"] += packed_host.nbytes
            except Exception as e:  # noqa: BLE001 — the dispatch is
                # async: a device-side execution failure surfaces at
                # this D2H sync, AFTER self.state was reassigned to the
                # reset result. Restore the pre-close state (functional
                # update, still valid) and close the same windows on
                # the reference path instead of killing the query.
                log.warning(
                    "fused close fetch failed (%s: %s); degrading to "
                    "the per-slot reference close", type(e).__name__, e)
                self._fused_close_ok = False
                self.device_fallbacks += 1
                self.state = prev_state
                return self._close_windows_ref(ows)
            with trace_span(self.tracer, "close_decode"):
                if self._top is not None:
                    rows = self._decode_top_batch(packed_host, full,
                                                  starts)
                else:
                    rows = self._decode_extract_batch(packed_host,
                                                      starts)
        for s in starts:
            self._no_close.discard(s)
        return rows

    # contract: dispatches<=0 fetches<=1
    def _decode_top_batch(self, top: np.ndarray, full,
                          starts: Sequence[int]
                          ) -> "ColumnarEmit | list[dict[str, Any]]":
        """Decode a top close's survivors `top` [P, 2+rows, R] (see
        lattice.build_extract_top_reset_slots) into a ColumnarEmit.
        Where more groups tie for a window's extreme than `top` holds,
        the masked full column `full` (still on the device) is fetched
        in their place, counted, so that a tie is never cut."""
        stats = self.close_stats
        widx, kids, outs = [], [], []
        overflow = False
        for p in range(len(starts)):
            n_keep, n_groups, kid, out = lattice.unpack_top_rows(
                self.spec, top[p])
            stats["close_rows_kept"] += n_keep
            stats["close_groups"] += n_groups
            overflow |= n_keep > len(kid)
            widx.append(np.full(len(kid), p, np.int64))
            kids.append(kid.astype(np.int64))
            outs.append(out)
        if overflow:
            stats["close_tie_refetches"] += 1
            stats["close_fetches"] += 1
            full_host = np.asarray(full)
            self.transfer_stats["d2h_bytes"] += full_host.nbytes
            return self._decode_extract_batch(full_host, starts)
        widx = np.concatenate(widx)
        if len(widx) == 0:
            return []
        return self._finish_extract(
            widx, np.concatenate(kids),
            {a.out_name: np.concatenate([o[a.out_name] for o in outs])
             for a in self.spec.aggs}, starts)

    def _close_windows_ref(self, ows: list) -> list[dict[str, Any]]:
        """The retained per-slot reference close (the equivalence path
        tests patch in): one extract + one reset dispatch per window,
        per-kid row decode. Only reached after a fused-close failure —
        correctness over dispatch count on a degraded executor."""
        rows: list[dict[str, Any]] = []
        for s, slot in ows:
            if not self.emit_changes:
                # degraded per-slot fallback after a fused-close
                # failure; one fetch per window is the price of
                # staying alive
                # analyze: ok dispatch-sync — reference close fallback
                packed = np.asarray(self._extract_slot(
                    self.state, np.int32(slot)))
                count, _sr, outs = lattice.unpack_extract_rows(
                    self.spec, packed)
                live = count > 0
                if self._top is not None and live.any():
                    # the filter across groups, on the host
                    agg = next(a for a in self.spec.aggs
                               if a.out_name == self._top.agg)
                    vals = count if agg.kind == AggKind.COUNT_ALL \
                        else outs[agg.out_name]
                    best = vals[live].max() if self._top.extreme == "max" \
                        else vals[live].min()
                    live &= vals == best
                for kid in np.nonzero(live)[0]:
                    row = self._agg_row(int(kid), outs, int(kid), s)
                    if row is not None:
                        rows.append(row)
            self.state = self._reset_slot(self.state, np.int32(slot))
            self._no_close.discard(s)
        return rows

    # contract: dispatches<=0 fetches<=1
    def drain_closed(self) -> list[dict[str, Any]]:
        """Decode every deferred window close (forces the device queue).
        Multiple pending close cycles fetch in ONE device->host transfer
        per buffer shape — fetch count, not bytes, dominates drain cost
        on real links."""
        if not self._pending_closes:
            return []
        # A fetch failure here deliberately propagates: the deferred
        # packed batches' source windows were reset when the close was
        # deferred, so there is no pre-close state to fall back to —
        # task death + supervised restart from snapshot (at-least-once
        # replay) is the correct recovery, unlike the in-place degrade
        # _close_windows can do at its own sync point.
        if FAULTS.active:  # chaos: fail/delay the deferred-close drain
            FAULTS.point("device.fetch")
        out = None
        if len(self._pending_closes) == 1:
            starts, packed_dev = self._pending_closes[0]
            self.close_stats["close_fetches"] += 1
            packed_host = np.asarray(packed_dev)
            self.transfer_stats["d2h_bytes"] += packed_host.nbytes
            out = self._decode_extract_batch(packed_host, starts)
            self._pending_closes.clear()  # only after decode succeeded
            return out if out is not None else []
        # Group by buffer shape: grow_keys() between two deferred closes
        # changes the K dimension (and cycle width changes P), and
        # jnp.stack over mixed shapes raises.
        by_shape: dict[tuple, list[tuple[list[int], Any]]] = {}
        for starts, packed in self._pending_closes:
            by_shape.setdefault(tuple(packed.shape), []).append(
                (starts, packed))
        for group in by_shape.values():
            self.close_stats["close_fetches"] += 1
            stacked = np.asarray(lattice.stack_pow2(
                [p for _, p in group]))
            self.transfer_stats["d2h_bytes"] += stacked.nbytes
            for (starts, _), packed in zip(group, stacked):
                out = extend_rows(
                    out, self._decode_extract_batch(packed, starts))
        self._pending_closes.clear()  # only after every decode succeeded
        return out if out is not None else []

    def close_due_windows(self) -> list[dict[str, Any]]:
        """Extract + reset every open window past end+grace: one fused
        device dispatch + one fetch for the whole cycle. Host-driven."""
        if self.window is None or self.watermark_abs < 0:
            return []
        w = self.window
        due = [s for s in self._open
               if s + w.size_ms + w.grace_ms <= self.watermark_abs
               and s not in self._no_close]
        return self._close_windows(sorted(due))

    def _key_rev_columns(self) -> list[np.ndarray]:
        """Per-group-column object arrays over the key dictionary, for
        vectorized key decode (one gather per column instead of one
        _decode_key dict per row): kept as ids are assigned and
        retired, never rebuilt."""
        return self._key_cols

    def _decode_extract_batch(self, packed: np.ndarray,
                              starts: Sequence[int | None],
                              kids: np.ndarray | None = None
                              ) -> "ColumnarEmit | list[dict[str, Any]]":
        """Vectorized decode of a batched extract buffer [P, 2+rows, K]
        into a ColumnarEmit: key decode is a cached reverse-index
        gather, agg finalization is columnar numpy, HAVING evaluates
        columnwise — no per-kid Python loop. `starts[p]` is window p's
        absolute start (None when windowless). `kids` are the key ids
        of the buffer's last axis where it holds some keys' rows and
        not the whole table's (a keyed peek)."""
        count = packed[:, 0, :]
        widx, at = np.nonzero(count > 0)
        if len(widx) == 0:
            return []
        return self._finish_extract(
            widx, at if kids is None else kids[at],
            lattice.gather_extract_batch(self.spec, packed, widx, at),
            starts)

    def _finish_extract(self, widx: np.ndarray, kids: np.ndarray,
                        outs: Mapping[str, np.ndarray],
                        starts: Sequence[int | None]
                        ) -> "ColumnarEmit | list[dict[str, Any]]":
        """The extracted (window, key) pairs as emitted columns: keys
        decoded through the reverse index, aggregates finalized to
        their emitted types, the window's bounds, then HAVING and the
        projections."""
        cols: dict[str, Any] = {}
        for name, arr in zip(self.group_cols, self._key_rev_columns()):
            cols[name] = arr[kids]
        for agg in self.spec.aggs:
            v = outs[agg.out_name]
            if agg.kind in (AggKind.TOPK, AggKind.TOPK_DISTINCT):
                finite = np.isfinite(v)
                vals = np.empty(len(v), object)
                vals[:] = [[float(x) for x in row[m]]
                           for row, m in zip(v, finite)]
                cols[agg.out_name] = vals
            elif agg.kind in (AggKind.COUNT_ALL, AggKind.COUNT,
                              AggKind.APPROX_COUNT_DISTINCT):
                cols[agg.out_name] = np.rint(v).astype(np.int64)
            else:
                cols[agg.out_name] = v.astype(np.float64)
        if self.window is not None and starts and starts[0] is not None:
            ws = np.asarray(starts, np.int64)[widx]
            cols["winStart"] = ws
            cols["winEnd"] = ws + self.window.size_ms
        return self._postprocess_cols(cols, len(widx))

    def _postprocess_cols(self, cols: dict[str, Any], n: int
                          ) -> "ColumnarEmit | list[dict[str, Any]]":
        """HAVING + SELECT projections over a columnar batch. The
        vectorized evaluator covers the numeric/comparison core; any
        op outside it falls back to the per-row interpreter so
        semantics match the legacy path exactly."""
        if self.node.having is not None:
            try:
                keep = np.broadcast_to(
                    np.asarray(eval_host_vec(self.node.having, cols),
                               np.bool_), (n,))
            except Exception:  # noqa: BLE001 — host-only op / NULLs:
                return self._postprocess_rows(ColumnarEmit(cols, n))
            if not keep.all():
                cols = {k: np.asarray(v)[keep] for k, v in cols.items()}
                n = int(keep.sum())
                if n == 0:
                    return []
        if self.node.post_projections:
            try:
                projected: dict[str, Any] = {}
                for name, expr in self.node.post_projections:
                    v = eval_host_vec(expr, cols)
                    projected[name] = np.broadcast_to(
                        np.asarray(v), (n,)) if np.ndim(v) == 0 \
                        else np.asarray(v)
                for meta in ("winStart", "winEnd"):
                    if meta in cols:
                        projected[meta] = cols[meta]
                cols = projected
            except Exception:  # noqa: BLE001
                return self._postprocess_rows(ColumnarEmit(cols, n))
        return ColumnarEmit(cols, n)

    def _postprocess_rows(self, rows) -> list[dict[str, Any]]:
        """Per-row HAVING/projection fallback (host-only ops)."""
        out = []
        for row in rows:
            row = self._postprocess(row)
            if row is not None:
                out.append(row)
        return out

    # ---- pull queries (materialized views) ---------------------------------

    # contract: dispatches<=0 fetches<=0
    def read_version(self) -> tuple:
        """Exact version of the peek-visible aggregate: equal tuples
        guarantee peek() would return the same rows (the read cache's
        validity key — ISSUE 20). Host ints only; lock-free readers get
        at worst a spurious mismatch."""
        return ("agg", self._read_nonce, self.read_epoch,
                self.close_stats["close_cycles"], self.watermark_abs)

    # contract: dispatches<=0 fetches<=0
    def live_min_win_end(self) -> int | None:
        """Smallest winEnd any live (open OR due-but-unclosed) window
        could emit, or None when no live window exists. Lets a reader
        whose WHERE bounds winEnd strictly below this skip peek()
        entirely — closed rows alone answer the query (ISSUE 20)."""
        if self.window is None or not self._open:
            return None
        return min(self._open) + self.window.size_ms

    # contract: dispatches<=1 fetches<=1
    def peek(self) -> list[dict[str, Any]]:
        """Current (open-window) aggregate rows without resetting state —
        the live half of a materialized view; closed windows are kept by
        the view store that owns this executor. ONE batched extract
        dispatch + ONE fetch covers every open window. The whole of it
        is the `peek` family's span on the caller's (a pull's) thread:
        `kernel_dispatch_ms{peek}` is dispatch + D2H sync + decode.

        A plan with QUALIFY ... OVER has no live rows: a window's
        extreme is known when it closes, so an open window gives a pull
        nothing, and the view's closed rows are the whole answer."""
        if self._top is not None:
            return []
        starts, slots = self._live_slots()
        if not starts:
            return []
        with kernel_family("peek", self.dispatch_observer):
            packed = np.asarray(self._extract_slots(
                self.state, self._pad_slots(slots)))
            return self._decode_extract_batch(packed, starts)

    def _live_slots(self) -> tuple[list[int | None], list[int]]:
        """(absolute starts, slots) of the windows a peek reads, oldest
        first: every open window, or the one slot of a windowless
        plan."""
        if self.window is None:
            return [None], [0]
        starts = sorted(self._open)
        return starts, [self._open[s].slot for s in starts]

    # contract: dispatches<=1 fetches<=1
    def peek_key(self, key: tuple
                 ) -> "ColumnarEmit | list[dict[str, Any]] | None":
        """`peek()`'s rows of ONE group key (`key`: its values in
        `group_cols` order), for a pull whose WHERE pins the key: that
        key's cells of every open window are extracted, fetched and
        decoded ([n_slots, 2+rows, 1], one shape a plan whatever is
        open) and not the table's [P, 2+rows, K]. The key is found as
        a batch's is, by `==` and `hash` in the key dictionary. None
        where nothing was dispatched: the dictionary has no such key
        (no batch named it, or its id was retired), no window is open,
        or the plan keeps a window's top (`peek()` has no rows)."""
        if self._top is not None:
            return None
        kid = self._key_ids.get(key)
        starts, slots = self._live_slots()
        if kid is None or not starts:
            return None
        kids = np.array([kid], np.int32)
        with kernel_family("peek", self.dispatch_observer):
            packed = np.asarray(self._extract_slots(
                self.state, self._pad_key_slots(slots), kids))
            return self._decode_extract_batch(packed, starts, kids)

    def _pad_key_slots(self, slots: list[int]) -> np.ndarray:
        """A keyed peek's slot vector: padded (with -1) to the plan's
        slot count, which bounds the open windows, so the program has
        one shape a key capacity."""
        padded = np.full(self.spec.n_slots, -1, np.int32)
        padded[:len(slots)] = slots
        return padded

    # contract: dispatches<=1 fetches<=0
    def build_peek_key(self) -> None:
        """Build `peek_key`'s program for the table's capacity before a
        reader needs it (the task does, where it pins a snapshot's
        copy: server/tasks.py `_build_pin`): one run over padding
        alone, nothing fetched. A plan no view peeks by key builds
        nothing."""
        if (self.peek_key is not None and self._top is None
                and not self.emit_changes):
            self._extract_slots(self.state, self._pad_key_slots([]),
                                np.zeros(1, np.int32))

    # contract: dispatches<=0 fetches<=1
    def block_until_ready(self) -> None:
        jax.block_until_ready(self.state)
