"""Stream-stream JOIN execution: interval joins and window joins.

Reference semantics (hstream-processing Stream.hs:222-300 /
joinStreamProcessor): each record is inserted into its side's
timestamped KV store, then probed against the other side's store over
[ts - within, ts + within]; matching pairs (equal join key) emit a
joined record whose fields are the union of both sides qualified by
stream name (genJoiner, Internal/Codegen.hs:62-67) and whose timestamp
is max(ts1, ts2). The joined stream feeds the rest of the plan
(filter -> window aggregate -> ...), exactly like the reference's
merged-stream task DAG (Codegen.hs:253-266).

Design: two execution paths with identical semantics.

  * Device path (the hot one): both sides live as device-resident
    sorted stores (engine.lattice interval-join kernels) and every
    micro-batch is ONE fused probe+insert dispatch plus ONE
    device->host fetch of the packed match buffer; watermark eviction
    is a vmapped two-sided compaction kernel and the int32 relative
    time space rebases on the shared join epoch instead of aborting.
    Matched pairs feed the inner aggregate columnar (optionally
    coalesced across micro-batches), so no joined-row dicts ever
    materialize. Activated once the columnar fast path is planned
    (`_plan_fast`); `use_device_join=False` forces the host path.
  * Host path (the equivalence reference): `_FlatIntervalStore` per
    side — flat sorted arrays probed with one searchsorted pair per
    batch, the batch restatement of the reference's per-record ordered
    map walk. Also serves plans the fast path cannot columnarize.

Join state is pruned by within + downstream grace, bounding memory
where the reference's in-memory store grows forever.

A WINDOW join (`JOIN ... WITHIN WINDOW`: a pair joins where both records
fall in the same window of the statement's tumbling window) runs on the
same two paths, stores and fused step; `JoinExecutor.__init__` lists
what differs: the probe's bounds, event time as the minimum over the
sources, eviction by closed windows with the join codes' reclamation,
and inner key ids that are dated and retire.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Mapping, Sequence

import contextlib
from collections import deque

import numpy as np

from hstream_tpu.common.errors import SQLCodegenError
from hstream_tpu.common.logger import get_logger
from hstream_tpu.common.tracing import kernel_family, trace_span
from hstream_tpu.engine.expr import BinOp, Col, Expr, eval_host
from hstream_tpu.engine.plan import AggregateNode
from hstream_tpu.engine.statestore import LastValueStore
from hstream_tpu.engine.types import ColumnType, canon_key, round_up_pow2
from hstream_tpu.engine.window import DEFAULT_GRACE_MS, TumblingWindow

log = get_logger("join")

_MISS = object()  # row.get sentinel: "field absent", distinct from None


def split_on_condition(on: Expr, left_streams: set[str],
                       right_streams: set[str]) -> tuple[list[Expr],
                                                         list[Expr]]:
    """Decompose `ON a.k1 = b.k2 [AND ...]` into per-side key-selector
    expression lists (evaluated over each side's RAW rows, so
    qualification is stripped). The reference's key selectors are
    functions of one side's record (Stream.hs:224-230)."""
    eqs: list[tuple[Expr, Expr]] = []

    def walk(e: Expr) -> None:
        if isinstance(e, BinOp) and e.op == "AND":
            walk(e.left)
            walk(e.right)
        elif isinstance(e, BinOp) and e.op == "=":
            eqs.append((e.left, e.right))
        else:
            raise SQLCodegenError(
                "JOIN ON must be a conjunction of equality comparisons")

    walk(on)

    def side_of(e: Expr) -> str:
        streams = set()

        def scan(x: Expr) -> None:
            if isinstance(x, Col):
                streams.add(x.stream)
            elif isinstance(x, BinOp):
                scan(x.left)
                scan(x.right)
            elif hasattr(x, "operand"):
                scan(x.operand)

        scan(e)
        named = {s for s in streams if s is not None}
        if named <= left_streams and named:
            return "l"
        if named <= right_streams and named:
            return "r"
        if not named:
            raise SQLCodegenError(
                "JOIN ON columns must be stream-qualified (s.col)")
        raise SQLCodegenError(
            f"JOIN ON side mixes streams {sorted(named)}")

    def strip(e: Expr) -> Expr:
        if isinstance(e, Col):
            return Col(e.name)
        if isinstance(e, BinOp):
            return BinOp(e.op, strip(e.left), strip(e.right))
        if hasattr(e, "operand"):
            return type(e)(e.op, strip(e.operand))
        return e

    lks: list[Expr] = []
    rks: list[Expr] = []
    for a, b in eqs:
        sa, sb = side_of(a), side_of(b)
        if sa == sb:
            raise SQLCodegenError("JOIN ON equality must relate both sides")
        if sa == "l":
            lks.append(strip(a))
            rks.append(strip(b))
        else:
            lks.append(strip(b))
            rks.append(strip(a))
    return lks, rks


class _JoinBase:
    """Shared plumbing of both join executors: alias/side routing, ON
    key split, joined-row construction, and the inner (downstream)
    executor lifecycle."""

    def __init__(self, plan, *, initial_keys: int = 1024,
                 batch_capacity: int = 4096, mesh=None,
                 data_axis: str = "data", key_axis: str = "key"):
        join = plan.join
        self.plan = plan
        # mesh-sharded execution: a mesh whose key axis has >1 devices
        # key-shards BOTH side stores (code % n_shards owns the entry)
        # and the inner aggregate; without one the join runs single-chip
        self.mesh = mesh
        self.data_axis = data_axis
        self.key_axis = key_axis
        self.left_name = plan.source
        self.right_name = join.right.name
        if self.right_name == self.left_name:
            raise SQLCodegenError("self-join needs distinct streams")
        self.join_type = join.join_type
        if self.join_type not in ("INNER", "JOIN"):
            raise SQLCodegenError(
                f"{self.join_type} JOIN not supported (INNER only, like "
                "the reference's RJoinInner path)")
        self._aliases = {self.left_name: "l", self.right_name: "r"}
        left_al = {self.left_name}
        right_al = {self.right_name}
        la = getattr(plan, "source_alias", None)
        if la:
            self._aliases[la] = "l"
            left_al.add(la)
        if join.right.alias:
            self._aliases[join.right.alias] = "r"
            right_al.add(join.right.alias)
        self.left_keys, self.right_keys = split_on_condition(
            join.on, left_al, right_al)
        self._inner = None
        self._inner_plan = replace(plan, join=None)
        self._initial_keys = initial_keys
        self._inner_keys_floor = 0  # (a window join starts it higher)
        self._batch_capacity = batch_capacity
        # deferred-change tuning proxied onto the (lazily created) inner
        # executor, so the server's _tune_executor and bench harnesses
        # treat a join exactly like a plain aggregate: the downstream
        # changelog extraction pipelines/batches instead of serializing
        # the join's compute loop with one D2H fetch per micro-batch
        self.emit_changes = bool(getattr(plan, "emit_changes", False))
        self.supports_deferred_changes = True
        self._inner_tuning: dict[str, object] = {}
        # observability plane (ISSUE 13): per-family dispatch observer
        # for the probe kernel (the inner aggregate carries its own)
        self.dispatch_observer = None   # callable (family, seconds)

    def _side_of(self, stream: str | None) -> str:
        if stream is None:
            raise SQLCodegenError(
                f"{type(self).__name__}.process requires stream=<name or "
                "alias>: a join consumes two streams and must know each "
                "batch's origin")
        side = self._aliases.get(stream)
        if side is None:
            raise SQLCodegenError(
                f"stream {stream!r} is not part of this join")
        return side

    def _joined_row(self, lrow: Mapping[str, Any],
                    rrow: Mapping[str, Any]) -> dict[str, Any]:
        """Union of both sides, stream-qualified (genJoiner); bare names
        kept as a convenience with left precedence."""
        out = {}
        for f, v in lrow.items():
            out[f"{self.left_name}.{f}"] = v
        for f, v in rrow.items():
            out[f"{self.right_name}.{f}"] = v
        for f, v in rrow.items():
            out.setdefault(f, v)
        for f, v in lrow.items():
            out[f] = v
        return out

    def _key(self, exprs: list[Expr], row: Mapping[str, Any]):
        try:
            vals = tuple(eval_host(e, row) for e in exprs)
        except (TypeError, KeyError):
            return None
        if any(v is None for v in vals):
            return None
        return canon_key(vals)

    def _inner_process(self, joined, jts):
        if self._inner is None:
            from hstream_tpu.sql.codegen import make_executor

            self._inner = make_executor(
                self._inner_plan, sample_rows=joined,
                initial_keys=max(self._initial_keys,
                                 self._inner_keys_floor),
                batch_capacity=self._batch_capacity,
                mesh=self.mesh)
            self._apply_inner_tuning()
        return self._inner.process(joined, jts)

    def _apply_inner_tuning(self) -> None:
        inner = self._inner
        if inner is None or not getattr(inner, "supports_deferred_changes",
                                        False):
            return
        for k, v in self._inner_tuning.items():
            setattr(inner, k, v)

    def _proxy_tuning(self, name: str, value) -> None:
        self._inner_tuning[name] = value
        self._apply_inner_tuning()

    # change-drain knobs ride through to the inner executor (set before
    # OR after its lazy creation); reads fall back to the pending value
    @property
    def defer_change_decode(self) -> bool:
        return bool(self._inner_tuning.get("defer_change_decode", False))

    @defer_change_decode.setter
    def defer_change_decode(self, v: bool) -> None:
        self._proxy_tuning("defer_change_decode", bool(v))

    @property
    def change_drain_depth(self) -> int:
        return int(self._inner_tuning.get("change_drain_depth", 1))

    @change_drain_depth.setter
    def change_drain_depth(self, v: int) -> None:
        self._proxy_tuning("change_drain_depth", int(v))

    @property
    def async_change_drain(self) -> bool:
        return bool(self._inner_tuning.get("async_change_drain", False))

    @async_change_drain.setter
    def async_change_drain(self, v: bool) -> None:
        self._proxy_tuning("async_change_drain", bool(v))

    # ---- drains (API parity with QueryExecutor) ----------------------------

    def flush_changes(self) -> list[dict[str, Any]]:
        """Deliver every lagging emission: coalesced match rows staged
        for the inner step first, then the inner executor's deferred
        changelog extracts — the same barrier QueryExecutor exposes.
        A lone columnar change batch rides through unmaterialized."""
        from hstream_tpu.common.columnar import extend_rows

        rows = (self.flush_staged()
                if hasattr(self, "flush_staged") else [])
        inner = self._inner
        if inner is not None and hasattr(inner, "flush_changes"):
            rows = extend_rows(rows, inner.flush_changes())
        return rows if rows is not None else []

    def has_pending_changes(self) -> bool:
        if getattr(self, "_staged_n", 0):
            return True
        if getattr(self, "_pending_matches", None):
            return True
        inner = self._inner
        if inner is None:
            return False
        hp = getattr(inner, "has_pending_changes", None)
        if hp is not None:
            return bool(hp())
        return bool(getattr(inner, "_pending_changes", None))

    def peek(self) -> list[dict[str, Any]]:
        return [] if self._inner is None else self._inner.peek()

    # contract: dispatches<=0 fetches<=0
    def read_version(self) -> tuple | None:
        """Read-cache validity key (ISSUE 20): peek() serves the inner
        aggregate's state, so the version IS the inner's — prefixed
        pre-creation so an empty join caches too. None (inner without
        versioning) disables caching for this executor."""
        inner = self._inner
        if inner is None:
            return ("join-empty", id(self))
        fn = getattr(inner, "read_version", None)
        return None if fn is None else fn()

    # contract: dispatches<=0 fetches<=0
    def live_min_win_end(self) -> int | None:
        """Smallest live winEnd of the inner aggregate (ISSUE 20
        closed-only fast path); None = no live window could emit one."""
        fn = getattr(self._inner, "live_min_win_end", None)
        return None if fn is None else fn()

    def close_due_windows(self) -> list[dict[str, Any]]:
        if self._inner is None or not hasattr(self._inner,
                                              "close_due_windows"):
            return []
        return self._inner.close_due_windows()

    # contract: dispatches<=0 fetches<=1
    def block_until_ready(self) -> None:
        if self._inner is not None and hasattr(self._inner,
                                               "block_until_ready"):
            self._inner.block_until_ready()

    # contract: dispatches<=0 fetches<=0
    def device_plane_bytes(self) -> dict[str, int]:
        """Device bytes of the inner aggregate's planes, "agg."-
        prefixed (JoinExecutor extends this with its device stores) —
        nbytes metadata reads only (ISSUE 18)."""
        fn = getattr(self._inner, "device_plane_bytes", None)
        if fn is None:
            return {}
        return {f"agg.{k}": v for k, v in fn().items()}


class TableJoinExecutor(_JoinBase):
    """Executes `SELECT ... FROM l INNER JOIN TABLE(r) ON ...`.

    Reference semantics (Stream.hs:302-344, joinStreamTable): the right
    side is a TABLE — the latest row per join key of a changelog stream.
    Stream records probe the table and emit one joined row when the key
    is present; table records only update state (no retroactive
    emission). State is bounded by the table's key cardinality.
    """

    def __init__(self, plan, *, initial_keys: int = 1024,
                 batch_capacity: int = 4096):
        super().__init__(plan, initial_keys=initial_keys,
                         batch_capacity=batch_capacity)
        # the keyed last-value table (engine.statestore.LastValueStore)
        self._table = LastValueStore()

    @property
    def table(self) -> dict:
        """key -> (ts, row) view of the last-value table (snapshots,
        introspection)."""
        return self._table.data

    def process(self, rows: Sequence[Mapping[str, Any]],
                ts_ms: Sequence[int], stream: str | None = None
                ) -> list[dict[str, Any]]:
        side = self._side_of(stream)
        if side == "r":
            for row, ts in zip(rows, ts_ms):
                key = self._key(self.right_keys, row)
                if key is None:
                    continue
                self._table.update(key, int(ts), row)
            return []
        joined: list[dict[str, Any]] = []
        jts: list[int] = []
        for row, ts in zip(rows, ts_ms):
            key = self._key(self.left_keys, row)
            if key is None:
                continue
            match = self._table.lookup(key)
            if match is None:
                continue  # INNER: stream rows without a table row drop
            joined.append(self._joined_row(row, match))
            jts.append(int(ts))
        if not joined:
            return []
        return self._inner_process(joined, jts)


class _FlatIntervalStore:
    """One side of the interval join as flat sorted arrays.

    Rows live in arrays sorted by a composite (key code, ts) int64 —
    code * 2^41 + (ts - t0) — so a WHOLE batch probes with one
    searchsorted pair and inserts with one np.insert: no per-key Python.
    The reference walks a per-record ordered map instead
    (Processing/Store.hs tksPut/tksRange); this is that store's batch
    restatement. Key codes are dense ints owned by the executor
    (shared across both sides so probes and inserts agree).
    """

    TS_BITS = 41                     # ~69 years of ms offsets
    SPAN = 1 << TS_BITS

    def __init__(self, key_rev: list):
        self.code = np.empty(0, np.int64)
        self.ts = np.empty(0, np.int64)
        self.comp = np.empty(0, np.int64)
        self.rows = np.empty(0, object)
        self.t0: int | None = None
        self.key_rev = key_rev       # shared code -> canon key (executor)

    def __len__(self) -> int:
        return len(self.code)

    def _rebase(self, t0: int) -> None:
        self.t0 = t0
        self.comp = self.code * self.SPAN + (self.ts - t0)

    def insert_sorted(self, code: np.ndarray, ts: np.ndarray,
                      rows: np.ndarray) -> None:
        """Insert a batch already sorted by (code, ts)."""
        if len(code) == 0:
            return
        mn = int(ts.min())
        new_t0 = mn if self.t0 is None else min(mn, self.t0)
        hi = int(ts.max())
        if len(self.ts):
            hi = max(hi, int(self.ts.max()))
        if hi - new_t0 >= self.SPAN:
            # an offset past 2^41 ms (~69 years) would overflow into a
            # neighboring code's composite range and silently corrupt
            # probes — loud failure beats wrong join results. Checked
            # over existing AND incoming rows: a rebase to an older t0
            # shifts every resident row's offset too.
            raise SQLCodegenError(
                "join record timestamps span more than 2^41 ms; "
                "timestamps must be epoch milliseconds")
        if self.t0 is None or new_t0 < self.t0:
            self._rebase(new_t0)
        bcomp = code * self.SPAN + (ts - self.t0)
        if len(self.comp) == 0:
            self.code, self.ts, self.comp = code, ts, bcomp
            self.rows = rows
            return
        idx = np.searchsorted(self.comp, bcomp)
        self.code = np.insert(self.code, idx, code)
        self.ts = np.insert(self.ts, idx, ts)
        self.comp = np.insert(self.comp, idx, bcomp)
        self.rows = np.insert(self.rows, idx, rows)

    def probe(self, code: np.ndarray, lo_ts: np.ndarray,
              hi_ts: np.ndarray) -> tuple[np.ndarray, np.ndarray] | None:
        """Per query i: [start, end) indices of rows with this code and
        lo_ts[i] <= ts <= hi_ts[i]."""
        if len(self.comp) == 0:
            return None
        lo = np.clip(lo_ts - self.t0, 0, self.SPAN - 1)
        hi = np.clip(hi_ts - self.t0, -1, self.SPAN - 1)
        lo_i = np.searchsorted(self.comp, code * self.SPAN + lo, "left")
        hi_i = np.searchsorted(self.comp, code * self.SPAN + hi, "right")
        return lo_i, np.maximum(hi_i, lo_i)

    def prune(self, min_ts: int) -> None:
        keep = self.ts >= min_ts
        if not keep.all():
            self.code = self.code[keep]
            self.ts = self.ts[keep]
            self.comp = self.comp[keep]
            self.rows = self.rows[keep]

    def remap_codes(self, new_of_old: np.ndarray,
                    resort: bool = False) -> None:
        """Apply a code compaction. A dense remap preserves sorted
        order; a shard-class-preserving remap (sharded device mode)
        does not, so ``resort`` re-sorts by the new composite."""
        self.code = new_of_old[self.code]
        if self.t0 is None:
            return
        self.comp = self.code * self.SPAN + (self.ts - self.t0)
        if resort and len(self.comp):
            order = np.argsort(self.comp, kind="stable")
            self.code = self.code[order]
            self.ts = self.ts[order]
            self.comp = self.comp[order]
            self.rows = self.rows[order]

    @property
    def by_key(self) -> dict:
        """key tuple -> (ts list, rows list) view (snapshots; same shape
        TimestampedKVStore exposes, so the blob format is unchanged)."""
        out: dict[tuple, tuple[list, list]] = {}
        for i in range(len(self.code)):
            key = self.key_rev[int(self.code[i])]
            tss, rows = out.setdefault(key, ([], []))
            tss.append(int(self.ts[i]))
            rows.append(self.rows[i])
        return out


class JoinExecutor(_JoinBase):
    """Executes `SELECT ... FROM l [INNER|LEFT] JOIN r WITHIN(...) ON ...`.

    API: process(rows, ts_ms, stream=<source name or alias>) — the task
    runtime feeds records from BOTH streams through the one executor,
    tagging each batch with its origin (the reference merges both
    sources into one task, Codegen.hs:250-266). Joined rows feed the
    inner (aggregate/stateless) executor built over the joined schema.
    """

    # the task runtime may feed columnar batches straight through
    # process_columnar (no row materialization on the server path)
    supports_columnar_join = True

    def __init__(self, plan, *, initial_keys: int = 1024,
                 batch_capacity: int = 4096, mesh=None,
                 data_axis: str = "data", key_axis: str = "key"):
        super().__init__(plan, initial_keys=initial_keys,
                         batch_capacity=batch_capacity, mesh=mesh,
                         data_axis=data_axis, key_axis=key_axis)
        join = plan.join
        node = plan.node
        # a window join (JOIN ... WITHIN WINDOW): a pair joins where
        # both records fall in the same window of the statement's own
        # tumbling window. The same stores, kernels and fused step as
        # the interval join; what differs is stated where it does:
        #   * a probe spans its record's window [start, start + size),
        #     and `within` (the kernels' operand) is that size;
        #   * event time is the MINIMUM over both sources' progress
        #     (`_src_hi`): the probe mask, the eviction and the inner
        #     executor's watermark follow it, so a batch is never lost
        #     because the other log was read further;
        #   * eviction drops whole closed windows from both stores at
        #     every close and frees the join-key codes no stored row
        #     names any more (`_close_join_windows`);
        #   * the GROUP BY key may hold, beside the join key, further
        #     columns of the same side that the join key determines
        #     (`_window_kids`); inner key ids are dated like any other
        #     query's, so the inner executor retires them.
        self.window_join = bool(getattr(join, "window", False))
        if self.window_join:
            if not (isinstance(node, AggregateNode)
                    and isinstance(node.window, TumblingWindow)):
                raise SQLCodegenError(
                    "JOIN ... WITHIN WINDOW needs a TUMBLING GROUP BY "
                    "window")
            if self.emit_changes:
                raise SQLCodegenError(
                    "JOIN ... WITHIN WINDOW with EMIT CHANGES is not "
                    "supported")
            self.within = node.window.size_ms
            self.mesh = None  # single-chip (plan.single_chip_reason)
            # the inner key table starts where a window's keys would
            # take it anyway: every doubling on the way is a rebuild of
            # the fused step (the table's size is part of its program),
            # and 2^18 rows of a count plane are three megabytes
            self._inner_keys_floor = self.WINDOW_INNER_KEYS
        else:
            self.within = join.within.ms

        # retention: a future in-grace record can probe back `within`;
        # grace defaults to the downstream window's (or the SQL default)
        grace = DEFAULT_GRACE_MS
        if isinstance(node, AggregateNode) and node.window is not None:
            grace = node.window.grace_ms
        self._grace_ms = grace
        self.retention_ms = self.within + grace

        # shared join-key code space across both sides
        self._jcode: dict[tuple, int] = {}
        self._jcode_rev: list[tuple] = []
        self._kid_lut = np.full(1024, -1, np.int32)  # code -> inner key id
        # window join: free codes (reused before new ones are minted),
        # the newest window each code's inner key id was named in (the
        # id is good while that window is open), each source's newest
        # event time, and the start of the oldest window still open
        self._jcode_free: list[int] = []
        self._kid_win = np.full(1024, -1, np.int64)
        self._src_hi = {"l": -1, "r": -1}
        self._open_from: int | None = None
        self.tracer = None  # QueryTracer of the owning task (spans)
        self._input_cols: dict | None = None  # input_columns' memo
        self._stores = {"l": _FlatIntervalStore(self._jcode_rev),
                        "r": _FlatIntervalStore(self._jcode_rev)}
        self.watermark: int = -1
        # fast-path plumbing (computed lazily once the inner executor
        # and both sides' observed fields exist)
        self._fields = {"l": set(), "r": set()}
        self._fast: dict | None = None   # None = unknown yet
        # opt-in: accumulate this many matched rows before stepping the
        # inner executor — on a real link every step dispatch pays a
        # round trip, so small probe batches must coalesce (the same
        # lever as the ingest pipeline's staged caps). Emission then
        # lags by the coalesce horizon; callers flush via flush_staged.
        self.coalesce_rows = 0
        self._staged: list[tuple] = []   # (key_ids, jts, cols, nulls)
        self._staged_n = 0
        # Device-resident join: once the columnar fast path is planned,
        # both sides migrate into device stores and each micro-batch
        # becomes ONE fused probe+insert dispatch + ONE fetch of the
        # packed match buffer (engine.lattice interval-join kernels).
        # use_device_join=False pins the host reference path.
        self.use_device_join = True
        self._dev: dict | None = None
        # >1 defers match-buffer fetches: buffers stack into one
        # batched D2H transfer every `depth` micro-batches, so the
        # round trip amortizes (emission then lags; flush_staged is
        # the barrier). The fused close's deferred-fetch idiom.
        self.match_drain_depth = 1
        self._pending_matches: list[tuple] = []
        self._inflight: deque = deque()  # fused window-join batches
        # probe-path dispatch accounting: the device-join contract is
        # ONE probe dispatch per micro-batch (and fetches <= batches);
        # tests and bench assert probe_dispatches == probe_batches
        self.join_stats = {
            "probe_batches": 0, "probe_dispatches": 0,
            "probe_fetches": 0, "match_redispatches": 0,
            "evict_dispatches": 0, "rebase_dispatches": 0,
            "store_grows": 0, "fused_batches": 0,
            # window join: rows that arrived behind the eviction bound
            # (their window had closed: 0 is a deployment's guarantee),
            # join-key codes freed at a close, matched pairs
            "rows_past_retention": 0, "codes_reclaimed": 0,
            "matches": 0,
        }
        # device activations that failed and degraded (permanently, for
        # this executor) to the retained host reference path; the query
        # task mirrors deltas into the device_path_fallbacks counter
        self.device_fallbacks = 0
        # dispatches that ran under shard_map (probe/fused/evict); the
        # query task mirrors deltas into the sharded_dispatches family
        self._sharded_dispatches = 0

    @property
    def late_drops(self) -> int:
        """Rows this executor itself dropped as late: a window join's
        rows behind its eviction bound (`rows_past_retention`; their
        window had closed). The task folds it with the inner
        aggregate's count into `late_drops` (`engine_total`), so a
        deployment that holds `late_drops` to 0 holds this too."""
        return self.join_stats["rows_past_retention"]

    @property
    def sharded_dispatches(self) -> int:
        """Sharded device dispatches, join probe plane + the inner
        aggregate's own (step/extract) — the per-query counter the
        stats plane exposes."""
        return self._sharded_dispatches + int(getattr(
            self._inner, "sharded_dispatches", 0) or 0)

    # ---- device cost plane (ISSUE 18) --------------------------------------

    # contract: dispatches<=0 fetches<=0
    def _device_values(self):
        """Live device values of the probe plane — the fence/measure
        target of the device-time sampler (late-bound: stores and the
        inner state are REPLACED by every probe/fused dispatch)."""
        dev = self._dev
        if dev is None:
            return ()
        vals = [dev["stores"]["l"], dev["stores"]["r"]]
        inner_state = getattr(self._inner, "state", None)
        if inner_state is not None:
            vals.append(inner_state)
        return vals

    # contract: dispatches<=0 fetches<=0
    def device_plane_bytes(self) -> dict[str, int]:
        """Exact per-plane device bytes: both sides' interval stores
        ("l."/"r."-prefixed) plus the inner aggregate's lattice planes
        ("agg."-prefixed) — nbytes metadata reads, zero dispatches."""
        from hstream_tpu.stats.devicecost import plane_bytes

        out = super().device_plane_bytes()
        dev = self._dev
        if dev is not None:
            for side in ("l", "r"):
                for k, v in plane_bytes(dev["stores"][side]).items():
                    out[f"{side}.{k}"] = v
        return out

    # ---- ingest ------------------------------------------------------------
    #
    # Batched: the per-record reference loop (insert my side, probe the
    # other side over [ts-within, ts+within], Stream.hs:238-300) is
    # restated as: group the batch by join key, batch-append each group
    # to my side's store, then probe the other side with ONE
    # searchsorted pair per group (the other side never changes during
    # the batch, so insert/probe need no interleaving). Matched pairs
    # feed the inner aggregate COLUMNAR (key ids broadcast per group
    # when the GROUP BY key is the join key) — no joined-row dicts on
    # the steady path.

    def process(self, rows: Sequence[Mapping[str, Any]],
                ts_ms: Sequence[int], stream: str | None = None
                ) -> list[dict[str, Any]]:
        side = self._side_of(stream)
        mine = self._stores[side]
        other = self._stores["r" if side == "l" else "l"]
        my_keys = self.left_keys if side == "l" else self.right_keys
        n = len(rows)
        out: list[dict[str, Any]] = []
        if n:
            if rows[0]:
                self._fields[side].update(rows[0])
            ts = np.asarray(ts_ms, np.int64)
            codes = self._batch_codes(my_keys, rows)       # -1 = no key
            keep = codes >= 0
            if not keep.all():
                kidx = np.nonzero(keep)[0]
                codes = codes[kidx]
                bts = ts[kidx]
            else:
                kidx = None
                bts = ts
            if self.window_join and len(codes):
                fresh = self._in_open_windows(bts)
                if fresh is not None:
                    codes, bts = codes[fresh], bts[fresh]
                    kidx = (np.nonzero(fresh)[0] if kidx is None
                            else kidx[fresh])
            if len(codes):
                order = np.lexsort((bts, codes))
                codes = codes[order]
                bts = bts[order]
                ridx = order if kidx is None else kidx[order]
                ready = self._device_ready()
                if self.window_join and self._fast_info() is not None:
                    self._window_kids(
                        side, codes, bts,
                        lambda c: [rows[j].get(c) for j in ridx.tolist()])
                if ready:
                    lay = self._dev["lay"][side]
                    flags, vals = self._encode_join_cols(
                        lay, [rows[j] for j in ridx.tolist()])
                    out = self._device_batch(side, codes, bts, flags,
                                             vals)
                else:
                    out = self._host_batch(side, mine, other, codes,
                                           bts, rows, ridx)
        return self._after_batch(
            side, out, max((int(t) for t in ts_ms), default=-1))

    def process_columnar(self, ts_ms, cols: Mapping[str, np.ndarray],
                         nulls: Mapping[str, np.ndarray] | None = None,
                         *, stream: str | None = None
                         ) -> list[dict[str, Any]]:
        """Columnar twin of process(): int64 absolute-ms timestamps plus
        named numpy columns (str/object arrays for strings; a null-mask
        cell means the field is ABSENT from that record, like the
        per-record decode's dropped keys). On the device path the batch
        packs straight from the columns — vectorized key encode, no
        per-row Python at all; until the device path activates (or on
        the host reference path) rows materialize once and take the row
        path, so semantics are identical."""
        n = len(ts_ms)
        if n == 0:
            return []
        side = self._side_of(stream)
        self._fields[side].update(cols.keys())
        ts = np.asarray(ts_ms, np.int64)
        out: list[dict[str, Any]] = []
        enc = None
        if self._device_ready():
            my_keys = (self.left_keys if side == "l"
                       else self.right_keys)
            with trace_span(self.tracer, "join_key_codes"):
                enc = self._columnar_batch(side, my_keys, ts, cols,
                                           nulls)
        if enc is not None:
            codes, bts, flags, vals = enc
            if len(codes):
                out = self._device_batch(side, codes, bts, flags, vals)
            return self._after_batch(side, out, int(ts.max()))
        # fallback: materialize rows once (pre-activation, non-Col ON
        # keys, or untyped columns) and run the row path
        return self.process(self._rows_from_cols(cols, nulls, n),
                            ts.tolist(), stream=stream)

    def _after_batch(self, side: str, out, batch_hi: int):
        """A batch is done: event time moves, and with it retention.
        A window join also closes what its new event time closes (the
        stores' windows here, the inner executor's after them) and
        gives those rows behind the batch's own."""
        if batch_hi < 0:
            return out
        if not self.window_join:
            self._advance_watermark(batch_hi)
            return out
        from hstream_tpu.common.columnar import extend_rows

        if batch_hi > self._src_hi[side]:
            self._src_hi[side] = batch_hi
        new_wm = min(self._src_hi.values())
        if new_wm <= self.watermark:
            return out
        self.watermark = new_wm
        self._close_join_windows()
        inner = self._inner
        if inner is not None and getattr(inner, "watermark_abs",
                                         None) is not None:
            if self.watermark > inner.watermark_abs:
                inner.watermark_abs = self.watermark
            out = extend_rows(out if out else None,
                              inner.close_due_windows())
        return out if out is not None else []

    def _advance_watermark(self, new_wm: int) -> None:
        if new_wm <= self.watermark:
            return
        self.watermark = new_wm
        cutoff = self.watermark - self.retention_ms
        if cutoff > 0:
            if self._dev is not None:
                self._maybe_evict(cutoff)
            else:
                self._stores["l"].prune(cutoff)
                self._stores["r"].prune(cutoff)

    # ---- window join: event time, retention, keys --------------------------

    def _cutoff_abs(self) -> int | None:
        """The retention bound in absolute ms: a stored row older than
        it is invisible to probes and due for eviction. Interval join:
        `watermark - retention`. Window join: the start of the oldest
        window still open at the watermark (the minimum over both
        sources), so whole closed windows go. None before event time
        has a value."""
        if self.watermark < 0:
            return None
        if not self.window_join:
            return self.watermark - self.retention_ms
        t = self.watermark - self._grace_ms
        return t - t % self.within

    def _in_open_windows(self, bts: np.ndarray) -> np.ndarray | None:
        """Window join: which rows of a batch lie in a window still
        open (None: all of them). A row behind the eviction bound has
        no window to join in: it is counted (`rows_past_retention`)
        and goes no further, as the inner executor would drop the
        pairs it made as late."""
        cutoff = self._cutoff_abs()
        if cutoff is None or int(bts.min()) >= cutoff:
            return None
        fresh = bts >= cutoff
        self.join_stats["rows_past_retention"] += int(
            len(bts) - np.count_nonzero(fresh))
        return fresh

    def _grow_code_luts(self) -> None:
        n = len(self._jcode_rev)
        if len(self._kid_lut) < n:
            size = max(n, 2 * len(self._kid_lut))
            lut = np.full(size, -1, np.int32)
            lut[:len(self._kid_lut)] = self._kid_lut
            self._kid_lut = lut
        if self.window_join and len(self._kid_win) < len(self._kid_lut):
            win = np.full(len(self._kid_lut), -1, np.int64)
            win[:len(self._kid_win)] = self._kid_win
            self._kid_win = win

    def _window_kids(self, side: str, codes: np.ndarray,
                     bts: np.ndarray, column) -> None:
        """Window join, fast path: the inner key ids of a batch of the
        side that carries the GROUP BY columns (the `det` side: the
        join key and whatever further columns of that side the join
        key determines). New codes get their ids in one piece
        (`key_ids_for`), every id the batch names is dated
        (`note_key_use`), so the inner executor retires it once its
        window has closed; `_kid_win` says up to which window a code's
        id is good. A row whose GROUP BY columns differ from its
        code's key breaks the dependency the statement rests on and is
        refused by name. `column(name)` gives a source column of the
        batch in the order of `codes`."""
        self._grow_code_luts()
        det_side, det_cols = self._fast["det"]
        if side != det_side:
            return
        inner = self._inner
        lut = self._kid_lut
        vals = [np.asarray(column(c), object) for c in det_cols]
        kid = lut[codes]
        new = np.flatnonzero(kid < 0)
        if len(new):
            ucodes, first = np.unique(codes[new], return_index=True)
            at = new[first]
            keys = list(zip(*(v[at].tolist() for v in vals)))
            lut[ucodes] = inner.key_ids_for(keys)
            kid = lut[codes]
        for g, (name, v) in enumerate(zip(det_cols, vals)):
            have = inner._key_cols[g][kid]
            if not (np.array_equal(have, v) or have.tolist() == [
                    canon_key((x,))[0] for x in v.tolist()]):
                raise SQLCodegenError(
                    f"window join: GROUP BY column {name!r} is not "
                    "determined by the join key (two records of one "
                    "join key differ in it)")
        win = bts - bts % self.within
        np.maximum.at(self._kid_win, codes, win)
        inner.note_key_use(kid, int(bts.max()))

    def _close_join_windows(self) -> None:
        """Window join: event time has moved. Once it opens a new
        window, every older one is closed for good: drop their rows
        from both stores (one eviction dispatch on the device path),
        forget the inner key ids that were good for them, and free the
        join-key codes no stored row names any more, so the code
        dictionary, `_kid_lut` and the stores are bounded by the open
        windows."""
        cutoff = self._cutoff_abs()
        if cutoff is None or (self._open_from is not None
                              and cutoff <= self._open_from):
            return
        self._open_from = cutoff
        with trace_span(self.tracer, "join_evict"):
            dev = self._dev
            if dev is not None:
                for st in dev["shadow"].values():
                    st.prune(cutoff)
                if dev["t0"] is not None and (dev["n"]["l"]
                                              or dev["n"]["r"]):
                    self._dispatch_evict(cutoff, 0)
                stores = dev["shadow"]
            else:
                for st in self._stores.values():
                    st.prune(cutoff)
                stores = self._stores
            self._grow_code_luts()
            n = len(self._jcode_rev)
            stale = np.flatnonzero(self._kid_win[:n] < cutoff)
            self._kid_lut[stale] = -1
            live = np.zeros(n, np.bool_)
            for st in stores.values():
                live[st.code] = True
            rev = self._jcode_rev
            dead = [c for c in np.flatnonzero(~live).tolist()
                    if rev[c] is not None]
            if dead:
                jcode = self._jcode
                for c in dead:
                    del jcode[rev[c]]
                    rev[c] = None
                self._kid_win[dead] = -1
                self._jcode_free.extend(reversed(dead))
                self.join_stats["codes_reclaimed"] += len(dead)

    def _mint_code(self, key) -> int:
        """A code for a join key the dictionary does not hold: a freed
        one first (a window join frees them at every close)."""
        rev = self._jcode_rev
        if self._jcode_free:
            c = self._jcode_free.pop()
            rev[c] = key
        else:
            c = len(rev)
            rev.append(key)
        self._jcode[key] = c
        return c

    def input_columns(self, stream: str) -> frozenset | None:
        """The columns of `stream`'s records this join reads: its ON
        keys and what the plan above it names (GROUP BY, aggregate
        arguments, WHERE); a bare name may be either side's. None
        where that cannot be said from the plan (a stateless plan
        forwards whole records, a key that is an expression)."""
        if self._input_cols is None:
            self._input_cols = self._plan_columns() or {}
        return self._input_cols.get(self._side_of(stream))

    def _plan_columns(self) -> dict | None:
        from hstream_tpu.engine.expr import columns_of
        from hstream_tpu.engine.plan import FilterNode

        node = self.plan.node
        keys = {"l": self.left_keys, "r": self.right_keys}
        if not isinstance(node, AggregateNode) or not all(
                isinstance(e, Col) for ks in keys.values() for e in ks):
            return None
        names: set[str] = set()
        for g in node.group_keys:
            names |= columns_of(g)
        for a in node.aggs:
            if a.input is not None:
                names |= columns_of(a.input)
        child = node.child
        while isinstance(child, FilterNode):
            names |= columns_of(child.predicate)
            child = child.child
        out = {s: {e.name for e in ks} for s, ks in keys.items()}
        for name in names:
            pre, _, col = name.partition(".")
            side = self._aliases.get(pre) if col else None
            if side is not None:
                out[side].add(col)
            else:
                out["l"].add(name)
                out["r"].add(name)
        return {s: frozenset(v) for s, v in out.items()}

    def join_gauges(self) -> dict[str, int]:
        """The join's own counts for `admin stats queries` and
        /metrics: `join_stats`, the live join-key codes and each
        side's stored rows inside retention (on the device path by the
        host shadow, which counts them exactly). Host ints, no
        dispatch."""
        out = dict(self.join_stats)
        out["codes_live"] = len(self._jcode)
        dev = self._dev
        for side, name in (("l", "left"), ("r", "right")):
            out[f"store_rows_{name}"] = len(
                dev["shadow"][side] if dev is not None
                else self._stores[side])
        return out

    def _host_batch(self, side, mine, other, codes, bts, rows,
                    ridx) -> list[dict[str, Any]]:
        """The host reference path: batch searchsorted probe over the
        flat sorted stores (see _FlatIntervalStore)."""
        brows = np.empty(len(ridx), object)
        for i, j in enumerate(ridx.tolist()):
            brows[i] = dict(rows[j])
        # probe the other side BEFORE inserting: the reference
        # loop probes only the opposite store, which this batch
        # never mutates, so insert/probe need no interleaving
        pr = other.probe(codes, *self._probe_span(bts))
        mine.insert_sorted(codes, bts, brows)
        if pr is None:
            return []
        lo_i, hi_i = pr
        cnt = hi_i - lo_i
        tot = int(cnt.sum())
        if not tot:
            return []
        self.join_stats["matches"] += tot
        start = np.cumsum(cnt) - cnt
        oidx = (np.arange(tot, dtype=np.int64)
                - np.repeat(start, cnt)
                + np.repeat(lo_i, cnt))
        rep = np.repeat(np.arange(len(codes)), cnt)
        jts = np.maximum(bts[rep], other.ts[oidx])
        return self._emit_matches(side, brows, rep, codes[rep], other,
                                  oidx, jts)

    def _probe_span(self, bts: np.ndarray) -> tuple:
        """The closed span [lo, hi] of event time a record's partners
        lie in, per record: `within` around it, or (window join) its
        own window; never older than the retention bound."""
        if self.window_join:
            lo = bts - bts % self.within
            hi = lo + (self.within - 1)
        else:
            lo, hi = bts - self.within, bts + self.within
        cutoff = self._cutoff_abs()
        return (lo if cutoff is None else np.maximum(lo, cutoff)), hi

    def _batch_codes(self, my_keys, rows) -> np.ndarray:
        """Dense join-key code per row (-1 = null key, skipped). One
        shared code space for both sides; compacted when it outgrows
        the composite-key budget."""
        # compact BEFORE encoding so this batch's fresh keys get live
        # codes (compacting afterwards would remap them to -1 and drop
        # the rows)
        if len(self._jcode_rev) + len(rows) >= (1 << 22) - 1:
            self._compact_codes()
            if len(self._jcode_rev) + len(rows) >= (1 << 22) - 1:
                raise SQLCodegenError(
                    "join key cardinality within the retention window "
                    f"exceeds {1 << 22} distinct keys")
        jcode = self._jcode
        mint = self._mint_code
        out = np.empty(len(rows), np.int64)

        def code_of(k) -> int:
            c = jcode.get(k)
            return mint(k) if c is None else c

        if all(isinstance(e, Col) for e in my_keys):
            names = [e.name for e in my_keys]
            if len(names) == 1:
                nm = names[0]
                for i, r in enumerate(rows):
                    v = r.get(nm)
                    out[i] = -1 if v is None else code_of(canon_key((v,)))
            else:
                for i, r in enumerate(rows):
                    vals = tuple(r.get(c) for c in names)
                    out[i] = (-1 if any(v is None for v in vals)
                              else code_of(canon_key(vals)))
        else:
            for i, r in enumerate(rows):
                k = self._key(my_keys, r)
                out[i] = -1 if k is None else code_of(k)
        return out

    # contract: dispatches<=0 fetches<=1
    def _compact_codes(self) -> None:
        """Code-space compaction: keep only codes still live in either
        store (retention bounds them), reassign codes, remap stores +
        shadows + lut + dict.

        Device mode fetches BOTH sides' code planes in one stacked
        transfer (they share cap): hstream-analyze's dispatch pass
        caught the original per-side fetch loop — two round trips on
        the ingest path every time the code space filled.

        Single-chip remaps densely in sorted order (store order is
        preserved). Sharded mode must keep every code's shard
        residence, so the remap is residue-class-preserving (new =
        rank-within-class * n_shards + class): per-shard device order
        survives the gather remap, but GLOBAL (code, ts) order does
        not — the host shadows re-sort, and the code space keeps holes
        where the classes are unbalanced."""
        from hstream_tpu.engine import lattice

        sjl = self._dev.get("sjl") if self._dev is not None else None
        parts = [self._stores["l"].code, self._stores["r"].code]
        if self._dev is not None:
            self._refresh_counts()
            if self._dev["n"]["l"] or self._dev["n"]["r"]:
                import jax.numpy as jnp

                codes = np.asarray(jnp.stack(
                    [self._dev["stores"]["l"]["code"],
                     self._dev["stores"]["r"]["code"]]))
                # eviction is lazy: dead-but-resident entries past the
                # live prefix must stay mapped too, so take every
                # non-sentinel slot (works for flat and sharded planes)
                parts.append(np.unique(
                    codes[codes < lattice.JOIN_SENT_CODE]
                ).astype(np.int64))
        live = np.union1d(parts[0], np.concatenate(parts[1:])
                          if len(parts) > 1 else parts[0])
        if sjl is not None:
            cls = live % sjl.n_shards
            new_codes = np.empty(len(live), np.int64)
            for s in range(sjl.n_shards):
                msk = cls == s
                new_codes[msk] = (np.arange(int(msk.sum()),
                                            dtype=np.int64)
                                  * sjl.n_shards + s)
        else:
            new_codes = np.arange(len(live), dtype=np.int64)
        new_of_old = np.full(len(self._jcode_rev), -1, np.int64)
        new_of_old[live] = new_codes
        resort = sjl is not None
        for st in self._stores.values():
            st.remap_codes(new_of_old, resort=resort)
        if self._dev is not None:
            for st in self._dev["shadow"].values():
                # the shadows size every match buffer: leaving them on
                # the old code space would corrupt probe totals
                st.remap_codes(new_of_old, resort=resort)
            self._remap_device_codes(new_of_old)
        new_rev: list = [None] * (int(new_codes.max()) + 1
                                  if len(live) else 0)
        for nc, oc in zip(new_codes.tolist(), live.tolist()):
            new_rev[nc] = self._jcode_rev[oc]
        self._jcode.clear()
        self._jcode.update({k: i for i, k in enumerate(new_rev)
                            if k is not None})
        self._jcode_rev[:] = new_rev      # in place: stores share it
        lut = np.full(max(len(new_rev), 1024), -1, np.int32)
        old_lut = self._kid_lut
        inb = live < len(old_lut)
        lut[new_codes[inb]] = old_lut[live[inb]]
        self._kid_lut = lut

    # ---- match emission ----------------------------------------------------

    def _feed_inner_columnar(self, key_ids, jts, cols, nulls
                             ) -> list[dict[str, Any]]:
        """Step (or coalesce-stage) one columnar match batch into the
        inner executor — shared by the host and device probe paths.
        The joined stream's watermark is the JOIN's watermark (both
        probe paths forward it before stepping matches, so the fused
        device kernel and this host feed apply the same late mask)."""
        inner = self._inner
        if (getattr(inner, "watermark_abs", None) is not None
                and self.watermark > inner.watermark_abs):
            inner.watermark_abs = self.watermark
        if self.coalesce_rows > 0:
            self._staged.append((key_ids, jts, cols, nulls))
            self._staged_n += len(key_ids)
            if self._staged_n < self.coalesce_rows:
                return []
            return self._drain_staged(keep_tail=True)
        return self._inner.process_columnar(key_ids, jts, cols, nulls)

    def _emit_matches(self, side, brows, rep, mcodes, other, oidx,
                      jts) -> list[dict[str, Any]]:
        fast = self._fast_info()
        if fast is not None:
            key_ids = self._match_key_ids(mcodes)
            cols, nulls = self._match_cols(fast, side, brows, rep,
                                           other, oidx)
            return self._feed_inner_columnar(key_ids, jts, cols, nulls)
        # general path: materialize joined-row dicts (also the sample
        # source for the inner executor's construction)
        orows = other.rows[oidx]
        joined: list[dict[str, Any]] = []
        for i in range(len(rep)):
            row, orow = brows[rep[i]], orows[i]
            joined.append(self._joined_row(row, orow) if side == "l"
                          else self._joined_row(orow, row))
        res = self._inner_process(joined, jts.tolist())
        # re-plan while disabled: a field observed on a later batch can
        # make a previously-unresolvable column resolvable
        if not self._fast:
            self._plan_fast()
        return res

    def _match_key_ids(self, mcodes: np.ndarray) -> np.ndarray:
        """Inner-executor key ids per match via a code-indexed LUT (the
        GROUP BY key IS the join key on this path)."""
        self._grow_code_luts()
        lut = self._kid_lut
        if self.window_join:
            # `_window_kids` named every id a pair can carry: a pair
            # holds a stored or probing row of the det side, whose
            # window is open
            return lut[mcodes]
        need = np.unique(mcodes[lut[mcodes] < 0])
        for c in need.tolist():
            lut[c] = self._inner.key_id_for(self._jcode_rev[c])
        return lut[mcodes]

    def flush_staged(self) -> list[dict[str, Any]]:
        """Step the inner executor with every lagging match: deferred
        device match buffers fetch + decode first (they may stage into
        the coalesce buffer), then every coalesced row steps. A lone
        columnar batch from either half stays a ColumnarEmit."""
        from hstream_tpu.common.columnar import extend_rows

        while self._inflight:
            self._settle_fused()
        out = self._drain_matches() if self._pending_matches else None
        out = extend_rows(out, self._drain_staged(keep_tail=False))
        return out if out is not None else []

    def _drain_staged(self, *, keep_tail: bool) -> list[dict[str, Any]]:
        """Step coalesced matches. keep_tail=True steps only whole
        inner-batch-capacity chunks and re-stages the remainder, so the
        steady state reuses ONE compiled step shape (each distinct
        padded cap is a separate XLA compile)."""
        if not self._staged:
            return []
        staged, self._staged = self._staged, []
        self._staged_n = 0
        key_ids = np.concatenate([s[0] for s in staged])
        jts = np.concatenate([s[1] for s in staged])
        names = staged[0][2].keys()
        cols = {c: np.concatenate([s[2][c] for s in staged])
                for c in names}
        nulls = None
        if any(s[3] for s in staged):
            nulls = {}
            for c in names:
                parts = [s[3][c] if (s[3] and c in s[3])
                         else np.zeros(len(s[0]), np.bool_)
                         for s in staged]
                m = np.concatenate(parts)
                if m.any():
                    nulls[c] = m
            nulls = nulls or None
        n = len(key_ids)
        cap = self._inner.batch_capacity
        cut = n - (n % cap) if keep_tail else n
        if keep_tail and cut < n:
            tail_nulls = (None if nulls is None else
                          {c: m[cut:] for c, m in nulls.items()})
            self._staged.append((key_ids[cut:], jts[cut:],
                                 {c: v[cut:] for c, v in cols.items()},
                                 tail_nulls))
            self._staged_n = n - cut
        if cut == 0:
            return []
        head_nulls = (None if nulls is None else
                      {c: m[:cut] for c, m in nulls.items()})
        return self._inner.process_columnar(
            key_ids[:cut], jts[:cut],
            {c: v[:cut] for c, v in cols.items()}, head_nulls)

    def _fast_info(self) -> dict | None:
        if self._fast is None and self._inner is not None:
            self._plan_fast()
        return self._fast if isinstance(self._fast, dict) else None

    def _resolve_col(self, name: str) -> tuple[str, str] | None:
        """Joined-row column name -> (side, source column): qualified
        names split on the alias; bare names take left precedence, the
        same rule _joined_row applies."""
        if "." in name:
            pre, col = name.split(".", 1)
            s = self._aliases.get(pre)
            if s is not None:
                return s, col
        if name in self._fields["l"]:
            return "l", name
        if name in self._fields["r"]:
            return "r", name
        return None

    def close_due_windows(self) -> list[dict[str, Any]]:
        from hstream_tpu.common.columnar import extend_rows

        rows = (self.flush_staged()
                if (self._staged or self._pending_matches) else [])
        # flush_staged can surface a lone ColumnarEmit (no .extend)
        rows = extend_rows(rows, super().close_due_windows())
        return rows if rows is not None else []

    def _plan_fast(self) -> None:
        """Enable the columnar match path when (a) the inner executor
        has one, (b) its GROUP BY columns are exactly the join key (so
        inner key ids broadcast per probe group), and (c) every column
        the inner step needs resolves to one side. A window join's
        GROUP BY may hold, beside every join key column of one side,
        further columns of that side (`_window_kids`)."""
        inner = self._inner
        self._fast = False
        if inner is None or not hasattr(inner, "process_columnar"):
            return
        # after a snapshot restore the observed-field sets are empty;
        # reseed them from any stored row so bare names still resolve
        for s in ("l", "r"):
            if not self._fields[s] and len(self._stores[s]):
                self._fields[s].update(self._stores[s].rows[0])
        knames_l = ([e.name for e in self.left_keys]
                    if all(isinstance(e, Col) for e in self.left_keys)
                    else None)
        knames_r = ([e.name for e in self.right_keys]
                    if all(isinstance(e, Col) for e in self.right_keys)
                    else None)
        resolved = [self._resolve_col(c) for c in inner.group_cols]
        if any(r is None for r in resolved):
            return
        gs = [s for s, _ in resolved]
        gcols = [c for _, c in resolved]
        if len(set(gs)) != 1:
            return
        knames = knames_l if gs[0] == "l" else knames_r
        if knames is None or not (
                gcols == knames
                or (self.window_join and set(knames) <= set(gcols))):
            return
        need = {}
        for name in inner._needed_cols:
            if "." in name:
                pre, col = name.split(".", 1)
                s = self._aliases.get(pre)
                if s is not None:
                    need[name] = (s, col)
                    continue
            if (name in self._fields["l"]
                    or name in self._fields["r"]):
                # bare name: gather per match row with _joined_row's
                # left-precedence (observation can't tell which side a
                # heterogeneous stream carries the field on)
                need[name] = ("both", name)
            else:
                return
        self._fast = {"need": need, "det": (gs[0], gcols)}
        if self.window_join:
            # rows stored before this plan existed carry no inner key
            # id yet: name them now, from the rows themselves
            st = self._stores[gs[0]]
            self._grow_code_luts()
            bare = np.flatnonzero(self._kid_lut[st.code] < 0)
            if len(bare):
                self._window_kids(
                    gs[0], st.code[bare], st.ts[bare],
                    lambda c: [r.get(c) for r in st.rows[bare]])

    def _match_cols(self, fast, side, brows, rep, other,
                    oidx) -> tuple[dict, dict | None]:
        """Columns the inner step needs, gathered straight from the
        matched source rows (no joined dicts)."""
        from hstream_tpu.engine.types import ColumnType

        inner = self._inner
        tot = len(rep)
        cols: dict[str, np.ndarray] = {}
        nulls: dict[str, np.ndarray] = {}
        src_cache: dict[tuple, list] = {}
        for name, (cside, col) in fast["need"].items():
            vals = src_cache.get((cside, col))
            if vals is None:
                if cside == "both":
                    # left-precedence bare name, decided per match row
                    lrows, lidx = ((brows, rep) if side == "l"
                                   else (other.rows, oidx))
                    rrows, ridx = ((other.rows, oidx) if side == "l"
                                   else (brows, rep))
                    vals = []
                    for li, ri in zip(lidx.tolist(), ridx.tolist()):
                        v = lrows[li].get(col, _MISS)
                        if v is _MISS:
                            v = rrows[ri].get(col)
                        vals.append(v)
                elif cside == side:
                    vals = [brows[i].get(col) for i in rep.tolist()]
                else:
                    vals = [other.rows[j].get(col)
                            for j in oidx.tolist()]
                src_cache[(cside, col)] = vals
            want = inner.schema.type_of(name)
            msk = np.zeros(tot, np.bool_)
            if want == ColumnType.STRING:
                enc = inner.dicts[name].encode
                arr = np.empty(tot, np.int32)
                for i, v in enumerate(vals):
                    if v is None:
                        arr[i] = -1
                        msk[i] = True
                    else:
                        arr[i] = enc(str(v))
            else:
                dt = (np.bool_ if want == ColumnType.BOOL
                      else np.int32 if want == ColumnType.INT
                      else np.float32)
                arr = np.zeros(tot, dt)
                for i, v in enumerate(vals):
                    if v is None or not isinstance(v, (int, float, bool)):
                        msk[i] = True
                    else:
                        arr[i] = v
            cols[name] = arr
            if msk.any():
                nulls[name] = msk
        return cols, (nulls or None)

    # ---- device-resident join ----------------------------------------------
    #
    # Once the columnar fast path is planned, both sides migrate onto
    # the device (engine.lattice interval-join kernels): per-side
    # sorted stores of (code, ts_rel, flags, packed needed columns),
    # one fused probe+insert dispatch per micro-batch, one D2H fetch of
    # the packed match buffer (deferrable/stackable via
    # match_drain_depth), vmapped two-sided eviction on watermark
    # advance, and epoch rebase instead of the host store's span abort.
    # Host stores stay the equivalence-reference path
    # (use_device_join=False).

    DEVICE_STORE_CAPACITY = 1 << 14   # initial per-side slots (grows)
    # a window join holds a whole window of both streams and its fused
    # step is rebuilt (a minute on the chip) for every size on the way:
    # it starts larger and grows in larger strides; all of it is small
    # beside a chip's memory
    WINDOW_STORE_CAPACITY = 1 << 16   # initial per-side slots (grows x16)
    WINDOW_MATCH_CAPACITY = 1 << 14   # initial match buffer width
    WINDOW_INNER_KEYS = 1 << 18       # the inner key table, at least
    REBASE_REL_MS = 1 << 30           # re-anchor epoch past this

    def _device_ready(self) -> bool:
        if self._dev is not None:
            return True
        if not self.use_device_join:
            return False
        fast = self._fast_info()
        if fast is None:
            return False
        try:
            from hstream_tpu.common.faultinject import FAULTS

            if FAULTS.active:  # chaos: provoke an activation failure
                FAULTS.point("device.activate")
            return self._activate_device(fast)
        except Exception as e:  # noqa: BLE001 — an activation failure
            # (kernel build, migration, device OOM, injected fault)
            # degrades to the retained host reference path instead of
            # killing the query; results are identical, only slower
            log.warning(
                "device join activation failed (%s: %s); staying on "
                "the host reference path", type(e).__name__, e)
            self._dev = None
            self.use_device_join = False
            self.device_fallbacks += 1
            return False

    def _activate_device(self, fast: dict) -> bool:
        """Plan per-side column layouts from the fast-path need map and
        migrate the host stores' contents into device stores. Each need
        name stores on every side it can resolve from ('both' = bare
        name with left precedence, stored on both sides with a present
        bit)."""
        from hstream_tpu.engine import lattice

        lay: dict[str, list[tuple[str, str]]] = {"l": [], "r": []}
        for name, (cside, col) in fast["need"].items():
            for s in ("l", "r"):
                if cside in (s, "both"):
                    lay[s].append((name, col))
        if max(len(lay["l"]), len(lay["r"])) > lattice.JOIN_MAX_COLS:
            self.use_device_join = False  # flags word out of bits
            return False
        cap = (self.WINDOW_STORE_CAPACITY if self.window_join
               else self.DEVICE_STORE_CAPACITY)
        need = max(len(self._stores["l"]), len(self._stores["r"])) * 2
        cap = round_up_pow2(need, lo=cap)
        cands = [int(st.ts.min()) for st in self._stores.values()
                 if len(st)]
        if self.watermark >= 0:
            cands.append(self.watermark)
        t0 = (self._anchor(min(cands) - self.retention_ms)
              if cands else None)
        sjl = None
        if (self.mesh is not None
                and self.key_axis in self.mesh.axis_names
                and self.mesh.shape[self.key_axis] > 1):
            from hstream_tpu.parallel.lattice import ShardedJoinLattice

            # per-shard capacity keeps the single-chip formula: the
            # worst key distribution lands every entry on one shard, so
            # this trades memory (n_shards x) for never growing on skew
            sjl = ShardedJoinLattice(
                self.mesh, self.key_axis, cap, 1024, 4096,
                len(lay["l"]), len(lay["r"]))
        self._dev = {
            "lay": lay,
            "cap": cap,
            "sjl": sjl,
            "t0": t0,
            "n": {"l": 0, "r": 0},
            # match buffers start small and stick at the pow2 the
            # workload's match totals actually need (the host shadow
            # sizes them EXACTLY per batch, so they never overflow):
            # a buffer sized to batch_capacity would make every fetch
            # pay for a worst case that never happens
            # (a window join starts where a frame of a one-to-many join
            # ends: each width on the way is a fused step rebuilt)
            "match_cap": self.WINDOW_MATCH_CAPACITY if self.window_join
            else 4096,
            "bcaps": set(),
            "evict_cutoff": -(1 << 62),
            "stores": {
                "l": (sjl.init_store("l") if sjl is not None
                      else lattice.init_join_store(cap, len(lay["l"]))),
                "r": (sjl.init_store("r") if sjl is not None
                      else lattice.init_join_store(cap, len(lay["r"]))),
            },
            # host shadow of each side's (code, ts) multiset, pruned at
            # the probe cutoff: gives EXACT match totals before every
            # dispatch (match buffers never overflow, the fused kernel
            # can never silently truncate) for the cost of a rowless
            # numpy insert+searchsorted per batch
            "shadow": {"l": _FlatIntervalStore(self._jcode_rev),
                       "r": _FlatIntervalStore(self._jcode_rev)},
        }
        self._dev["feed"] = self._build_feed_plans()
        # migrate BOTH sides before clearing either host store: a
        # failure partway (caught in _device_ready) must leave the host
        # reference path intact to fall back on
        for s in ("l", "r"):
            self._migrate_store(s)
        for s in ("l", "r"):
            self._stores[s] = _FlatIntervalStore(self._jcode_rev)
        return True

    def _anchor(self, t: int) -> int:
        """A join epoch at or before `t`: a window join keeps it a
        multiple of the window size, so the kernels cut relative time
        where absolute time cuts."""
        return t - t % self.within if self.window_join else t

    def _build_feed_plans(self) -> dict | None:
        """Hashable per-side plans mapping the inner step's needed
        columns (and null masks) onto match-buffer sources, for the
        fully fused probe->aggregate kernel. None when the inner
        executor is not a device lattice (stateless joins keep the
        match-fetch path)."""
        from hstream_tpu.engine import lattice
        from hstream_tpu.engine.expr import columns_of

        inner = self._inner
        if (getattr(inner, "spec", None) is None
                or not hasattr(inner, "_null_specs")):
            return None
        if ((self._dev.get("sjl") is not None)
                != (getattr(inner, "_sharded", None) is not None)):
            # a sharded join can only fuse into a sharded inner lattice
            # (and vice versa); a mismatch keeps the match-fetch path
            return None
        lay_idx = {s: {name: j for j, (name, _c)
                       in enumerate(self._dev["lay"][s])}
                   for s in ("l", "r")}
        plans: dict[str, tuple] = {}
        for side in ("l", "r"):
            other = "r" if side == "l" else "l"

            def entry(name):
                cside, _col = self._fast["need"][name]
                jm = lay_idx[side].get(name, -1)
                jo = lay_idx[other].get(name, -1)
                if cside == side:
                    return ("m", jm, jo)
                if cside == other:
                    return ("o", jm, jo)
                # bare name, left precedence: the SQL left side is the
                # probing batch when side == "l", else the probed store
                return ("both" if side == "l" else "both_o", jm, jo)

            feed = tuple(
                (name, lattice.layout_tag(inner.schema.type_of(name)))
                + entry(name)
                for name in self._fast["need"])
            nulls_plan = tuple(
                (key, tuple(entry(c) for c in refs))
                for key, refs in inner._null_specs)
            filter_nulls = (tuple(
                entry(c) for c in sorted(columns_of(inner._filter_expr)))
                if inner._filter_expr is not None else ())
            plans[side] = (feed, nulls_plan, filter_nulls)
        return plans

    def _migrate_store(self, side: str) -> None:
        """Move one host store's live entries into the device store
        (activation / snapshot restore): pack host rows into the device
        entry layout and device_put directly — already (code, ts)
        sorted, so no kernel dispatch is needed."""
        import jax
        import jax.numpy as jnp

        st = self._stores[side]
        n = len(st)
        if n == 0:
            return
        dev = self._dev
        if int(st.ts.max()) - dev["t0"] >= (1 << 31):
            # the host store's span guard allows 2^41 ms but the device
            # store's relative space is int32: a silent wrap here would
            # corrupt every probe bound (found by hstream-analyze,
            # overflow-narrowing)
            raise SQLCodegenError(
                "join store spans more than the int32 relative range "
                "at device activation; reduce within/grace retention")
        dev["shadow"][side].insert_sorted(
            st.code.copy(), st.ts.copy(), np.empty(n, object))
        lay = dev["lay"][side]
        flags, vals = self._encode_join_cols(
            lay, [st.rows[i] for i in range(n)])
        from hstream_tpu.engine import lattice

        cap = dev["cap"]
        sjl = dev.get("sjl")
        if sjl is not None:
            # distribute entries into their owning shard's slice; each
            # residue class of a (code, ts)-sorted sequence is itself
            # (code, ts)-sorted, so per-shard order needs no re-sort
            ns = sjl.n_shards
            scode = np.full((ns, cap), lattice.JOIN_SENT_CODE, np.int32)
            sts = np.zeros((ns, cap), np.int32)
            sfl = np.zeros((ns, cap), np.int32)
            scv = np.zeros((ns, len(lay), cap), np.int32)
            cls = (st.code % ns).astype(np.int64)
            for s in range(ns):
                m = np.nonzero(cls == s)[0]
                k = len(m)
                scode[s, :k] = st.code[m].astype(np.int32)
                sts[s, :k] = (st.ts[m] - dev["t0"]).astype(np.int32)
                sfl[s, :k] = flags[m]
                scv[s, :, :k] = vals[:, m]
            dev["stores"][side] = sjl.put_store(
                {"code": scode, "ts": sts, "flags": sfl, "cols": scv})
            dev["n"][side] = n
            return
        code = np.full(cap, lattice.JOIN_SENT_CODE, np.int32)
        code[:n] = st.code.astype(np.int32)
        ts = np.zeros(cap, np.int32)
        ts[:n] = (st.ts - dev["t0"]).astype(np.int32)
        f32 = np.zeros(cap, np.int32)
        f32[:n] = flags
        cv = np.zeros((len(lay), cap), np.int32)
        cv[:, :n] = vals
        dev["stores"][side] = {
            "code": jax.device_put(jnp.asarray(code)),
            "ts": jax.device_put(jnp.asarray(ts)),
            "flags": jax.device_put(jnp.asarray(f32)),
            "cols": jax.device_put(jnp.asarray(cv)),
        }
        dev["n"][side] = n

    def _encode_join_cols(self, lay, rows) -> tuple[np.ndarray,
                                                    np.ndarray]:
        """Pack one side's needed columns for a list of rows into
        (flags i32[n], values i32[len(lay), n]): 2 bits per column in
        flags (bit 2j = SQL NULL / non-scalar, bit 2j+1 = field
        present), values f32-bitcast / i32 / bool / dictionary id —
        the same per-value rules as the host fast path (_match_cols)."""
        from hstream_tpu.engine.types import ColumnType

        inner = self._inner
        n = len(rows)
        flags = np.zeros(n, np.int32)
        vals = np.zeros((len(lay), n), np.int32)
        for j, (name, col) in enumerate(lay):
            nullb = np.int32(1 << (2 * j))
            presb = np.int32(1 << (2 * j + 1))
            want = inner.schema.type_of(name)
            if want == ColumnType.STRING:
                enc = inner.dicts[name].encode
                arr = np.zeros(n, np.int32)
                for i, r in enumerate(rows):
                    v = r.get(col, _MISS)
                    if v is _MISS:
                        flags[i] |= nullb
                    elif v is None:
                        flags[i] |= nullb | presb
                    else:
                        arr[i] = enc(str(v))
                        flags[i] |= presb
                vals[j] = arr
            else:
                dt = (np.bool_ if want == ColumnType.BOOL
                      else np.int32 if want == ColumnType.INT
                      else np.float32)
                arr = np.zeros(n, dt)
                for i, r in enumerate(rows):
                    v = r.get(col, _MISS)
                    if v is _MISS:
                        flags[i] |= nullb
                    elif v is None or not isinstance(v, (int, float,
                                                         bool)):
                        flags[i] |= nullb | presb
                    else:
                        arr[i] = v
                        flags[i] |= presb
                vals[j] = (arr.view(np.int32) if dt is np.float32
                           else arr.astype(np.int32))
        return flags, vals

    # ---- columnar ingest (vectorized encode, no row dicts) ----------------

    def _columnar_batch(self, side, my_keys, ts, cols, nulls):
        """Vectorized (codes, bts, flags, vals) in (code, ts) sorted
        order for a columnar batch, or None when this batch cannot
        encode columnar (non-Col ON keys, untyped columns) — the
        caller materializes rows once and takes the row path."""
        codes = self._batch_codes_columnar(my_keys, cols, nulls,
                                           len(ts))
        if codes is None:
            return None
        enc = self._encode_join_cols_columnar(
            self._dev["lay"][side], cols, nulls, len(ts))
        if enc is None:
            return None
        flags, vals = enc
        keep = codes >= 0
        kidx = None if keep.all() else np.nonzero(keep)[0]
        if self.window_join and keep.any():
            fresh = self._in_open_windows(ts if kidx is None
                                          else ts[kidx])
            if fresh is not None:
                kidx = (np.nonzero(fresh)[0] if kidx is None
                        else kidx[fresh])
        if kidx is not None:
            codes = codes[kidx]
            bts = ts[kidx]
            flags = flags[kidx]
            vals = vals[:, kidx]
        else:
            bts = ts
        if not len(codes):
            return codes, bts, flags, vals
        order = np.lexsort((bts, codes))
        codes, bts = codes[order], bts[order]
        if self.window_join:
            src = order if kidx is None else kidx[order]
            self._window_kids(side, codes, bts,
                              lambda c: np.asarray(cols[c])[src])
        return codes, bts, flags[order], vals[:, order]

    def _batch_codes_columnar(self, my_keys, cols, nulls,
                              n: int) -> np.ndarray | None:
        """Dense join-key codes for a columnar batch: unique + encode
        per DISTINCT value, one gather per row — the vectorized twin of
        _batch_codes. None = fall back to the row path."""
        if not all(isinstance(e, Col) for e in my_keys):
            return None
        # compact BEFORE encoding, like _batch_codes
        if len(self._jcode_rev) + n >= (1 << 22) - 1:
            self._compact_codes()
            if len(self._jcode_rev) + n >= (1 << 22) - 1:
                raise SQLCodegenError(
                    "join key cardinality within the retention window "
                    f"exceeds {1 << 22} distinct keys")
        jcode = self._jcode
        mint = self._mint_code

        def code_of(k) -> int:
            c = jcode.get(k)
            return mint(k) if c is None else c

        col_vals: list[np.ndarray] = []
        col_codes: list[np.ndarray] = []
        null_any = np.zeros(n, np.bool_)
        for e in my_keys:
            arr = cols.get(e.name)
            if arr is None:
                return None if n else np.empty(0, np.int64)
            nm = nulls.get(e.name) if nulls else None
            if nm is not None:
                null_any |= nm
            try:
                uniq, inv = np.unique(np.asarray(arr),
                                      return_inverse=True)
            except TypeError:
                return None  # incomparable mixed values: row path
            col_vals.append(uniq)
            col_codes.append(inv.astype(np.int64))
        if len(my_keys) == 1:
            uniq = col_vals[0]
            lut = np.fromiter(
                (code_of(canon_key((v,))) for v in uniq.tolist()),
                np.int64, len(uniq))
            out = lut[col_codes[0]]
        else:
            combined = col_codes[0]
            for inv, uniq in zip(col_codes[1:], col_vals[1:]):
                combined = combined * len(uniq) + inv
            u, uinv = np.unique(combined, return_inverse=True)
            lut = np.empty(len(u), np.int64)
            for i, cu in enumerate(u.tolist()):
                idxs = []
                for uniq in reversed(col_vals[1:]):
                    idxs.append(cu % len(uniq))
                    cu //= len(uniq)
                idxs.append(cu)
                idxs.reverse()
                key = tuple(col_vals[k][i2].item()
                            if hasattr(col_vals[k][i2], "item")
                            else col_vals[k][i2]
                            for k, i2 in enumerate(idxs))
                lut[i] = code_of(canon_key(key))
            out = lut[uinv]
        if null_any.any():
            out = np.where(null_any, -1, out)
        return out

    def _encode_join_cols_columnar(self, lay, cols, nulls, n: int):
        """Vectorized twin of _encode_join_cols over whole columns:
        (flags i32[n], vals i32[len(lay), n]), or None when a column's
        dtype cannot encode without per-row inspection."""
        from hstream_tpu.engine.types import ColumnType

        inner = self._inner
        flags = np.zeros(n, np.int32)
        vals = np.zeros((len(lay), n), np.int32)
        for j, (name, col) in enumerate(lay):
            nullb = np.int32(1 << (2 * j))
            presb = np.int32(1 << (2 * j + 1))
            arr = cols.get(col)
            if arr is None:
                flags |= nullb  # field absent from every record
                continue
            arr = np.asarray(arr)
            nm = nulls.get(col) if nulls else None
            want = inner.schema.type_of(name)
            if want == ColumnType.STRING:
                enc = inner.dicts[name].encode
                try:
                    uniq, inv = np.unique(arr, return_inverse=True)
                except TypeError:
                    return None
                lut = np.fromiter((enc(str(v)) for v in uniq.tolist()),
                                  np.int32, len(uniq))
                vals[j] = lut[inv]
                row_flags = presb
            else:
                if arr.dtype == object or arr.dtype.kind in ("U", "S"):
                    return None  # untyped numerics: row path decides
                try:
                    if want == ColumnType.FLOAT:
                        vals[j] = arr.astype(
                            np.float32, copy=False).view(np.int32)
                    elif want == ColumnType.BOOL:
                        vals[j] = (np.asarray(arr) != 0).astype(
                            np.int32)
                    else:
                        vals[j] = arr.astype(np.int32)
                except (TypeError, ValueError):
                    return None
                row_flags = presb
            flags |= row_flags
            if nm is not None and nm.any():
                # a null-masked cell is an ABSENT field (drop_null row
                # parity): null bit on, present bit off, value zeroed
                flags[nm] = (flags[nm] | nullb) & ~presb
                vals[j, nm] = 0
        return flags, vals

    @staticmethod
    def _rows_from_cols(cols, nulls, n: int) -> list[dict[str, Any]]:
        """Materialize columnar input into per-row dicts (fallback /
        host reference path) with null-masked cells dropped — the same
        row shape the per-record decode produces, including
        columnar.to_rows' f64 parity (integral doubles decode as ints,
        like Struct number decoding)."""
        host = {}
        masks = {}
        for name, arr in cols.items():
            if isinstance(arr, np.ndarray) and arr.dtype == np.float64:
                vals = [int(v) if v.is_integer() else v
                        for v in arr.tolist()]
            elif isinstance(arr, np.ndarray):
                vals = arr.tolist()
            else:
                vals = list(arr)
            nm = nulls.get(name) if nulls else None
            if nm is not None and nm.any():
                masks[name] = nm.tolist()
            host[name] = vals
        names = list(host)
        if not names:
            return [{} for _ in range(n)]
        rows = [dict(zip(names, vv))
                for vv in zip(*(host[c] for c in names))]
        for name, mask in masks.items():
            for row, isnull in zip(rows, mask):
                if isnull:
                    del row[name]
        return rows

    def _dev_bcap(self, n: int) -> int:
        """Sticky pow2 batch capacity (each distinct shape is its own
        XLA compile; varying batch sizes converge on a few)."""
        caps = self._dev["bcaps"]
        if self.window_join:
            # one shape, the largest met: a window join's fused step
            # sorts a whole store with the batch, which takes the chip
            # a minute to build and which a few thousand padded rows do
            # not slow; so a source's smaller frames (and the one-row
            # closers) ride in the larger source's program. It starts
            # at twice the capacity the task sized to the first frame
            # it read: that frame was one source's
            cap = max(round_up_pow2(n, lo=1024), max(caps, default=0),
                      2 * self._batch_capacity)
            caps.add(cap)
            return cap
        for c in sorted(caps):
            if n <= c <= 8 * max(n, 1):
                return c
        cap = round_up_pow2(n, lo=1024)
        caps.add(cap)
        return cap

    # contract: dispatches<=1 fetches<=0
    def _device_batch(self, side, codes, bts, flags, vals
                      ) -> list[dict[str, Any]]:
        """One micro-batch on the device path: pack, ONE device
        dispatch. When the downstream aggregate can fuse, the dispatch
        scatters the matched pairs straight into the inner lattice —
        matches never leave the device; otherwise the packed match
        buffer is the one (deferrable, stackable) D2H fetch. `flags` /
        `vals` are the side's pre-encoded entry columns in (code, ts)
        sorted order (row or columnar encoder)."""
        from hstream_tpu.engine import lattice

        dev = self._dev
        n = len(codes)
        cutoff_abs = self._cutoff_abs()
        if dev["t0"] is None:
            dev["t0"] = self._anchor(int(bts.min()) - self.retention_ms)
        self._maybe_rebase(int(bts.min()), int(bts.max()))
        if self.window_join:
            # a window join's stores hold what its host shadows hold,
            # row for row (both lose a closed window at its close), so
            # the shadow's length is the store's, exactly and for free.
            # Grown with a quarter to spare, and as soon as a quarter
            # is not to spare: the capacity is then final within the
            # first window, not at whichever later close a source was
            # read a few frames further than the other (a recompile).
            # By sixteen: every capacity on the way is a fused step
            # built, and the stores are small
            live = len(dev["shadow"][side]) + n
            if live * 5 // 4 > dev["cap"]:
                self._grow_device(round_up_pow2(
                    live * 5 // 4, lo=dev["cap"] * 16))
        elif dev["n"][side] + n > dev["cap"]:
            self._refresh_counts()  # upper bound -> exact
            if dev["n"][side] + n > dev["cap"]:
                # capacity pressure: evict with the PRE-batch watermark
                # cutoff — the probe below must still see every entry
                # the host reference would (it prunes only after the
                # batch)
                self._dispatch_evict(self.watermark - self.retention_ms,
                                     0)
                self._refresh_counts()
                if dev["n"][side] + n > dev["cap"]:
                    self._grow_device(round_up_pow2(
                        dev["n"][side] + n, lo=dev["cap"] * 2))
                elif max(dev["n"].values()) + n > dev["cap"] // 2:
                    # hysteresis: an eviction that leaves the store
                    # more than half full would force another sort
                    # within a few batches — grow once instead of
                    # evicting every batch
                    self._grow_device(dev["cap"] * 2)
        # exact match total from the host shadow (code/ts only): sizes
        # the padded match width so the kernel can never truncate
        other_side = "r" if side == "l" else "l"
        shadow_o = dev["shadow"][other_side]
        with trace_span(self.tracer, "join_shadow"):
            pr = shadow_o.probe(codes, *self._probe_span(bts))
            sjl = dev.get("sjl")
            if pr is None:
                total = 0
            elif sjl is not None:
                # the match buffer is PER SHARD: size it to the worst
                # shard's total (each shard packs its own segment)
                per = np.bincount((codes % sjl.n_shards).astype(np.int64),
                                  weights=(pr[1] - pr[0]).astype(np.float64),
                                  minlength=sjl.n_shards)
                total = int(per.max())
            else:
                total = int((pr[1] - pr[0]).sum())
            self.join_stats["matches"] += total
            dev["shadow"][side].insert_sorted(codes, bts,
                                              np.empty(n, object))
            if (cutoff_abs is not None and cutoff_abs > 0
                    and not self.window_join):  # (pruned at every close)
                dev["shadow"][side].prune(cutoff_abs)
                shadow_o.prune(cutoff_abs)
        if total > dev["match_cap"]:
            dev["match_cap"] = round_up_pow2(total,
                                             lo=dev["match_cap"] * 2)
        with trace_span(self.tracer, "join_pack"):
            kid = self._match_key_ids(codes)
            lay = dev["lay"][side]
            bcap = self._dev_bcap(n)
            buf = np.zeros((4 + len(lay), bcap), np.int32)
            buf[0, :n] = codes
            buf[0, n:] = lattice.JOIN_SENT_CODE
            buf[1, :n] = (bts - dev["t0"]).astype(np.int32)
            buf[2, :n] = np.maximum(kid, 0)
            buf[3, :n] = flags
            if len(lay):
                buf[4:, :n] = vals
        other = dev["stores"][other_side]
        # the probe-visible retention cutoff mirrors the host
        # reference's prune-before-this-batch state: the device store
        # may still hold older entries (eviction is lazy, capacity
        # only), but matches must not see them
        cutoff = np.int32(np.clip(
            (cutoff_abs - dev["t0"]) if cutoff_abs is not None
            else -(1 << 31), -(1 << 31), (1 << 31) - 1))
        self.join_stats["probe_batches"] += 1
        self.join_stats["probe_dispatches"] += 1
        if dev.get("feed") is not None and self._fuse_ok(bts):
            return self._fused_batch(side, other_side, buf, n, cutoff,
                                     total)
        if sjl is not None:
            with kernel_family("probe", self.dispatch_observer,
                               ready=self._device_values):
                dev["stores"][side], packed = sjl.probe_insert(
                    side, dev["stores"][side], other, buf, np.int32(n),
                    np.int32(self.within), cutoff,
                    match_cap=dev["match_cap"])
            self._sharded_dispatches += 1
        else:
            kern = lattice.join_probe_insert(
                dev["cap"], bcap, dev["match_cap"], len(lay),
                len(dev["lay"][other_side]), self.window_join)
            with kernel_family(self._family, self.dispatch_observer,
                               ready=self._device_values):
                dev["stores"][side], packed = kern(
                    dev["stores"][side], other, buf, np.int32(n),
                    np.int32(self.within), cutoff)
        self._note_insert(side, n)
        # the pending entry keeps (batch, other-store ref) alive so a
        # truncated match buffer could re-probe wider (unreachable
        # while the shadow sizes the width, kept as belt-and-braces)
        self._pending_matches.append(
            (packed, side, dev["t0"], buf, n, other, cutoff))
        if len(self._pending_matches) >= max(self.match_drain_depth, 1):
            return self._drain_matches()
        return []

    # ---- fused probe -> inner aggregate (zero per-batch D2H) --------------

    def _fuse_ok(self, bts) -> bool:
        """Whether this batch can take the fully fused kernel: the
        inner executor's host window bookkeeping must be able to track
        the conservative joined-ts range [min bts, max bts + within]
        without a per-row scan — the windows-in-range set must fit the
        fast gate and introduce no slot aliasing (mirrors _gap_guard's
        collision check; a batch that fails falls back to the
        match-fetch path, which runs the full guard)."""
        inner = self._inner
        w = inner.window
        if w is None:
            return True
        if inner.epoch is not None and int(bts.min()) < inner.epoch:
            return False  # pre-epoch joined ts: row path handles
        adv = w.advance_ms
        lo, hi = self._joined_span(int(bts.min()), int(bts.max()))
        span = (hi - hi % adv - (lo - lo % adv)) // adv + 1
        back = w.windows_per_record - 1
        if span + back > min(inner.spec.n_slots, 64):
            return False
        period = adv * inner.spec.n_slots
        starts = np.arange(lo - lo % adv - back * adv,
                           hi - hi % adv + adv, adv)
        if inner.watermark_abs >= 0:
            starts = starts[starts + w.size_ms + w.grace_ms
                            > inner.watermark_abs]
        cand = set(starts.tolist()) | set(inner._open)
        by_res: dict[int, int] = {}
        for s in cand:
            r = s % period
            if r in by_res and by_res[r] != s:
                return False  # slot aliasing: let _gap_guard handle it
            by_res[r] = s
        return True

    def _joined_span(self, lo: int, hi: int) -> tuple[int, int]:
        """The span of event time the pairs of a batch spanning
        [lo, hi] can lie in (a pair's time is the later of its two):
        up to `within` past the batch, or (window join) inside the
        batch's own windows."""
        if self.window_join:
            return lo, hi - hi % self.within + self.within - 1
        return lo, hi + self.within

    @property
    def _family(self) -> str:
        """The kernel family of the probe dispatch: `join` for a window
        join (`dispatch:join`), `probe` for the interval join."""
        return "join" if self.window_join else "probe"

    # contract: dispatches<=1 fetches<=0
    def _fused_batch(self, side, other_side, buf, n, cutoff,
                     expected: int) -> list[dict[str, Any]]:
        """Dispatch the probe+insert+inner-scatter kernel: the matched
        pairs aggregate on device, so the batch costs ZERO D2H — the
        changelog extract (already deferred/batched) is the only fetch
        left on the join hot path."""
        from hstream_tpu.common.columnar import extend_rows
        from hstream_tpu.engine import lattice

        dev = self._dev
        inner = self._inner
        bmax = int(buf[1, :n].max()) + dev["t0"]  # the batch's newest
        lo, hi = self._joined_span(int(buf[1, :n].min()) + dev["t0"],
                                   bmax)
        inner._ensure_epoch(lo)
        inner._maybe_rebase(hi)
        # watermark forwarding: the joined stream's watermark is the
        # JOIN's watermark (both paths apply the same sync in
        # _feed_inner_columnar, so late-mask semantics stay identical)
        if self.watermark > inner.watermark_abs:
            inner.watermark_abs = self.watermark
        wm_rel = np.int32(max(inner.watermark_abs - inner.epoch, -1)
                          if inner.watermark_abs >= 0 else -1)
        ts_off = np.int32(dev["t0"] - inner.epoch)
        feed, nulls_plan, filter_nulls = dev["feed"][side]
        sjl = dev.get("sjl")
        if sjl is not None:
            with kernel_family("probe", self.dispatch_observer,
                               ready=self._device_values):
                dev["stores"][side], inner.state, _total = \
                    sjl.probe_insert_step(
                        side, inner._sharded, dev["stores"][side],
                        dev["stores"][other_side], buf, np.int32(n),
                        np.int32(self.within), cutoff, inner.state,
                        wm_rel, ts_off, feed_plan=feed,
                        nulls_plan=nulls_plan,
                        filter_nulls=filter_nulls,
                        match_cap=dev["match_cap"])
            self._sharded_dispatches += 1
        else:
            kern = lattice.join_probe_insert_step(
                dev["cap"], buf.shape[1], dev["match_cap"],
                len(dev["lay"][side]), len(dev["lay"][other_side]),
                inner.spec, inner.schema, inner._filter_expr, feed,
                nulls_plan, filter_nulls, self.window_join)
            with kernel_family(self._family, self.dispatch_observer,
                               ready=self._device_values):
                dev["stores"][side], inner.state, _total = kern(
                    dev["stores"][side], dev["stores"][other_side], buf,
                    np.int32(n), np.int32(self.within), cutoff,
                    inner.state, wm_rel, ts_off)
        self._note_insert(side, n)
        self.join_stats["fused_batches"] += 1
        if self.window_join:
            # the pairs never leave the device; their count does, a few
            # batches late: the one wait for the device on this path
            # (the task thread runs at most FUSED_DEPTH batches ahead
            # of it), and the check that the host shadow, which sized
            # the match buffer, counted what the kernel matched
            self._inflight.append((_total, expected))
            while len(self._inflight) > self.FUSED_DEPTH:
                self._settle_fused()
        # inner host bookkeeping over the conservative ts range (the
        # overapproximated window set is semantics-free: empty windows
        # close without emitting via the count>0 filter)
        try:
            if inner.window is not None:
                inner._track_windows(np.asarray([lo, hi], np.int64))
            # (a window join's event time is the minimum over both
            # sources, `_after_batch`: one batch does not move it)
            if not self.window_join and bmax > inner.watermark_abs:
                inner.watermark_abs = bmax
            out = None
            if inner.emit_changes:
                out = extend_rows(out, inner._drain_changes())
            out = extend_rows(out, inner.close_due_windows())
            # a lone ColumnarEmit rides through unmaterialized — the
            # fused path must not be the one place rows re-dictify
            return out if out is not None else []
        finally:
            inner._no_close.clear()
            inner._touched_this_call.clear()

    FUSED_DEPTH = 2   # fused window-join batches in flight

    # contract: dispatches<=0 fetches<=1
    def _settle_fused(self) -> None:
        total, expected = self._inflight.popleft()
        with trace_span(self.tracer, "join_fetch"):
            got = int(np.asarray(total))
        self.join_stats["probe_fetches"] += 1
        if got != expected:
            raise RuntimeError(
                f"window join: the device matched {got} pairs where "
                f"the host shadow counted {expected}")

    # contract: dispatches<=0 fetches<=1
    def _drain_matches(self) -> list[dict[str, Any]]:
        """Fetch + decode every pending match buffer: buffers of one
        shape stack into ONE device->host transfer (fetch count, not
        bytes, dominates on real links), then decode columnar and feed
        the inner executor."""
        from hstream_tpu.engine.lattice import stack_pow2

        if not self._pending_matches:
            return []
        pending, self._pending_matches = self._pending_matches, []
        # piggyback the deferred post-eviction counts on this sync:
        # everything queued ahead of the match buffers has executed by
        # the time they arrive, so the 2-int copy is free here and the
        # host upper bound stays fresh without hot-loop blocking
        self._refresh_counts()
        host: list[tuple] = []
        with trace_span(self.tracer, "join_fetch"):
            if len(pending) == 1:
                packed, *rest = pending[0]
                self.join_stats["probe_fetches"] += 1
                host.append((np.asarray(packed), *rest))
            else:
                by_shape: dict[tuple, list] = {}
                for ent in pending:
                    by_shape.setdefault(tuple(ent[0].shape), []).append(ent)
                groups: dict[int, tuple] = {}
                for group in by_shape.values():
                    self.join_stats["probe_fetches"] += 1
                    stacked = np.asarray(stack_pow2([e[0] for e in group]))
                    for ent, hbuf in zip(group, stacked):
                        groups[id(ent)] = (hbuf, *ent[1:])
                # preserve submission order across shape groups
                host = [groups[id(ent)] for ent in pending]
        from hstream_tpu.common.columnar import extend_rows

        out = None
        sjl = self._dev.get("sjl")
        for hbuf, side, t0, buf, n, other, cutoff in host:
            nm = len(self._dev["lay"][side])
            if sjl is not None:
                # per-shard headers sit at column s * match_cap; any
                # shard's truncation forces the whole-buffer redo
                mc = hbuf.shape[1] // sjl.n_shards
                total = max(int(hbuf[0, s * mc])
                            for s in range(sjl.n_shards))
                width = mc
            else:
                total = int(hbuf[0, 0])
                width = hbuf.shape[1]
            if total > width:
                hbuf = self._reprobe_wider(side, buf, n, other, cutoff,
                                           total)
            out = extend_rows(out, self._decode_matches(side, t0, hbuf,
                                                        nm))
        return out if out is not None else []

    # contract: dispatches<=1 fetches<=1
    def _reprobe_wider(self, side, buf, n, other, cutoff,
                       total) -> np.ndarray:
        """Match-overflow redo: probe-only at the next pow2 width (the
        batch is already inserted; `other` is the exact store the fused
        kernel probed, `cutoff` its retention mask)."""
        from hstream_tpu.engine import lattice

        dev = self._dev
        match_cap = round_up_pow2(total, lo=dev["match_cap"] * 2)
        dev["match_cap"] = max(dev["match_cap"], match_cap)
        other_side = "r" if side == "l" else "l"
        self.join_stats["match_redispatches"] += 1
        self.join_stats["probe_fetches"] += 1
        sjl = dev.get("sjl")
        if sjl is not None:
            self._sharded_dispatches += 1
            return np.asarray(sjl.probe_only(
                side, other, buf, np.int32(n), np.int32(self.within),
                cutoff, match_cap))
        kern = lattice.join_probe_only(
            other["code"].shape[0], buf.shape[1], match_cap,
            len(dev["lay"][side]), len(dev["lay"][other_side]),
            self.window_join)
        return np.asarray(kern(other, buf, np.int32(n),
                               np.int32(self.within), cutoff))

    def _decode_matches(self, side, t0, hbuf, nm
                        ) -> list[dict[str, Any]]:
        """Columnar decode of a fetched match buffer into the inner
        step's input: resolve each needed column from the probe/stored
        side (left precedence for bare names via the present bits) —
        the vectorized twin of _match_cols."""
        from hstream_tpu.engine import lattice
        from hstream_tpu.engine.types import ColumnType

        sjl = self._dev.get("sjl") if self._dev is not None else None
        if sjl is not None:
            total, kid, jts, mflags, oflags, mcols, ocols = \
                sjl.unpack_matches(hbuf, side)
        else:
            total, kid, jts, mflags, oflags, mcols, ocols = \
                lattice.unpack_join_matches(hbuf, nm)
        m = len(kid)
        if m == 0:
            return []
        with trace_span(self.tracer, "join_decode"):
            key_ids, jts_abs, cols, nulls = self._match_columns(
                side, t0, kid, jts, mflags, oflags, mcols, ocols)
        return self._feed_inner_columnar(key_ids, jts_abs, cols, nulls)

    def _match_columns(self, side, t0, kid, jts, mflags, oflags,
                       mcols, ocols) -> tuple:
        dev = self._dev
        other_side = "r" if side == "l" else "l"
        lidx = {name: j for j, (name, _c)
                in enumerate(dev["lay"]["l"])}
        ridx = {name: j for j, (name, _c)
                in enumerate(dev["lay"]["r"])}
        phys = {side: (mflags, mcols), other_side: (oflags, ocols)}
        inner = self._inner
        cols: dict[str, np.ndarray] = {}
        nulls: dict[str, np.ndarray] = {}
        for name, (cside, _col) in self._fast["need"].items():
            if cside == "both":
                lf, lv = phys["l"]
                rf, rv = phys["r"]
                lj, rj = lidx[name], ridx[name]
                lpres = ((lf >> (2 * lj + 1)) & 1).astype(np.bool_)
                val = np.where(lpres, lv[lj], rv[rj])
                nb = np.where(lpres, (lf >> (2 * lj)) & 1,
                              (rf >> (2 * rj)) & 1)
            else:
                f, v = phys[cside]
                j = lidx[name] if cside == "l" else ridx[name]
                val = v[j]
                nb = (f >> (2 * j)) & 1
            want = inner.schema.type_of(name)
            if want == ColumnType.FLOAT:
                cols[name] = np.ascontiguousarray(
                    val, np.int32).view(np.float32)
            elif want == ColumnType.BOOL:
                cols[name] = val != 0
            else:
                cols[name] = np.ascontiguousarray(val, np.int32)
            msk = nb.astype(np.bool_)
            if msk.any():
                nulls[name] = msk
        return (kid.astype(np.int32), jts.astype(np.int64) + t0, cols,
                nulls or None)

    def _maybe_rebase(self, min_ts: int, max_ts: int) -> None:
        """Keep device-relative time inside int32: re-anchor the join
        epoch down when an in-grace batch reaches below it, up when
        stream time approaches the threshold — the rebase rides the
        two-sided eviction kernel (delta arg), so it costs one rare
        dispatch instead of the host store's span abort."""
        dev = self._dev
        # the eviction riding the rebase runs BEFORE this batch's
        # probe, so its cutoff is the PRE-batch watermark's — exactly
        # the prune state the host reference would probe against
        cutoff_abs = self._cutoff_abs()
        if cutoff_abs is None:
            cutoff_abs = dev["t0"]
        if min_ts - dev["t0"] < 0:
            delta = self._anchor(min_ts - self.retention_ms) - dev["t0"]
        elif max_ts - dev["t0"] >= self.REBASE_REL_MS:
            delta = max(self._anchor(cutoff_abs) - dev["t0"], 0)
        else:
            return
        if max_ts - (dev["t0"] + delta) >= (1 << 31):
            # the span guard must fire even when retention pins the
            # epoch (delta == 0) — silently wrapping int32 relative
            # time would corrupt probe bounds
            raise SQLCodegenError(
                "join record timestamps span more than the int32 "
                "relative range even after epoch rebase; timestamps "
                "must be epoch milliseconds")
        if delta == 0:
            return
        self._dispatch_evict(cutoff_abs, delta)
        self.join_stats["rebase_dispatches"] += 1

    def _maybe_evict(self, cutoff_abs: int) -> None:
        """Watermark-advance eviction policy: dispatch the two-sided
        compaction once retention has advanced a full span past the
        last one AND the stores hold enough dead weight to be worth a
        sort (capacity pressure dispatches it unconditionally in
        _device_batch)."""
        dev = self._dev
        if cutoff_abs - dev["evict_cutoff"] < max(self.retention_ms, 1):
            return
        if dev["n"]["l"] + dev["n"]["r"] < dev["cap"] // 2:
            # mostly-empty stores: skip the sort, just note progress
            dev["evict_cutoff"] = cutoff_abs
            return
        self._dispatch_evict(cutoff_abs, 0)

    # contract: dispatches<=1 fetches<=0
    def _dispatch_evict(self, cutoff_abs: int, delta: int) -> None:
        """One vmapped two-sided eviction (+ rebase) dispatch. The live
        counts stay a DEVICE value (dev["pending_n"]) so the hot loop
        never blocks on them; host-side dev["n"] remains a safe upper
        bound (eviction only shrinks) and _refresh_counts() forces the
        tiny fetch only when a capacity decision needs exact numbers."""
        from hstream_tpu.engine import lattice

        dev = self._dev
        cutoff_rel = max(cutoff_abs - dev["t0"], 0)
        sjl = dev.get("sjl")
        if sjl is not None:
            left, right, narr = sjl.evict(
                dev["stores"]["l"], dev["stores"]["r"],
                np.int32(min(cutoff_rel, (1 << 31) - 1)),
                np.int32(delta))
            self._sharded_dispatches += 1
        else:
            kern = lattice.join_evict(dev["cap"], len(dev["lay"]["l"]),
                                      len(dev["lay"]["r"]),
                                      self.window_join)
            with (kernel_family("join_evict", self.dispatch_observer)
                  if self.window_join else contextlib.nullcontext()):
                left, right, narr = kern(
                    dev["stores"]["l"], dev["stores"]["r"],
                    np.int32(min(cutoff_rel, (1 << 31) - 1)),
                    np.int32(delta))
        dev["stores"]["l"] = left
        dev["stores"]["r"] = right
        # the deferred count snapshot reflects the store AT THIS
        # dispatch; inserts queued after it must be re-added when the
        # snapshot is finally read (_refresh_counts), or the capacity
        # upper bound would silently undercount and let the insert
        # kernel truncate live entries
        dev["pending_n"] = (narr, {"l": 0, "r": 0})
        dev["t0"] += delta
        dev["evict_cutoff"] = max(dev["evict_cutoff"], cutoff_abs)
        self.join_stats["evict_dispatches"] += 1

    def _note_insert(self, side: str, n: int) -> None:
        """Count an insert against the host bound AND any in-flight
        eviction snapshot."""
        dev = self._dev
        dev["n"][side] += n
        pend = dev.get("pending_n")
        if pend is not None:
            pend[1][side] += n

    # contract: dispatches<=0 fetches<=1
    def _refresh_counts(self) -> None:
        """Force the deferred post-eviction live counts (2-int fetch),
        re-adding inserts dispatched after the eviction."""
        dev = self._dev
        pend = dev.pop("pending_n", None)
        if pend is not None:
            narr, since = pend
            n = np.asarray(narr)
            if n.ndim == 2:       # sharded evict: per-shard [ns, 2]
                n = n.sum(axis=0)
            dev["n"] = {"l": int(n[0]) + since["l"],
                        "r": int(n[1]) + since["r"]}

    def _grow_device(self, new_cap: int) -> None:
        """Double a full store pair: pad every plane with empty slots
        (code sentinel) on device — rare, host-driven."""
        import jax.numpy as jnp

        from hstream_tpu.engine import lattice

        dev = self._dev
        extra = new_cap - dev["cap"]
        sjl = dev.get("sjl")
        for s in ("l", "r"):
            st = dev["stores"][s]
            if sjl is not None:
                # per-shard slot axis is axis 1 (leading axis is the
                # shard); re-put to keep the key-axis sharding
                dev["stores"][s] = sjl.put_store({
                    "code": jnp.pad(
                        st["code"], ((0, 0), (0, extra)),
                        constant_values=lattice.JOIN_SENT_CODE),
                    "ts": jnp.pad(st["ts"], ((0, 0), (0, extra))),
                    "flags": jnp.pad(st["flags"], ((0, 0), (0, extra))),
                    "cols": jnp.pad(st["cols"],
                                    ((0, 0), (0, 0), (0, extra))),
                })
            else:
                dev["stores"][s] = {
                    "code": jnp.pad(
                        st["code"], (0, extra),
                        constant_values=lattice.JOIN_SENT_CODE),
                    "ts": jnp.pad(st["ts"], (0, extra)),
                    "flags": jnp.pad(st["flags"], (0, extra)),
                    "cols": jnp.pad(st["cols"], ((0, 0), (0, extra))),
                }
        dev["cap"] = new_cap
        if sjl is not None:
            sjl.cap = new_cap
        self.join_stats["store_grows"] += 1

    def _remap_device_codes(self, new_of_old: np.ndarray) -> None:
        """Apply a code-space compaction to the device stores: live
        codes keep their sorted order under compaction, so a gather
        through the remap LUT suffices (no re-sort). Sentinel slots map
        to themselves."""
        import jax.numpy as jnp

        from hstream_tpu.engine import lattice

        lut = jnp.asarray(new_of_old.astype(np.int32))
        for s in ("l", "r"):
            st = self._dev["stores"][s]
            code = st["code"]
            live = code < np.int32(len(new_of_old))
            st["code"] = jnp.where(
                live, lut[jnp.where(live, code, 0)],
                lattice.JOIN_SENT_CODE)

    def device_store_counts(self) -> dict[str, int] | None:
        """Live entries per device store side (tests/introspection)."""
        if self._dev is None:
            return None
        self._refresh_counts()
        return dict(self._dev["n"])

    def _host_store_view(self) -> dict[str, "_FlatIntervalStore"]:
        """The two side stores as host _FlatIntervalStores (snapshot
        serialization, equivalence tests). Device mode fetches the
        stores and reconstructs per-entry rows from the packed needed
        columns — the only fields future matches can emit on the fast
        path, so the view is faithful for every downstream consumer."""
        if self._dev is None:
            return self._stores
        import jax

        self._refresh_counts()
        out: dict[str, _FlatIntervalStore] = {}
        # the device store evicts lazily (capacity only) and hides
        # expired entries from probes via the cutoff mask; the view
        # applies the same retention filter so it matches the host
        # reference's eagerly-pruned stores exactly
        cutoff = (self.watermark - self.retention_ms
                  if self.watermark >= 0 else None)
        for side in ("l", "r"):
            st = _FlatIntervalStore(self._jcode_rev)
            n = self._dev["n"][side]
            if n:
                # snapshot serialization, off the hot loop; the sides
                # differ in column layout so their fetches cannot stack.
                # analyze: ok dispatch-sync — rare, host-driven
                arrs = {k: np.asarray(v) for k, v in jax.device_get(
                    self._dev["stores"][side]).items()}
                if self._dev.get("sjl") is not None:
                    # flatten the per-shard planes into one globally
                    # (code, ts)-sorted sequence: live entries are each
                    # shard's non-sentinel slots, but shards interleave
                    # in global code order
                    from hstream_tpu.engine import lattice as _lat

                    shard, slot = np.nonzero(
                        arrs["code"] < _lat.JOIN_SENT_CODE)
                    fcols = arrs["cols"].transpose(1, 0, 2)[
                        :, shard, slot]
                    fcode = arrs["code"][shard, slot]
                    fts = arrs["ts"][shard, slot]
                    order = np.lexsort((fts, fcode))
                    arrs = {
                        "code": fcode[order],
                        "ts": fts[order],
                        "flags": arrs["flags"][shard, slot][order],
                        "cols": fcols[:, order],
                    }
                    n = len(order)
                if cutoff is not None:
                    keep = (arrs["ts"][:n].astype(np.int64)
                            + self._dev["t0"]) >= cutoff
                    arrs = {
                        "code": arrs["code"][:n][keep],
                        "ts": arrs["ts"][:n][keep],
                        "flags": arrs["flags"][:n][keep],
                        "cols": arrs["cols"][:, :n][:, keep],
                    }
                    n = int(keep.sum())
                if n == 0:
                    out[side] = st
                    continue
                st.insert_sorted(
                    arrs["code"][:n].astype(np.int64),
                    arrs["ts"][:n].astype(np.int64) + self._dev["t0"],
                    self._rows_from_planes(self._dev["lay"][side],
                                           arrs, n))
            out[side] = st
        return out

    def _rows_from_planes(self, lay, arrs: dict, n: int) -> np.ndarray:
        """The first `n` entries of a fetched store as host rows: each
        holds the packed needed columns, which are the only fields a
        future match can emit on the fast path."""
        from hstream_tpu.engine.types import ColumnType

        inner = self._inner
        decoded: list[tuple[str, list]] = []
        flags = arrs["flags"][:n]
        for j, (name, col) in enumerate(lay):
            want = inner.schema.type_of(name)
            raw = arrs["cols"][j, :n]
            nullm = ((flags >> (2 * j)) & 1).astype(np.bool_)
            presm = ((flags >> (2 * j + 1)) & 1).astype(np.bool_)
            if want == ColumnType.FLOAT:
                vv = np.ascontiguousarray(raw).view(np.float32)
                py = [float(x) for x in vv]
            elif want == ColumnType.BOOL:
                py = [bool(x) for x in raw]
            elif want == ColumnType.STRING:
                dec = inner.dicts[name].decode
                py = [dec(int(x)) if not nl else None
                      for x, nl in zip(raw, nullm)]
            else:
                py = [int(x) for x in raw]
            decoded.append((col, [
                (_MISS if not p else (None if nl else v))
                for v, nl, p in zip(py, nullm, presm)]))
        rows = np.empty(n, object)
        for i in range(n):
            row = {}
            for col, vals in decoded:
                if vals[i] is not _MISS:
                    row[col] = vals[i]
            rows[i] = row
        return rows

    # ---- a window join's snapshot, as columns ------------------------------
    #
    # The row-by-row host view above costs a second for every hundred
    # thousand stored rows, under the task's lock. A window join on the
    # device is captured as what it is: the two stores' planes by
    # reference (the fetch is phase 2's, off the lock), the code
    # dictionary as one array a key column where the keys are integers,
    # and the two code-indexed tables. Restore rebuilds the host stores
    # and the device path re-activates and re-migrates on the next
    # batch, like every other join's.

    def pinned_planes(self) -> dict:
        """The device arrays a snapshot of this executor captures by
        reference (the task copies them on the device, one program a
        shape: it builds that program when the shapes move, not at
        whichever snapshot comes next). {} where a snapshot takes the
        host view."""
        dev = self._dev
        if not self.window_join or dev is None or dev.get("sjl"):
            return {}
        return {f"j/{side}.{plane}": v for side in ("l", "r")
                for plane, v in dev["stores"][side].items()}

    def capture_device(self) -> tuple[dict, dict]:
        dev = self._dev
        rev = self._jcode_rev
        n = len(rev)
        self._grow_code_luts()
        held = np.fromiter((k is not None for k in rev), np.bool_, n)
        meta = {"t0": dev["t0"], "lay": dev["lay"], "codes": None}
        arrays: dict[str, Any] = {
            "j/held": held,
            "j/kid_lut": self._kid_lut[:n].copy(),
            "j/kid_win": self._kid_win[:n].copy(),
        }
        keys = None
        try:
            keys = np.array([k for k in rev if k is not None])
        except ValueError:
            pass
        if keys is not None and keys.dtype == np.int64 and (
                keys.ndim == 2 or n == 0 or not held.any()):
            arrays["j/keys"] = keys.reshape(int(held.sum()), -1)
        else:
            from hstream_tpu.engine.snapshot import _enc

            meta["codes"] = [None if k is None else _enc(k) for k in rev]
        arrays.update(self.pinned_planes())
        return meta, arrays

    def restore_device(self, meta: dict, arrays: dict) -> None:
        """Install what `capture_device` took, into the host stores
        (the inner executor is already restored)."""
        from hstream_tpu.engine import lattice
        from hstream_tpu.engine.snapshot import _dec

        held = np.asarray(arrays["j/held"], np.bool_)
        if meta["codes"] is not None:
            rev = [None if k is None else tuple(_dec(k))
                   for k in meta["codes"]]
        else:
            rev = [None] * len(held)
            keys = np.asarray(arrays["j/keys"]).tolist()
            for c, k in zip(np.flatnonzero(held).tolist(), keys):
                rev[c] = tuple(k)
        self._jcode_rev[:] = rev
        self._jcode.clear()
        self._jcode.update({k: c for c, k in enumerate(rev)
                            if k is not None})
        self._jcode_free = [c for c in range(len(rev) - 1, -1, -1)
                            if rev[c] is None]
        self._grow_code_luts()
        self._kid_lut[:len(rev)] = arrays["j/kid_lut"]
        self._kid_win[:len(rev)] = arrays["j/kid_win"]
        cutoff = self._cutoff_abs()
        for side in ("l", "r"):
            arrs = {plane: np.asarray(arrays[f"j/{side}.{plane}"])
                    for plane in ("code", "ts", "flags", "cols")}
            ts = arrs["ts"].astype(np.int64) + (meta["t0"] or 0)
            keep = arrs["code"] < lattice.JOIN_SENT_CODE
            if cutoff is not None:
                keep &= ts >= cutoff
            n = int(keep.sum())
            if n == 0:
                continue
            arrs = {"code": arrs["code"][keep], "ts": ts[keep],
                    "flags": arrs["flags"][keep],
                    "cols": arrs["cols"][:, keep]}
            lay = [tuple(x) for x in meta["lay"][side]]
            self._stores[side].insert_sorted(
                arrs["code"].astype(np.int64), arrs["ts"],
                self._rows_from_planes(lay, arrs, n))

