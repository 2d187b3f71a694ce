"""The window-state lattice: device state + the jitted micro-batch step.

This is the hot path. The reference's equivalent is the per-record
aggregate processor (hstream-processing TimeWindowedStream.hs:82-103: per
record compute `windowsFor ts`, drop if past grace, get/agg/put a KV store
keyed by serialized (key, window)). Here the same semantics are one fused
scatter pass over a dense state lattice:

    state[plane][key_id, slot, ...]     slot = (win_start // advance) % W

W (hstream_tpu.engine.window.num_slots) covers every window that can still
receive records given the grace period, so a slot is always closed and
reset by the host watermark loop before it could be reused — `slot_start`
tracks the window start currently occupying each slot.

Late records (win_end + grace <= watermark, the reference's
`observedStreamTime` check at TimeWindowedStream.hs:92) are masked out and
scattered to a dropped out-of-bounds row (`mode="drop"`).

All accumulator updates are commutative monoid ops (add / min / max /
register-max / bin-add), so partial lattices from different chips merge
exactly — the basis for the data-parallel sharding in hstream_tpu.parallel.

Watermark lives on the HOST, not in device state: the step function is a
pure scatter-aggregation with no device->host sync; the host decides when
to call extract/reset for closed slots (rare, off the hot path).

Device time is int32 ms relative to a per-query epoch; `rebase` shifts
`slot_start` when the host re-anchors the epoch.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Mapping, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from hstream_tpu.engine.plan import AggKind, AggSpec
from hstream_tpu.engine.sketches import (
    HLLConfig,
    QuantileConfig,
    hll_estimate,
    hll_update_indices,
    quantile_bin,
    quantile_estimate,
)
from hstream_tpu.engine.window import FixedWindow, num_slots

NEG_INF = jnp.float32(-jnp.inf)
POS_INF = jnp.float32(jnp.inf)
EMPTY_START = -(1 << 31)  # slot_start sentinel for "slot unoccupied"

# The served path's programs, as a profiler trace names them (`jit_` +
# the jitted function's name). A trace reduction finds a program by
# this name, so each is pinned here and held to its function by a test
# (tests/test_host_timeline.py): rename the function and the constant
# together, and say so to whoever reads traces.
STEP_PROGRAM = "jit_step"                    # build_step_encoded / _packed
CLOSE_PROGRAM = "jit_extract_and_reset"      # build_extract_reset_slots
# the close of a plan that keeps a window's extreme groups (plan.WindowTop)
TOP_CLOSE_PROGRAM = "jit_extract_top_and_reset"  # build_extract_top_reset_slots
PEEK_PROGRAM = "jit_peek_slots"              # build_extract_slots: it alone
# the device session lattice's three (record mode)
SESSION_STEP_PROGRAM = "jit_session_step"        # session_step_kernel
SESSION_EXTRACT_PROGRAM = "jit_session_extract"  # session_extract_kernel
SESSION_REMAP_PROGRAM = "jit_session_remap"      # session_remap_kernel
# the window join's two (JOIN ... WITHIN WINDOW, engine/join.py): the
# fused probe + insert + inner step of a batch, and the eviction of
# closed windows from both stores. The interval join's programs keep
# their names (jit_probe_insert_step, jit_evict)
WINDOW_JOIN_STEP_PROGRAM = "jit_window_join_step"    # join_probe_insert_step
WINDOW_JOIN_EVICT_PROGRAM = "jit_window_join_evict"  # join_evict

# `jax.named_scope` of each aggregate's scatter inside the step (op
# metadata only: the computation and its cache key do not change)
_AGG_SCOPE = {
    AggKind.COUNT: "count", AggKind.SUM: "sum", AggKind.AVG: "avg",
    AggKind.MIN: "minmax", AggKind.MAX: "minmax",
    AggKind.APPROX_COUNT_DISTINCT: "hll",
    AggKind.APPROX_QUANTILE: "quantile",
    AggKind.TOPK: "topk", AggKind.TOPK_DISTINCT: "topk",
}


@dataclass(frozen=True)
class LatticeSpec:
    """Static configuration the step function is specialized on."""

    n_keys: int
    window: FixedWindow | None          # None = windowless global group-by
    aggs: tuple[AggSpec, ...]
    hll: HLLConfig = HLLConfig()
    qcfg: QuantileConfig = QuantileConfig()
    # changelog tracking (EMIT CHANGES): when False the per-batch
    # `touched` scatter is skipped — one fewer memory pass per record
    track_touched: bool = True

    @property
    def n_slots(self) -> int:
        return 1 if self.window is None else num_slots(self.window)

    @property
    def windows_per_record(self) -> int:
        return 1 if self.window is None else self.window.windows_per_record


def _plane_name(i: int, agg: AggSpec) -> str:
    return f"a{i}_{agg.kind.value}"


_TOPK_KINDS = (AggKind.TOPK, AggKind.TOPK_DISTINCT)


def agg_width(agg: AggSpec) -> int:
    """Values per key this aggregate emits (k for TOPK, else 1)."""
    if agg.kind in _TOPK_KINDS:
        if agg.k is None or agg.k < 1:
            raise ValueError(f"{agg.kind.value} needs k >= 1, got {agg.k}")
        return agg.k
    return 1


def init_state(spec: LatticeSpec) -> dict[str, jnp.ndarray]:
    K, W = spec.n_keys, spec.n_slots
    state: dict[str, jnp.ndarray] = {
        "count": jnp.zeros((K, W), jnp.int32),
        "slot_start": jnp.full((W,), EMPTY_START, jnp.int32),
        "touched": jnp.zeros((K, W), jnp.bool_),
    }
    for i, agg in enumerate(spec.aggs):
        name = _plane_name(i, agg)
        if agg.kind == AggKind.COUNT_ALL:
            continue  # aliases the built-in `count` plane (same mask)
        if agg.kind == AggKind.COUNT:
            state[name] = jnp.zeros((K, W), jnp.int32)
        elif agg.kind == AggKind.SUM:
            state[name] = jnp.zeros((K, W), jnp.float32)
        elif agg.kind == AggKind.AVG:
            state[name] = jnp.zeros((K, W), jnp.float32)
            state[name + "_n"] = jnp.zeros((K, W), jnp.int32)  # non-null count
        elif agg.kind == AggKind.MIN:
            state[name] = jnp.full((K, W), POS_INF, jnp.float32)
        elif agg.kind == AggKind.MAX:
            state[name] = jnp.full((K, W), NEG_INF, jnp.float32)
        elif agg.kind == AggKind.APPROX_COUNT_DISTINCT:
            state[name] = jnp.zeros((K, W, spec.hll.m), jnp.int8)
        elif agg.kind == AggKind.APPROX_QUANTILE:
            state[name] = jnp.zeros((K, W, spec.qcfg.n_bins), jnp.int32)
        elif agg.kind in _TOPK_KINDS:
            # fixed-k plane of the current top values, kept sorted
            # descending; merging = concat + re-sort (see step)
            state[name] = jnp.full((K, W, agg_width(agg)), NEG_INF,
                                   jnp.float32)
        else:
            raise NotImplementedError(f"agg {agg.kind}")
    return state


ValueFn = Callable[[Mapping[str, jnp.ndarray]], jnp.ndarray]

# per-agg input: (value fn | None for COUNT(*), null-mask column key | None)
AggInput = tuple[ValueFn | None, str | None]


def build_step_fn(spec: LatticeSpec,
                  agg_inputs: list[AggInput],
                  filter_fn: ValueFn | None = None):
    """The micro-batch step, untraced (jit/shard_map applied by callers).

    step(state, watermark, key_ids i32[B], ts i32[B], valid bool[B],
         cols {name: [B]}) -> state'

    `agg_inputs[i]` is (value_fn, null_key): value_fn computes agg i's
    input column (None for COUNT(*)); null_key names a bool column in
    `cols` that is True where the input is SQL NULL (missing field).
    NULL and non-finite inputs do not contribute to COUNT(col) / SUM /
    AVG / MIN / MAX / sketches, matching SQL aggregate semantics.
    `filter_fn` is the WHERE mask. All are traced into the same jit.

    Out-of-range key ids (negative or >= n_keys) are dropped whenever
    `valid` is False for that record — the sharded wrapper
    (hstream_tpu.parallel) relies on this to mask out keys owned by other
    shards.
    """
    K, W = spec.n_keys, spec.n_slots
    n_per = spec.windows_per_record
    win = spec.window

    def step(state, watermark, key_ids, ts, valid, cols, slot_valid=None):
        # `slot_valid` (default: valid) masks the key-independent
        # slot_start update separately: the sharded wrapper passes the
        # pre-key-ownership mask here so every key shard computes the
        # SAME slot_start and the replicated out-spec is actually true.
        if slot_valid is None:
            slot_valid = valid
        if filter_fn is not None:
            f = filter_fn(cols)
            valid = valid & f
            slot_valid = slot_valid & f

        if win is None:
            starts = jnp.zeros((key_ids.shape[0], 1), jnp.int32)
            ok = valid[:, None]
            ok_slot = slot_valid[:, None]
            slots = jnp.zeros_like(starts)
        else:
            advance, size, grace = win.advance_ms, win.size_ms, win.grace_ms
            latest = ts - jnp.mod(ts, advance)
            offs = (jnp.arange(n_per, dtype=jnp.int32) * advance)[None, :]
            starts = latest[:, None] - offs                     # [B, n_per]
            late = (starts + (size + grace)) <= watermark
            in_range = ~late & (starts >= 0)
            ok = valid[:, None] & in_range
            ok_slot = slot_valid[:, None] & in_range
            slots = jnp.mod(starts // advance, W)

        flat_k = jnp.where(ok, key_ids[:, None], K).reshape(-1)  # K = OOB -> drop
        flat_s = jnp.where(ok, slots, 0).reshape(-1)
        flat_ok = ok.reshape(-1)
        flat_starts = starts.reshape(-1)

        out = dict(state)
        with jax.named_scope("count"):
            out["count"] = state["count"].at[flat_k, flat_s].add(
                flat_ok.astype(jnp.int32), mode="drop")
        out["slot_start"] = state["slot_start"].at[
            jnp.where(ok_slot.reshape(-1), slots.reshape(-1), W)].max(
            flat_starts, mode="drop")
        if spec.track_touched:
            out["touched"] = state["touched"].at[flat_k, flat_s].set(
                True, mode="drop")

        for i, agg in enumerate(spec.aggs):
            name = _plane_name(i, agg)
            vfn, null_key = agg_inputs[i]
            if agg.kind == AggKind.COUNT_ALL:
                continue  # reads the built-in `count` plane at finalize
            # one scope per aggregate: an xprof reader sees which
            # aggregate a fusion of the step belongs to
            with jax.named_scope(_AGG_SCOPE[agg.kind]):
                v = vfn(cols)                                        # [B]
                # input validity: not SQL NULL, and finite for float inputs
                input_ok = jnp.ones(v.shape, jnp.bool_)
                if null_key is not None:
                    input_ok = input_ok & ~cols[null_key]
                if jnp.issubdtype(v.dtype, jnp.floating):
                    input_ok = input_ok & jnp.isfinite(v)
                iok = flat_ok & jnp.repeat(input_ok, n_per)
                v_rep = jnp.repeat(v, n_per)
                if agg.kind == AggKind.COUNT:
                    out[name] = state[name].at[flat_k, flat_s].add(
                        iok.astype(jnp.int32), mode="drop")
                elif agg.kind == AggKind.SUM:
                    vals = jnp.where(iok, v_rep.astype(jnp.float32), 0.0)
                    out[name] = state[name].at[flat_k, flat_s].add(vals, mode="drop")
                elif agg.kind == AggKind.AVG:
                    vals = jnp.where(iok, v_rep.astype(jnp.float32), 0.0)
                    out[name] = state[name].at[flat_k, flat_s].add(vals, mode="drop")
                    out[name + "_n"] = state[name + "_n"].at[flat_k, flat_s].add(
                        iok.astype(jnp.int32), mode="drop")
                elif agg.kind == AggKind.MIN:
                    vals = jnp.where(iok, v_rep.astype(jnp.float32), POS_INF)
                    out[name] = state[name].at[flat_k, flat_s].min(vals, mode="drop")
                elif agg.kind == AggKind.MAX:
                    vals = jnp.where(iok, v_rep.astype(jnp.float32), NEG_INF)
                    out[name] = state[name].at[flat_k, flat_s].max(vals, mode="drop")
                elif agg.kind == AggKind.APPROX_COUNT_DISTINCT:
                    reg, rank = hll_update_indices(v, spec.hll)
                    reg_rep = jnp.repeat(reg, n_per)
                    rank_rep = jnp.where(iok, jnp.repeat(rank, n_per), 0)
                    out[name] = state[name].at[flat_k, flat_s, reg_rep].max(
                        rank_rep, mode="drop")
                elif agg.kind == AggKind.APPROX_QUANTILE:
                    b_rep = jnp.repeat(quantile_bin(v, spec.qcfg), n_per)
                    out[name] = state[name].at[flat_k, flat_s, b_rep].add(
                        iok.astype(jnp.int32), mode="drop")
                elif agg.kind in _TOPK_KINDS:
                    out[name] = _topk_step(
                        state[name], agg, spec,
                        jnp.where(iok, v_rep.astype(jnp.float32), NEG_INF),
                        flat_k, flat_s, iok)
                else:
                    raise NotImplementedError(agg.kind)
        return out

    return step


def _topk_step(plane, agg: AggSpec, spec: LatticeSpec, vals, flat_k,
               flat_s, ok):
    """Fold one batch into a TOPK plane [K, W, k].

    Batch-local top-k per (key, slot) via ONE lexicographic device sort
    (segment id asc, value desc) + segmented ranking, scattered into a
    scratch plane; then the scratch merges with the stored plane by
    concat + re-sort along the k axis — top-k of a union is a
    commutative monoid, so the fold order never matters."""
    K, W = spec.n_keys, spec.n_slots
    kk = agg_width(agg)
    seg = jnp.where(ok, flat_k * W + flat_s, K * W).astype(jnp.int32)
    sseg, sneg = jax.lax.sort((seg, -vals), num_keys=2)
    sval = -sneg
    idx = jnp.arange(sseg.shape[0], dtype=jnp.int32)
    first = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), sseg[1:] != sseg[:-1]])
    if agg.kind == AggKind.TOPK_DISTINCT:
        # count only the first record of each (segment, value) run
        newval = first | jnp.concatenate(
            [jnp.ones((1,), jnp.bool_), sval[1:] != sval[:-1]])
        c = jnp.cumsum(newval.astype(jnp.int32))
        base = jax.lax.cummax(
            jnp.where(first, c - newval.astype(jnp.int32), 0))
        rank = jnp.where(newval, c - 1 - base, kk)
    else:
        seg_start = jax.lax.cummax(jnp.where(first, idx, 0))
        rank = idx - seg_start
    keep = (rank < kk) & (sseg < K * W) & (sval > NEG_INF)
    kf = jnp.where(keep, sseg // W, K)
    sf = jnp.where(keep, sseg % W, 0)
    rf = jnp.where(keep, rank, 0)
    scratch = jnp.full((K, W, kk), NEG_INF, jnp.float32)
    scratch = scratch.at[kf, sf, rf].set(
        jnp.where(keep, sval, NEG_INF), mode="drop")
    comb = jnp.concatenate([plane, scratch], axis=-1)
    comb = -jnp.sort(-comb, axis=-1)
    if agg.kind == AggKind.TOPK_DISTINCT:
        dup = jnp.concatenate(
            [jnp.zeros(comb.shape[:-1] + (1,), jnp.bool_),
             comb[..., 1:] == comb[..., :-1]], axis=-1)
        comb = jnp.where(dup, NEG_INF, comb)
        comb = -jnp.sort(-comb, axis=-1)
    return comb[..., :kk]


# ---- packed batch transport ------------------------------------------------
#
# Every host->device transfer pays a fixed dispatch cost, so the executor
# ships each micro-batch as ONE int32 buffer [3 + n_cols, B]:
#   row 0: key ids        row 1: ts (relative ms)
#   row 2: flag bits — bit 0 valid, bit 1+j = null mask of the j-th
#          null-tracked aggregate
#   row 3+i: the i-th needed column (f32 bitcast / i32 / bool as 0-1)
# Layout is a hashable tuple of (col_name, "f32"|"i32"|"bool").

ColLayout = tuple[tuple[str, str], ...]


def layout_tag(ctype) -> str:
    from hstream_tpu.engine.types import ColumnType

    return {ColumnType.FLOAT: "f32", ColumnType.INT: "i32",
            ColumnType.BOOL: "bool", ColumnType.STRING: "i32"}[ctype]


def pack_batch_host(capacity: int, n: int, key_ids, ts_rel, valid,
                    cols: Mapping[str, np.ndarray],
                    null_masks: list[np.ndarray | None],
                    layout: ColLayout) -> np.ndarray:
    """Assemble the packed int32 batch buffer on host (vectorized copies).
    `valid` may be None (all n records valid)."""
    buf = np.zeros((3 + len(layout), capacity), dtype=np.int32)
    buf[0, :n] = key_ids[:n]
    buf[1, :n] = ts_rel[:n]
    if valid is None:
        flags = np.ones(n, dtype=np.int32)  # bit0: valid
    else:
        flags = valid[:n].astype(np.int32)
    for j, nm in enumerate(null_masks):
        if nm is not None:
            flags |= nm[:n].astype(np.int32) << (1 + j)
    buf[2, :n] = flags
    for i, (name, tag) in enumerate(layout):
        src = cols[name]
        if tag == "f32":
            buf[3 + i, :n] = src[:n].astype(np.float32, copy=False).view(
                np.int32)
        elif tag == "bool":
            buf[3 + i, :n] = src[:n].astype(np.int32)
        else:
            buf[3 + i, :n] = src[:n]
    return buf


def unpack_batch_device(packed, layout: ColLayout, null_keys):
    """(key_ids, ts, valid, cols) from the packed buffer, traced."""
    key_ids = packed[0]
    ts = packed[1]
    flags = packed[2]
    valid = (flags & 1) != 0
    cols = {}
    for i, (name, tag) in enumerate(layout):
        row = packed[3 + i]
        if tag == "f32":
            cols[name] = jax.lax.bitcast_convert_type(row, jnp.float32)
        elif tag == "bool":
            cols[name] = row != 0
        else:
            cols[name] = row
    for j, nk in enumerate(nk for nk in null_keys if nk is not None):
        cols[nk] = ((flags >> (1 + j)) & 1) != 0
    return key_ids, ts, valid, cols


def build_step_packed(spec: LatticeSpec, agg_inputs: list[AggInput],
                      filter_fn: ValueFn | None, layout: ColLayout,
                      null_keys) -> Callable:
    """step(state, watermark, packed i32[3+n_cols, B]) -> state'."""
    base = build_step_fn(spec, agg_inputs, filter_fn)

    def step(state, watermark, packed):
        key_ids, ts, valid, cols = unpack_batch_device(packed, layout,
                                                       null_keys)
        return base(state, watermark, key_ids, ts, valid, cols)

    return step


def build_step_encoded(spec: LatticeSpec, agg_inputs: list[AggInput],
                       filter_fn: ValueFn | None, combo, cap: int,
                       null_keys) -> Callable:
    """step(state, watermark, n, bases i32[streams], words u32) -> state'
    over the bit-packed transport (engine.transport): the column decode
    is traced into the same jit as the scatter, so XLA fuses unpack
    shifts with the aggregation. Null-flag streams absent from the wire
    are constant-folded to all-False."""
    from hstream_tpu.engine import transport as tp

    base = build_step_fn(spec, agg_inputs, filter_fn)

    def step(state, watermark, n, bases, words):  # STEP_PROGRAM
        with jax.named_scope("wire_decode"):
            key_ids, ts, valid, cols = tp.decode_batch(words, combo, cap,
                                                       n, bases)
            for nk in null_keys:
                if nk is not None and nk not in cols:
                    cols[nk] = jnp.zeros((cap,), jnp.bool_)
        return base(state, watermark, key_ids, ts, valid, cols)

    return step


def finalize_column(spec: LatticeSpec, state_col: Mapping[str, jnp.ndarray]):
    """Finalize one slot column {plane: [K, ...]} -> {out_name: [K] f32}."""
    outs = {}
    for i, agg in enumerate(spec.aggs):
        name = _plane_name(i, agg)
        if agg.kind == AggKind.COUNT_ALL:
            outs[agg.out_name] = state_col["count"].astype(jnp.float32)
        elif agg.kind == AggKind.AVG:
            denom = jnp.maximum(state_col[name + "_n"].astype(jnp.float32), 1.0)
            outs[agg.out_name] = state_col[name] / denom
        elif agg.kind == AggKind.APPROX_COUNT_DISTINCT:
            outs[agg.out_name] = hll_estimate(state_col[name], spec.hll)
        elif agg.kind == AggKind.APPROX_QUANTILE:
            outs[agg.out_name] = quantile_estimate(
                state_col[name], agg.quantile or 0.5, spec.qcfg)
        elif agg.kind == AggKind.MIN:
            outs[agg.out_name] = jnp.where(
                state_col["count"] > 0, state_col[name], 0.0)
        elif agg.kind == AggKind.MAX:
            outs[agg.out_name] = jnp.where(
                state_col["count"] > 0, state_col[name], 0.0)
        elif agg.kind in _TOPK_KINDS:
            outs[agg.out_name] = state_col[name]  # [K, k] passthrough
        else:
            outs[agg.out_name] = state_col[name].astype(jnp.float32)
    return outs


def _agg_out_rows(spec: LatticeSpec, outs):
    """Flatten finalized agg outputs into bitcast int32 rows — ONE place
    defines the row layout (width-k aggs contribute k rows); the unpack
    inverse is _unpack_agg_rows."""
    for agg in spec.aggs:
        o = outs[agg.out_name].astype(jnp.float32)
        if agg.kind in _TOPK_KINDS:
            for j in range(agg_width(agg)):
                yield jax.lax.bitcast_convert_type(o[:, j], jnp.int32)
        else:
            yield jax.lax.bitcast_convert_type(o, jnp.int32)


def _unpack_agg_rows(spec: LatticeSpec, rows2d: np.ndarray):
    """Inverse of _agg_out_rows: int32 rows -> {name: [N] or [N, k] f32}."""
    outs = {}
    row = 0
    for agg in spec.aggs:
        w = agg_width(agg)
        if agg.kind in _TOPK_KINDS:
            outs[agg.out_name] = np.stack(
                [rows2d[row + j].view(np.float32) for j in range(w)],
                axis=1)
        else:
            outs[agg.out_name] = rows2d[row].view(np.float32)
        row += w
    return outs


def pack_extract_rows(spec: LatticeSpec, count, win_start, outs):
    """Stack (count, win_start, finalized agg outputs) into ONE int32
    buffer [2 + sum(widths), K] (float outputs bitcast) so the host pays
    a single device->host fetch per drain instead of one per plane —
    host sync count, not bytes, dominates drain cost. A width-k agg
    (TOPK) contributes k rows."""
    k = count.shape[0]
    rows = [count.astype(jnp.int32),
            jnp.broadcast_to(jnp.asarray(win_start, jnp.int32), (k,))]
    rows.extend(_agg_out_rows(spec, outs))
    return jnp.stack(rows)


def stack_pow2(bufs):
    """jnp.stack with the depth padded to a power of two (zero-filled
    tail buffers). Each distinct stack depth is its own XLA program, so
    stacking raw pending-counts on the drain paths compiled one
    executable per count ever seen — found live by the RetraceGuard
    server drive (ISSUE 7). Padding converges the depths to a handful;
    callers zip the fetched stack against the UNPADDED group, and a
    zero buffer decodes as zero rows anyway (row0 col0 == 0)."""
    p = 1
    while p < len(bufs):
        p *= 2
    bufs = list(bufs)
    if p != len(bufs):
        bufs.extend([jnp.zeros_like(bufs[0])] * (p - len(bufs)))
    return jnp.stack(bufs)


def unpack_extract_rows(spec: LatticeSpec, packed: np.ndarray):
    """(count [K], win_start [K], {name: [K] or [K, width] f32}) from
    pack_extract_rows."""
    count = packed[0]
    win_start = packed[1]
    return count, win_start, _unpack_agg_rows(spec, packed[2:])


def gather_extract_batch(spec: LatticeSpec, packed: np.ndarray,
                         widx: np.ndarray, kids: np.ndarray):
    """Columnar gather over a batched extract buffer [P, 2+rows, K]:
    for the selected (window, key) pairs, return {out_name: [n] f64 or
    [n, width] f32} — the vectorized inverse of per-row _agg_row
    decoding. The fancy-index gather yields contiguous int32 vectors,
    so the f32 bitcast is a view, not a copy-per-cell."""
    outs: dict[str, np.ndarray] = {}
    row = 2
    for agg in spec.aggs:
        w = agg_width(agg)
        if agg.kind in _TOPK_KINDS:
            outs[agg.out_name] = np.stack(
                [np.ascontiguousarray(packed[widx, row + j, kids])
                 .view(np.float32) for j in range(w)], axis=1)
        else:
            outs[agg.out_name] = np.ascontiguousarray(
                packed[widx, row, kids]).view(np.float32).astype(
                np.float64)
        row += w
    return outs


def build_extract_slot(spec: LatticeSpec):
    """extract(state, slot) -> packed int32 [2+n_aggs, K] (see
    pack_extract_rows): finalized values for one slot column, fetched by
    the host in a single transfer when the watermark closes a window.
    Kept as the per-slot reference kernel (equivalence tests); the close
    path itself dispatches build_extract_reset_slots."""

    @jax.jit
    def extract(state, slot):
        col = {k: v[:, slot] for k, v in state.items()
               if k not in ("slot_start", "touched")}
        outs = finalize_column(spec, col)
        return pack_extract_rows(spec, col["count"],
                                 state["slot_start"][slot], outs)

    return extract


def build_reset_slot(spec: LatticeSpec):
    @jax.jit
    def reset(state, slot):
        out = dict(state)
        for i, agg in enumerate(spec.aggs):
            if agg.kind == AggKind.COUNT_ALL:
                continue  # no own plane; `count` below resets it
            name = _plane_name(i, agg)
            out[name] = state[name].at[:, slot].set(init_value(agg))
            if agg.kind == AggKind.AVG:
                out[name + "_n"] = state[name + "_n"].at[:, slot].set(0)
        out["count"] = state["count"].at[:, slot].set(0)
        out["touched"] = state["touched"].at[:, slot].set(False)
        out["slot_start"] = state["slot_start"].at[slot].set(EMPTY_START)
        return out

    return reset


# ---- fused multi-slot close -------------------------------------------------
#
# A close cycle may find many windows due at once (hopping windows, a
# watermark jump, a deferred-close drain). Dispatching extract+reset per
# slot costs 2 kernel launches + 1 blocking device->host fetch PER
# WINDOW. The fused kernels below take
# a PADDED slot vector (entries < 0 are padding) so one dispatch covers
# every due window and the host pays ONE fetch for the whole cycle; the
# extract is vmapped over slots and the reset is folded into the same
# jit (it reads the pre-reset state, so extract values are unaffected).
#
# The one-dispatch-one-fetch economics are ENFORCED, not just
# documented: the executor drivers declare `# contract: dispatches<=N
# fetches<=M` budgets checked by the tools/analyze dispatch pass, the
# lru_cache'd factories here are the retrace pass's sanctioned
# memoization shape, and the runtime RetraceGuard (the tier-1
# test_retrace_guard_zero_steady_state_* tests) asserts zero
# steady-state recompiles through these kernels.


def _reset_slots_tree(spec: LatticeSpec, state, rs):
    """Reset the slot columns named by rs (int32 [P]; out-of-range
    entries drop) in every plane — shared by the fused extract+reset and
    the reset-only kernel."""
    out = dict(state)
    for i, agg in enumerate(spec.aggs):
        if agg.kind == AggKind.COUNT_ALL:
            continue  # no own plane; `count` below resets it
        name = _plane_name(i, agg)
        out[name] = state[name].at[:, rs].set(init_value(agg), mode="drop")
        if agg.kind == AggKind.AVG:
            out[name + "_n"] = state[name + "_n"].at[:, rs].set(
                0, mode="drop")
    out["count"] = state["count"].at[:, rs].set(0, mode="drop")
    out["touched"] = state["touched"].at[:, rs].set(False, mode="drop")
    out["slot_start"] = state["slot_start"].at[rs].set(
        EMPTY_START, mode="drop")
    return out


def _extract_slots_packed(spec: LatticeSpec, state, slots, kids=None):
    """Vmapped extract of the slot columns named by `slots` (padding
    entries < 0 produce all-zero packed rows, so the host decode's
    count>0 filter skips them) -> packed int32 [P, 2+rows, K]. With
    `kids` (i32[Q]) only those keys' rows are read and finalized:
    packed int32 [P, 2+rows, Q], cell q the key `kids[q]`."""
    valid = slots >= 0
    safe = jnp.where(valid, slots, 0)
    keys = slice(None) if kids is None else kids

    def one(slot):
        col = {k: v[keys, slot] for k, v in state.items()
               if k not in ("slot_start", "touched")}
        with jax.named_scope("finalize"):
            outs = finalize_column(spec, col)
        return pack_extract_rows(spec, col["count"],
                                 state["slot_start"][slot], outs)

    packed = jax.vmap(one)(safe)
    return jnp.where(valid[:, None, None], packed, 0)


def build_extract_reset_slots(spec: LatticeSpec):
    """extract_and_reset(state, slots i32[P]) ->
    (state', packed i32[P, 2+rows, K]).

    One device dispatch closes every due window: the vmapped extract
    finalizes each requested slot column and the reset of those same
    slots rides in the same jit (XLA schedules both off the pre-reset
    state). Padding entries (slot < 0) extract zeros and reset nothing."""

    @jax.jit
    def extract_and_reset(state, slots):  # CLOSE_PROGRAM
        packed = _extract_slots_packed(spec, state, slots)
        rs = jnp.where(slots >= 0, slots, spec.n_slots)  # OOB -> drop
        return _reset_slots_tree(spec, state, rs), packed

    return extract_and_reset


# ---- the top across groups at a close ---------------------------------------
#
# A plan with a `WindowTop` (SQL: `QUALIFY agg >= MAX(agg) OVER
# (PARTITION BY winStart, winEnd)`) keeps, of every closing window, the
# groups whose aggregate is the window's extreme. The extreme is taken
# over the key axis of the closing slot ON THE DEVICE, inside the fused
# close, so the host fetches and decodes the rows that survive (a few)
# and not every key's row (`packed i32[P, 2+rows, K]`).

TOP_ROWS = 256    # surviving rows a top close ships per window
_TOP_BLOCK = 256  # keys a block: survivors are looked for block by block
_TOP_BLOCKS = 64  # blocks with a survivor that are looked into


def top_rows(n_keys: int) -> int:
    """Width of the survivors' buffer: the header needs four cells."""
    return max(4, min(n_keys, TOP_ROWS))


def _first_true(mask, n_keys: int, width: int):
    """(the first `width` positions where `mask` [K] is True, ascending
    and zero-filled; how many of them are real). In two levels, so that
    no prefix sum runs over the whole key axis (at 2^20 keys one takes
    the chip's compiler a quarter of a minute, and a table that doubles
    its way there compiles it at every size): the blocks of `_TOP_BLOCK`
    keys that hold a True, the first `_TOP_BLOCKS` of them, and the
    positions inside those. What lies past either bound is not shown,
    which the count says."""
    block = _TOP_BLOCK if n_keys % _TOP_BLOCK == 0 else n_keys
    n_blocks = n_keys // block
    look = min(n_blocks, _TOP_BLOCKS)
    blocks = mask.reshape(n_blocks, block)
    holds = jnp.any(blocks, axis=1)
    chosen = jnp.nonzero(holds, size=look, fill_value=0)[0]
    real = jnp.arange(look) < jnp.sum(holds.astype(jnp.int32))
    sub = jnp.where(real[:, None], blocks[chosen], False).reshape(-1)
    at = jnp.nonzero(sub, size=width, fill_value=0)[0]
    shown = jnp.minimum(jnp.sum(sub.astype(jnp.int32)), width)
    pos = chosen[at // block] * block + at % block
    return jnp.where(jnp.arange(width) < shown, pos, 0), shown


def _top_values(spec: LatticeSpec, agg: AggSpec, col, outs):
    """The column the extreme is taken over: a count plane as the int32
    it is (exact past 2^24), every other aggregate as finalized."""
    if agg.kind == AggKind.COUNT_ALL:
        return col["count"]
    if agg.kind == AggKind.COUNT:
        return col[_plane_name(spec.aggs.index(agg), agg)]
    return outs[agg.out_name]


def build_extract_top_reset_slots(spec: LatticeSpec, top_agg: str,
                                  extreme: str):
    """extract_top_and_reset(state, slots i32[P]) ->
    (state', top i32[P, 2+rows, R], full i32[P, 2+rows, K]).

    The fused close of `build_extract_reset_slots`, with the window's
    extreme of aggregate `top_agg` ("max" | "min") taken over the groups
    that hold a count in the closing slot. `top` is what the host
    fetches: row 0 the header (cell 0 the groups that reach the extreme,
    cell 1 the groups that held a count, cell 2 the window's start,
    cell 3 how many of the first are shown), row 1 the key ids of those
    shown, rows 2+ their finalized aggregates in `_agg_out_rows` order
    (`_first_true` says which are shown: at most `top_rows(K)`). `full`
    is the whole packed column with the count of every other group
    zeroed: it stays on the device unless more groups tie than `top`
    shows (the host then fetches it, and counts that it did), so a tie
    is never cut. Padding entries (slot < 0) give zeros and reset
    nothing."""
    agg = next(a for a in spec.aggs if a.out_name == top_agg)
    if agg.kind in _TOPK_KINDS:
        raise ValueError(f"{agg.kind.value} has no single value a "
                         "group to take an extreme of")
    if extreme not in ("max", "min"):
        raise ValueError(f"extreme {extreme!r}")
    width = top_rows(spec.n_keys)

    def one(state, slot):
        col = {k: v[:, slot] for k, v in state.items()
               if k not in ("slot_start", "touched")}
        with jax.named_scope("finalize"):
            outs = finalize_column(spec, col)
        with jax.named_scope("top"):
            live = col["count"] > 0
            vals = _top_values(spec, agg, col, outs)
            if jnp.issubdtype(vals.dtype, jnp.floating):
                worst = -jnp.inf if extreme == "max" else jnp.inf
            else:
                info = jnp.iinfo(vals.dtype)
                worst = info.min if extreme == "max" else info.max
            masked = jnp.where(live, vals, worst)
            best = jnp.max(masked) if extreme == "max" \
                else jnp.min(masked)
            keep = live & (vals == best)
            n_keep = jnp.sum(keep.astype(jnp.int32))
            n_groups = jnp.sum(live.astype(jnp.int32))
            kids, shown = _first_true(keep, spec.n_keys, width)
            ok = jnp.arange(width) < shown
            win_start = state["slot_start"][slot]
            header = jnp.zeros((width,), jnp.int32).at[0].set(
                n_keep).at[1].set(n_groups).at[2].set(win_start).at[
                3].set(shown)
            rows = [header, kids.astype(jnp.int32)]
            rows.extend(jnp.where(ok, r[kids], 0)
                        for r in _agg_out_rows(spec, outs))
        full = pack_extract_rows(
            spec, jnp.where(keep, col["count"], 0), win_start, outs)
        return jnp.stack(rows), full

    @jax.jit
    def extract_top_and_reset(state, slots):  # TOP_CLOSE_PROGRAM
        valid = slots >= 0
        top, full = jax.vmap(lambda s: one(state, s))(
            jnp.where(valid, slots, 0))
        top = jnp.where(valid[:, None, None], top, 0)
        full = jnp.where(valid[:, None, None], full, 0)
        rs = jnp.where(valid, slots, spec.n_slots)  # OOB -> drop
        return _reset_slots_tree(spec, state, rs), top, full

    return extract_top_and_reset


@functools.lru_cache(maxsize=512)
def compiled_top_close(spec: LatticeSpec, top_agg: str,
                       extreme: str) -> Callable:
    """Shared, cached top close of one (spec, aggregate, extreme)."""
    return build_extract_top_reset_slots(spec, top_agg, extreme)


def unpack_top_rows(spec: LatticeSpec, top: np.ndarray):
    """One window's survivors from `top[p]`: (groups that reach the
    extreme, groups that held a count, key ids [n], {name: [n] or
    [n, width] f32}), n the survivors shown: fewer than the first
    number where more tie than the buffer shows."""
    n_keep, n_groups, n = int(top[0, 0]), int(top[0, 1]), int(top[0, 3])
    return n_keep, n_groups, top[1, :n], _unpack_agg_rows(
        spec, top[2:, :n])


def build_extract_slots(spec: LatticeSpec):
    """peek_slots(state, slots i32[P]) -> packed i32[P, 2+rows, K]: the
    read-only half of the fused close — one dispatch serves a pull
    query / view peek over every open window. With `kids` i32[Q], the
    peek of a pull that pins its group key: packed i32[P, 2+rows, Q],
    those keys' rows alone. One function, so both are PEEK_PROGRAM in a
    trace."""

    @jax.jit
    def peek_slots(state, slots, kids=None):  # PEEK_PROGRAM
        return _extract_slots_packed(spec, state, slots, kids)

    return peek_slots


def build_reset_slots(spec: LatticeSpec):
    """reset(state, slots i32[P]) -> state': batched reset without the
    extract (EMIT CHANGES mode closes emit nothing — the changelog
    already carried the final values)."""

    @jax.jit
    def reset(state, slots):
        rs = jnp.where(slots >= 0, slots, spec.n_slots)
        return _reset_slots_tree(spec, state, rs)

    return reset


def init_value(agg: AggSpec):
    if agg.kind == AggKind.MIN:
        return POS_INF
    if agg.kind in (AggKind.MAX,) + _TOPK_KINDS:
        return NEG_INF
    return 0


def pack_touched_rows(spec: LatticeSpec, n, kidx, win_start, outs,
                      max_out: int):
    """ONE int32 buffer [3 + sum(widths), max_out]: row0 col0 = n,
    row1 = key ids, row2 = win starts, rows 3+ = bitcast float agg
    outputs (width-k aggs contribute k rows)."""
    rows = [jnp.zeros((max_out,), jnp.int32).at[0].set(n),
            kidx.astype(jnp.int32), win_start.astype(jnp.int32)]
    rows.extend(_agg_out_rows(spec, outs))
    return jnp.stack(rows)


def unpack_touched_rows(spec: LatticeSpec, packed: np.ndarray):
    """(n, kidx [n], win_start [n], {name: [n] or [n, width] f32})."""
    n = int(packed[0, 0])
    outs = _unpack_agg_rows(spec, packed[3:, :n])
    return n, packed[1, :n], packed[2, :n], outs


def build_extract_touched(spec: LatticeSpec, max_out: int):
    """Changelog extraction for EMIT CHANGES: all (key, window) pairs
    touched since the last call, with finalized current values.

    extract(state) -> (state with touched cleared,
                       packed int32 [3+n_aggs, max_out] — see
                       pack_touched_rows)

    Deviation from the reference (documented): the reference emits one
    change per input record (TimeWindowedStream.hs:101); a batched engine
    emits one change per touched (key, window) per micro-batch."""

    @jax.jit
    def extract(state):
        mask = state["touched"]
        n = jnp.sum(mask.astype(jnp.int32))
        kidx, sidx = jnp.nonzero(mask, size=max_out, fill_value=0)
        valid = jnp.arange(max_out) < n
        col = {k: v[kidx, sidx] for k, v in state.items()
               if k not in ("slot_start", "touched")}
        outs = finalize_column(spec, col)
        win_start = jnp.where(valid, state["slot_start"][sidx], 0)
        out_state = dict(state)
        out_state["touched"] = jnp.zeros_like(mask)
        return out_state, pack_touched_rows(spec, n, kidx, win_start,
                                            outs, max_out)

    return extract


def plane_merge_kinds(spec: LatticeSpec) -> dict[str, str]:
    """Monoid merge op per state plane ("sum" | "min" | "max").

    Every accumulator is a commutative monoid, so partial lattices from
    different chips (or a restored checkpoint plus fresh state) combine
    exactly with these elementwise ops. `touched` merges with max (logical
    or); `slot_start` with max (EMPTY_START is the identity)."""
    kinds = {"count": "sum", "touched": "max", "slot_start": "max"}
    for i, agg in enumerate(spec.aggs):
        name = _plane_name(i, agg)
        if agg.kind == AggKind.COUNT_ALL:
            continue  # no own plane
        if agg.kind == AggKind.MIN:
            kinds[name] = "min"
        elif agg.kind in (AggKind.MAX, AggKind.APPROX_COUNT_DISTINCT):
            kinds[name] = "max"
        elif agg.kind in _TOPK_KINDS:
            # NOT elementwise: merging two top-k planes needs
            # concat+sort; sharded execution rejects these specs
            kinds[name] = "topk"
        else:
            kinds[name] = "sum"
            if agg.kind == AggKind.AVG:
                kinds[name + "_n"] = "sum"
    return kinds


def compile_agg_inputs(spec: LatticeSpec, schema) -> tuple[
        list[AggInput], tuple[str | None, ...]]:
    """Device value-fns + null-mask column keys for each aggregate."""
    from hstream_tpu.engine.expr import compile_device

    agg_inputs: list[AggInput] = []
    null_keys: list[str | None] = []
    for i, agg in enumerate(spec.aggs):
        if agg.input is None:
            agg_inputs.append((None, None))
            null_keys.append(None)
        else:
            key = f"__null_a{i}"
            agg_inputs.append((compile_device(agg.input, schema), key))
            null_keys.append(key)
    return agg_inputs, tuple(null_keys)


class CompiledLattice(NamedTuple):
    step: Callable
    extract_slot: Callable      # per-slot reference kernels (tests)
    reset_slot: Callable
    extract_reset_slots: Callable  # fused multi-slot close (one dispatch)
    extract_slots: Callable        # batched read-only extract (peek)
    reset_slots: Callable          # batched reset (EMIT CHANGES closes)
    extract_touched: Callable
    null_keys: tuple[str | None, ...]  # per agg: the __null_a{i} cols key


@functools.lru_cache(maxsize=512)
def compiled(spec: LatticeSpec, schema, filter_expr, max_out: int,
             layout: ColLayout) -> CompiledLattice:
    """Shared, cached compilation of all lattice functions for a given
    (spec, schema, filter, layout) — executors with identical shapes reuse
    the same jitted callables (and therefore the same XLA executables).
    Requires expressions with string literals pre-encoded
    (expr.encode_strings)."""
    from hstream_tpu.engine.expr import compile_device

    agg_inputs, null_keys = compile_agg_inputs(spec, schema)
    filter_fn = compile_device(filter_expr, schema) if filter_expr is not None \
        else None
    return CompiledLattice(
        step=jax.jit(build_step_packed(spec, agg_inputs, filter_fn,
                                       layout, null_keys)),
        extract_slot=build_extract_slot(spec),
        reset_slot=build_reset_slot(spec),
        extract_reset_slots=build_extract_reset_slots(spec),
        extract_slots=build_extract_slots(spec),
        reset_slots=build_reset_slots(spec),
        extract_touched=build_extract_touched(spec, max_out),
        null_keys=null_keys,
    )


@functools.lru_cache(maxsize=2048)
def compiled_encoded_step(spec: LatticeSpec, schema, filter_expr,
                          combo, cap: int, *,
                          donate_words: bool = False) -> Callable:
    """Cached jit of the v2-transport step for one encoding combo. The
    state argument is donated: steady-state ingest re-uses the lattice
    buffers in place instead of allocating a fresh copy per micro-batch.
    donate_words=True additionally donates the uploaded wire buffer (arg
    4) — the ingest pipeline uses each staged buffer exactly once, so
    donating it recycles the device staging slot for the next upload;
    callers that re-dispatch one staged batch (kernel microbenchmarks)
    must keep the default."""
    from hstream_tpu.engine.expr import compile_device

    agg_inputs, null_keys = compile_agg_inputs(spec, schema)
    filter_fn = compile_device(filter_expr, schema) if filter_expr is not None \
        else None
    # donation is a TPU/GPU optimization; CPU (the test backend) ignores
    # it with a warning per call, so only request it where it helps
    donate: tuple[int, ...] = ()
    if jax.default_backend() != "cpu":
        donate = (0, 4) if donate_words else (0,)
    return jax.jit(build_step_encoded(spec, agg_inputs, filter_fn, combo,
                                      cap, null_keys),
                   donate_argnums=donate)


# ---- interval-join lattice kernels ------------------------------------------
#
# The TPU analogue of the reference's timestamped two-sided KV stores
# (Stream.hs:267-300 joinStreamProcessor): each join side is a device-
# resident flat store of (key code, ts, packed columns) kept sorted by
# (code, ts), and one fused jitted kernel per micro-batch
#   * probes the OTHER side over each record's within-interval span
#     [ts - within, ts + within] (a segmented two-sided bound over the
#     sorted store, computed by a stable merge-rank — see
#     _join_bounds), emitting matched pairs into ONE padded buffer, and
#   * inserts the (pre-sorted) batch into THIS side's store with one
#     2-key merge sort.
# Watermark eviction is a separate vmapped kernel over both sides
# (join_evict), dispatched by the host when retention advances; it also
# carries the epoch-rebase delta so the int32 relative-time space never
# overflows (the device restatement of _FlatIntervalStore's span
# guard — rebase instead of abort).
#
# Everything is int32 (no x64 dependence): ts is milliseconds relative
# to a host-managed join epoch, codes are the executor's dense join-key
# codes, column values are f32-bitcast/i32/bool/dict-id int32 rows, and
# per-entry null/present bits pack into one flags word (2 bits per
# stored column). One dispatch + one D2H fetch (the match buffer) per
# micro-batch, regardless of match count — match widths share compiled
# shapes via the same pow2 padding trick as the fused window close.
#
# Batch layout (int32 [4 + n_cols, bcap], host-packed, sorted by
# (code, ts)): row 0 code, row 1 ts_rel, row 2 inner key id, row 3
# flags, rows 4+ packed column values.
#
# Match buffer (int32 [5 + n_cols_mine + n_cols_other, match_cap]):
# row 0 header ([0] = true match total — may exceed match_cap, the
# host then re-probes at the next pow2 width), row 1 inner key id,
# row 2 joined ts (max of the pair, relative), row 3 probe-side flags,
# row 4 stored-side flags, rows 5+ probe-side then stored-side columns.

JOIN_SENT_CODE = (1 << 22)  # code sentinel: empty/evicted slots (> any
                            # live code — the executor compacts at 2^22)
JOIN_MAX_COLS = 14          # 2 bits (null, present) per column in one
                            # int32 flags word


def init_join_store(cap: int, n_cols: int) -> dict[str, jnp.ndarray]:
    """One empty join side: all slots carry the code sentinel."""
    return {
        "code": jnp.full((cap,), JOIN_SENT_CODE, jnp.int32),
        "ts": jnp.zeros((cap,), jnp.int32),
        "flags": jnp.zeros((cap,), jnp.int32),
        "cols": jnp.zeros((n_cols, cap), jnp.int32),
    }


def _join_bounds(store_code, store_ts, qcode, lo_ts, hi_ts):
    """Vectorized [lower, upper) bounds of each query's (code, ts)
    span in a store sorted by (code, ts) — int32-safe searchsorted over
    a 2-key space. ONE stable 3-key sort ranks both query sets among
    the store entries: a query landing at final position p with k
    queries (of either set) before it has exactly p - k store entries
    before it, which IS its bound. The tie-break tag orders lo-queries
    BEFORE equal-key store entries (lower bound) and hi-queries AFTER
    them (upper bound)."""
    cap = store_code.shape[0]
    bcap = qcode.shape[0]
    codes = jnp.concatenate([store_code, qcode, qcode])
    tss = jnp.concatenate([store_ts, lo_ts, hi_ts])
    tags = jnp.concatenate([jnp.ones((cap,), jnp.int32),
                            jnp.zeros((bcap,), jnp.int32),
                            jnp.full((bcap,), 2, jnp.int32)])
    pay = jnp.concatenate([jnp.full((cap,), 2 * bcap, jnp.int32),
                           jnp.arange(bcap, dtype=jnp.int32),
                           bcap + jnp.arange(bcap, dtype=jnp.int32)])
    _, _, _, spay = jax.lax.sort((codes, tss, tags, pay), num_keys=3)
    pos = jnp.arange(cap + 2 * bcap, dtype=jnp.int32)
    is_q = spay < 2 * bcap
    k = jnp.cumsum(is_q.astype(jnp.int32)) - 1
    bounds = jnp.zeros((2 * bcap,), jnp.int32).at[
        jnp.where(is_q, spay, 2 * bcap)].set(pos - k, mode="drop")
    return bounds[:bcap], bounds[bcap:]


def _join_match_arrays(other, batch, n, within, cutoff, bcap: int,
                       match_cap: int, owned=None, window: bool = False):
    """Shared probe core: expand the per-record [lower, upper) spans
    into padded match index arrays. Returns (total, rec, oidx, mvalid,
    jts) — rec indexes the probing batch, oidx the probed store.
    `window` (static): a window join, whose `within` operand is the
    window's size W and whose probe spans the record's own window
    [ts - ts % W, ts - ts % W + W); the host keeps the join epoch a
    multiple of W, so relative time cuts where absolute time does."""
    cap = other["code"].shape[0]
    bcode = batch[0]
    bts = batch[1]
    bvalid = (jnp.arange(bcap) < n) & (bcode < JOIN_SENT_CODE)
    if owned is not None:
        bvalid = bvalid & owned
    qcode = jnp.where(bvalid, bcode, JOIN_SENT_CODE)
    if window:
        lo_ts = bts - bts % within       # floor: relative ts may be < 0
        hi_ts = lo_ts + (within - 1)
    else:
        lo_ts, hi_ts = bts - within, bts + within
    lo_i, hi_i = _join_bounds(other["code"], other["ts"], qcode,
                              jnp.maximum(lo_ts, cutoff), hi_ts)
    cnt = jnp.where(bvalid, jnp.maximum(hi_i - lo_i, 0), 0)
    ccnt = jnp.cumsum(cnt)
    total = ccnt[-1]
    j = jnp.arange(match_cap, dtype=jnp.int32)
    rec = jnp.clip(jnp.searchsorted(ccnt, j, side="right"), 0, bcap - 1)
    mvalid = j < jnp.minimum(total, match_cap)
    oidx = lo_i[rec] + (j - (ccnt[rec] - cnt[rec]))
    oidx = jnp.where(mvalid, jnp.clip(oidx, 0, cap - 1), 0)
    jts = jnp.where(mvalid, jnp.maximum(bts[rec], other["ts"][oidx]), 0)
    return total, rec, oidx, mvalid, jts


def _join_probe(other, batch, n, within, cutoff, bcap: int,
                match_cap: int, n_cols_mine: int, owned=None,
                window: bool = False):
    """Probe `other` with the batch; emit the packed match buffer (see
    module comment). `cutoff` masks entries past retention out of the
    probe (the lower bound is max(ts - within, cutoff)): the host
    reference prunes its stores on every watermark advance, so the
    device store — which evicts lazily, for capacity only — must hide
    expired entries from matches to stay equivalent. `owned`
    (bool[bcap] or None) additionally masks which batch records this
    shard probes/inserts (key-sharded mirror)."""
    total, rec, oidx, mvalid, jts = _join_match_arrays(
        other, batch, n, within, cutoff, bcap, match_cap, owned, window)
    header = jnp.zeros((match_cap,), jnp.int32).at[0].set(total)
    rows = [header,
            jnp.where(mvalid, batch[2][rec], 0),                 # kid
            jts,
            jnp.where(mvalid, batch[3][rec], 0),                 # my flags
            jnp.where(mvalid, other["flags"][oidx], 0)]
    mcols = jnp.where(mvalid[None, :], batch[4:4 + n_cols_mine][:, rec], 0)
    ocols = jnp.where(mvalid[None, :], other["cols"][:, oidx], 0)
    return jnp.concatenate([jnp.stack(rows), mcols, ocols], axis=0)


def _join_insert(mine, batch, n, bcap: int, n_cols: int, owned=None):
    """Merge the (pre-sorted) batch into a sorted store: one stable
    2-key sort of the concatenation; overflow never truncates live
    entries because the host checks capacity before dispatching."""
    cap = mine["code"].shape[0]
    bcode = batch[0]
    bvalid = (jnp.arange(bcap) < n) & (bcode < JOIN_SENT_CODE)
    if owned is not None:
        bvalid = bvalid & owned
    code = jnp.concatenate(
        [mine["code"], jnp.where(bvalid, bcode, JOIN_SENT_CODE)])
    ts = jnp.concatenate([mine["ts"], batch[1]])
    idx = jnp.arange(cap + bcap, dtype=jnp.int32)
    scode, sts, order = jax.lax.sort((code, ts, idx), num_keys=2)
    order = order[:cap]
    flags = jnp.concatenate([mine["flags"], batch[3]])[order]
    cols = jnp.concatenate([mine["cols"], batch[4:4 + n_cols]],
                           axis=1)[:, order]
    return {"code": scode[:cap], "ts": sts[:cap], "flags": flags,
            "cols": cols}


@functools.lru_cache(maxsize=256)
def join_probe_insert(cap: int, bcap: int, match_cap: int,
                      n_cols_mine: int, n_cols_other: int,
                      window: bool = False):
    """The fused per-micro-batch kernel: probe the other side, insert
    into mine — ONE device dispatch; the match buffer is the one D2H
    fetch. (state_mine, state_other, batch, n, within, cutoff) ->
    (state_mine', packed matches)."""

    @jax.jit
    def probe_insert(mine, other, batch, n, within, cutoff):
        packed = _join_probe(other, batch, n, within, cutoff, bcap,
                             match_cap, n_cols_mine, window=window)
        return _join_insert(mine, batch, n, bcap, n_cols_mine), packed

    return probe_insert


@functools.lru_cache(maxsize=256)
def join_probe_only(cap: int, bcap: int, match_cap: int,
                    n_cols_mine: int, n_cols_other: int,
                    window: bool = False):
    """Probe without insert: the match-overflow redo path (the batch is
    already inserted; the other side is unchanged, so re-probing at a
    wider match_cap is exact)."""

    @jax.jit
    def probe(other, batch, n, within, cutoff):
        return _join_probe(other, batch, n, within, cutoff, bcap,
                           match_cap, n_cols_mine, window=window)

    return probe


def _join_match_feed(other, batch, n, within, cutoff, bcap: int,
                     match_cap: int, feed_plan, nulls_plan,
                     filter_nulls, owned=None, window: bool = False):
    """Probe + inner-feed core shared by the fused single-chip kernel
    and the key-sharded mirror (parallel.ShardedJoinLattice): expand
    the match spans and resolve every inner-step column straight from
    the match sources. Returns (total, kid, jts_rel, valid, cols) —
    `cols` includes the __null_a{i} masks, `valid` has filter-NULL
    records already masked out. `owned` (bool[bcap] or None) restricts
    which batch records this shard probes."""
    total, rec, oidx, mvalid, jts = _join_match_arrays(
        other, batch, n, within, cutoff, bcap, match_cap, owned, window)
    mflags = batch[3][rec]
    oflags = other["flags"][oidx]

    def lpres_of(src, jm, jo):
        # which physical side is the SQL left side: "both" = the
        # probing batch, "both_o" = the probed store
        if src == "both":
            return ((mflags >> (2 * jm + 1)) & 1) != 0
        return ((oflags >> (2 * jo + 1)) & 1) != 0

    def null_bit(src, jm, jo):
        mnull = (((mflags >> (2 * jm)) & 1) != 0 if jm >= 0
                 else None)
        onull = (((oflags >> (2 * jo)) & 1) != 0 if jo >= 0
                 else None)
        if src == "m":
            return mnull
        if src == "o":
            return onull
        left, right = ((mnull, onull) if src == "both"
                       else (onull, mnull))
        return jnp.where(lpres_of(src, jm, jo), left, right)

    def raw_val(src, jm, jo):
        mv = batch[4 + jm][rec] if jm >= 0 else 0
        ov = other["cols"][jo][oidx] if jo >= 0 else 0
        if src == "m":
            return mv
        if src == "o":
            return ov
        left, right = (mv, ov) if src == "both" else (ov, mv)
        return jnp.where(lpres_of(src, jm, jo), left, right)

    cols = {}
    for name, tag, src, jm, jo in feed_plan:
        raw = raw_val(src, jm, jo)
        if tag == "f32":
            cols[name] = jax.lax.bitcast_convert_type(raw, jnp.float32)
        elif tag == "bool":
            cols[name] = raw != 0
        else:
            cols[name] = raw
    for null_key, refs in nulls_plan:
        m = jnp.zeros((match_cap,), jnp.bool_)
        for src, jm, jo in refs:
            m = m | null_bit(src, jm, jo)
        cols[null_key] = m
    valid = mvalid
    for src, jm, jo in filter_nulls:
        valid = valid & ~null_bit(src, jm, jo)
    kid = jnp.where(mvalid, batch[2][rec], 0)
    return total, kid, jts, valid, cols


@functools.lru_cache(maxsize=256)
def join_probe_insert_step(cap: int, bcap: int, match_cap: int,
                           n_cols_mine: int, n_cols_other: int,
                           inner_spec: "LatticeSpec", schema,
                           filter_expr, feed_plan, nulls_plan,
                           filter_nulls, window: bool = False):
    """The FULLY fused interval-join kernel (`window`: the window
    join's, under WINDOW_JOIN_STEP_PROGRAM, see _join_match_arrays):
    probe the other side, insert into mine, and scatter the matched pairs straight into the
    downstream aggregate lattice — matches never leave the device, so
    the per-micro-batch D2H cost drops to zero (the changelog extract
    is the only remaining fetch, already batched/deferred).

    `feed_plan` maps the inner step's needed columns onto match
    sources, one hashable entry per column:
        (name, tag, src, j_mine, j_other)
    src "m" gathers from the probing batch, "o" from the probed store,
    "both" resolves per match by the LEFT side's present bit (bare-name
    left precedence; j_mine indexes my side's layout, j_other the
    other's — which physical side is "left" is baked into the plan by
    the caller). `nulls_plan` builds each aggregate's __null_a{i}
    column as the OR of its referenced columns' null bits, and
    `filter_nulls` masks records whose WHERE columns are NULL out of
    `valid` (SQL: NULL predicate is not-true).

    (mine, other, batch, n, within, cutoff, inner_state, wm_rel,
     ts_off) -> (mine', inner_state', total_matches i32)
    """
    agg_inputs, _null_keys = compile_agg_inputs(inner_spec, schema)
    from hstream_tpu.engine.expr import compile_device

    filter_fn = (compile_device(filter_expr, schema)
                 if filter_expr is not None else None)
    base_step = build_step_fn(inner_spec, agg_inputs, filter_fn)

    def probe_insert_step(mine, other, batch, n, within, cutoff,
                          inner_state, wm_rel, ts_off):
        total, kid, jts, valid, cols = _join_match_feed(
            other, batch, n, within, cutoff, bcap, match_cap,
            feed_plan, nulls_plan, filter_nulls, window=window)
        ts_inner = jts + ts_off
        new_inner = base_step(inner_state, wm_rel, kid, ts_inner,
                              valid, cols)
        new_mine = _join_insert(mine, batch, n, bcap, n_cols_mine)
        return new_mine, new_inner, total

    if window:  # WINDOW_JOIN_STEP_PROGRAM
        probe_insert_step.__name__ = "window_join_step"
    return jax.jit(probe_insert_step)


@functools.lru_cache(maxsize=256)
def join_evict(cap: int, n_cols_l: int, n_cols_r: int,
               window: bool = False):
    """Vmapped two-sided eviction + epoch rebase (`window`: the same
    pass under WINDOW_JOIN_EVICT_PROGRAM, whose cutoff is the start of
    the oldest open window, so whole closed windows go): drop entries past the
    retention cutoff from BOTH stores and shift surviving timestamps by
    -delta (0 outside a rebase), in one dispatch. The (code, ts) core
    compaction is vmapped over the side axis; the per-side column
    gathers ride the same jit. Returns (left', right', live counts
    i32[2]) — the count fetch is the only extra transfer eviction
    costs, and it is rare."""

    def _core(code, ts, cutoff, delta):
        alive = (code < JOIN_SENT_CODE) & (ts >= cutoff)
        code2 = jnp.where(alive, code, JOIN_SENT_CODE)
        ts2 = jnp.where(alive, ts - delta, 0)
        idx = jnp.arange(cap, dtype=jnp.int32)
        scode, sts, order = jax.lax.sort((code2, ts2, idx), num_keys=2)
        return scode, sts, order, jnp.sum(alive.astype(jnp.int32))

    def evict(left, right, cutoff, delta):
        code = jnp.stack([left["code"], right["code"]])
        ts = jnp.stack([left["ts"], right["ts"]])
        scode, sts, order, n = jax.vmap(
            _core, in_axes=(0, 0, None, None))(code, ts, cutoff, delta)
        out = []
        for s, st in enumerate((left, right)):
            out.append({"code": scode[s], "ts": sts[s],
                        "flags": st["flags"][order[s]],
                        "cols": st["cols"][:, order[s]]})
        return out[0], out[1], n

    if window:  # WINDOW_JOIN_EVICT_PROGRAM
        evict.__name__ = "window_join_evict"
    return jax.jit(evict)


def unpack_join_matches(packed: np.ndarray, n_cols_mine: int):
    """(total, kid, jts_rel, my_flags, other_flags, my_cols, other_cols)
    from a fetched match buffer; arrays sliced to the in-buffer match
    count (total may exceed it — the caller re-probes wider)."""
    total = int(packed[0, 0])
    m = min(total, packed.shape[1])
    return (total, packed[1, :m], packed[2, :m], packed[3, :m],
            packed[4, :m], packed[5:5 + n_cols_mine, :m],
            packed[5 + n_cols_mine:, :m])


def pad_slots(slots) -> np.ndarray:
    """Slot-index vector padded (with -1) to a power of two, so cycles
    of varying width share a handful of compiled shapes instead of one
    XLA executable per distinct count — shared by the fused window
    close, batched peek, and the session extract path."""
    p = 1
    while p < len(slots):
        p *= 2
    out = np.full(p, -1, np.int32)
    out[:len(slots)] = slots
    return out


# ---- session lattice kernels -------------------------------------------------
#
# The TPU restatement of the reference's SessionStore + merge-on-overlap
# loop (SessionWindowedStream.hs:84-118, hstream-processing SessionWindows):
# open sessions live in a device-resident ARENA of (key code, t0, t1,
# acc planes) kept sorted by (code, t0), and each micro-batch is ONE
# fused dispatch that
#   1. sorts (arena entries ∪ batch records) by (code, start) with one
#      stable `lax.sort` — a record is a degenerate session [ts, ts];
#   2. runs a SEGMENTED SCAN over the sorted sequence: a chain breaks at
#      a key change or where start > running-max(end) + gap. Because
#      merging only ever grows intervals, the sorted sweep's chains are
#      exactly the fixpoint of the reference's sequential merge-on-
#      overlap (interval clustering is confluent), and every accumulator
#      is a commutative monoid, so folding a whole chain is exact;
#   3. scatters each chain into a fresh compacted arena slot (merge and
#      compaction are the same scatter) — per-record values land via
#      the same masked monoid updates as the window lattice step.
# Closed sessions are dropped lazily: the host passes the close cutoff
# of its last close cycle and the kernel retires entries with
# t1 <= cutoff before the sort (eviction rides the merge dispatch).
# The step fetches NOTHING — the per-batch D2H cost of the session path
# is zero; the close extract (below) is the only fetch and is dispatched
# per close cycle, pow2-padded like the fused window close.
#
# The HOST keeps an exact interval mirror (code, t0, t1 — no accs) of
# the arena, updated with the numpy twin of the same sort+scan: the
# mirror decides late-record drops, close cycles, arena capacity, and
# slot indices without ever syncing the device. All times are int32 ms
# relative to a host-managed epoch (rebase delta rides the step).

SESSION_SENT_CODE = JOIN_SENT_CODE  # empty/evicted arena slots
_SESSION_NEG = -(1 << 30)           # safe "minus infinity" for the scan


@dataclass(frozen=True)
class SessionSpec:
    """Static configuration the session kernels are specialized on."""

    aggs: tuple[AggSpec, ...]
    hll: HLLConfig = HLLConfig()
    qcfg: QuantileConfig = QuantileConfig()


def session_plane_names(spec: SessionSpec) -> list[str]:
    """Canonical plane name per agg index: aggregates with the same
    (kind, input) share ONE arena plane — p50 + p99 over one column
    keep a single histogram; only the extract-time estimate differs.
    The first such agg owns the plane; kernels skip non-owners so
    additive planes never double-count."""
    seen: dict = {}
    out: list[str] = []
    for i, agg in enumerate(spec.aggs):
        key = (agg.kind, agg.input)
        name = seen.get(key)
        if name is None:
            name = _plane_name(i, agg)
            seen[key] = name
        out.append(name)
    return out


def session_plane_np(spec: SessionSpec, cap: int) -> dict[str, np.ndarray]:
    """Host-side (numpy) empty arena planes — the migration path fills
    these and device_puts once, with no device round trip."""
    arena: dict[str, np.ndarray] = {
        "code": np.full(cap, SESSION_SENT_CODE, np.int32),
        "t0": np.zeros(cap, np.int32),
        "t1": np.zeros(cap, np.int32),
    }
    for name, agg in zip(session_plane_names(spec), spec.aggs):
        if name in arena:
            continue  # aliased to an earlier same-(kind, input) agg
        if agg.kind in (AggKind.COUNT_ALL, AggKind.COUNT):
            arena[name] = np.zeros(cap, np.int32)
        elif agg.kind == AggKind.SUM:
            arena[name] = np.zeros(cap, np.float32)
        elif agg.kind == AggKind.AVG:
            arena[name] = np.zeros(cap, np.float32)
            arena[name + "_n"] = np.zeros(cap, np.int32)
        elif agg.kind == AggKind.MIN:
            arena[name] = np.full(cap, np.inf, np.float32)
        elif agg.kind == AggKind.MAX:
            arena[name] = np.full(cap, -np.inf, np.float32)
        elif agg.kind == AggKind.APPROX_COUNT_DISTINCT:
            arena[name] = np.zeros((cap, spec.hll.m), np.int8)
        elif agg.kind == AggKind.APPROX_QUANTILE:
            arena[name] = np.zeros((cap, spec.qcfg.n_bins), np.int32)
        else:
            raise NotImplementedError(f"session agg {agg.kind}")
    return arena


def grow_session_arena(spec: SessionSpec, arena: dict, new_cap: int
                       ) -> dict[str, jnp.ndarray]:
    """Pad every arena plane to new_cap (identity values in the tail)."""
    fresh = init_session_arena(spec, new_cap)
    return {k: fresh[k].at[:v.shape[0]].set(v) for k, v in arena.items()}


def init_session_arena(spec, cap: int) -> dict[str, jnp.ndarray]:
    """One empty session arena on device. Derives from session_plane_np
    so the per-AggKind dtype/identity table lives in ONE place (a
    migration/arena mismatch would corrupt state only on the rare
    activation-with-live-sessions path)."""
    return {k: jnp.asarray(v)
            for k, v in session_plane_np(spec, cap).items()}


def _session_chain_slots(code_all, start_all, end_all, gap, cap):
    """The shared sort + segmented-scan core: one stable lax.sort by
    (code, start, end), then a segmented running-max-of-end scan whose
    breaks (key change, or start past running end + gap) are the merged
    session chains. Returns per-ORIGIN destination slots: dest[i] is the
    compacted chain slot of concat-domain entry i (cap = dropped)."""
    m = code_all.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    scode, sstart, send, sidx = jax.lax.sort(
        (code_all, start_all, end_all, idx), num_keys=3)
    newrun = jnp.concatenate(
        [jnp.ones((1,), jnp.bool_), scode[1:] != scode[:-1]])

    def comb(a, b):
        fa, ma = a
        fb, mb = b
        return fa | fb, jnp.where(fb, mb, jnp.maximum(ma, mb))

    _, runmax = jax.lax.associative_scan(comb, (newrun, send))
    prev_end = jnp.concatenate(
        [jnp.full((1,), _SESSION_NEG, jnp.int32), runmax[:-1]])
    brk = newrun | (sstart > prev_end + gap)
    cid = jnp.cumsum(brk.astype(jnp.int32)) - 1
    live = scode < SESSION_SENT_CODE  # sentinels sort last
    slot = jnp.where(live, cid, cap)
    # scatter destinations back to the concat (origin) domain
    return jnp.zeros((m,), jnp.int32).at[sidx].set(slot)


@functools.lru_cache(maxsize=256)
def session_step_kernel(spec, schema, layout: ColLayout, cap: int,
                        bcap: int):
    """The fused per-micro-batch session kernel — ONE dispatch, ZERO
    fetches: (arena, packed i32[3+n_cols, bcap], gap, close_cut, delta)
    -> arena'. `close_cut` retires already-closed entries (t1 <= cut)
    before the merge; `delta` shifts arena times on an epoch rebase.
    Late-record drops are decided by the HOST mirror before packing, so
    every packed record participates."""
    agg_inputs, null_keys = compile_agg_inputs(spec, schema)

    @jax.jit
    def session_step(arena, packed, gap, close_cut, delta):
        # SESSION_STEP_PROGRAM
        codes_b, ts_b, valid, cols = unpack_batch_device(
            packed, layout, null_keys)
        acode = arena["code"]
        alive = (acode < SESSION_SENT_CODE) & (arena["t1"] > close_cut)
        acode = jnp.where(alive, acode, SESSION_SENT_CODE)
        at0 = jnp.where(alive, arena["t0"] - delta, 0)
        at1 = jnp.where(alive, arena["t1"] - delta, 0)
        bcode = jnp.where(valid, codes_b, SESSION_SENT_CODE)
        dest = _session_chain_slots(
            jnp.concatenate([acode, bcode]),
            jnp.concatenate([at0, ts_b]),
            jnp.concatenate([at1, ts_b]), gap, cap)
        da, db = dest[:cap], dest[cap:]

        out = {
            "code": jnp.full((cap,), SESSION_SENT_CODE, jnp.int32)
            .at[da].min(acode, mode="drop")
            .at[db].min(bcode, mode="drop"),
            "t0": jnp.full((cap,), np.iinfo(np.int32).max, jnp.int32)
            .at[da].min(at0, mode="drop")
            .at[db].min(ts_b, mode="drop"),
            "t1": jnp.full((cap,), _SESSION_NEG, jnp.int32)
            .at[da].max(at1, mode="drop")
            .at[db].max(ts_b, mode="drop"),
        }
        empty = out["code"] >= SESSION_SENT_CODE
        out["t0"] = jnp.where(empty, 0, out["t0"])
        out["t1"] = jnp.where(empty, 0, out["t1"])

        done: set[str] = set()
        for i, (name, agg) in enumerate(zip(session_plane_names(spec),
                                            spec.aggs)):
            if name in done:
                continue  # aliased plane: the owner already updated it
            done.add(name)
            vfn, null_key = agg_inputs[i]
            if agg.kind == AggKind.COUNT_ALL:
                out[name] = jnp.zeros((cap,), jnp.int32) \
                    .at[da].add(arena[name], mode="drop") \
                    .at[db].add(valid.astype(jnp.int32), mode="drop")
                continue
            v = vfn(cols)
            input_ok = valid
            if null_key is not None:
                input_ok = input_ok & ~cols[null_key]
            if jnp.issubdtype(v.dtype, jnp.floating):
                input_ok = input_ok & jnp.isfinite(v)
            vf = v.astype(jnp.float32)
            if agg.kind == AggKind.COUNT:
                out[name] = jnp.zeros((cap,), jnp.int32) \
                    .at[da].add(arena[name], mode="drop") \
                    .at[db].add(input_ok.astype(jnp.int32), mode="drop")
            elif agg.kind == AggKind.SUM:
                out[name] = jnp.zeros((cap,), jnp.float32) \
                    .at[da].add(arena[name], mode="drop") \
                    .at[db].add(jnp.where(input_ok, vf, 0.0), mode="drop")
            elif agg.kind == AggKind.AVG:
                out[name] = jnp.zeros((cap,), jnp.float32) \
                    .at[da].add(arena[name], mode="drop") \
                    .at[db].add(jnp.where(input_ok, vf, 0.0), mode="drop")
                out[name + "_n"] = jnp.zeros((cap,), jnp.int32) \
                    .at[da].add(arena[name + "_n"], mode="drop") \
                    .at[db].add(input_ok.astype(jnp.int32), mode="drop")
            elif agg.kind == AggKind.MIN:
                out[name] = jnp.full((cap,), POS_INF, jnp.float32) \
                    .at[da].min(arena[name], mode="drop") \
                    .at[db].min(jnp.where(input_ok, vf, POS_INF),
                                mode="drop")
            elif agg.kind == AggKind.MAX:
                out[name] = jnp.full((cap,), NEG_INF, jnp.float32) \
                    .at[da].max(arena[name], mode="drop") \
                    .at[db].max(jnp.where(input_ok, vf, NEG_INF),
                                mode="drop")
            elif agg.kind == AggKind.APPROX_COUNT_DISTINCT:
                reg, rank = hll_update_indices(vf, spec.hll)
                out[name] = jnp.zeros((cap, spec.hll.m), jnp.int8) \
                    .at[da].max(arena[name], mode="drop") \
                    .at[db, reg].max(jnp.where(input_ok, rank, 0),
                                     mode="drop")
            elif agg.kind == AggKind.APPROX_QUANTILE:
                b = quantile_bin(vf, spec.qcfg)
                out[name] = jnp.zeros((cap, spec.qcfg.n_bins), jnp.int32) \
                    .at[da].add(arena[name], mode="drop") \
                    .at[db, b].add(input_ok.astype(jnp.int32),
                                   mode="drop")
            else:
                raise NotImplementedError(f"session agg {agg.kind}")
        return out

    return session_step


@functools.lru_cache(maxsize=256)
def session_merge_kernel(spec, cap: int, scap: int):
    """The segment-mode session kernel: the host pre-reduces the batch's
    rows into per-SEGMENT plane contributions (the reference host path's
    vectorized reduceat/add.at machinery — segments are the batch's own
    gap-chains, so the pre-merge is exact), and this kernel merges the
    segment arena into the open-session arena: ONE dispatch running the
    same sort + segmented scan over cap + scap entries, then row-level
    monoid scatters per plane. Chosen on backends where per-record
    device scatters lose to the host's vectorized reduction (CPU); the
    record-mode step (session_step_kernel) stays the wire-frugal
    default for real accelerators.

    (arena, seg {same planes, [scap]}, gap, close_cut, delta) -> arena'
    """

    @jax.jit
    def merge(arena, seg, gap, close_cut, delta):
        acode = arena["code"]
        alive = (acode < SESSION_SENT_CODE) & (arena["t1"] > close_cut)
        acode = jnp.where(alive, acode, SESSION_SENT_CODE)
        at0 = jnp.where(alive, arena["t0"] - delta, 0)
        at1 = jnp.where(alive, arena["t1"] - delta, 0)
        dest = _session_chain_slots(
            jnp.concatenate([acode, seg["code"]]),
            jnp.concatenate([at0, seg["t0"]]),
            jnp.concatenate([at1, seg["t1"]]), gap, cap)
        da, db = dest[:cap], dest[cap:]
        out = {
            "code": jnp.full((cap,), SESSION_SENT_CODE, jnp.int32)
            .at[da].min(acode, mode="drop")
            .at[db].min(seg["code"], mode="drop"),
            "t0": jnp.full((cap,), np.iinfo(np.int32).max, jnp.int32)
            .at[da].min(at0, mode="drop")
            .at[db].min(seg["t0"], mode="drop"),
            "t1": jnp.full((cap,), _SESSION_NEG, jnp.int32)
            .at[da].max(at1, mode="drop")
            .at[db].max(seg["t1"], mode="drop"),
        }
        empty = out["code"] >= SESSION_SENT_CODE
        out["t0"] = jnp.where(empty, 0, out["t0"])
        out["t1"] = jnp.where(empty, 0, out["t1"])
        done: set[str] = set()
        for name, agg in zip(session_plane_names(spec), spec.aggs):
            if name in done:
                continue  # aliased plane: the owner already merged it
            done.add(name)
            names = [name] if agg.kind != AggKind.AVG \
                else [name, name + "_n"]
            for nm in names:
                plane = arena[nm]
                if agg.kind == AggKind.MIN:
                    out[nm] = jnp.full((cap,), POS_INF, jnp.float32) \
                        .at[da].min(plane, mode="drop") \
                        .at[db].min(seg[nm], mode="drop")
                elif agg.kind == AggKind.MAX:
                    out[nm] = jnp.full((cap,), NEG_INF, jnp.float32) \
                        .at[da].max(plane, mode="drop") \
                        .at[db].max(seg[nm], mode="drop")
                elif agg.kind == AggKind.APPROX_COUNT_DISTINCT:
                    out[nm] = jnp.zeros(plane.shape, plane.dtype) \
                        .at[da].max(plane, mode="drop") \
                        .at[db].max(seg[nm], mode="drop")
                else:  # counts / sums / histograms: additive
                    out[nm] = jnp.zeros(plane.shape, plane.dtype) \
                        .at[da].add(plane, mode="drop") \
                        .at[db].add(seg[nm], mode="drop")
        return out

    return merge


@functools.lru_cache(maxsize=256)
def session_extract_kernel(spec, cap: int, pcap: int):
    """Read-only extract of the arena slots named by `slots` (pow2-
    padded, entries < 0 extract zeros): finalize every acc plane on
    device and pack into ONE int32 buffer [1 + n_aggs, pcap] — row 0 is
    the slot's code (host mirror cross-check), counts/HLL rows are i32,
    float rows f32-bitcast. One dispatch + one fetch serves a whole
    close cycle or peek, exactly like the fused window close."""

    @jax.jit
    def session_extract(arena, slots):  # SESSION_EXTRACT_PROGRAM
        ok = slots >= 0
        at = jnp.where(ok, slots, 0)
        rows = [jnp.where(ok, arena["code"][at], SESSION_SENT_CODE)]
        for name, agg in zip(session_plane_names(spec), spec.aggs):
            if agg.kind in (AggKind.COUNT_ALL, AggKind.COUNT):
                rows.append(jnp.where(ok, arena[name][at], 0))
                continue
            if agg.kind == AggKind.AVG:
                v = arena[name][at] / jnp.maximum(
                    arena[name + "_n"][at].astype(jnp.float32), 1.0)
            elif agg.kind == AggKind.MIN:
                v = arena[name][at]
                v = jnp.where(v == POS_INF, 0.0, v)
            elif agg.kind == AggKind.MAX:
                v = arena[name][at]
                v = jnp.where(v == NEG_INF, 0.0, v)
            elif agg.kind == AggKind.APPROX_COUNT_DISTINCT:
                est = hll_estimate(arena[name][at], spec.hll)
                rows.append(jnp.where(
                    ok, jnp.rint(est).astype(jnp.int32), 0))
                continue
            elif agg.kind == AggKind.APPROX_QUANTILE:
                hist = arena[name][at]
                est = quantile_estimate(hist, agg.quantile or 0.5,
                                        spec.qcfg)
                # an all-NULL-input session has an empty histogram:
                # the estimator's max(total, 1) target would read the
                # LAST bin; the host reference emits 0.0
                v = jnp.where(jnp.sum(hist, axis=-1) > 0, est, 0.0)
            else:
                v = arena[name][at].astype(jnp.float32)
            rows.append(jax.lax.bitcast_convert_type(
                jnp.where(ok, v, 0.0), jnp.int32))
        return jnp.stack(rows)

    return session_extract


@functools.lru_cache(maxsize=64)
def session_remap_kernel(cap: int, lcap: int):
    """Code-space compaction: live arena codes gather a dense, ORDER-
    PRESERVING new code through the pow2-padded LUT (codes >= lcap —
    including the sentinel — pass through), so the arena stays (code,
    t0)-sorted across the remap. One dispatch, no fetch."""

    @jax.jit
    def session_remap(arena, lut):  # SESSION_REMAP_PROGRAM
        code = arena["code"]
        out = dict(arena)
        out["code"] = jnp.where(code < lcap,
                                lut[jnp.clip(code, 0, lcap - 1)], code)
        return out

    return session_remap


@jax.jit
def rebase(state, delta):
    """Shift device-relative time by -delta (host re-anchored the epoch)."""
    out = dict(state)
    occupied = state["slot_start"] != EMPTY_START
    out["slot_start"] = jnp.where(
        occupied, state["slot_start"] - delta, state["slot_start"])
    return out


def grow_keys(state: dict[str, jnp.ndarray], spec: LatticeSpec,
              new_n_keys: int) -> dict[str, jnp.ndarray]:
    """Pad every keyed plane from K to new_n_keys (host, rare)."""
    old = spec.n_keys
    extra = new_n_keys - old
    out = {}
    for k, v in state.items():
        if k == "slot_start":
            out[k] = v
            continue
        pad_width = [(0, extra)] + [(0, 0)] * (v.ndim - 1)
        if k.endswith("_min"):
            out[k] = jnp.pad(v, pad_width, constant_values=np.float32(np.inf))
        elif k.endswith(("_max", "_topk", "_topk_distinct")):
            out[k] = jnp.pad(v, pad_width, constant_values=np.float32(-np.inf))
        else:
            out[k] = jnp.pad(v, pad_width)
    return out
