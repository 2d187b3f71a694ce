"""Operator-state snapshots: serialize / restore executor state.

The reference checkpoints only READER positions (three backends,
hstream-store/HStream/Store/Internal/LogDevice/Checkpoint.hs:37-46);
operator state lives in in-memory KV stores (mkInMemoryStateKVStore,
Codegen.hs:374-385), so a restarted query silently re-aggregates from
the checkpoint — every window spanning the restart undercounts. SURVEY
§7 item 8 asks to beat that: here the FULL operator state — lattice
planes, key dictionary, string dictionaries, epoch/watermark/open
windows, session state, join side-stores — serializes to one blob,
written ATOMICALLY with the read checkpoints it corresponds to, so
resume is exact (at-least-once only across the sink boundary: rows
emitted after the last snapshot are re-emitted on replay).

Wire format: a single .npz container; entry "__meta__" is UTF-8 JSON
(uint8 array), remaining entries are numpy arrays referenced from the
meta. Nested executors (a join's inner aggregate) embed their own npz
blob as a uint8 array.
"""

from __future__ import annotations

import io
import json
import math
import struct
import zlib
from typing import Any

import jax
import numpy as np

from hstream_tpu.common.errors import SQLCodegenError, StoreError
from hstream_tpu.engine.types import ColumnType, Schema, StringDictionary

SNAPSHOT_VERSION = 1

# ---- CRC-sealed blob framing ------------------------------------------------
#
# A snapshot blob written to the meta KV is sealed with a magic + crc32
# + length header so a torn or bit-rotted write is DETECTED at restore
# instead of surfacing as a numpy/JSON parse error (or worse, parsing
# into wrong state). The two-slot last-good rotation in
# server.tasks relies on this: a corrupt newest slot falls back to the
# previous sealed slot and replays the gap.

SEAL_MAGIC = b"HSNP1\x00"
_SEAL_HEADER = len(SEAL_MAGIC) + 8  # + u32 crc + u32 length


class SnapshotCorrupt(StoreError):
    """A sealed snapshot blob failed its integrity check."""


def seal_blob(blob: bytes) -> bytes:
    """Frame a snapshot blob with magic + crc32 + length."""
    return (SEAL_MAGIC
            + struct.pack("<II", zlib.crc32(blob) & 0xFFFFFFFF,
                          len(blob))
            + blob)


def open_blob(data: bytes) -> bytes:
    """Verify and unwrap a sealed blob. Legacy blobs (pre-seal raw npz,
    which always starts with the zip magic ``PK``) pass through
    unverified so snapshots written by older servers still restore.
    Raises SnapshotCorrupt on truncation or checksum mismatch."""
    if data.startswith(b"PK"):
        return data  # legacy unsealed npz
    if not data.startswith(SEAL_MAGIC):
        raise SnapshotCorrupt(
            f"snapshot blob has neither seal nor npz magic "
            f"({data[:6]!r})")
    if len(data) < _SEAL_HEADER:
        raise SnapshotCorrupt("snapshot blob truncated inside header")
    crc, length = struct.unpack_from("<II", data, len(SEAL_MAGIC))
    blob = data[_SEAL_HEADER:]
    if len(blob) != length:
        raise SnapshotCorrupt(
            f"snapshot blob truncated: {len(blob)} of {length} bytes")
    if (zlib.crc32(blob) & 0xFFFFFFFF) != crc:
        raise SnapshotCorrupt("snapshot blob checksum mismatch")
    return blob


# ---- tagged JSON for scalars JSON cannot carry ------------------------------

def _enc(v: Any) -> Any:
    if isinstance(v, np.ndarray):
        return {"__nd__": v.dtype.str, "d": v.tolist()}
    if isinstance(v, tuple):
        return {"__tp__": [_enc(x) for x in v]}
    if isinstance(v, float) and math.isinf(v):
        return {"__inf__": 1 if v > 0 else -1}
    if isinstance(v, dict):
        return {k: _enc(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_enc(x) for x in v]
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return v


def _dec(v: Any) -> Any:
    if isinstance(v, dict):
        if "__nd__" in v:
            return np.asarray(v["d"], dtype=np.dtype(v["__nd__"]))
        if "__tp__" in v:
            return tuple(_dec(x) for x in v["__tp__"])
        if "__inf__" in v:
            return math.inf if v["__inf__"] > 0 else -math.inf
        return {k: _dec(x) for k, x in v.items()}
    if isinstance(v, list):
        return [_dec(x) for x in v]
    return v


def _pack(meta: dict, arrays: dict[str, np.ndarray]) -> bytes:
    buf = io.BytesIO()
    meta_bytes = np.frombuffer(json.dumps(meta).encode("utf-8"),
                               dtype=np.uint8)
    np.savez(buf, __meta__=meta_bytes, **arrays)
    return buf.getvalue()


def _unpack(blob: bytes) -> tuple[dict, dict[str, np.ndarray]]:
    with np.load(io.BytesIO(blob)) as z:
        meta = json.loads(bytes(z["__meta__"].tobytes()).decode("utf-8"))
        arrays = {k: z[k] for k in z.files if k != "__meta__"}
    return meta, arrays


# ---- executor dispatch ------------------------------------------------------

def capture_executor(ex, extra: dict | None = None
                     ) -> tuple[dict, dict[str, Any]]:
    """Phase 1: take a CONSISTENT capture of an executor's state.

    Designed to be cheap enough to run under the executor's state lock:
    device arrays are captured by reference (jax arrays are immutable —
    steps replace the state dict, never mutate buffers), host structures
    are shallow-copied or encoded. Heavy work (device->host sync,
    npz/zlib packing) happens in serialize_capture() WITHOUT the lock."""
    from hstream_tpu.engine.executor import QueryExecutor
    from hstream_tpu.engine.join import JoinExecutor, TableJoinExecutor
    from hstream_tpu.engine.session import SessionExecutor
    from hstream_tpu.engine.stateless import StatelessExecutor

    if isinstance(ex, QueryExecutor):
        meta, arrays = _lattice_state(ex)
    elif isinstance(ex, SessionExecutor):
        meta, arrays = _session_state(ex)
    elif isinstance(ex, TableJoinExecutor):
        meta, arrays = _table_join_state(ex)
    elif isinstance(ex, JoinExecutor):
        meta, arrays = _join_state(ex)
    elif isinstance(ex, StatelessExecutor):
        meta, arrays = {"kind": "stateless"}, {}
    else:
        raise SQLCodegenError(
            f"cannot snapshot {type(ex).__name__}")
    meta["version"] = SNAPSHOT_VERSION
    meta["extra"] = extra or {}
    return meta, arrays


def serialize_capture(meta: dict, arrays: dict[str, Any]) -> bytes:
    """Phase 2: heavy serialization of a capture (no lock needed)."""
    return _pack(meta, {k: np.asarray(v) for k, v in arrays.items()})


def snapshot_executor(ex, extra: dict | None = None) -> bytes:
    """Serialize any executor's state to bytes. `extra` (JSON-able, e.g.
    the read checkpoints this state corresponds to) rides in the blob so
    the state/ckp pair is one atomic write."""
    meta, arrays = capture_executor(ex, extra)
    return serialize_capture(meta, arrays)


def restore_executor(plan, blob: bytes, *, initial_keys: int = 1024,
                     batch_capacity: int = 4096, mesh=None):
    """Rebuild an executor from a snapshot blob for a lowered SELECT
    plan. Returns (executor, extra). With `mesh`, lattice state restores
    into a ShardedQueryExecutor (snapshots are mesh-portable: capture
    merges shard partials into ONE canonical lattice, restore scatters
    it back — see _scatter_state)."""
    meta, arrays = _unpack(blob)
    ver = meta.get("version")
    if ver != SNAPSHOT_VERSION:
        raise SQLCodegenError(
            f"snapshot format version {ver!r} != supported "
            f"{SNAPSHOT_VERSION}; refusing to deserialize")
    kind = meta["kind"]
    if kind == "tablejoin":
        ex = _restore_table_join(plan, meta, arrays,
                                 initial_keys=initial_keys,
                                 batch_capacity=batch_capacity)
    elif kind == "join":
        ex = _restore_join(plan, meta, arrays,
                           initial_keys=initial_keys,
                           batch_capacity=batch_capacity, mesh=mesh)
    elif kind == "lattice":
        ex = _restore_lattice(plan.node, meta, arrays,
                              batch_capacity=batch_capacity, mesh=mesh)
    elif kind == "session":
        ex = _restore_session(plan.node, meta, arrays, mesh=mesh)
    elif kind == "stateless":
        from hstream_tpu.engine.stateless import StatelessExecutor

        ex = StatelessExecutor(plan.node)
    else:
        raise SQLCodegenError(f"unknown snapshot kind {kind!r}")
    return ex, meta.get("extra", {})


# ---- lattice (QueryExecutor) ------------------------------------------------

def _lattice_state(ex) -> tuple[dict, dict[str, np.ndarray]]:
    if ex._pending_closes:
        raise SQLCodegenError(
            "snapshot with deferred closes pending; drain_closed() first")
    if getattr(ex, "_pending_changes", None) \
            or getattr(ex, "_drain_futs", None):
        # the touched mask was already cleared on device: the queued
        # extracts (and any in-flight async drains) are the ONLY copy
        # of those change rows
        raise SQLCodegenError(
            "snapshot with deferred changes pending; flush_changes() "
            "first")
    key_rev, key_arrays = _keys_state(ex)
    meta = {
        "kind": "lattice",
        "n_keys": ex.spec.n_keys,
        "batch_capacity": ex.batch_capacity,
        "epoch": ex.epoch,
        "watermark_abs": ex.watermark_abs,
        "emit_changes": ex.emit_changes,
        "open": [[s, ow.slot] for s, ow in sorted(ex._open.items())],
        "key_rev": key_rev,
        "dicts": {name: d._values for name, d in ex.dicts.items()},
        "null_sticky": sorted(ex._null_sticky),
        "schema": [[n, t.value] for n, t in ex.schema.fields],
    }
    arrays = lattice_planes(ex)
    # the newest event time each id was named at: what decides when a
    # key may be retired (a copy: the executor writes it in place)
    arrays["k/last"] = ex._key_last[:len(ex._key_rev)].copy()
    arrays.update(key_arrays)
    return meta, arrays


def lattice_planes(ex) -> dict[str, Any]:
    """The planes of a lattice executor as a snapshot holds them, by
    reference: jax arrays are immutable; np.asarray (the device sync)
    happens in serialize_capture, outside the caller's lock.
    Sharded executors (leading data axis on every plane) canonicalize:
    merge the partial lattices with each plane's monoid op so the blob
    is mesh-portable (restorable single-chip or onto any mesh)."""
    if hasattr(ex, "_sharded"):
        return {f"s/{k}": v for k, v in _merge_partials(ex).items()}
    return {f"s/{k}": v for k, v in ex.state.items()}


def _keys_state(ex) -> tuple[list | None, dict[str, np.ndarray]]:
    """The key dictionary for a snapshot: (`key_rev` for the meta, with
    None where an id is free (its key retired), no arrays), so that a
    restore hands the same ids to the same keys and the free ones out
    again in the same order. Where the schema types every group column
    a number and the values are integers (int64 is what numpy infers
    from them: no null, no fraction, none past int64), the dictionary
    goes as arrays instead (None, {`k/held`: which ids hold a key,
    `k/c<g>`: their values of group column g}): NEXmark's auction ids
    are a million entries, and a million JSON objects encoded under the
    executor's lock stop the query for seconds. Beside a numeric
    column, a column whose values are all strings goes the same way (a
    window join's `id, name` keys are a few hundred thousand). Every
    other dictionary (string keys alone, a null, a bool, a fraction)
    goes as JSON, its values unread."""
    from hstream_tpu.engine.executor import _KEY_FREE

    types = dict(ex.schema.fields)
    kinds = [types.get(c) for c in ex.group_cols]
    if (any(k in (ColumnType.FLOAT, ColumnType.INT) for k in kinds)
            and all(k in (ColumnType.FLOAT, ColumnType.INT,
                          ColumnType.STRING) for k in kinds)):
        n = len(ex._key_rev)
        held = ex._key_last[:n] > _KEY_FREE
        cols = [np.array(c[:n][held].tolist()) for c in ex._key_cols]
        if all(c.dtype == np.int64 or c.dtype.kind == "U"
               for c in cols):
            out = {f"k/c{g}": c for g, c in enumerate(cols)}
            out["k/held"] = held
            return None, out
    return [_enc(k) for k in ex._key_rev], {}


def _keys_from(meta: dict, arrays: dict) -> list:
    """`key_rev` as `_keys_state` left it, in either form."""
    if meta["key_rev"] is not None:
        return [None if k is None else tuple(_dec(k))
                for k in meta["key_rev"]]
    held = np.asarray(arrays["k/held"], np.bool_)
    cols = []
    g = 0
    while f"k/c{g}" in arrays:
        cols.append(np.asarray(arrays[f"k/c{g}"]).tolist())
        g += 1
    rev: list = [None] * len(held)
    for i, key in zip(np.flatnonzero(held).tolist(), zip(*cols)):
        rev[i] = key
    return rev


def _merge_partials(ex) -> dict[str, Any]:
    """Reduce the leading data axis of a sharded executor's state with
    each plane's merge monoid -> canonical [K, W, ...] state (exact: all
    accumulators are commutative monoids, lattice.plane_merge_kinds).

    The reductions are DISPATCHED on device (jnp, async) so this stays
    cheap under the caller's state lock; the host sync happens in
    serialize_capture's np.asarray, outside the lock."""
    import jax.numpy as jnp

    from hstream_tpu.engine import lattice

    kinds = lattice.plane_merge_kinds(ex.spec)
    out = {}
    for k, v in ex.state.items():
        kind = kinds.get(k, "sum")
        if kind == "min":
            out[k] = jnp.min(v, axis=0)
        elif kind == "max":
            out[k] = (jnp.any(v, axis=0) if v.dtype == jnp.bool_
                      else jnp.max(v, axis=0).astype(v.dtype))
        elif kind == "sum":
            out[k] = jnp.sum(v, axis=0).astype(v.dtype)
        else:
            # e.g. "topk": summing shard partials would corrupt state.
            # Sharded execution currently rejects such specs upstream;
            # fail loudly if that restriction is ever lifted.
            raise SQLCodegenError(
                f"no shard-merge rule for plane {k!r} (kind {kind!r})")
    return out


def _restore_lattice(node, meta, arrays, *, batch_capacity: int = 4096,
                     mesh=None):
    from hstream_tpu.engine.executor import QueryExecutor, _OpenWindow

    schema = Schema(tuple((n, ColumnType(t)) for n, t in meta["schema"]))
    cap = meta.get("batch_capacity", batch_capacity)
    if mesh is not None:
        from hstream_tpu.engine.plan import single_chip_reason

        if single_chip_reason(node) is not None:
            mesh = None  # as make_executor decides for a fresh one
    if mesh is not None:
        from hstream_tpu.parallel import ShardedQueryExecutor

        ex = ShardedQueryExecutor(
            node, schema, mesh=mesh, emit_changes=meta["emit_changes"],
            initial_keys=meta["n_keys"], batch_capacity=cap)
    else:
        ex = QueryExecutor(node, schema,
                           emit_changes=meta["emit_changes"],
                           initial_keys=meta["n_keys"],
                           batch_capacity=cap)
    # __init__ re-encodes string literals deterministically (same node,
    # same schema => same dictionary prefix), so overwriting the dict
    # contents with the snapshot's (literals + runtime values, in the
    # original insertion order) keeps compiled literal ids consistent.
    for name, values in meta["dicts"].items():
        d = StringDictionary()
        for v in values:
            d.encode(v)
        ex.dicts[name] = d
    # a snapshot from before ids had dates has no "k/last": its keys
    # stay pinned (never retired), as they were
    ex._load_keys(_keys_from(meta, arrays), arrays.get("k/last"))
    ex.epoch = meta["epoch"]
    ex.watermark_abs = meta["watermark_abs"]
    ex._open = {s: _OpenWindow(start_abs=s, slot=slot)
                for s, slot in meta["open"]}
    ex._null_sticky = set(meta["null_sticky"])
    canonical = {k[len("s/"):]: v
                 for k, v in arrays.items() if k.startswith("s/")}
    if mesh is not None:
        ex.state = _scatter_state(ex, canonical)
    else:
        ex.state = {k: jax.device_put(v) for k, v in canonical.items()}
    return ex


def _scatter_state(ex, canonical: dict[str, np.ndarray]):
    """Install a canonical (merged) lattice into a sharded executor:
    data-shard 0 carries the whole canonical lattice, the other shards
    carry merge identities — their monoid merge at drain points yields
    exactly the canonical values."""
    from hstream_tpu.engine import lattice

    identities = lattice.init_state(ex.spec)
    sh = ex._sharded
    out = {}
    for k, v in canonical.items():
        if k not in identities:
            # plane from an older snapshot format (e.g. the removed
            # COUNT_ALL alias plane): now derived, safe to drop
            continue
        ident = np.asarray(identities[k])
        g = np.broadcast_to(ident[None],
                            (sh.n_data,) + ident.shape).copy()
        g[0] = v
        out[k] = jax.device_put(g, sh.state_sharding(k))
    return out


# ---- session ----------------------------------------------------------------

def _session_state(ex) -> tuple[dict, dict[str, Any]]:
    if getattr(ex, "_pending_closes", None):
        # the deferred extract buffers are the ONLY copy of those
        # closed-session rows (mirror entries already retired)
        raise SQLCodegenError(
            "snapshot with deferred session closes pending; "
            "drain_closed() first")
    meta = {
        "kind": "session",
        "watermark": ex.watermark,
        "emit_changes": ex.emit_changes,
        "schema": [[n, t.value] for n, t in ex.schema.fields],
    }
    if getattr(ex, "_dev", None) is not None:
        # device-resident sessions are captured as columns (planes by
        # reference, mirror rows, one key a code): the fetch and the
        # packing are phase 2's, off the lock; restore rebuilds the host
        # engine and the device path re-activates and re-migrates lazily
        # on the next batch, like the join store
        meta["device"], arrays = ex.capture_device()
        return meta, arrays
    meta["sessions"] = [
        {"k": _enc(key),
         "s": [{"a": s.start, "b": s.end, "acc": _enc(s.accs)}
               for s in sess_list]}
        for key, sess_list in ex.sessions.items()
    ]
    return meta, {}


def _restore_session(node, meta, arrays, mesh=None):
    """Session snapshots are mesh-portable: the blob holds the host
    view, or the arena's rows with each row's shard and slot, so
    restoring with a different `mesh` (or none) just re-shards when the
    device path re-activates on the next batch."""
    from hstream_tpu.engine.session import SessionExecutor, _Session

    schema = Schema(tuple((n, ColumnType(t)) for n, t in meta["schema"]))
    kw = {} if mesh is None else {"mesh": mesh}
    ex = SessionExecutor(node, schema, emit_changes=meta["emit_changes"],
                         **kw)
    ex.watermark = meta["watermark"]
    cap = meta.get("device")
    if cap is not None:
        at = (arrays["sess.slot"],) if "sess.shard" not in arrays \
            else (arrays["sess.shard"], arrays["sess.slot"])
        head = "sess.plane."
        ex.sessions = SessionExecutor.sessions_from_rows(
            ex.aggs, cap["planes"],
            [cap["keys"][k] for k in arrays["sess.key"].tolist()],
            arrays["sess.t0"], arrays["sess.t1"],
            {name[len(head):]: plane[at]
             for name, plane in arrays.items() if name.startswith(head)})
        return ex
    for ent in meta["sessions"]:
        key = tuple(_dec(ent["k"]))
        ex.sessions[key] = [
            _Session(start=s["a"], end=s["b"], accs=_dec(s["acc"]))
            for s in ent["s"]]
    return ex


# ---- stream-table join ------------------------------------------------------


def _table_join_state(ex) -> tuple[dict, dict[str, np.ndarray]]:
    meta = {
        "kind": "tablejoin",
        "batch_capacity": ex._batch_capacity,
        "table": [{"k": _enc(key), "t": ts, "r": row}
                  for key, (ts, row) in ex.table.items()],
    }
    arrays = {}
    if ex._inner is not None:
        arrays["i/blob"] = np.frombuffer(snapshot_executor(ex._inner),
                                         dtype=np.uint8)
    return meta, arrays


def _restore_table_join(plan, meta, arrays, *, initial_keys: int,
                        batch_capacity: int):
    from hstream_tpu.engine.join import TableJoinExecutor

    ex = TableJoinExecutor(plan, initial_keys=initial_keys,
                           batch_capacity=meta.get("batch_capacity",
                                                   batch_capacity))
    for ent in meta["table"]:
        ex.table[tuple(_dec(ent["k"]))] = (int(ent["t"]), ent["r"])
    if "i/blob" in arrays:
        inner, _ = restore_executor(ex._inner_plan,
                                    arrays["i/blob"].tobytes(),
                                    initial_keys=initial_keys,
                                    batch_capacity=batch_capacity)
        ex._inner = inner
    return ex


# ---- join -------------------------------------------------------------------
#
# (The stream-TABLE join above stays single-chip — mesh_exclusion_reason
# keeps its keyed last-value state on the host — so its restore takes no
# mesh. The interval join below re-shards.)

def _join_state(ex) -> tuple[dict, dict[str, np.ndarray]]:
    if getattr(ex, "_staged", None) or getattr(ex, "_pending_matches",
                                               None):
        # coalesced matches / deferred device match buffers live
        # outside the inner executor's state; the owning runtime must
        # flush_staged() (sinking the emitted rows) before a snapshot,
        # like deferred changelog extracts
        raise SQLCodegenError(
            "snapshot with coalesced join matches staged; "
            "flush_staged() first")

    def dump_store(store):
        return [{"k": _enc(key), "t": tss, "r": rows}
                for key, (tss, rows) in store.by_key.items()]

    # device-resident stores serialize through the same host view
    # (fetch + row reconstruction from the packed needed columns);
    # restore refills the host stores and the device re-activates and
    # re-migrates lazily on the next probe
    meta = {
        "kind": "join",
        "batch_capacity": ex._batch_capacity,
        "watermark": ex.watermark,
    }
    arrays = {}
    dev = getattr(ex, "_dev", None)
    if (getattr(ex, "window_join", False) and dev is not None
            and dev.get("sjl") is None and ex._inner is not None):
        # a window join's device stores go as columns (planes by
        # reference, the fetch is phase 2's): engine/join.py
        # capture_device
        meta["stores"] = {}
        meta["device"], arrays = ex.capture_device()
    else:
        stores = (ex._host_store_view()
                  if hasattr(ex, "_host_store_view") else ex._stores)
        meta["stores"] = {side: dump_store(st)
                          for side, st in stores.items()}
    if getattr(ex, "window_join", False):
        meta["window"] = {"src_hi": dict(ex._src_hi),
                          "open_from": ex._open_from}
    if ex._inner is not None:
        inner_blob = snapshot_executor(ex._inner)
        arrays["i/blob"] = np.frombuffer(inner_blob, dtype=np.uint8)
    return meta, arrays


def _restore_join(plan, meta, arrays, *, initial_keys: int,
                  batch_capacity: int, mesh=None):
    """Join snapshots are mesh-portable like session ones: the blob
    holds the gathered host store view; a different `mesh` re-shards
    both side stores when the device path re-activates."""
    from hstream_tpu.engine.join import JoinExecutor

    ex = JoinExecutor(plan, initial_keys=initial_keys,
                      batch_capacity=meta.get("batch_capacity",
                                              batch_capacity),
                      mesh=mesh)
    ex.watermark = meta["watermark"]
    for side, ents in meta["stores"].items():
        codes: list[int] = []
        tss: list[int] = []
        rows: list = []
        for ent in ents:
            key = tuple(_dec(ent["k"]))
            c = ex._jcode.get(key)
            if c is None:
                c = len(ex._jcode_rev)
                ex._jcode[key] = c
                ex._jcode_rev.append(key)
            for t, r in zip(ent["t"], ent["r"]):
                codes.append(c)
                tss.append(int(t))
                rows.append(r)
        if not codes:
            continue
        code_a = np.asarray(codes, np.int64)
        ts_a = np.asarray(tss, np.int64)
        rows_a = np.empty(len(rows), object)
        rows_a[:] = rows
        order = np.lexsort((ts_a, code_a))
        ex._stores[side].insert_sorted(code_a[order], ts_a[order],
                                       rows_a[order])
    if "i/blob" in arrays:
        # the downstream aggregate re-shards with the join: a mixed
        # sharded-join / single-chip-inner pair would refuse the fused
        # feed plan (correct, but a silent perf cliff)
        inner, _ = restore_executor(ex._inner_plan,
                                    arrays["i/blob"].tobytes(),
                                    initial_keys=initial_keys,
                                    batch_capacity=batch_capacity,
                                    mesh=mesh)
        ex._inner = inner
    if meta.get("window") is not None:
        ex._src_hi = {s: int(v)
                      for s, v in meta["window"]["src_hi"].items()}
        ex._open_from = meta["window"]["open_from"]
    if meta.get("device") is not None:
        ex.restore_device(meta["device"], arrays)
    return ex
