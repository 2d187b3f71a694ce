"""Columnar batch payload: the high-throughput producer format.

A RAW-flagged HStreamRecord whose payload starts with the HSCB1 magic
carries a whole COLUMN-oriented event batch: one i64 timestamp array
plus named columns (f32 / i64 / bool / dictionary-encoded strings).
Appending one columnar record per micro-batch skips per-event protobuf
and JSON entirely — the server's query tasks detect the magic and feed
the columns straight into the jitted lattice step (engine ingest
contract), the path the 10M events/s target is specified against.

The reference's wire is one protobuf per event (BuildRecord.hs:28-70);
this is the TPU-first divergence SURVEY §7 prescribes ("protobuf decode
+ key dictionary off the critical path — columnar staging").

Layout: MAGIC | u32 header_len | header JSON | ts i64[n] | col bytes...
        | null-mask bytes (u8[n] per masked column, ISSUE 12)...
header: {"n": int, "cols": [[name, kind], ...], "dicts": {name: [str]},
         "nulls": [name, ...]}        # optional; names masks in order
kinds: "f32" | "i64" | "bool" | "str" (i32 ids into header dict)

The optional per-column null masks carry missing/NULL cells on the
wire (the framed append path's staging layout): a masked cell behaves
exactly like a field a per-record producer never sent. Payloads
without the "nulls" header key are the legacy layout — old producers
and old decoders interoperate unchanged.

Reading a header (`decode_columnar_nulls`, the one decode the append
door and every reader share): the dictionaries are most of it (one
entry a distinct string: 4.6 MB of a 7 MB NEXmark bid frame), and
`json.loads` over them holds the GIL for 4-16 ms to build strings that
a plan may never read. So a native scan (`jsondec.cpp`
`jd_header_dicts`, GIL released) checks each dictionary's syntax and
counts its entries, `json.loads` parses the ~100 bytes that are left,
every size check runs against the native counts, and a string column's
dictionary is a `LazyDictionary`: its length is known, its strings are
parsed the first time something reads one. A header the scan does not
recognise (an escape, a byte outside printable ASCII, anything but the
client encoder's own compact form), or a library that did not build,
takes the whole-header parse, as before: same answers, same refusals.
"""

from __future__ import annotations

import ctypes
import json
from collections.abc import Sequence
from typing import Any, Mapping

import numpy as np

from hstream_tpu.common import jsondec
from hstream_tpu.common.logger import get_logger

log = get_logger("columnar")

MAGIC = b"HSCB1\x00"

_KIND_DTYPE = {"f32": np.float32, "f64": np.float64, "i64": np.int64,
               "bool": np.uint8, "str": np.int32}


def is_columnar(payload: bytes) -> bool:
    return payload[: len(MAGIC)] == MAGIC


def encode_columnar(ts_ms: np.ndarray,
                    cols: Mapping[str, np.ndarray | list],
                    *, float_kind: str = "f32",
                    nulls: Mapping[str, np.ndarray] | None = None
                    ) -> bytes:
    """Columns -> payload bytes. String columns (lists or object/str
    arrays) are dictionary-encoded; numeric arrays are cast to
    f32/i64/bool. float_kind="f64" keeps float columns at full double
    precision (sink emission of host-finalized aggregates). `nulls`
    (name -> bool[n]) marks missing cells; masks ride after the column
    bytes and decode back via decode_columnar_nulls."""
    ts = np.ascontiguousarray(ts_ms, np.int64)
    n = len(ts)
    meta_cols: list[list[str]] = []
    dicts: dict[str, list[str]] = {}
    bufs: list[bytes] = [ts.tobytes()]
    for name, v in cols.items():
        arr = np.asarray(v)
        if arr.dtype.kind in ("U", "S", "O"):
            uniq, inv = np.unique(arr.astype(str), return_inverse=True)
            dicts[name] = uniq.tolist()
            data = inv.astype(np.int32)
            kind = "str"
        elif arr.dtype.kind == "b":
            data = arr.astype(np.uint8)
            kind = "bool"
        elif arr.dtype.kind in ("i", "u"):
            data = arr.astype(np.int64)
            kind = "i64"
        else:
            kind = float_kind
            data = arr.astype(_KIND_DTYPE[kind])
        if len(data) != n:
            raise ValueError(f"column {name!r} length {len(data)} != {n}")
        meta_cols.append([name, kind])
        bufs.append(np.ascontiguousarray(data).tobytes())
    meta = {"n": n, "cols": meta_cols, "dicts": dicts}
    if nulls:
        mask_names = []
        for name, m in nulls.items():
            if name not in cols:
                raise ValueError(
                    f"null mask for unknown column {name!r}")
            m = np.asarray(m, np.bool_)
            if len(m) != n:
                raise ValueError(
                    f"null mask {name!r} length {len(m)} != {n}")
            mask_names.append(name)
            bufs.append(np.ascontiguousarray(m, np.uint8).tobytes())
        meta["nulls"] = mask_names
    header = json.dumps(meta, separators=(",", ":")).encode()
    out = bytearray(MAGIC)
    out += np.uint32(len(header)).tobytes()
    out += header
    for b in bufs:
        out += b
    return bytes(out)


# string columns one header's scan reports; a header with more takes
# the whole-header parse
_MAX_SCANNED_DICTS = 64

_warned = False


def load_native():
    """The library that scans a header's dictionaries, built and loaded
    on the first call (a server makes it at boot, so that no append
    waits for a compiler); None, said once in the log, where it cannot
    be built: every header then takes the whole-header parse."""
    global _warned
    lib = jsondec.load()
    if lib is None and not _warned:
        _warned = True
        log.warning("native header scan unavailable (cpp/jsondec.cpp did "
                    "not build or load): every columnar header is parsed "
                    "whole, dictionaries and all, holding the GIL")
    return lib


class LazyDictionary(Sequence):
    """A string column's dictionary whose entries are still the header's
    bytes. The native scan has checked them (JSON strings of printable
    ASCII, no escape) and counted them, so `len()` costs nothing; any
    other use (`d[i]`, iteration, `list(d)`, `np.asarray(d)`) makes the
    strings once and keeps the list. `built` says whether that has
    happened: a column no plan reads never pays for it."""

    __slots__ = ("_span", "_n", "_strs")

    def __init__(self, span, n: int):
        self._span = span     # the header's bytes from '[' to ']'
        self._n = n
        self._strs: list[str] | None = None

    def __len__(self) -> int:
        return self._n

    @property
    def built(self) -> bool:
        return self._strs is not None

    def strings(self) -> list[str]:
        """The entries as a list, made on the first call. No entry
        holds a quote or a backslash (the scan refuses both), so the
        bytes between the quotes are the strings, and `","` occurs
        between entries alone: a split is what `json.loads` gives, in
        a third of its time."""
        if self._strs is None:
            self._strs = str(self._span[2:-2], "ascii").split('","') \
                if self._n else []
        return self._strs

    def __getitem__(self, i):
        return self.strings()[i]

    def __iter__(self):
        return iter(self.strings())

    def __eq__(self, other):
        if isinstance(other, (list, LazyDictionary)):
            return self.strings() == list(other)
        return NotImplemented

    __hash__ = None

    def __repr__(self) -> str:
        return f"LazyDictionary(n={self._n}, built={self.built})"


def _dictionary_spans(hdr) -> list[list[int]] | None:
    """(offset of '[', offset past ']', entries) of every array under
    the header's "dicts", in the order met, by the native scan; None
    for a header it does not recognise, or without the library."""
    lib = load_native()
    if lib is None or not len(hdr):
        return None
    spans = np.empty((_MAX_SCANNED_DICTS, 3), np.int64)
    k = lib.jd_header_dicts(
        np.frombuffer(hdr, np.uint8).ctypes.data, len(hdr),
        spans.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        _MAX_SCANNED_DICTS)
    return None if k < 0 else spans[:k].tolist()


def _scan_header(hdr) -> tuple[dict, bool]:
    """The header's JSON object, and whether its dictionaries were
    checked natively: `header["dicts"]` then maps each name to a
    `LazyDictionary` over `hdr`'s bytes, and nothing but the rest of
    the header went through `json.loads`. Raises what `json.loads`
    raises on the whole header."""
    spans = _dictionary_spans(hdr)
    if spans is None:
        return json.loads(bytes(hdr)), False
    # each array gives way to its index: a complete value for a complete
    # value, so what is left parses, or fails, as the whole would; every
    # member of "dicts" is such an index
    rest, lazy, pos = [], [], 0
    for i, (a, b, count) in enumerate(spans):
        rest += (bytes(hdr[pos:a]), b"%d" % i)
        lazy.append(LazyDictionary(hdr[a:b], count))
        pos = b
    rest.append(bytes(hdr[pos:]))
    header = json.loads(b"".join(rest))
    if lazy:
        header["dicts"] = {name: lazy[i]
                           for name, i in header["dicts"].items()}
    return header, True


def _decode(payload) -> tuple[np.ndarray, dict[str, Any],
                              dict[str, np.ndarray] | None, bool]:
    """`decode_columnar_nulls`, and whether the header's dictionaries
    were checked natively (`_scan_header`)."""
    if not is_columnar(payload):
        raise ValueError("not a columnar payload")
    off = len(MAGIC)
    if len(payload) < off + 4:
        raise ValueError("truncated columnar header")
    hlen = int(np.frombuffer(payload, np.uint32, 1, off)[0])
    off += 4
    if len(payload) - off < hlen:
        raise ValueError("columnar header shorter than declared")
    try:
        header, native = _scan_header(
            memoryview(payload)[off: off + hlen])
    except ValueError as e:
        raise ValueError(f"bad columnar header JSON: {e}") from None
    off += hlen
    n = header["n"]
    # forged headers must fail HERE, not deep inside the engine: a
    # negative n would make frombuffer read "the rest", a giant n would
    # over-read; both are rejected by explicit bounds checks
    if not isinstance(n, int) or n < 0:
        raise ValueError(f"bad columnar n={n!r}")
    mask_names = header.get("nulls") or []
    col_names = [name for name, _kind in header["cols"]]
    if not isinstance(mask_names, list) \
            or not set(mask_names) <= set(col_names):
        raise ValueError("null masks name unknown columns")
    need = 8 * n + len(mask_names) * n
    for _, kind in header["cols"]:
        if kind not in _KIND_DTYPE:
            raise ValueError(f"unknown column kind {kind!r}")
        need += np.dtype(_KIND_DTYPE[kind]).itemsize * n
    if len(payload) - off < need:
        raise ValueError("columnar payload shorter than header claims")
    ts = np.frombuffer(payload, np.int64, n, off)
    off += 8 * n
    cols: dict[str, Any] = {}
    for name, kind in header["cols"]:
        dt = _KIND_DTYPE[kind]
        arr = np.frombuffer(payload, dt, n, off)
        off += arr.itemsize * n
        if kind == "bool":
            arr = arr.astype(np.bool_)
        d = header["dicts"].get(name)
        if kind == "str":
            if not isinstance(d, (list, LazyDictionary)):
                raise ValueError(f"string column {name!r} missing dict")
            if n and (int(arr.min()) < 0 or int(arr.max()) >= len(d)):
                raise ValueError(
                    f"string column {name!r} ids out of dict range")
        cols[name] = (kind, arr, d)
    nulls: dict[str, np.ndarray] | None = None
    if mask_names:
        nulls = {}
        for name in mask_names:
            nulls[name] = np.frombuffer(payload, np.uint8, n,
                                        off).astype(np.bool_)
            off += n
    if off != len(payload):
        # exact-bounds contract: trailing undeclared bytes mean either
        # a corrupt/forged block or a NEWER layout this decoder does
        # not understand — refusing beats silently misreading it (an
        # extension section ignored as junk could change row meaning,
        # exactly what unread null masks would have done)
        raise ValueError(
            f"columnar payload longer than header claims "
            f"({len(payload) - off} trailing bytes)")
    return ts, cols, nulls, native


def decode_columnar_nulls(payload) -> tuple[np.ndarray, dict[str, Any],
                                            dict[str, np.ndarray] | None]:
    """payload -> (ts i64[n], {name: (kind, array, dict|None)},
    {name: bool[n]} | None).

    Arrays are zero-copy views into the payload where alignment allows;
    accepts bytes or a memoryview (the framed append path hands the
    frame's payload view straight in). Every declared size is checked
    against the actual bytes BEFORE any array is built — a forged or
    torn payload fails here, not deep inside the engine. A string
    column's dictionary is a list, or a `LazyDictionary` where the
    header's dictionaries were checked natively (module docstring):
    equal to the list once read, and every check here is the same."""
    return _decode(payload)[:3]


def decode_columnar(payload) -> tuple[np.ndarray, dict[str, Any]]:
    """Legacy 2-tuple decode (ts, cols) — null masks, if any, dropped;
    null-aware consumers use decode_columnar_nulls."""
    ts, cols, _nulls = decode_columnar_nulls(payload)
    return ts, cols


def validate_block(payload) -> tuple[int, int, bool]:
    """Bounds-check one columnar block withOUT materializing a single
    row or a single dictionary string: header sizes vs actual bytes,
    column kinds, string dict ranges, null-mask coverage (all via the
    zero-copy decode). Returns (n_rows, last_ts_ms, whether the
    header's dictionaries were checked natively). Raises ValueError on
    anything malformed — the ingress door (colframe.check_block) maps
    that to the typed INVALID_ARGUMENT refusal. Empty blocks are
    refused: an append of zero rows is a producer bug, not a no-op."""
    ts, _cols, _nulls, native = _decode(payload)
    n = int(len(ts))
    if n == 0:
        raise ValueError("empty columnar block (n=0)")
    return n, int(ts[-1]), native


def to_rows(ts: np.ndarray, cols: dict,
            nulls: Mapping[str, np.ndarray] | None = None,
            *, drop_null: bool = False) -> list[dict[str, Any]]:
    """Materialize decoded columns back into per-row dicts (consumers
    that need row shape: joins, sessions, connectors, push-query
    streaming). `nulls` marks missing/null cells -> None. f64 columns
    (native JSON decode, sink emission) intify integral values, matching
    records.record_to_dict's Struct number decoding.

    drop_null=True omits null-masked cells from the row dicts instead of
    carrying explicit Nones — the shape the per-record decode path
    produces for a heterogeneous batch (a record never mentions columns
    it doesn't carry), so executors see the same rows regardless of how
    the producer batched its appends."""
    host = {}
    masks = {}
    for name, (kind, arr, d) in cols.items():
        if kind == "str":
            strs = list(d)  # a lazy dictionary's, made once
            vals = [strs[int(i)] for i in arr]
        elif kind == "f64":
            vals = [int(v) if v.is_integer() else v
                    for v in arr.tolist()]
        else:
            vals = arr.tolist()
        nm = nulls.get(name) if nulls else None
        if nm is not None and nm.any():
            if drop_null:
                masks[name] = nm.tolist()
            else:
                vals = [None if isnull else v
                        for v, isnull in zip(vals, nm.tolist())]
        host[name] = vals
    names = list(host)
    if not names:
        # empty-payload records still ARE records: n empty dicts, like
        # the per-record decode path (record_to_dict returns {})
        return [{} for _ in range(len(ts))]
    rows = [dict(zip(names, vals))
            for vals in zip(*(host[c] for c in names))]
    for name, mask in masks.items():
        for row, isnull in zip(rows, mask):
            if isnull:
                del row[name]
    return rows


def payload_rows(payload: bytes) -> list[dict[str, Any]] | None:
    """Rows from a RAW record payload when it carries a columnar batch;
    None when it is not columnar or is malformed (callers skip it, like
    any other unrecognized RAW record). The one shared expansion for
    every columnar-record consumer (push-query streaming, connectors,
    gateway)."""
    if not is_columnar(payload):
        return None
    try:
        ts, cols, nulls = decode_columnar_nulls(payload)
    except Exception:  # noqa: BLE001 — malformed payloads are skipped
        return None
    # drop_null: a masked cell is a field the producer never sent, so
    # the row shape matches the per-record decode path
    return to_rows(ts, cols, nulls, drop_null=True)


class ColumnarEmit(Sequence):
    """A batch of emitted aggregate rows kept COLUMNAR until the wire.

    The window-close path finalizes whole slot columns on device; this
    carries the result as named columns (numpy arrays, or object arrays
    for strings / TOPK lists) instead of N per-row dicts. Consumers that
    can stay columnar (the stream sink's columnar record, the native
    codec) read `.cols` / `to_payload()` directly; everything else sees
    a lazy Sequence of per-row dicts identical to the legacy list shape
    (len / bool / iterate / index / extend-into-a-list all work), so the
    row materialization happens at most once, at the first row-shaped
    consumer — ideally the wire boundary.
    """

    __slots__ = ("cols", "n", "_rows")

    def __init__(self, cols: Mapping[str, Any], n: int):
        self.cols = dict(cols)
        self.n = int(n)
        self._rows: list[dict[str, Any]] | None = None

    def __len__(self) -> int:
        return self.n

    def rows(self) -> list[dict[str, Any]]:
        """Materialize (and cache) the per-row dict view."""
        if self._rows is None:
            names = list(self.cols)
            if not names:
                self._rows = [{} for _ in range(self.n)]
            else:
                pyd = [v.tolist() if isinstance(v, np.ndarray) else list(v)
                       for v in self.cols.values()]
                self._rows = [dict(zip(names, vals))
                              for vals in zip(*pyd)]
        return self._rows

    def __getitem__(self, i):
        return self.rows()[i]

    def __iter__(self):
        return iter(self.rows())

    # list-concat ergonomics: emitted batches historically were plain
    # lists, so `acc += ex.process(...)` and `rows + more` must keep
    # working when either side is a columnar batch (materializes —
    # callers that care use extend_rows to stay columnar)
    def __add__(self, other):
        return self.rows() + list(other)

    def __radd__(self, other):
        return list(other) + self.rows()

    def __repr__(self) -> str:
        return (f"ColumnarEmit(n={self.n}, "
                f"cols={list(self.cols)})")

    def to_payload(self, ts_ms: int) -> bytes | None:
        """ONE columnar wire record for the whole batch, straight from
        the columns (no per-row dicts); None when a column is not
        wire-encodable (TOPK lists, mixed/None values) — the caller
        falls back to per-row records."""
        if self.n == 0:
            return None
        wire: dict[str, np.ndarray] = {}
        for name, v in self.cols.items():
            arr = np.asarray(v) if not isinstance(v, np.ndarray) else v
            if arr.dtype.kind == "O":
                if not all(isinstance(x, str) for x in arr.tolist()):
                    return None  # None / lists -> per-row records
            elif arr.dtype.kind == "f":
                arr = arr.astype(np.float64, copy=False)
            elif arr.dtype.kind not in ("i", "u", "b", "U", "S"):
                return None
            wire[name] = arr
        ts = np.full(self.n, int(ts_ms), np.int64)
        return encode_columnar(ts, wire, float_kind="f64")


def extend_rows(acc, rows):
    """Accumulate emitted row batches across pipeline stages while
    keeping a LONE ColumnarEmit columnar: acc is None | list |
    ColumnarEmit; returns the new accumulator. Only when a second batch
    arrives does the first materialize into a plain list — the common
    case (one close cycle per drain) reaches the sink columnar."""
    if rows is None or len(rows) == 0:
        return acc
    if acc is None or (isinstance(acc, list) and not acc):
        return rows
    if not isinstance(acc, list):
        acc = list(acc)
    acc.extend(rows)
    return acc


def rows_to_payload(rows: list[Mapping[str, Any]],
                    ts_ms: int) -> bytes | None:
    """One columnar payload for a homogeneous batch of flat scalar rows
    (the steady-state changelog / window-close output), or None when the
    rows are not uniformly shaped (heterogeneous keys, NULLs, list
    values like TOPK) — the caller falls back to per-row records.

    Emitting the sink batch as ONE columnar record instead of N protobuf
    Structs keeps the server's emit stage off the per-row Python path
    (the reference serializes one protobuf per sunk record,
    HStore.hs:152-163). A ColumnarEmit batch encodes straight from its
    columns — no per-row dicts at all."""
    if isinstance(rows, ColumnarEmit):
        return rows.to_payload(ts_ms)
    if not rows:
        return None
    names = list(rows[0])
    nlen = len(names)
    if any(len(r) != nlen for r in rows):
        return None
    cols: dict[str, Any] = {}
    try:
        for c in names:
            vals = [r[c] for r in rows]
            v0 = vals[0]
            if isinstance(v0, bool):
                if not all(isinstance(v, bool) for v in vals):
                    return None
                cols[c] = np.asarray(vals, np.bool_)
            elif isinstance(v0, int):
                if not all(type(v) is int for v in vals):
                    # ints mixed with floats -> f64 keeps exactness of
                    # both (i64 would truncate, f32 would round counts)
                    if not all(isinstance(v, (int, float))
                               and not isinstance(v, bool) for v in vals):
                        return None
                    cols[c] = np.asarray(vals, np.float64)
                else:
                    cols[c] = np.asarray(vals, np.int64)
            elif isinstance(v0, float):
                if not all(isinstance(v, (int, float))
                           and not isinstance(v, bool) for v in vals):
                    return None
                cols[c] = np.asarray(vals, np.float64)
            elif isinstance(v0, str):
                if not all(isinstance(v, str) for v in vals):
                    return None
                cols[c] = np.asarray(vals, object)
            else:
                return None  # None / lists / nested -> per-row records
    except (KeyError, OverflowError):
        return None
    ts = np.full(len(rows), ts_ms, np.int64)
    return encode_columnar(ts, cols, float_kind="f64")
