"""Group-key value -> key id table for ONE group column (the task's
`key_encode` stage, server/tasks.py `_columnar_key_ids`).

Derived state: the executor's `_key_ids` / `_key_rev` stay the truth and
assign every id (`key_id_for`, `key_ids_for`); this table only remembers
what they said, and is rebuilt from `_key_rev` whenever it is out of step
with it (an executor restored from a snapshot, keys registered on another
path), so nothing of it is persisted. It is bounded by the live key set:
only canonical values, the ones `_key_rev` holds, are kept, and a key the
executor retires (`QueryExecutor._retire_keys`: every window it was named
in has closed, and its id goes to the next new key) is forgotten in the
same breath (`forget`), so the table never answers with an id that has
passed to another key. An id the table answers with is the caller's to
protect until its batch is dated: `pin_keys`, then `note_key_use`.

Two forms behind one interface. With the native library
(cpp/encode.cpp `kt_*`) strings live there and a batch's whole
dictionary resolves in ONE call with the GIL released. Without it the
same table is a plain dict consulted at C level (`map(dict.get, ...)`),
said once in the log. Values that are not strings (None, numbers, bools)
always live in the dict.
"""

from __future__ import annotations

import itertools
from typing import Any

import numpy as np

from hstream_tpu.common.logger import get_logger
from hstream_tpu.engine import codec_native

log = get_logger(__name__)

_warned = False


def _ptr(arr: np.ndarray):
    return arr.ctypes.data_as(codec_native._p_i32)


def _joined(strs: list[str]) -> bytes:
    # surrogatepass: json.loads hands out lone surrogates, and distinct
    # strings must stay distinct bytes
    return "\0".join(strs).encode("utf-8", "surrogatepass")


class KeyTable:
    def __init__(self) -> None:
        global _warned
        self._h = None
        self._lib = codec_native.load()
        if self._lib is not None:
            self._h = self._lib.kt_new()
        elif not _warned:
            _warned = True
            log.warning("native key table unavailable (cpp/encode.cpp did "
                        "not build or load): string group keys resolve "
                        "through a dict, holding the GIL")
        self._memo: dict[Any, int] = {}
        # one value's id, None where unknown (the per-value path)
        self.get = self._memo.get
        # len(_key_rev) this table was last in step with
        self.covered = 0
        # the native form holds keys the executor has retired since
        self._stale = False
        self.lookups = 0
        self.misses = 0

    def __del__(self) -> None:
        if self._h is not None:
            self._lib.kt_free(self._h)
            self._h = None

    def __len__(self) -> int:
        n = len(self._memo)
        return n if self._h is None else n + self._lib.kt_size(self._h)

    def sync(self, key_rev: list) -> None:
        """Rebuild from the executor's `_key_rev` (None where an id is
        free) if out of step."""
        if self.covered == len(key_rev) and not self._stale:
            return
        self._stale = False
        self._memo.clear()
        if self._h is not None:
            self._lib.kt_free(self._h)
            self._h = self._lib.kt_new()
        native: list[tuple] = []   # (string, id): the library's
        other: list[tuple] = []    # (value, id): the dict's
        for i, k in enumerate(key_rev):
            if k is None:
                continue
            v = k[0]
            if self._h is not None and type(v) is str and "\0" not in v:
                native.append((v, i))
            else:
                other.append((v, i))
        self._memo.update(other)
        if native:
            self._insert([v for v, _i in native],
                         np.asarray([i for _v, i in native], np.int32))
        self.covered = len(key_rev)

    def forget(self, keys: list[tuple]) -> None:
        """Keys the executor has retired: their ids pass to other
        keys, so no lookup may answer with them. The native form has no
        erase: it is rebuilt from `_key_rev` at the next `sync`."""
        pop = self._memo.pop
        elsewhere = False   # a key the dict did not hold
        for k in keys:
            if pop(k[0], None) is None:
                elsewhere = True
        if elsewhere and self._h is not None:
            self._stale = True

    def resolve(self, d: list) -> "np.ndarray | None":
        """Key ids of a whole string dictionary, -1 where the table has
        none. None when `d` cannot go through in one piece (an entry that
        is no string, or holds a NUL): the caller takes the per-value
        path."""
        n = len(d)
        if self._h is None:
            try:
                out = np.fromiter(
                    map(self.get, d, itertools.repeat(-1)), np.int32, n)
            except TypeError:  # an unhashable entry
                return None
        else:
            try:
                buf = _joined(d)
            except TypeError:
                return None
            out = np.empty(n, np.int32)
            if self._lib.kt_resolve(self._h, buf, len(buf), n,
                                    _ptr(out)) != n:
                return None
        self.lookups += n
        return out

    def register_strings(self, ex, strs: list[str]) -> list[int]:
        """The misses of `resolve`, in the order given: each gets its id
        from the executor and is remembered."""
        kids = ex.key_ids_for([(s,) for s in strs], canonical=True)
        self.misses += len(strs)
        if self._h is None:
            self._memo.update(zip(strs, kids.tolist()))
        else:
            self._insert(strs, kids)
        self.covered = len(ex._key_rev)
        return kids.tolist()

    def register_values(self, ex, vals: list, *,
                        canonical: bool) -> np.ndarray:
        """The values `get` did not know, in the order given, in one
        registration; each is remembered if it is the canonical value
        itself (`canonical`: none is a float, so all are)."""
        kids = ex.key_ids_for([(v,) for v in vals], canonical=canonical)
        self.misses += len(vals)
        if canonical:
            self._memo.update(zip(vals, kids.tolist()))
        else:
            rev = ex._key_rev
            self._memo.update((v, k) for v, k in zip(vals, kids.tolist())
                              if rev[k][0] == v)
        self.covered = len(ex._key_rev)
        return kids

    def register(self, ex, v: Any) -> int:
        """One value `get` did not know: its id from the executor,
        remembered if `v` is the canonical value itself (a float the
        executor canonicalised to another is asked for again)."""
        kid = ex.key_id_for((v,))
        self.misses += 1
        if ex._key_rev[kid][0] == v:
            self._memo[v] = kid
        self.covered = len(ex._key_rev)
        return kid

    def take_counts(self) -> tuple[int, int]:
        """(entries looked up, entries that went through `key_id_for`)
        since the last call."""
        out = (self.lookups, self.misses)
        self.lookups = self.misses = 0
        return out

    def _insert(self, strs: list[str], kids: np.ndarray) -> None:
        # refused only when the arena is full (4 GiB of key bytes): the
        # table then lacks these entries, which is safe, since a miss
        # asks the executor again
        buf = _joined(strs)
        self._lib.kt_insert(self._h, buf, len(buf), len(strs), _ptr(kids))
