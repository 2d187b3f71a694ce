"""Group-key value -> key id table for ONE group column (the task's
`key_encode` stage, server/tasks.py `_columnar_key_ids`).

Derived state: the executor's `_key_ids` / `_key_rev` stay the truth and
assign every id (`key_id_for`); this table only remembers what they said,
and is rebuilt from `_key_rev` whenever it is out of step with it (an
executor restored from a snapshot, keys registered on another path), so
nothing of it is persisted. It is bounded by the key space itself: only
canonical values, the ones `_key_rev` holds, are kept.

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
        self.lookups = 0
        self.misses = 0

    def __del__(self) -> None:
        if self._h is not None:
            self._lib.kt_free(self._h)
            self._h = None

    def __len__(self) -> int:
        n = len(self._memo)
        return n if self._h is None else n + self._lib.kt_size(self._h)

    def sync(self, key_rev: list[tuple]) -> None:
        """Rebuild from the executor's `_key_rev` if out of step."""
        if self.covered == len(key_rev):
            return
        self._memo.clear()
        if self._h is not None:
            self._lib.kt_free(self._h)
            self._h = self._lib.kt_new()
        vals = [k[0] for k in key_rev]
        if self._h is None:
            self._memo.update(zip(vals, range(len(vals))))
        else:
            native = [type(v) is str and "\0" not in v for v in vals]
            kids = np.flatnonzero(native).astype(np.int32)
            self._insert([vals[i] for i in kids.tolist()], kids)
            self._memo.update((v, i) for i, v in enumerate(vals)
                              if not native[i])
        self.covered = len(key_rev)

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
        kids = [ex.key_id_for((s,)) for s in strs]
        self.misses += len(strs)
        if self._h is None:
            self._memo.update(zip(strs, kids))
        else:
            self._insert(strs, np.asarray(kids, np.int32))
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
