"""The seeded sensor-stream generator: one function of (size, seed,
frame index), so the producer's encoder processes and the reference
after the window regenerate the same frames without sharing a byte.
A configuration names it with `"generator": "sensor"`; `run.py`, the
producer and the reader ask this module for everything that is the
stream's and the query's shape (the duties are listed in
`benchmarks/README.md`): `streams`, `warm_frames`, `frame`, `closers`,
`pulls`, `reader_pull`. The references read `draw`, the same frame
before its keys are named.

A stream is a sequence of frames of `frame_rows` events. Frame `i` lies
in event-time pane `i // frames_per_pane` (a pane is one window advance,
`advance_ms`); its event times are uniform inside that pane, so events
are out of order within a pane and never late. Keys are uniform over
`keys`; values are normal(mean, std) rounded to `decimals` (the
codec-canonical one-decimal f32 form, copied from `chip_smoke.py`
`tumbling_frame`).

The first `warm_frames(size)` frames are the warm phase: they carry
every key once (so the key table reaches its final capacity before the
window) and fill pane 0, plus one call's worth of frames in pane 1 so
that one close cycle runs in set-up. Measured frames start at pane 2.
Every seed gives the same number of frames of the same size at the same
event density — only the keys, values and times inside a frame differ.
"""

from __future__ import annotations

import functools

import numpy as np

BASE = 1_700_000_000_000  # absolute epoch ms of pane 0
WARM_PANES = 2            # panes 0 and 1 belong to set-up


def frames_per_pane(size: dict) -> int:
    n, rem = divmod(size["events_per_advance"], size["frame_rows"])
    if rem or n < 1:
        raise ValueError("events_per_advance must be a multiple of "
                         "frame_rows")
    return n


def warm_frames(size: dict) -> int:
    """Frames of the warm phase: enough all-key frames in pane 0 to name
    every key (whole calls), then one call in pane 1."""
    per_call = size["frames_per_call"]
    cover = -(-size["keys"] // size["frame_rows"])
    cover = -(-cover // per_call) * per_call
    return cover + per_call


def pane_of(size: dict, index: int) -> int:
    """Event-time pane of frame `index`."""
    n_warm = warm_frames(size)
    if index < n_warm:
        return 0 if index < n_warm - size["frames_per_call"] else 1
    return WARM_PANES + (index - n_warm) // frames_per_pane(size)


def draw(size: dict, seed: int, index: int) -> tuple:
    """Frame `index` of the stream for `seed`, keys still ids:
    (key ids i32[n], value tenths i32[n], values f32[n], times i64[n])."""
    n = size["frame_rows"]
    pane = pane_of(size, index)
    rng = np.random.default_rng([int(seed), 1, index])
    if index < warm_frames(size) - size["frames_per_call"]:
        # warm: every key in turn, so pane 0 names the whole key table
        kids = ((np.arange(n, dtype=np.int64) + index * n)
                % size["keys"]).astype(np.int32)
    else:
        kids = rng.integers(0, size["keys"], n).astype(np.int32)
    val = size["value"]
    scale = 10 ** int(val["decimals"])
    tenths = np.rint(rng.normal(val["mean"], val["std"], n)
                     * scale).astype(np.int32)
    temps = tenths.astype(np.float32) * np.float32(1.0 / scale)
    adv = size["advance_ms"]
    ts = BASE + pane * adv + rng.integers(0, adv, n)
    return kids, tenths, temps, ts.astype(np.int64)


@functools.lru_cache(maxsize=4)
def _names(fmt: str, keys: int) -> np.ndarray:
    return np.array([fmt.format(k) for k in range(keys)])


def key_names(size: dict) -> np.ndarray:
    """Every key's name, by id (made once a process)."""
    return _names(size["key_format"], size["keys"])


def streams(size: dict) -> list[dict]:
    """The streams to create, each with the schema its frames carry."""
    return [{"name": size["stream"], "schema": dict(size["schema"])}]


def frame(size: dict, seed: int, index: int) -> tuple:
    """Frame `index` as it is sent: (the stream it goes to, ts, cols as
    the client library's `encode_batch` takes them, the events it
    carries). Indices are in the order the one ordered producer sends."""
    kids, _tenths, temps, ts = draw(size, seed, index)
    key_col, val_col = size["columns"]
    return (size["stream"], ts,
            {key_col: key_names(size)[kids], val_col: temps}, len(ts))


def closers(size: dict, n_frames: int) -> list[tuple]:
    """One record a stream, past the end of every window that holds an
    event of the last frame's pane: it closes them all (GRACE 0). Each
    has a frame's form."""
    last_pane = pane_of(size, n_frames - 1)
    ts = BASE + (last_pane + 1) * size["advance_ms"] + size["size_ms"]
    key_col, val_col = size["columns"]
    return [(size["stream"], np.array([ts], np.int64),
             {key_col: key_names(size)[:1],
              val_col: np.array([size["value"]["mean"]], np.float32)}, 1)]


def pulls(size: dict, n_frames: int) -> dict:
    """The answers to pull once `n_frames` frames are consumed:
    statements to run before and after the closers, each with the
    windows (by first pane) whose every key must then be there, and the
    event time at and past which a window is still open. `if_rows`: the
    windows count as due only if the statement returned a row (the view
    keeps `view_rows_kept` rows: at 100 000 keys, one window)."""
    view = size["view"]
    width = size["size_ms"] // size["advance_ms"]
    last_pane = pane_of(size, n_frames - 1)
    newest_closed = last_pane - width
    n_windows = last_pane + width
    fit = max(1, size["view_rows_kept"] // size["keys"] - 1)
    return {
        "before": [{"sql": f"SELECT * FROM {view} WHERE winStart = "
                           f"{BASE + newest_closed * size['advance_ms']};",
                    "complete": [newest_closed], "if_rows": True}],
        "after": [{"sql": f"SELECT * FROM {view};",
                   "complete": list(range(
                       last_pane - min(n_windows, fit) + 1,
                       last_pane + 1))}],
        "horizon": int(closers(size, n_frames)[0][1][0]),
    }


def reader_pull(size: dict, rng: np.random.Generator) -> dict:
    """One draw of the reader: the statement, and what the reference
    needs to know of it (kept beside the rows it returned)."""
    k = int(rng.integers(0, size["keys"]))
    return {"sql": f"SELECT * FROM {size['view']} WHERE "
                   f"{size['columns'][0]} = '{key_names(size)[k]}';",
            "key": k}
