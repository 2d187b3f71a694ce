"""A toy generator, kept with the tests: everything the sensor generator
is not. Another schema (`site STRING, bytes INT`), Zipf keys, and events
out of pane order inside the grace the configuration states: the first
half of a pane's frames keep their event times inside the pane's first
`grace_ms` and carry one event in five back in the pane before, whose
window is still open (the watermark has not passed its end by the
grace); the second half fill the whole pane. Nothing is ever late.

It answers every duty `benchmarks/README.md` lists and shares no line
with `benchmarks/generators/sensor.py`; `run.py`, the producer and the
reader reach it through the fixture manifest beside it.
"""

from __future__ import annotations

import numpy as np

T0 = 1_800_000_000_000  # absolute epoch ms of pane 0
BACK_SHARE = 0.2


def frames_per_pane(size: dict) -> int:
    return size["events_per_advance"] // size["frame_rows"]


def warm_frames(size: dict) -> int:
    """Pane 0 whole (every site once, then Zipf), and one call in pane
    1 past its grace, so that pane 0's window closes in set-up."""
    per_call = size["frames_per_call"]
    return -(-frames_per_pane(size) // per_call) * per_call + per_call


def pane_of(size: dict, index: int) -> int:
    n_warm = warm_frames(size)
    if index < n_warm:
        return 0 if index < n_warm - size["frames_per_call"] else 1
    return 2 + (index - n_warm) // frames_per_pane(size)


def site_names(size: dict) -> np.ndarray:
    return np.array([f"site-{k:04d}.example" for k in range(size["keys"])])


def _zipf(size: dict) -> np.ndarray:
    p = 1.0 / np.arange(1, size["keys"] + 1) ** size["zipf_s"]
    return p / p.sum()


def draw(size: dict, seed: int, index: int) -> tuple:
    """(site ids, bytes, times) of frame `index`."""
    n = size["frame_rows"]
    adv, grace = size["advance_ms"], size["grace_ms"]
    pane = pane_of(size, index)
    rng = np.random.default_rng([int(seed), 77, index])
    n_warm = warm_frames(size)
    if index == 0:
        kids = np.arange(n, dtype=np.int64) % size["keys"]
    else:
        kids = rng.choice(size["keys"], n, p=_zipf(size))
    nbytes = rng.integers(0, 1 << 10, n).astype(np.int64)  # sums exact in f32
    lo = T0 + pane * adv
    if index < n_warm:
        measured_pos = index  # warm: pane 0 in pane order, pane 1 late
        early = pane == 0 and index < frames_per_pane(size) // 2
    else:
        measured_pos = (index - n_warm) % frames_per_pane(size)
        early = measured_pos < frames_per_pane(size) // 2
    if pane == 1 and index < n_warm:
        ts = lo + grace + rng.integers(0, adv - grace, n)
    elif early:
        ts = lo + rng.integers(0, grace, n)
        if pane > 0:
            back = rng.random(n) < BACK_SHARE
            ts = np.where(back, lo - adv + rng.integers(0, adv, n), ts)
    else:
        ts = lo + rng.integers(0, adv, n)
    return kids.astype(np.int64), nbytes, ts.astype(np.int64)


def streams(size: dict) -> list[dict]:
    return [{"name": size["stream"], "schema": dict(size["schema"])}]


def frame(size: dict, seed: int, index: int) -> tuple:
    kids, nbytes, ts = draw(size, seed, index)
    return (size["stream"], ts,
            {"site": site_names(size)[kids], "bytes": nbytes}, len(ts))


def closers(size: dict, n_frames: int) -> list[tuple]:
    last = pane_of(size, n_frames - 1)
    ts = T0 + (last + 1) * size["advance_ms"] + size["grace_ms"]
    return [(size["stream"], np.array([ts], np.int64),
             {"site": site_names(size)[:1],
              "bytes": np.array([0], np.int64)}, 1)]


def pulls(size: dict, n_frames: int) -> dict:
    last = pane_of(size, n_frames - 1)
    return {"before": [],
            "after": [{"sql": f"SELECT * FROM {size['view']};",
                       "complete": list(range(max(0, last - 20),
                                              last + 1))}],
            "horizon": int(closers(size, n_frames)[0][1][0])}


def reader_pull(size: dict, rng: np.random.Generator) -> dict:
    k = int(rng.choice(size["keys"], p=_zipf(size)))
    site = str(site_names(size)[k])
    return {"sql": f"SELECT * FROM {size['view']} WHERE site = '{site}';",
            "site": site}
