"""A toy generator with two streams, kept with the tests: `lhs` and
`rhs` have schemas and frame sizes of their own, and the generator fixes
how they interleave in the one producer's order: calls go lhs, lhs, rhs,
and again. Every row carries its frame's index, so a log read back says
which frames it holds and in what order. It has no query: the test
drives the producer process against a server and reads the logs."""

from __future__ import annotations

import numpy as np

T0 = 1_900_000_000_000
PATTERN = ("lhs", "lhs", "rhs")
ROWS = {"lhs": 256, "rhs": 96}


def streams(size: dict) -> list[dict]:
    return [{"name": "lhs", "schema": {"k": "INT", "seq": "INT"}},
            {"name": "rhs", "schema": {"k": "INT", "seq": "INT",
                                       "w": "FLOAT"}}]


def warm_frames(size: dict) -> int:
    return 3 * size["frames_per_call"]  # one turn of the pattern


def stream_of(size: dict, index: int) -> str:
    return PATTERN[(index // size["frames_per_call"]) % len(PATTERN)]


def frame(size: dict, seed: int, index: int) -> tuple:
    stream = stream_of(size, index)
    n = ROWS[stream]
    rng = np.random.default_rng([int(seed), 5, index])
    ts = T0 + index * 10 + np.sort(rng.integers(0, 10, n))
    cols = {"k": rng.integers(0, size["keys"], n).astype(np.int64),
            "seq": np.full(n, index, np.int64)}
    if stream == "rhs":
        cols["w"] = rng.integers(0, 100, n).astype(np.float32)
    return stream, ts.astype(np.int64), cols, n


def closers(size: dict, n_frames: int) -> list[tuple]:
    ts = np.array([T0 + n_frames * 10 + 60_000], np.int64)
    return [("lhs", ts, {"k": np.array([0], np.int64),
                         "seq": np.array([-1], np.int64)}, 1),
            ("rhs", ts, {"k": np.array([0], np.int64),
                         "seq": np.array([-1], np.int64),
                         "w": np.array([0.0], np.float32)}, 1)]
