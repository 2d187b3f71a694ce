"""What the plain references share: the control's lower precision, and
matching served rows to key indices."""

from __future__ import annotations

import numpy as np


def lower(values: np.ndarray, precision: str) -> np.ndarray:
    """Values as the stated precision holds them: f32 is the
    configuration's; bf16 is the control's (the nearest below)."""
    if precision == "f32":
        return values
    if precision == "bf16":
        import ml_dtypes

        return values.astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def match(rows: list, lookup: dict, key_col: str) -> tuple:
    """(key index of each known row, those rows, rows that count as
    missing: of an unknown key, or a key given twice)."""
    idx = np.fromiter((lookup.get(r[key_col], -1) for r in rows),
                      np.int64, len(rows))
    known = idx >= 0
    bad = int((~known).sum())
    idx = idx[known]
    rows = [r for r, k in zip(rows, known) if k]
    bad += len(idx) - len(set(idx.tolist()))
    return idx, rows, bad
