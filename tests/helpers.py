"""Shared test helpers: readiness waits instead of sleeps.

SURVEY §4 flags the reference's sleep-based test sync ("FIXME: requires
a notification mechanism", RunSQLSpec.hs:54); QueryTask.attached is
that mechanism — set once the reader is attached to every source at its
start LSN (tasks.attached_lsns)."""

from __future__ import annotations

import time


def wait_attached(ctx, query_id: str, timeout: float = 10.0):
    """Block until the query's task is registered AND attached to its
    source streams; returns the task."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        task = ctx.running_queries.get(query_id)
        if task is not None and task.attached.wait(0.05):
            return task
        time.sleep(0.01)
    raise TimeoutError(f"query {query_id!r} never attached "
                       f"(running: {list(ctx.running_queries)})")


def wait_any_attached(ctx, timeout: float = 10.0, *, exclude=()):
    """Block until a running query task OUTSIDE `exclude` is attached
    (push queries have generated ids the test cannot predict; pass the
    pre-existing query ids so a stale attached task cannot satisfy the
    wait)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        for qid, task in list(ctx.running_queries.items()):
            if qid in exclude:
                continue
            if getattr(task, "attached", None) is not None \
                    and task.attached.is_set():
                return task
        time.sleep(0.01)
    raise TimeoutError("no (new) query task attached")


def fresh_code_columns(ex) -> list:
    """A session executor's decode columns built from scratch, the plain
    way: one walk of the WHOLE code dictionary a group column, a
    sharded compaction's holes left None."""
    import numpy as np

    out = []
    for g in range(len(ex.group_cols)):
        arr = np.empty(len(ex._code_rev), object)
        for i, key in enumerate(ex._code_rev):
            if key is not None:
                arr[i] = key[g]
        out.append(arr)
    return out


def typed(values) -> list:
    """Values with their types: 1, 1.0 and True are three keys."""
    return [(type(v).__name__, v) for v in values]


def assert_code_columns_fresh(ex) -> None:
    """The incrementally kept decode columns equal a from-scratch build
    of `_code_rev`, value for value and type for type."""
    got, want = ex._code_rev_columns(), fresh_code_columns(ex)
    assert len(got) == len(want) == len(ex.group_cols)
    for g, w in zip(got, want):
        assert g.dtype == object and g.shape == w.shape
        assert typed(g.tolist()) == typed(w.tolist())
