"""A view with QUALIFY ... OVER on the served path, at the NEXmark Q5
deployment's toy sizes: a pull that does not bound `winEnd` (`SELECT *
FROM hot_items`, or a WHERE the read plane cannot prove closed-only) is
answered from the closed rows alone while windows are open: an open
window has no row yet, its extreme being known when it closes. The same
statement without its QUALIFY shows every group of its open windows, as
any view does.

One served run a statement: the generator's frames appended over gRPC,
one call each, through the door and the store, pulled before the closer
(five windows open) and after it.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.generators import nexmark_q5 as gen  # noqa: E402
from benchmarks.harness import manifest, served  # noqa: E402
from benchmarks.references import hot_items as ref  # noqa: E402
from hstream_tpu.server.main import serve  # noqa: E402

with open(os.path.join(ROOT, "benchmarks", "configs",
                       "nexmark_q5.json")) as _f:
    CONFIG = json.load(_f)
DRY = manifest.size_of(CONFIG, True)
N_FRAMES = gen.warm_frames(DRY) + 12
SEED = 2**31 + 47
VIEW = DRY["view"]
UNBOUNDED = [
    f"SELECT * FROM {VIEW};",
    f"SELECT * FROM {VIEW} WHERE num > 0;",
    f"SELECT auction, num, winStart, winEnd FROM {VIEW} WHERE winEnd > 0;",
]


def _served_run(sql: str) -> dict:
    server, ctx = serve("127.0.0.1", 0, "mem://")
    client = served.Client(ctx.port)
    out: dict = {}
    try:
        for st in gen.streams(DRY):
            client.sql(f"CREATE STREAM {st['name']};")
        client.sql(sql)
        task = served.wait_task(ctx, f"view-{VIEW}")
        for i in range(N_FRAMES):
            client.append_call([gen.frame(DRY, SEED, i)])
        served.wait_consumed(ctx, task, 300)
        plan = gen.pulls(DRY, N_FRAMES)
        with task.state_lock:
            ex = task.executor
            out["open"] = sorted(ex._open)
            out["live"] = ex.peek()
        out["closed"] = client.sql(plan["before"][0]["sql"])
        out["unbounded"] = [client.sql(q) for q in UNBOUNDED]
        for closer in gen.closers(DRY, N_FRAMES):
            client.append_call([closer])
        served.wait_consumed(ctx, task, 300)
        out["after"] = client.sql(UNBOUNDED[0])
        out["horizon"] = plan["horizon"]
    finally:
        client.close()
        server.stop(grace=1)
        ctx.shutdown()
    return out


def _rows(rows: list) -> set:
    return {(r["winStart"], r["winEnd"], r["auction"], r["num"])
            for r in rows}


@pytest.fixture(scope="module")
def run():
    return _served_run(DRY["sql"])


@pytest.fixture(scope="module")
def hot():
    return ref.hot_items(DRY, SEED, N_FRAMES)


def test_windows_are_open_and_the_peek_gives_nothing(run):
    per = DRY["size_ms"] // DRY["advance_ms"]
    assert len(run["open"]) == per
    assert run["live"] == []
    assert len(run["closed"]) >= 5


@pytest.mark.parametrize("which", range(len(UNBOUNDED)),
                         ids=["star", "where_num", "where_winEnd"])
def test_a_pull_that_bounds_nothing_gives_the_closed_rows_alone(run,
                                                                which):
    got = run["unbounded"][which]
    assert _rows(got) == _rows(run["closed"])
    assert len(got) == len(run["closed"])
    last = gen.last_time(DRY, N_FRAMES)
    assert all(r["winEnd"] <= last for r in got)   # none of an open one


def test_the_comparison_holds_such_a_pull_to_the_reference(run, hot):
    srv = {"final": run["closed"], "complete": ["before_closer"],
           "pulls": [{"rows": rows} for rows in run["unbounded"]],
           "horizon": run["horizon"]}
    assert set(ref.compare(DRY, SEED, N_FRAMES, srv, hot).values()) == {0}


def test_after_the_closer_every_window_has_its_row(run, hot):
    srv = {"final": run["after"], "complete": ["after_closer"],
           "pulls": [], "horizon": run["horizon"]}
    assert set(ref.compare(DRY, SEED, N_FRAMES, srv, hot).values()) == {0}
    assert {r["winStart"] for r in run["after"]} >= set(hot)


def test_without_the_filter_a_pull_shows_the_open_windows_groups(hot):
    """The contrast, and what the comparison makes of such rows: every
    group of an open window that is no final winner is a row too many,
    a leader so far a count that is not the final one."""
    out = _served_run(DRY["sql"].split(" QUALIFY ")[0] + ";")
    last = gen.last_time(DRY, N_FRAMES)
    live = [r for r in out["unbounded"][0] if r["winEnd"] > last]
    assert len(out["live"]) == len(live) > len(out["open"])
    srv = {"final": [], "complete": [], "pulls": [{"rows": live}],
           "horizon": out["horizon"]}
    got = ref.compare(DRY, SEED, N_FRAMES, srv, hot)
    # all but those that already stand at a final winner's final count
    winners = sum(len(hot[ws]) for ws in out["open"])
    assert got["window_mismatch"] == 0
    assert got["rows_extra"] >= len(live) - winners > 0
    assert got["rows_extra"] + got["num_mismatch"] \
        >= len(live) - winners
