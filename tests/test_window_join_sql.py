"""`JOIN ... WITHIN WINDOW`: the one spelling of a tumbling-window
stream-stream join (a pair joins where both records fall in the same
window of the statement's own GROUP BY window), what EXPLAIN says of it,
and every other use refused by name. `WITHIN (<interval>)` keeps its
meaning."""

import pytest

from hstream_tpu.common.errors import (
    SQLCodegenError,
    SQLParseError,
    SQLValidateError,
)
from hstream_tpu.engine.join import JoinExecutor
from hstream_tpu.engine.plan import single_chip_reason
from hstream_tpu.sql import stream_codegen
from hstream_tpu.sql.codegen import (
    explain_text,
    make_executor,
    mesh_exclusion_reason,
)

Q8 = ("CREATE VIEW new_users AS SELECT person.id, person.name, "
      "COUNT(*) AS auctions FROM person INNER JOIN auction WITHIN WINDOW "
      "ON person.id = auction.seller GROUP BY person.id, person.name, "
      "TUMBLING (INTERVAL 10 SECOND) GRACE BY INTERVAL 0 SECOND;")


def test_the_statement_lowers_to_a_window_join():
    plan = stream_codegen(Q8).select
    assert plan.join.window and plan.join.within is None
    assert not plan.join.table
    assert plan.join.right.name == "auction" and plan.source == "person"
    ex = make_executor(plan)
    assert isinstance(ex, JoinExecutor) and ex.window_join
    assert ex.within == 10_000 and ex.mesh is None
    assert [g.name for g in plan.node.group_keys] == ["person.id",
                                                       "person.name"]


def test_aliases_and_a_bare_join_keyword():
    plan = stream_codegen(
        "CREATE VIEW v AS SELECT p.id, COUNT(*) AS n FROM person AS p "
        "JOIN auction AS a WITHIN WINDOW ON p.id = a.seller "
        "GROUP BY p.id, TUMBLING (INTERVAL 1 SECOND);").select
    assert plan.join.window and plan.join.join_type == "INNER"
    assert make_executor(plan).within == 1_000


def test_explain_shows_a_window_join():
    text = explain_text(stream_codegen(Q8))
    assert "JOIN auction WITHIN WINDOW [window join" in text
    assert "TUMBLING 10000ms" in text
    assert "minimum over both sources" in text
    assert "MESH: single-chip" in text and "window join" in text


def test_the_interval_join_keeps_its_meaning():
    plan = stream_codegen(
        "SELECT l.k, COUNT(*) AS c FROM l INNER JOIN r WITHIN (INTERVAL "
        "1 SECOND) ON l.k = r.k GROUP BY l.k, TUMBLING (INTERVAL 10 "
        "SECOND) EMIT CHANGES;")
    assert not plan.join.window and plan.join.within.ms == 1_000
    ex = make_executor(plan)
    assert not ex.window_join and ex.within == 1_000
    assert "WITHIN 1000ms" in explain_text(plan)
    assert mesh_exclusion_reason(plan) is None


def test_a_mesh_is_refused_by_name():
    plan = stream_codegen(Q8).select
    reason = single_chip_reason(plan.node, plan.join)
    assert reason is not None and "window join" in reason
    assert mesh_exclusion_reason(plan) == reason
    assert single_chip_reason(plan.node) is None  # the aggregate shards


HEAD = "SELECT person.id, COUNT(*) AS n FROM person "
TAIL = "GROUP BY person.id, TUMBLING (INTERVAL 10 SECOND)"
ON = "ON person.id = auction.seller "
REFUSED = {
    "no_window": (
        "CREATE VIEW v AS " + HEAD + "INNER JOIN auction WITHIN WINDOW "
        + ON + "GROUP BY person.id;",
        SQLValidateError, "GROUP BY window"),
    "hopping": (
        "CREATE VIEW v AS " + HEAD + "INNER JOIN auction WITHIN WINDOW "
        + ON + "GROUP BY person.id, HOPPING (INTERVAL 10 SECOND, INTERVAL "
        "2 SECOND);", SQLValidateError, "HOPPING"),
    "session": (
        "CREATE VIEW v AS " + HEAD + "INNER JOIN auction WITHIN WINDOW "
        + ON + "GROUP BY person.id, SESSION (INTERVAL 10 SECOND);",
        SQLValidateError, "SESSION"),
    "left": (
        "CREATE VIEW v AS " + HEAD + "LEFT JOIN auction WITHIN WINDOW "
        + ON + TAIL + ";", SQLValidateError, "LEFT JOIN"),
    "outer": (
        "CREATE VIEW v AS " + HEAD + "OUTER JOIN auction WITHIN WINDOW "
        + ON + TAIL + ";", SQLValidateError, "OUTER JOIN"),
    "emit_changes": (
        HEAD + "INNER JOIN auction WITHIN WINDOW " + ON + TAIL
        + " EMIT CHANGES;", SQLValidateError, "EMIT CHANGES"),
    "join_table": (
        "CREATE VIEW v AS " + HEAD + "INNER JOIN TABLE(auction) WITHIN "
        "WINDOW " + ON + TAIL + ";", SQLParseError, "ON"),
    "second_join": (
        "CREATE VIEW v AS " + HEAD + "INNER JOIN auction WITHIN WINDOW "
        + ON + "INNER JOIN bid WITHIN WINDOW ON person.id = bid.bidder "
        + TAIL + ";", SQLParseError, "second JOIN"),
    "second_interval_join": (
        HEAD + "INNER JOIN auction WITHIN (INTERVAL 1 SECOND) " + ON
        + "JOIN bid WITHIN (INTERVAL 1 SECOND) ON person.id = bid.bidder "
        + TAIL + " EMIT CHANGES;", SQLParseError, "second JOIN"),
    "no_interval_and_no_window": (
        "CREATE VIEW v AS " + HEAD + "INNER JOIN auction WITHIN " + ON
        + TAIL + ";", SQLParseError, "expected"),
    "qualify": (
        "CREATE VIEW v AS " + HEAD + "INNER JOIN auction WITHIN WINDOW "
        + ON + TAIL + " QUALIFY COUNT(*) >= MAX(COUNT(*)) OVER (PARTITION "
        "BY winStart, winEnd);", SQLValidateError, "QUALIFY"),
    "unqualified_on": (
        "CREATE VIEW v AS " + HEAD + "INNER JOIN auction WITHIN WINDOW "
        "ON id = seller " + TAIL + ";", SQLValidateError,
        "stream-qualified"),
    "self_join": (
        "CREATE VIEW v AS " + HEAD + "INNER JOIN person WITHIN WINDOW "
        "ON person.id = person.id " + TAIL + ";", SQLValidateError,
        "self-join"),
}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_every_other_use_is_refused_by_name(case):
    sql, error, says = REFUSED[case]
    with pytest.raises(error) as e:
        stream_codegen(sql)
    assert says in str(e.value), str(e.value)


def test_the_executor_refuses_a_plan_it_cannot_run():
    # a plan built by hand around the SQL front door: the executor
    # holds the same line (a window join needs a TUMBLING aggregate)
    from dataclasses import replace

    from hstream_tpu.engine.window import HoppingWindow

    plan = stream_codegen(Q8).select
    bad = replace(plan, node=replace(
        plan.node, window=HoppingWindow(10_000, 2_000, 0)))
    with pytest.raises(SQLCodegenError) as e:
        make_executor(bad)
    assert "TUMBLING" in str(e.value)
