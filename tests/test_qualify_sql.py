"""QUALIFY <agg> >= MAX(<agg>) OVER (PARTITION BY winStart, winEnd): the
one form the dialect states a window's top across groups in. It parses,
lowers to `AggregateNode.top`, shows in EXPLAIN as a line of its own,
and every other use is refused with a typed error that names what is
not supported."""

import pytest

from hstream_tpu.common.errors import (
    SQLCodegenError,
    SQLParseError,
    SQLValidateError,
)
from hstream_tpu.engine.plan import WindowTop, single_chip_reason
from hstream_tpu.sql import ast
from hstream_tpu.sql.codegen import (
    explain_text,
    make_executor,
    mesh_exclusion_reason,
    stream_codegen,
)
from hstream_tpu.sql.parser import parse
from hstream_tpu.sql.refine import parse_and_refine

HEAD = ("SELECT auction, COUNT(*) AS num FROM bid GROUP BY auction, "
        "HOPPING (INTERVAL 10 SECOND, INTERVAL 2 SECOND) "
        "GRACE BY INTERVAL 0 SECOND ")
OVER = "OVER (PARTITION BY winStart, winEnd)"
Q5 = f"CREATE VIEW hot_items AS {HEAD}QUALIFY COUNT(*) >= MAX(COUNT(*)) {OVER};"


def test_the_form_parses_into_the_ast():
    stmt = parse(Q5)
    q = stmt.select.qualify
    assert isinstance(q, ast.Qualify) and q.op == ">="
    assert q.func == ast.SetFunc(ast.SetFuncKind.COUNT_ALL, None, None,
                                 "COUNT(*)")
    assert isinstance(q.over, ast.OverFunc)
    assert q.over.kind == ast.SetFuncKind.MAX and q.over.arg == q.func
    assert q.over.partition == ("winStart", "winEnd")
    assert parse_and_refine(Q5) == stmt


@pytest.mark.parametrize("tail,top", [
    (f"QUALIFY COUNT(*) >= MAX(COUNT(*)) {OVER}", ("num", "max")),
    (f"QUALIFY COUNT(*) = MAX(COUNT(*)) {OVER}", ("num", "max")),
    (f"QUALIFY COUNT(*) <= MIN(COUNT(*)) {OVER}", ("num", "min")),
    ("QUALIFY COUNT(*) >= MAX(COUNT(*)) OVER (PARTITION BY winend, "
     "WINSTART)", ("num", "max")),
    ("", None),
])
def test_it_lowers_to_the_plans_top(tail, top):
    plan = stream_codegen(f"CREATE VIEW v AS {HEAD}{tail};").select
    want = None if top is None else WindowTop(agg="COUNT(*)",
                                              extreme=top[1])
    assert plan.node.top == want
    # the emitted row is the statement's: the alias, not the plane
    assert [n for n, _e in plan.node.post_projections] == ["auction", "num"]


def test_the_extreme_of_another_aggregate_of_the_statement():
    plan = stream_codegen(
        "CREATE VIEW v AS SELECT k, COUNT(*) AS n, SUM(x) AS s FROM t "
        "GROUP BY k, TUMBLING (INTERVAL 5 SECOND) QUALIFY SUM(x) <= "
        f"MIN(SUM(x)) {OVER};").select
    assert plan.node.top == WindowTop(agg="SUM(x)", extreme="min")


def test_explain_shows_the_filter_as_a_line_of_its_own():
    text = stream_codegen(f"EXPLAIN {Q5}").text
    lines = text.splitlines()
    (line,) = [ln for ln in lines if ln.startswith("QUALIFY")]
    assert "MAX(COUNT(*)) OVER (PARTITION BY winStart, winEnd)" in line
    assert lines.index(line) + 1 == next(
        i for i, ln in enumerate(lines) if ln.startswith("AGGREGATE"))
    plain = explain_text(stream_codegen(f"CREATE VIEW v AS {HEAD};"))
    assert "QUALIFY" not in plain
    # and it round-trips: the explained statement is the plan's own
    assert stream_codegen(Q5).select.node.top is not None


def test_the_mesh_refusal_is_typed_and_said():
    plan = stream_codegen(Q5)
    reason = mesh_exclusion_reason(plan)
    assert reason is not None and "QUALIFY" in reason
    assert reason == single_chip_reason(plan.select.node)
    assert f"MESH: single-chip — {reason}" in explain_text(plan)
    assert "PACK: unpackable — qualify" in explain_text(plan)
    assert mesh_exclusion_reason(
        stream_codegen(f"CREATE VIEW v AS {HEAD};")) is None


def test_a_mesh_is_not_used_for_it():
    from hstream_tpu.engine.executor import QueryExecutor
    from hstream_tpu.parallel import ShardedQueryExecutor, make_mesh

    mesh = make_mesh(n_data=1, n_key=8)
    top = make_executor(stream_codegen(Q5).select,
                        sample_rows=[{"auction": 1}], mesh=mesh)
    assert type(top) is QueryExecutor
    plain = make_executor(
        stream_codegen(f"CREATE VIEW v AS {HEAD};").select,
        sample_rows=[{"auction": 1}], mesh=mesh)
    assert type(plain) is ShardedQueryExecutor


REFUSED = [
    # (statement, error class, what its message must name)
    ("SELECT auction, COUNT(*) AS num FROM bid GROUP BY auction "
     f"QUALIFY COUNT(*) >= MAX(COUNT(*)) {OVER} EMIT CHANGES;",
     SQLValidateError, "without a time window"),
    (f"{HEAD}QUALIFY COUNT(*) >= MAX(COUNT(*)) OVER (PARTITION BY "
     "auction);", SQLValidateError, "PARTITION BY winStart, winEnd"),
    (f"{HEAD}QUALIFY COUNT(*) >= MAX(COUNT(*)) OVER (PARTITION BY "
     "winStart);", SQLValidateError, "PARTITION BY winStart, winEnd"),
    (f"{HEAD}QUALIFY COUNT(*) >= MAX(COUNT(*)) OVER ();",
     SQLValidateError, "PARTITION BY winStart, winEnd"),
    (f"{HEAD}QUALIFY COUNT(*) >= MAX(COUNT(*)) OVER (PARTITION BY "
     "winStart, winEnd ORDER BY auction);", SQLParseError,
     "ORDER BY inside OVER"),
    (f"{HEAD}QUALIFY COUNT(*) >= MAX(COUNT(*)) OVER (PARTITION BY "
     "winStart, winEnd ROWS 3);", SQLParseError, "window frame"),
    (f"{HEAD}QUALIFY COUNT(*) >= MAX(auction) {OVER};",
     SQLValidateError, "set function"),
    (f"{HEAD}QUALIFY COUNT(*) >= SUM(COUNT(*)) {OVER};", SQLParseError,
     "only MAX and MIN take OVER"),
    (f"{HEAD}QUALIFY COUNT(*) >= COUNT(*) {OVER};", SQLParseError,
     "only MAX and MIN take OVER"),
    (f"{HEAD}QUALIFY COUNT(*) >= MAX(COUNT(*)) {OVER} EMIT CHANGES;",
     SQLValidateError, "EMIT CHANGES"),
    ("SELECT auction, COUNT(*) AS num FROM bid GROUP BY auction, "
     "SESSION (INTERVAL 10 SECOND) QUALIFY COUNT(*) >= MAX(COUNT(*)) "
     f"{OVER};", SQLValidateError, "SESSION"),
    ("SELECT b.auction, COUNT(*) AS num FROM bid AS b INNER JOIN ask AS "
     "a WITHIN (INTERVAL 5 SECOND) ON b.auction = a.auction GROUP BY "
     "b.auction, TUMBLING (INTERVAL 10 SECOND) QUALIFY COUNT(*) >= "
     f"MAX(COUNT(*)) {OVER};", SQLValidateError, "JOIN"),
    (f"{HEAD}HAVING COUNT(*) > 1 QUALIFY COUNT(*) >= MAX(COUNT(*)) "
     f"{OVER};", SQLValidateError, "HAVING"),
    (f"{HEAD}QUALIFY COUNT(*) > 3;", SQLParseError,
     "QUALIFY is not supported but as"),
    (f"{HEAD}QUALIFY COUNT(*) >= 3;", SQLValidateError,
     "QUALIFY is not supported but as"),
    (f"{HEAD}QUALIFY COUNT(*) <= MAX(COUNT(*)) {OVER};",
     SQLValidateError, "keeps no group or every group"),
    (f"{HEAD}QUALIFY COUNT(*) >= MIN(COUNT(*)) {OVER};",
     SQLValidateError, "keeps no group or every group"),
    (f"{HEAD}QUALIFY COUNT(auction) >= MAX(COUNT(*)) {OVER};",
     SQLValidateError, "the left side must be COUNT(*)"),
    (f"{HEAD}QUALIFY COUNT(*) >= MAX(COUNT(*)) {OVER} AND COUNT(*) > 1;",
     SQLParseError, "AND / OR"),
    (f"SELECT auction, MAX(COUNT(*)) {OVER} AS m FROM bid GROUP BY "
     "auction, TUMBLING (INTERVAL 10 SECOND);", SQLValidateError,
     "QUALIFY alone"),
    ("SELECT auction, TOPK(price, 3) AS t FROM bid GROUP BY auction, "
     "TUMBLING (INTERVAL 10 SECOND) QUALIFY TOPK(price, 3) >= "
     f"MAX(TOPK(price, 3)) {OVER};", SQLValidateError, "TOPK"),
    ("SELECT auction, COUNT(*) AS num FROM bid GROUP BY auction, "
     "TUMBLING (INTERVAL 10 SECOND) QUALIFY SUM(price) >= "
     f"MAX(SUM(price)) {OVER};", SQLCodegenError,
     "the SELECT list does not compute"),
]


@pytest.mark.parametrize("sql,err,names", REFUSED,
                         ids=[f"{i}-{r[2][:24]}" for i, r in
                              enumerate(REFUSED)])
def test_every_other_use_is_refused_by_name(sql, err, names):
    with pytest.raises(err) as got:
        stream_codegen(f"CREATE VIEW v AS {sql}"
                       if "EMIT" not in sql else sql)
    assert names in str(got.value), str(got.value)
