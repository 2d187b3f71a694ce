"""Logical query plans.

The reference's SQL codegen lowers SELECT into a processor-DAG builder
(hstream-sql Codegen.hs:532-567: source -> filter -> map/groupBy -> window
aggregate -> having -> sink). Here the DAG survives only as this logical
plan; the physical form is a single jitted step function built by
hstream_tpu.engine.compile (no per-record closures).
"""

from __future__ import annotations

import enum
from dataclasses import dataclass, field

from hstream_tpu.engine.expr import Col, Expr
from hstream_tpu.engine.types import Schema
from hstream_tpu.engine.window import WindowSpec


class AggKind(enum.Enum):
    COUNT_ALL = "count_all"        # COUNT(*)
    COUNT = "count"                # COUNT(col) — non-null count
    SUM = "sum"
    AVG = "avg"
    MIN = "min"
    MAX = "max"
    APPROX_COUNT_DISTINCT = "approx_count_distinct"  # HLL sketch
    APPROX_QUANTILE = "approx_quantile"              # log-binned histogram
    TOPK = "topk"                  # top-k values per group/window
    TOPK_DISTINCT = "topk_distinct"


@dataclass(frozen=True)
class AggSpec:
    kind: AggKind
    out_name: str
    input: Expr | None = None      # None for COUNT(*)
    quantile: float | None = None  # for APPROX_QUANTILE
    k: int | None = None           # for TOPK


@dataclass(frozen=True)
class WindowTop:
    """Keep, of every closed window, the groups whose aggregate `agg`
    (an `AggSpec.out_name` of the node) equals the window's extreme of
    it over all groups: SQL's `QUALIFY agg >= MAX(agg) OVER (PARTITION
    BY winStart, winEnd)`. Ties all stay; a window without a group emits
    nothing."""
    agg: str
    extreme: str                   # "max" | "min"


@dataclass
class PlanNode:
    pass


@dataclass
class SourceNode(PlanNode):
    stream: str
    schema: Schema


@dataclass
class FilterNode(PlanNode):
    child: PlanNode
    predicate: Expr


@dataclass
class ProjectNode(PlanNode):
    """SELECT expressions for non-aggregating queries (host-evaluated on
    the emitted rows; device path forwards source columns)."""
    child: PlanNode
    exprs: list[tuple[str, Expr]]  # (output name, expr)


@dataclass
class AggregateNode(PlanNode):
    child: PlanNode
    group_keys: list[Expr]         # grouping columns
    window: WindowSpec | None      # None = global group-by
    aggs: list[AggSpec]
    having: Expr | None = None
    # host-side projections over aggregate outputs, e.g. SUM(x)/2 AS y
    post_projections: list[tuple[str, Expr]] = field(default_factory=list)
    # the filter across groups at a window's close (None: every group)
    top: WindowTop | None = None


@dataclass
class JoinNode(PlanNode):
    left: PlanNode
    right: PlanNode
    left_key: Expr
    right_key: Expr
    window_ms: int                 # |ts_l - ts_r| <= window_ms (JOIN WITHIN)
    left_name: str = "l"
    right_name: str = "r"


@dataclass
class SinkNode(PlanNode):
    child: PlanNode
    stream: str


def plan_source(node: PlanNode) -> SourceNode:
    """The (single) source under a linear plan chain."""
    while not isinstance(node, SourceNode):
        if isinstance(node, (FilterNode, ProjectNode, AggregateNode, SinkNode)):
            node = node.child
        else:
            raise ValueError(f"no single source under {type(node).__name__}")
    return node


def emitted_group_cols(node: AggregateNode) -> list[str]:
    """Names under which the group-key columns appear in EMITTED rows.

    Without post projections rows carry the plan column names; with them
    (any aliased/computed select item) a key column emits under the name
    of the first projected item that is exactly that column — e.g.
    `SELECT city AS c ... GROUP BY city` emits the key as "c". Consumers
    keying on emitted rows (materialized views) must use these names."""
    out = []
    for g in node.group_keys:
        if not isinstance(g, Col):
            continue
        name = g.name
        for out_name, e in (node.post_projections or []):
            if isinstance(e, Col) and e.name == g.name:
                name = out_name
                break
        out.append(name)
    return out


def single_chip_reason(node: AggregateNode, join=None) -> str | None:
    """Why an aggregate (under `join`, the statement's JOIN clause, if
    it has one) cannot execute over the device mesh (None: it shards).
    One predicate for EXPLAIN, the task's gate, the executor factory
    and a snapshot's restore."""
    if join is not None and getattr(join, "window", False):
        return ("a window join (JOIN ... WITHIN WINDOW) probes and "
                "evicts whole windows over both stores on one chip; "
                "the query runs single-chip")
    if any(a.kind in (AggKind.TOPK, AggKind.TOPK_DISTINCT)
           for a in node.aggs):
        return ("TOPK/TOPK_DISTINCT planes have no elementwise shard "
                "merge; the query runs single-chip")
    if node.top is not None:
        return ("a window's top across groups (QUALIFY ... OVER) is "
                "taken over the whole key axis on one chip; the query "
                "runs single-chip")
    return None
