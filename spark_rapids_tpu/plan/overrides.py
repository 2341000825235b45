"""TpuOverrides: the plan-override engine retargeting CPU operators to TPU.

Reference: GpuOverrides.scala (apply:4557, wrapAndTagPlan:4358, doConvertPlan:4364,
applyOverrides:4685) + GpuTransitionOverrides.scala (insert transitions at
CPU↔device boundaries). Flow:
  CPU physical plan → wrap in PlanMeta tree → tag (reasons) → convert supported
  subtrees to Tpu execs → insert HostToDevice/DeviceToHost at boundaries →
  explain/fallback reporting (spark.rapids.sql.explain) and explainOnly mode.
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, List, Optional, Type

from ..config import (EXPLAIN, FILTER_ENABLED, PROJECT_ENABLED, RapidsConf,
                      SQL_ENABLED, TEST_ASSERT_ON_TPU)
from ..execs import basic as TB
from ..execs import cpu as CE
from ..execs.base import CpuExec, PhysicalPlan, TpuExec
from ..execs.transitions import DeviceToHostExec, HostToDeviceExec
from .meta import PlanMeta

log = logging.getLogger("spark_rapids_tpu")


class ExecRule:
    """Replacement rule for one CPU exec class (reference `exec[INPUT](...)`,
    GpuOverrides.scala:817).

    `tpu_cls` (dotted path under spark_rapids_tpu, e.g. "execs.sort.
    TpuSortExec") names the converted operator and `metrics` the operator
    metrics the rule promises it registers beyond the base set —
    tools/api_validation.py resolves the class lazily and fails the build
    when a declared name is missing from the class's metric registration
    (the reference validates exec signatures per shim the same way)."""

    def __init__(self, cpu_cls: type, desc: str, conf_key: str,
                 tag: Callable[[PlanMeta], None],
                 convert: Callable[[PlanMeta, List[PhysicalPlan]], PhysicalPlan],
                 tpu_cls: Optional[str] = None,
                 metrics: tuple = ()):
        self.cpu_cls = cpu_cls
        self.desc = desc
        self.conf_key = conf_key
        self._tag = tag
        self._convert = convert
        self.tpu_cls = tpu_cls
        self.metrics = tuple(metrics)

    def tag(self, meta: PlanMeta) -> None:
        if not meta.conf.is_op_enabled(self.conf_key, True):
            meta.will_not_work_on_tpu(f"disabled via {self.conf_key}")
        self._tag(meta)

    def convert(self, meta: PlanMeta, children: List[PhysicalPlan]) -> PhysicalPlan:
        children = [ensure_device(c) for c in children]
        return self._convert(meta, children)


def ensure_device(plan: PhysicalPlan) -> PhysicalPlan:
    if plan.is_tpu:
        return plan
    return HostToDeviceExec(plan)


def ensure_host(plan: PhysicalPlan) -> PhysicalPlan:
    if plan.is_tpu:
        return DeviceToHostExec(plan)
    return plan


_EXEC_RULES: Dict[type, ExecRule] = {}


def register_exec(cpu_cls: type, desc: str, conf_key: str, tag=None,
                  convert=None, tpu_cls=None, metrics=()):
    _EXEC_RULES[cpu_cls] = ExecRule(cpu_cls, desc, conf_key,
                                    tag or (lambda m: None), convert,
                                    tpu_cls=tpu_cls, metrics=metrics)


def exec_rules() -> Dict[type, ExecRule]:
    return dict(_EXEC_RULES)


# ---------------------------------------------------------------------------
# Built-in rules
# ---------------------------------------------------------------------------

def _tag_project(meta: PlanMeta) -> None:
    meta.add_exprs(meta.plan.exprs)


def _convert_project(meta: PlanMeta, children):
    p = meta.plan
    return TB.TpuProjectExec(p.exprs, children[0], p.output)


def _tag_filter(meta: PlanMeta) -> None:
    meta.add_exprs([meta.plan.condition])


def _convert_filter(meta: PlanMeta, children):
    return TB.TpuFilterExec(meta.plan.condition, children[0])


def _convert_scan(meta: PlanMeta, children):
    # local table scan stays host-side; upload happens via transition
    raise AssertionError("scan conversion handled via transition")


register_exec(CE.CpuProjectExec, "projection", "spark.rapids.sql.exec.ProjectExec",
              _tag_project, _convert_project,
              tpu_cls="execs.basic.TpuProjectExec")
register_exec(CE.CpuFilterExec, "filter", "spark.rapids.sql.exec.FilterExec",
              _tag_filter, _convert_filter,
              tpu_cls="execs.basic.TpuFilterExec")
register_exec(
    CE.CpuRangeExec, "range", "spark.rapids.sql.exec.RangeExec",
    lambda m: None,
    lambda m, ch: TB.TpuRangeExec(m.plan.start, m.plan.end, m.plan.step,
                                  m.plan.num_partitions(), m.plan.output))

from ..execs.transitions import CpuDeviceScanExec as _CpuDevScan  # noqa: E402


def _convert_device_scan(meta: PlanMeta, ch):
    from ..execs.transitions import TpuDeviceScanExec
    return TpuDeviceScanExec(meta.plan.batches, meta.plan.output)


register_exec(_CpuDevScan, "device-cached scan",
              "spark.rapids.sql.exec.InMemoryTableScanExec",
              lambda m: None, _convert_device_scan)
register_exec(
    CE.CpuUnionExec, "union", "spark.rapids.sql.exec.UnionExec",
    lambda m: None,
    lambda m, ch: TB.TpuUnionExec(ch, m.plan.output))
register_exec(
    CE.CpuLocalLimitExec, "local limit", "spark.rapids.sql.exec.LocalLimitExec",
    lambda m: None,
    lambda m, ch: TB.TpuLocalLimitExec(m.plan.n, ch[0]))
register_exec(
    CE.CpuGlobalLimitExec, "global limit", "spark.rapids.sql.exec.GlobalLimitExec",
    lambda m: None,
    lambda m, ch: TB.TpuGlobalLimitExec(m.plan.n, ch[0], m.plan.offset))


register_exec(
    CE.CpuTopNExec, "top-N (sort+limit fusion)",
    "spark.rapids.sql.exec.TakeOrderedAndProjectExec",
    lambda m: m.add_exprs([o.child for o in m.plan.order]),
    lambda m, ch: _TpuTopN(m.plan.n, m.plan.order, ch[0], m.plan.offset),
    tpu_cls="execs.sort.TpuTopNExec", metrics=("sortTime",))


def _TpuTopN(n, order, child, offset):
    from ..execs.sort import TpuTopNExec
    return TpuTopNExec(n, order, child, offset)


def _register_sample():
    from ..execs.sample import CpuSampleExec, TpuSampleExec
    register_exec(
        CpuSampleExec, "sample", "spark.rapids.sql.exec.SampleExec",
        lambda m: None,
        lambda m, ch: TpuSampleExec(m.plan.fraction, m.plan.with_replacement,
                                    m.plan.seed, ch[0]),
        tpu_cls="execs.sample.TpuSampleExec", metrics=("sampleTime",))


_register_sample()


def _tag_sort(meta: PlanMeta) -> None:
    meta.add_exprs([o.child for o in meta.plan.order])


def _convert_sort(meta: PlanMeta, ch):
    from ..execs.sort import TpuSortExec
    return TpuSortExec(meta.plan.order, meta.plan.global_sort, ch[0])


register_exec(CE.CpuSortExec, "sort", "spark.rapids.sql.exec.SortExec",
              _tag_sort, _convert_sort,
              tpu_cls="execs.sort.TpuSortExec", metrics=("sortTime",))


def _tag_aggregate(meta: PlanMeta) -> None:
    from ..execs.aggregates import split_result_exprs
    from ..expressions.aggregates import AggregateFunction
    p = meta.plan
    meta.add_exprs(p.grouping)
    agg_fns, result_exprs = split_result_exprs(p.aggregates)
    supported = {"sum", "count", "min", "max", "avg", "first", "last",
                 "stddev_samp", "stddev_pop", "var_samp", "var_pop",
                 "collect_list", "collect_set", "percentile",
                 "approx_percentile", "covar_samp", "covar_pop", "corr",
                 "bloom_filter"}
    from .typechecks import conf_gate_reason
    for fn in agg_fns:
        if fn.update_op not in supported:
            meta.will_not_work_on_tpu(
                f"aggregate {type(fn).__name__} is not supported on TPU")
        gate = conf_gate_reason(fn, meta.conf)
        if gate:
            meta.will_not_work_on_tpu(gate)
        for c in fn.children:
            meta.add_exprs([c])
    meta.add_exprs(result_exprs)


def _convert_aggregate(meta: PlanMeta, ch):
    from ..execs.aggregates import TpuHashAggregateExec
    p = meta.plan
    return TpuHashAggregateExec(p.grouping, p.aggregates, ch[0], p.output,
                                per_partition=p.per_partition)


from ..execs.aggregates import CpuHashAggregateExec as _CpuAgg  # noqa: E402

register_exec(_CpuAgg, "hash aggregate", "spark.rapids.sql.exec.HashAggregateExec",
              _tag_aggregate, _convert_aggregate,
              tpu_cls="execs.aggregates.TpuHashAggregateExec",
              metrics=("sortTime", "reduceTime", "numGroups"))


def _tag_hash_join(meta: PlanMeta) -> None:
    p = meta.plan
    meta.add_exprs(p.left_keys)
    meta.add_exprs(p.right_keys)
    if p.condition is not None:
        meta.add_exprs([p.condition])


def _convert_hash_join(meta: PlanMeta, ch):
    from ..config import SYMMETRIC_JOIN_ENABLED
    from ..execs.joins import (_MIRROR_JOIN, TpuShuffledHashJoinExec,
                               TpuShuffledSymmetricHashJoinExec)
    p = meta.plan
    ch = _maybe_coordinated_readers(meta, ch)
    if meta.conf.get(SYMMETRIC_JOIN_ENABLED) and p.join_type in _MIRROR_JOIN:
        return TpuShuffledSymmetricHashJoinExec(
            ch[0], ch[1], p.join_type, p.left_keys, p.right_keys,
            p.condition, p.output, per_partition=p.per_partition)
    return TpuShuffledHashJoinExec(ch[0], ch[1], p.join_type, p.left_keys,
                                   p.right_keys, p.condition, p.output,
                                   per_partition=p.per_partition)


def _maybe_coordinated_readers(meta: PlanMeta, ch):
    """Wrap a co-partitioned join's two exchanges in coordinated AQE readers
    (shared coalesce + skew-split specs — reference OptimizeSkewedJoin /
    CoalesceShufflePartitions planning GpuCustomShuffleReaderExec)."""
    from ..config import (AQE_ADVISORY_PARTITION_BYTES, AQE_COALESCE_ENABLED,
                          AQE_SKEW_FACTOR, AQE_SKEW_JOIN_ENABLED,
                          AQE_SKEW_THRESHOLD)
    from ..shuffle.aqe import (JoinReaderCoordinator,
                               TpuCoordinatedShuffleReaderExec)
    from ..shuffle.exchange import TpuShuffleExchangeExec
    p = meta.plan
    coalesce = meta.conf.get(AQE_COALESCE_ENABLED)
    skew = meta.conf.get(AQE_SKEW_JOIN_ENABLED)
    if not (coalesce or skew):
        return ch
    if not (getattr(p, "per_partition", False)
            and isinstance(ch[0], TpuShuffleExchangeExec)
            and isinstance(ch[1], TpuShuffleExchangeExec)
            and ch[0].partitioning == "hash"
            and ch[1].partitioning == "hash"):
        return ch
    coord = JoinReaderCoordinator(
        ch[0], ch[1], p.join_type,
        meta.conf.get(AQE_ADVISORY_PARTITION_BYTES),
        meta.conf.get(AQE_SKEW_THRESHOLD) if skew else (1 << 62),
        meta.conf.get(AQE_SKEW_FACTOR), coalesce=bool(coalesce))
    l = TpuCoordinatedShuffleReaderExec(ch[0], coord, 0, conf=meta.conf)
    r = TpuCoordinatedShuffleReaderExec(ch[1], coord, 1, conf=meta.conf)
    return [l, r]


def _tag_bnlj(meta: PlanMeta) -> None:
    if meta.plan.condition is not None:
        meta.add_exprs([meta.plan.condition])


def _convert_bnlj(meta: PlanMeta, ch):
    from ..execs.joins import TpuBroadcastNestedLoopJoinExec
    p = meta.plan
    return TpuBroadcastNestedLoopJoinExec(ch[0], ch[1], p.join_type,
                                          p.condition, p.output)


from ..execs.joins import (CpuBroadcastNestedLoopJoinExec as _CpuBnlj,  # noqa: E402
                           CpuShuffledHashJoinExec as _CpuShj)

register_exec(_CpuShj, "shuffled hash join",
              "spark.rapids.sql.exec.ShuffledHashJoinExec",
              _tag_hash_join, _convert_hash_join,
              tpu_cls="execs.joins.TpuShuffledHashJoinExec",
              metrics=("buildTime", "joinTime", "numPairs"))
def _convert_broadcast_join(meta: PlanMeta, ch):
    from ..execs.broadcast import TpuBroadcastHashJoinExec
    p = meta.plan
    return TpuBroadcastHashJoinExec(ch[0], ch[1], p.join_type, p.left_keys,
                                    p.right_keys, p.condition, p.output)


from ..execs.broadcast import CpuBroadcastHashJoinExec as _CpuBhj  # noqa: E402

register_exec(_CpuBhj, "broadcast hash join",
              "spark.rapids.sql.exec.BroadcastHashJoinExec",
              _tag_hash_join, _convert_broadcast_join,
              tpu_cls="execs.broadcast.TpuBroadcastHashJoinExec",
              metrics=("buildTime", "joinTime", "numPairs"))
register_exec(_CpuBnlj, "broadcast nested loop join",
              "spark.rapids.sql.exec.BroadcastNestedLoopJoinExec",
              _tag_bnlj, _convert_bnlj)


def _convert_cartesian(meta: PlanMeta, ch):
    from ..execs.joins import TpuCartesianProductExec
    p = meta.plan
    return TpuCartesianProductExec(ch[0], ch[1], p.condition, p.output)


from ..execs.joins import CpuCartesianProductExec as _CpuCart  # noqa: E402

register_exec(_CpuCart, "cartesian product",
              "spark.rapids.sql.exec.CartesianProductExec",
              _tag_bnlj, _convert_cartesian,
              tpu_cls="execs.joins.TpuCartesianProductExec",
              metrics=("joinTime", "numPairs"))


def _tag_write(meta: PlanMeta) -> None:
    from ..config import ORC_WRITE_ENABLED, PARQUET_WRITE_ENABLED
    fmt = meta.plan.spec.fmt
    keys = {"parquet": PARQUET_WRITE_ENABLED, "orc": ORC_WRITE_ENABLED}
    entry = keys.get(fmt)
    if entry is not None and not meta.conf.get(entry):
        meta.will_not_work_on_tpu(f"{fmt} writes disabled via {entry.key}")


def _convert_write(meta: PlanMeta, ch):
    from ..execs.write import TpuDataWritingCommandExec
    return TpuDataWritingCommandExec(ch[0], meta.plan.spec)


from ..execs.write import CpuDataWritingCommandExec as _CpuWrite  # noqa: E402

register_exec(_CpuWrite, "data writing command",
              "spark.rapids.sql.exec.DataWritingCommandExec",
              _tag_write, _convert_write,
              tpu_cls="execs.write.TpuDataWritingCommandExec",
              metrics=("writeTime", "numFiles", "numWrittenRows"))


def _convert_subquery_broadcast(meta: PlanMeta, ch):
    from ..execs.subquery import TpuSubqueryBroadcastExec
    return TpuSubqueryBroadcastExec(ch[0], meta.plan.key_ordinal)


from ..execs.subquery import CpuSubqueryBroadcastExec as _CpuSubq  # noqa: E402

register_exec(_CpuSubq, "subquery broadcast (DPP key collection)",
              "spark.rapids.sql.exec.SubqueryBroadcastExec",
              None, _convert_subquery_broadcast)


def _tag_exchange(meta: PlanMeta) -> None:
    meta.add_exprs(meta.plan.keys)


def _mesh_align_consistent(meta: PlanMeta) -> bool:
    """May this exchange re-plan to mesh-size partitions without breaking
    co-partitioning? A join pairs partition i of both inputs, so BOTH of
    its exchanges must make the same alignment decision — each side
    independently checks every sibling exchange's static eligibility and
    aligns only when all would. Non-join parents have no pairing
    constraint."""
    from ..parallel.mesh import collective_payload
    from ..shuffle.exchange import CpuShuffleExchangeExec
    parent = meta.parent
    if parent is None or "Join" not in type(parent.plan).__name__:
        return True
    for sib in parent.child_plans:
        sp = sib.plan
        if isinstance(sp, CpuShuffleExchangeExec) \
                and sp.partitioning == "hash" \
                and collective_payload(sp.output, meta.conf) is None:
            return False
    return True


def _convert_exchange(meta: PlanMeta, ch):
    from ..config import (AQE_COALESCE_ENABLED,
                          AQE_ADVISORY_PARTITION_BYTES,
                          MESH_ALIGN_PARTITIONS, MESH_COLLECTIVE_ENABLED)
    from ..parallel.mesh import collective_payload, mesh_session_active
    from ..shuffle.exchange import (TpuShuffleExchangeExec,
                                    TpuShuffleReaderExec)
    p = meta.plan
    n_out = p.num_partitions()
    # mesh session (docs/distributed.md): the planner — not a runtime
    # probe — selects the collective data plane. Hash exchanges re-plan to
    # mesh-size partitions (alignPartitions) so the on-device murmur3 % n
    # routing matches the shard count, and eligible exchanges carry
    # `collective_planned` so materialization runs ONE fabric collective.
    # String payloads are eligible via the dictionary-encode pass
    # (collective_payload == "dict"): the fabric carries int32 codes plus
    # one broadcast dictionary instead of raw bytes.
    ms = mesh_session_active(meta.conf)
    mesh = ms if meta.conf.get(MESH_COLLECTIVE_ENABLED) else None
    payload = collective_payload(ch[0].output, meta.conf) \
        if mesh is not None else None
    eligible = mesh is not None \
        and p.partitioning in ("hash", "single") \
        and payload is not None
    if eligible and p.partitioning == "hash" \
            and meta.conf.get(MESH_ALIGN_PARTITIONS) \
            and _mesh_align_consistent(meta):
        n_out = mesh.devices.size
    exch = TpuShuffleExchangeExec(ch[0], p.partitioning, p.keys, n_out)
    if eligible and (p.partitioning == "single"
                     or n_out == mesh.devices.size):
        exch.collective_planned = True
    elif ms is not None:
        # plan-time "why not collective" (obs/mesh_profile.py): a mesh
        # session routed this exchange per-map — say why in the plan
        # (node_desc → explain("metrics")) instead of a code comment
        if mesh is None:
            reason = "collective_conf_off"
        elif p.partitioning not in ("hash", "single"):
            reason = f"partitioning_{p.partitioning}"
        elif collective_payload(ch[0].output, meta.conf) is None:
            reason = "string_or_nested_payload"
        else:
            reason = "partitions_misaligned"
        exch._collective_reason = reason
    # AQE partition coalescing (reference GpuCustomShuffleReaderExec).
    # NOT applied when the exchange feeds a co-partitioned join: each side
    # would coalesce on its own sizes and partition i of the left would no
    # longer hold the same key hashes as partition i of the right (Spark's
    # AQE coordinates both sides through the query stage; we keep the safe
    # subset — aggregates and other single-input consumers).
    parent_plan = meta.parent.plan if meta.parent is not None else None
    feeds_join = parent_plan is not None and \
        "Join" in type(parent_plan).__name__
    if meta.conf.get(AQE_COALESCE_ENABLED) and p.partitioning == "hash" \
            and not feeds_join:
        return TpuShuffleReaderExec(
            exch, meta.conf.get(AQE_ADVISORY_PARTITION_BYTES),
            conf=meta.conf)
    return exch


from ..shuffle.exchange import CpuShuffleExchangeExec as _CpuExch  # noqa: E402

register_exec(_CpuExch, "shuffle exchange",
              "spark.rapids.sql.exec.ShuffleExchangeExec",
              _tag_exchange, _convert_exchange,
              tpu_cls="shuffle.exchange.TpuShuffleExchangeExec",
              metrics=("partitionTime", "serializationTime",
                       "deserializationTime", "dictionaryEncodeTime"))


def _tag_file_scan(meta: PlanMeta) -> None:
    from ..config import (CSV_ENABLED, JSON_ENABLED, ORC_ENABLED,
                          PARQUET_ENABLED)
    fmt_keys = {"parquet": PARQUET_ENABLED, "csv": CSV_ENABLED,
                "json": JSON_ENABLED, "orc": ORC_ENABLED}
    entry = fmt_keys.get(meta.plan.fmt)
    if entry is not None and not meta.conf.get(entry):
        meta.will_not_work_on_tpu(f"{meta.plan.fmt} scans disabled via {entry.key}")


def _convert_file_scan(meta: PlanMeta, ch):
    from ..io.parquet import TpuFileScanExec
    p = meta.plan
    return TpuFileScanExec(p.paths, p.fmt, p.output,
                           pushed_filters=p.pushed_filters, options=p.options,
                           num_partitions=p.num_partitions())


from ..io.parquet import CpuFileScanExec as _CpuScan  # noqa: E402

register_exec(_CpuScan, "file scan", "spark.rapids.sql.exec.FileSourceScanExec",
              _tag_file_scan, _convert_file_scan,
              tpu_cls="io.parquet.TpuFileScanExec",
              metrics=("scanTime", "uploadTime", "filesRead"))


def _tag_window(meta: PlanMeta) -> None:
    from ..expressions.aggregates import AggregateFunction
    from ..window import (CumeDist, DenseRank, Lag, Lead, NTile, PercentRank,
                          Rank, RowNumber,
                          UNBOUNDED_FOLLOWING, UNBOUNDED_PRECEDING, CURRENT_ROW)
    for we in meta.plan.window_exprs:
        fn = we.function
        if isinstance(fn, AggregateFunction):
            if fn.update_op not in ("sum", "count", "avg", "min", "max",
                                    "collect_list", "collect_set"):
                meta.will_not_work_on_tpu(
                    f"window aggregate {type(fn).__name__} not supported on TPU")
            # bounded min/max frames run via the sparse-table range reduce
            # (TpuWindowExec._bounded_minmax); collect_list lowers to a
            # ragged gather for running/whole-partition frames and the
            # host-assisted oracle otherwise (collect_set always host)
            for c in fn.children:
                meta.add_exprs([c])
        elif not isinstance(fn, (RowNumber, Rank, DenseRank, Lead, Lag,
                                 NTile, PercentRank, CumeDist)):
            meta.will_not_work_on_tpu(
                f"window function {type(fn).__name__} not supported on TPU")
        meta.add_exprs(we.spec.partition_by)
        meta.add_exprs([o.child for o in we.spec.order_by])


def _convert_window(meta: PlanMeta, ch):
    from ..execs.window import TpuWindowExec
    return TpuWindowExec(meta.plan.window_exprs, ch[0], meta.plan.output)


from ..execs.window import CpuWindowExec as _CpuWin  # noqa: E402

register_exec(_CpuWin, "window", "spark.rapids.sql.exec.WindowExec",
              _tag_window, _convert_window,
              tpu_cls="execs.window.TpuWindowExec")


def _tag_generate(meta: PlanMeta) -> None:
    from ..expressions.generators import Explode, Stack
    from ..expressions.json import JsonTuple
    gen = meta.plan.generator
    if not isinstance(gen, (Explode, Stack, JsonTuple)):
        meta.will_not_work_on_tpu(
            f"generator {type(gen).__name__} is not supported on TPU")
    meta.add_exprs(list(gen.children))


def _convert_generate(meta: PlanMeta, ch):
    from ..execs.generate import TpuGenerateExec
    p = meta.plan
    return TpuGenerateExec(p.generator, p.gen_names, ch[0], p.output)


def _tag_expand(meta: PlanMeta) -> None:
    for proj in meta.plan.projections:
        meta.add_exprs(proj)


def _convert_expand(meta: PlanMeta, ch):
    from ..execs.generate import TpuExpandExec
    return TpuExpandExec(meta.plan.projections, ch[0], meta.plan.output)


from ..execs.generate import (CpuExpandExec as _CpuExpand,  # noqa: E402
                              CpuGenerateExec as _CpuGen)

register_exec(_CpuGen, "generate", "spark.rapids.sql.exec.GenerateExec",
              _tag_generate, _convert_generate,
              tpu_cls="execs.generate.TpuGenerateExec",
              metrics=("numInputRows",))
register_exec(_CpuExpand, "expand", "spark.rapids.sql.exec.ExpandExec",
              _tag_expand, _convert_expand)


def wrap_and_tag_plan(plan: PhysicalPlan, conf: RapidsConf) -> PlanMeta:
    """reference wrapAndTagPlan (GpuOverrides.scala:4358)."""
    rule = _EXEC_RULES.get(type(plan))
    meta = PlanMeta(plan, conf, rule)
    meta.child_plans = [wrap_and_tag_plan(c, conf) for c in plan.children]
    for cm in meta.child_plans:
        cm.parent = meta
    return meta


class TpuOverrides:
    """reference GpuOverrides.apply (GpuOverrides.scala:4557)."""

    @staticmethod
    def apply(plan: PhysicalPlan, conf: RapidsConf) -> PhysicalPlan:
        if not conf.get(SQL_ENABLED):
            return plan
        meta = wrap_and_tag_plan(plan, conf)
        meta.tag_for_tpu()
        from .cbo import apply_cbo
        for opt in apply_cbo(meta, conf):
            log.info(opt)
        explain = str(conf.get(EXPLAIN)).upper()
        if explain in ("NOT_ON_TPU", "ALL"):
            reasons: List[str] = []
            meta.collect_fallback_reasons(reasons)
            for r in reasons:
                log.info(r)
        if conf.explain_only:
            reasons = []
            meta.collect_fallback_reasons(reasons)
            return plan  # explainOnly: report, execute on CPU
        converted = meta.convert_if_needed()
        final = TpuTransitionOverrides.apply(converted, conf)
        from ..execs.compiled import compile_agg_stages
        from ..execs.compiled_join import compile_join_agg_stages
        final = compile_agg_stages(compile_join_agg_stages(final, conf), conf)
        # whole-stage segment fusion for whatever the compiled stages left
        # on the general path (execs/fusion.py): adjacent project/filter
        # chains — plus an inner-join probe at the segment bottom
        # (opjit.fuseJoins) and a trailing grouped aggregate at its top
        # (opjit.fuseAggs) — collapse into one segment between exchanges
        from ..execs.fusion import fuse_stage_segments
        final = fuse_stage_segments(final, conf)
        # batch coalescing (execs/coalesce.py): small batches concatenate up
        # to the batch-size targets ahead of batch-hungry operators — runs
        # last so fused segments are insertion targets too (a segment that
        # absorbed a join gets require_single on its build children)
        from ..execs.coalesce import insert_coalesce
        return insert_coalesce(final, conf)

    @staticmethod
    def explain_plan(plan: PhysicalPlan, conf: RapidsConf) -> str:
        """reference ExplainPlan.explainCatalystSQLPlan."""
        meta = wrap_and_tag_plan(plan, conf)
        meta.tag_for_tpu()
        reasons: List[str] = []
        meta.collect_fallback_reasons(reasons)
        if not reasons:
            return "The whole plan can run on the TPU"
        return "\n".join(reasons)


# ---------------------------------------------------------------------------
# The planning entry: which passes make a plan, and in which order, is
# decided here and nowhere else (collect, write, explain, the plan cache).
# ---------------------------------------------------------------------------

def plan_cpu(logical, conf: RapidsConf):
    """Logical plan → (CPU physical plan, optimized logical plan, applied
    optimizer rule names): `optimize_logical`, then `plan_physical`. For
    callers that put a node of their own over the plan before overriding
    (a write command) or only report on it (`explain_fallback`)."""
    from .optimizer import optimize_logical
    from .planner import plan_physical
    optimized, rules = optimize_logical(logical, conf)
    return plan_physical(optimized, conf), optimized, rules


def plan_query(logical, conf: RapidsConf):
    """Logical plan → (executable physical plan, optimized logical plan,
    applied optimizer rule names): `plan_cpu`, then `TpuOverrides.apply`."""
    cpu_physical, optimized, rules = plan_cpu(logical, conf)
    return TpuOverrides.apply(cpu_physical, conf), optimized, rules


class TpuTransitionOverrides:
    """reference GpuTransitionOverrides.scala: final boundary fixups + the
    everything-on-TPU test assertion (assertIsOnTheGpu:616)."""

    @staticmethod
    def apply(plan: PhysicalPlan, conf: RapidsConf) -> PhysicalPlan:
        plan = _collapse_transitions(plan)
        plan = ensure_host(plan)  # query output is host rows
        if conf.get(TEST_ASSERT_ON_TPU):
            TpuTransitionOverrides.assert_is_on_tpu(plan)
        return plan

    @staticmethod
    def assert_is_on_tpu(plan: PhysicalPlan) -> None:
        allowed_cpu = (DeviceToHostExec, HostToDeviceExec,
                       CE.CpuLocalTableScanExec, CE.CpuCachedScanExec)
        for node in plan.collect_nodes():
            if isinstance(node, CpuExec) and not isinstance(node, allowed_cpu):
                raise AssertionError(
                    f"Part of the plan is not columnar: {node.node_desc()}\n"
                    + plan.tree_string())


def _collapse_transitions(plan: PhysicalPlan) -> PhysicalPlan:
    """Remove HostToDevice(DeviceToHost(x)) → x and vice versa."""
    new_children = [_collapse_transitions(c) for c in plan.children]
    if isinstance(plan, HostToDeviceExec) and isinstance(new_children[0], DeviceToHostExec):
        return new_children[0].children[0]
    if isinstance(plan, DeviceToHostExec) and isinstance(new_children[0], HostToDeviceExec):
        return new_children[0].children[0]
    if all(a is b for a, b in zip(new_children, plan.children)):
        return plan
    import copy
    new = copy.copy(plan)
    new.children = new_children
    return new
