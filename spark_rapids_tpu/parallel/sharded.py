"""Plan-driven sharded multi-chip query execution over the mesh data plane.

This module is the driver side of ROADMAP item 2 ("make the ICI mesh the
production data plane"): given any session query, it runs the SAME plan two
ways —

  * **mesh**: a mesh session (`spark.rapids.tpu.mesh.enabled`, ICI shuffle
    mode) where the planner aligns hash exchanges to the mesh, eligible
    exchanges materialize as ONE fabric collective each
    (`parallel/mesh.py`), AQE consumes the exchange-time device-side size
    counters, and the session's root pull drives all partitions through the
    grouped multi-partition dispatch;
  * **single-device baseline**: the identical plan with the mesh disabled
    (per-map device-resident ICI path on the default device) — the
    bit-identity oracle and the 1-chip denominator for scaling efficiency.

and returns per-query statistics: wall times, per-chip rows/s, the
collective launch count against the plan's exchange count (the
O(exchanges) assertion — launches must NOT scale with partitions), the
staging/launch/wait/compact phase breakdown of collective time
(`parallel.mesh.collective_stats` + the per-exchange profiles and skew
tables from `obs/mesh_profile.py`), the per-map "why not collective"
reasons, and the named-phase `efficiency_attribution` of the profiled
mesh wall (docs/distributed.md "Diagnosing poor scaling").

Nothing here is query-specific: the planner — not this runner — decides
which exchanges ride the fabric, so any session query (TPC-H, TPC-DS,
ad-hoc DataFrames) shards the same way.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .mesh import collective_stats


def mesh_settings(n_devices: int, extra: Optional[Dict[str, str]] = None
                  ) -> Dict[str, str]:
    """Session settings for a mesh data-plane session of `n_devices`
    chips. Compiled whole-stage shortcuts are disabled so every stage
    boundary is a REAL exchange (the thing this data plane accelerates);
    the partition batch matches the mesh so whole-stage segments launch
    once per group."""
    s = {
        "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.tpu.mesh.enabled": "true",
        "spark.rapids.tpu.mesh.size": str(n_devices),
        "spark.sql.shuffle.partitions": str(n_devices),
        "spark.rapids.tpu.dispatch.partitionBatch": str(n_devices),
        "spark.sql.autoBroadcastJoinThreshold": "0",
        "spark.rapids.tpu.agg.compiledStage.enabled": "false",
        "spark.rapids.tpu.join.compiledStage.enabled": "false",
    }
    s.update(extra or {})
    return s


def baseline_settings(n_devices: int,
                      extra: Optional[Dict[str, str]] = None
                      ) -> Dict[str, str]:
    """The single-device baseline: identical plan shape (same partition
    count, same device-resident ICI shuffle, same disabled shortcuts) with
    the mesh off — per-map materialization on the default device."""
    s = mesh_settings(n_devices, extra)
    s["spark.rapids.tpu.mesh.enabled"] = "false"
    return s


def compare_tables(a, b) -> Tuple[bool, float]:
    """(bit_identical, max_abs_err) between two Arrow tables after a
    canonical whole-row sort. Identity is EXACT (float bit patterns, null
    masks); max_abs_err reports the largest float divergence when not."""
    import pyarrow as pa
    if a.num_rows != b.num_rows or a.column_names != b.column_names:
        return False, float("inf")
    if a.num_rows:
        keys = [(n, "ascending") for n in a.column_names]
        a = a.sort_by(keys)
        b = b.sort_by(keys)
    worst = 0.0
    same = True
    for name in a.column_names:
        ca = a.column(name).combine_chunks()
        cb = b.column(name).combine_chunks()
        # host Arrow values throughout (the query already collected):
        # .to_numpy on the pyarrow arrays, never np.asarray on anything the
        # taint walk could grade device (TL011 covers parallel/)
        na = ca.is_null().to_numpy(zero_copy_only=False)
        nb = cb.is_null().to_numpy(zero_copy_only=False)
        if not np.array_equal(na, nb):
            return False, float("inf")
        if pa.types.is_floating(ca.type):
            va = ca.to_numpy(zero_copy_only=False)
            vb = cb.to_numpy(zero_copy_only=False)
            va = np.where(na, 0.0, va)
            vb = np.where(nb, 0.0, vb)
            if not np.array_equal(va, vb, equal_nan=True):
                same = False
                both = np.isfinite(va) & np.isfinite(vb)
                if both.any():
                    worst = max(worst,
                                float(np.abs(va[both] - vb[both]).max()))
                else:
                    worst = float("inf")
        else:
            if ca.drop_null().to_pylist() != cb.drop_null().to_pylist():
                return False, float("inf")
    return same, worst


def _count_exchanges(session) -> int:
    """Exchange nodes in the last executed plan (the session snapshots the
    tree for every query — works untraced)."""
    tree = getattr(session, "_last_plan_tree", None) or []
    return sum(1 for n in tree if "ShuffleExchange" in str(n.get("name", "")))


def _dispatch_kind(kind: str) -> int:
    from ..execs import opjit
    return opjit.cache_stats()["calls_by_kind"].get(kind, 0)


def run_mesh_query(name: str, build: Callable, *, n_devices: int,
                   iters: int = 2,
                   extra_conf: Optional[Dict[str, str]] = None) -> Dict:
    """Run `build(session) -> DataFrame` on the mesh data plane and on the
    single-device baseline; return the comparison record (see module
    docstring). `build` is called once per session — its DataFrame is
    collected `iters` times on each (first collect warms the executable
    caches; the best of the rest is the wall time)."""
    from ..session import TpuSession

    def timed_run(settings, measure: bool) -> Tuple[object, float, Dict]:
        from ..obs import mesh_profile
        s = TpuSession(dict(settings))
        q = build(s)
        out = q.to_arrow()  # warm: traces/compiles every program
        best = float("inf")
        for _ in range(max(1, iters)):
            t0 = time.perf_counter()
            out = q.to_arrow()
            best = min(best, time.perf_counter() - t0)
        if not measure:
            # the baseline contributes only results + wall time — skip the
            # counter-bracketed extra collect (a whole wasted execution)
            return out, best, {}
        # one more collect bracketed by the collective counters: exchanges
        # re-materialize per collect, so this measures launches PER QUERY.
        # The SAME collect's wall anchors the phase attribution (the phase
        # walls and the wall must come from one execution or the
        # percentages lie).
        before_launches = collective_stats()
        before_kind = _dispatch_kind("mesh_collective")
        seq0 = mesh_profile.current_seq()
        t0 = time.perf_counter()
        out = q.to_arrow()
        wall_profiled = time.perf_counter() - t0
        stats = collective_stats()
        delta = {k: stats[k] - before_launches[k] for k in stats}
        delta["dispatch_kind"] = _dispatch_kind("mesh_collective") \
            - before_kind
        profiles = mesh_profile.profiles_since(seq0)
        reasons: Dict[str, int] = {}
        for f in mesh_profile.fallbacks_since(seq0):
            reasons[f["reason"]] = reasons.get(f["reason"], 0) + 1
        return out, best, {"collective": delta,
                           "exchanges": _count_exchanges(s),
                           "wall_profiled_s": wall_profiled,
                           "profiles": profiles,
                           "per_map_reasons": reasons}

    out_mesh, wall_mesh, info = timed_run(
        mesh_settings(n_devices, extra_conf), measure=True)
    out_one, wall_one, _ = timed_run(
        baseline_settings(n_devices, extra_conf), measure=False)
    identical, max_err = compare_tables(out_mesh, out_one)
    col = info["collective"]
    launches = col["launches"]
    # O(exchanges): each exchange materializes at most ONE collective per
    # query — never one per partition. The dispatch-accounting kind must
    # agree with the mesh module's own launch counter.
    launches_ok = (launches <= info["exchanges"]
                   and launches == col["dispatch_kind"])
    # worst-skew exchange of the profiled collect (the per-exchange skew
    # tables ride the full record; this is the one-line summary)
    profiles = info.get("profiles") or []
    worst = max(profiles, key=lambda p: p["skew"]["imbalance"],
                default=None)
    return {
        "query": name,
        "rows_out": out_mesh.num_rows,
        "n_devices": n_devices,
        "wall_ms_mesh": round(wall_mesh * 1e3, 1),
        "wall_ms_single": round(wall_one * 1e3, 1),
        "wall_ms_profiled": round(info["wall_profiled_s"] * 1e3, 1),
        "scaling_vs_single": round(wall_one / wall_mesh, 3)
        if wall_mesh > 0 else None,
        "bit_identical": identical,
        "max_abs_err": max_err,
        "exchanges": info["exchanges"],
        "collective_launches": launches,
        "collective_launches_O_exchanges": launches_ok,
        # dictionary-encoded string exchanges (codes + one broadcast
        # dictionary over the fabric) and their map-side encode wall
        "string_collectives": col.get("dict_exchanges", 0),
        "dict_encode_ms": round(col.get("dict_encode_ns", 0) / 1e6, 2),
        "collective_rows": col["rows_sent"],
        # fused dataplane keys: compact fused into the collective
        # dispatch on EVERY profiled exchange, staged pad pieces served
        # from the staging pool, and segments launched by the overlapped
        # path (0 = the correctness-first unsegmented default)
        "compact_fused": all(p.get("compact_fused") for p in profiles)
        if profiles else True,
        "staging_reuse_hits": col.get("staging_reuse_hits", 0),
        "overlap_segments": col.get("overlap_segments", 0),
        "collective_stage_ms": round(col["stage_ns"] / 1e6, 2),
        "collective_launch_ms": round(col["launch_ns"] / 1e6, 2),
        "collective_wait_ms": round(col["wait_ns"] / 1e6, 2),
        "collective_compact_ms": round(col["compact_ns"] / 1e6, 2),
        "exchange_profiles": profiles,
        "per_map_reasons": info.get("per_map_reasons") or {},
        "skew_worst": None if worst is None else {
            "exchange": worst["exchange"], **worst["skew"]},
        "watchdog_fired": any(p.get("watchdog_fired") for p in profiles),
    }


def attribute_efficiency(record: Dict) -> Dict[str, float]:
    """Named-phase attribution of one query's PROFILED mesh wall
    (staging / launch / collective-wait / compact from the collective
    counters, compute = the residual outside the exchange path) as
    percentages — the `efficiency_attribution` of `summarize`'s compact
    line. The phase
    walls and the wall come from the SAME collect (run_mesh_query's
    bracketed execution), so the split is exact."""
    wall_ms = record.get("wall_ms_profiled") or record.get("wall_ms_mesh")
    if not wall_ms:
        return {}
    phases = {
        "staging": record.get("collective_stage_ms", 0.0),
        "launch": record.get("collective_launch_ms", 0.0),
        "collective_wait": record.get("collective_wait_ms", 0.0),
        "compact": record.get("collective_compact_ms", 0.0),
    }
    out = {k: round(100.0 * v / wall_ms, 1) for k, v in phases.items()}
    named = sum(out.values())
    out["compute"] = round(max(0.0, 100.0 - named), 1)
    # NOT clamped to 100: a value above 100 means the summed phase walls
    # exceeded the wall they were measured against (a phase/wall mismatch
    # bug) — clamping would mask exactly the overcount this key exists to
    # surface
    out["attributed_pct"] = round(named + out["compute"], 1)
    return out


def summarize(records: List[Dict], n_devices: int,
              input_rows: Dict[str, int]) -> Dict:
    """A compact summary of mesh records (ONE parseable line). Per-chip
    rows/s is the mesh run's input-row throughput divided by the chip
    count; scaling efficiency is speedup-over-1-chip / n_chips. Collective
    time is the per-phase walls + skew summary + efficiency_attribution
    (obs/mesh_profile.py); the full per-exchange profiles ride the detail
    records."""
    per_query = {}
    total_launches = 0
    total_collective_ms = 0.0
    total_string_collectives = 0
    total_dict_encode_ms = 0.0
    all_identical = True
    all_o_exchanges = True
    for r in records:
        rows = input_rows.get(r["query"], 0)
        mesh_s = r["wall_ms_mesh"] / 1e3
        phases = {
            "staging": round(r["collective_stage_ms"], 1),
            "launch": round(r["collective_launch_ms"], 1),
            "collective_wait": round(r["collective_wait_ms"], 1),
            "compact": round(r.get("collective_compact_ms", 0.0), 1),
        }
        # compact-line discipline: no key whose value is derivable from another —
        # rows/bit_identical/wall_ms_single ride the detail records, the
        # worst-skew summary keeps only the verdict fields
        sk = r.get("skew_worst")
        ea = attribute_efficiency(r)
        ea = {k: v for k, v in ea.items()
              if v or k in ("compute", "attributed_pct")}
        per_query[r["query"]] = {
            "per_chip_rows_per_s": round(rows / mesh_s / n_devices, 1)
            if mesh_s > 0 else None,
            "wall_ms": r["wall_ms_mesh"],
            "scaling_efficiency": round(
                (r["scaling_vs_single"] or 0) / n_devices, 3),
            "exchanges": r["exchanges"],
            "collective_launches": r["collective_launches"],
            "string_collectives": r.get("string_collectives", 0),
            "dict_encode_ms": r.get("dict_encode_ms", 0.0),
            # fused dataplane keys (ISSUE 16): compact_fused is the
            # headline invariant (never elided — a False here means a
            # regression back to host compact); the counters elide at zero
            "compact_fused": bool(r.get("compact_fused", False)),
            "staging_reuse_hits": r.get("staging_reuse_hits", 0),
            "overlap_segments": r.get("overlap_segments", 0),
            "phases_ms": phases,
            "efficiency_attribution": ea,
            "skew": None if sk is None else {
                "exchange": sk["exchange"],
                "imbalance": sk["imbalance"],
                "straggler_chip": sk["straggler_chip"]},
            "per_map_exchanges": r.get("per_map_reasons") or {},
        }
        if not per_query[r["query"]]["string_collectives"]:
            # compact-line discipline: zero-valued dictionary keys elide
            del per_query[r["query"]]["string_collectives"]
            del per_query[r["query"]]["dict_encode_ms"]
        for zk in ("staging_reuse_hits", "overlap_segments"):
            if not per_query[r["query"]][zk]:
                del per_query[r["query"]][zk]
        total_launches += r["collective_launches"]
        total_collective_ms += sum(phases.values())
        total_string_collectives += r.get("string_collectives", 0)
        total_dict_encode_ms += r.get("dict_encode_ms", 0.0)
        all_identical = all_identical and r["bit_identical"]
        all_o_exchanges = all_o_exchanges \
            and r["collective_launches_O_exchanges"]
    return {
        "metric": "multichip_sharded_execution",
        "n_devices": n_devices,
        "queries": per_query,
        "collective_launches_total": total_launches,
        # string exchanges riding the fabric as dictionary codes + one
        # broadcast dictionary each
        "string_collectives_total": total_string_collectives,
        "dict_encode_ms_total": round(total_dict_encode_ms, 2),
        # the four phases summed, compact included
        "collective_phases_ms_total": round(total_collective_ms, 2),
        "bit_identical_all": all_identical,
        # the fused-compact invariant over the whole round: False means
        # some exchange fell back to a host-side compact
        "compact_fused_all": all(bool(r.get("compact_fused", False))
                                 for r in records),
        "collective_launches_O_exchanges": all_o_exchanges,
        "watchdog_fired_any": any(r.get("watchdog_fired")
                                  for r in records),
    }
