"""Exports over one raw tracer profile: Chrome trace JSON, the span tree,
and the per-query diagnostics bundle.

Three views of the SAME record (the reference ships these as separate
artifacts — the xprof/NVTX timeline, the Spark SQL UI plan graph, and the
profiler's file dumps; here they are projections of one ring buffer):

* :func:`chrome_trace` — trace-event JSON loadable in perfetto or
  ``chrome://tracing`` (complementing profiling.trace_scope's xprof
  timeline, which sees XLA internals but not engine semantics);
* :func:`span_tree` — the nested query → task → operator → shuffle-map
  structure with per-span instant events;
* :func:`build_bundle` — the machine-readable diagnostics bundle
  (``session.last_query_profile()``), including per-operator dispatch and
  sync counts RECONCILED against the opjit ``calls_by_kind`` delta and the
  SyncLedger delta for the same query — the two pre-existing counters are
  the ground truth, and a mismatch (other than ring-buffer overflow) marks
  the bundle unreconciled rather than silently disagreeing.
"""

from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Tuple

from .tracer import (REC_ARGS, REC_CAT, REC_NAME, REC_OP, REC_PARENT,
                     REC_PHASE, REC_SPAN, REC_TID, REC_TS)


def span_tree(profile: Dict[str, Any]) -> Dict[str, Any]:
    """Reconstruct the span tree from the raw ring. Spans whose begin
    record was overwritten (ring overflow) are dropped; spans recorded on
    threads with no open parent attach to the query root."""
    root_id = profile["root"]
    nodes: Dict[int, Dict[str, Any]] = {}
    order: List[int] = []
    for rec in profile["events"]:
        ph = rec[REC_PHASE]
        if ph == "B":
            nodes[rec[REC_SPAN]] = {
                "id": rec[REC_SPAN], "name": rec[REC_NAME],
                "cat": rec[REC_CAT], "op": rec[REC_OP],
                "tid": rec[REC_TID], "t_start_ns": rec[REC_TS],
                "dur_ns": None, "parent": rec[REC_PARENT],
                "args": rec[REC_ARGS] or {}, "children": [], "events": []}
            order.append(rec[REC_SPAN])
        elif ph == "E":
            n = nodes.get(rec[REC_SPAN])
            if n is not None:
                n["dur_ns"] = rec[REC_TS] - n["t_start_ns"]
        else:  # instant
            n = nodes.get(rec[REC_SPAN]) if rec[REC_SPAN] else None
            target = n if n is not None else nodes.get(root_id)
            if target is not None:
                target["events"].append({
                    "name": rec[REC_NAME], "cat": rec[REC_CAT],
                    "op": rec[REC_OP], "t_ns": rec[REC_TS],
                    "args": rec[REC_ARGS] or {}})
    root = nodes.get(root_id)
    if root is None:  # root begin overwritten: synthesize
        root = {"id": root_id, "name": profile.get("name", "query"),
                "cat": "query", "op": None, "tid": None, "t_start_ns": 0,
                "dur_ns": profile.get("duration_ns"), "parent": None,
                "args": {}, "children": [], "events": []}
        nodes[root_id] = root
    for sid in order:
        if sid == root_id:
            continue
        n = nodes[sid]
        parent = nodes.get(n["parent"]) if n["parent"] is not None else None
        (parent if parent is not None else root)["children"].append(n)
    for n in nodes.values():
        n.pop("parent", None)
    return root


#: pid of the synthesized per-device track group in the Chrome trace (the
#: engine's real threads render under pid 1)
MESH_DEVICE_PID = 2


def _mesh_tracks(profile: Dict[str, Any]) -> tuple:
    """Synthesize the multi-chip view from the SAME ring record: one track
    per device (pid ``MESH_DEVICE_PID``, tid = device index) with the
    collective wait of every exchange as an "X" complete event ALIGNED
    across tracks (the wait is the fabric barrier: every chip is in it
    together), plus flow events ("s"/"f", id = the exchange's profile seq)
    tying each producer ``mesh.profile`` record to its consumer
    ``mesh.read`` events. Emitted through the existing tracer records, so
    concurrent-query routing needs no new machinery — a query's trace
    only ever contains its own exchanges. Returns (events, device_ids)."""
    evs: List[Dict[str, Any]] = []
    devices: set = set()
    reads: Dict[int, List[Tuple[float, int]]] = {}  # seq -> [(ts_us, tid)]
    for rec in profile["events"]:
        if rec[REC_PHASE] != "i" or rec[REC_NAME] != "mesh.read":
            continue
        args = rec[REC_ARGS] or {}
        seq = args.get("exchange_seq")
        if seq is not None:
            reads.setdefault(int(seq), []).append(
                (rec[REC_TS] / 1e3, rec[REC_TID]))
    for rec in profile["events"]:
        if rec[REC_PHASE] != "i" or rec[REC_NAME] != "mesh.profile":
            continue
        args = rec[REC_ARGS] or {}
        phases = args.get("phases_ms") or {}
        n_dev = int(args.get("n_dev", 0))
        seq = args.get("exchange_seq")
        if not n_dev or seq is None:
            continue
        # the profile event is recorded at the end of compact: walk back
        # through the phase walls to place the aligned wait window
        end_us = rec[REC_TS] / 1e3
        compact_us = float(phases.get("compact", 0.0)) * 1e3
        wait_us = float(phases.get("collective_wait", 0.0)) * 1e3
        wait_end = end_us - compact_us
        wait_start = max(0.0, wait_end - wait_us)
        recv = args.get("recv_rows") or []
        skew = args.get("skew") or {}
        name = f"collective s{args.get('shuffle', '?')}"
        for d in range(n_dev):
            devices.add(d)
            dev_args = {"exchange_seq": seq,
                        "rows_recv": recv[d] if d < len(recv) else None}
            if skew.get("straggler_chip") == d:
                dev_args["straggler"] = True
            evs.append({"ph": "X", "name": name, "cat": "mesh",
                        "ts": wait_start, "dur": max(wait_us, 1.0),
                        "pid": MESH_DEVICE_PID, "tid": d,
                        "args": dev_args})
        # producer→consumer flows: anchor the start on the producing
        # thread inside the exchange span, finish at each consumer read
        consumers = reads.get(int(seq), [])
        if consumers:
            evs.append({"ph": "s", "id": int(seq), "name": "mesh.flow",
                        "cat": "mesh", "ts": end_us, "pid": 1,
                        "tid": rec[REC_TID]})
            for ts_us, tid in consumers:
                evs.append({"ph": "f", "bp": "e", "id": int(seq),
                            "name": "mesh.flow", "cat": "mesh",
                            "ts": max(ts_us, end_us), "pid": 1,
                            "tid": tid})
    return evs, sorted(devices)


def chrome_trace(profile: Dict[str, Any],
                 process_name: str = "spark-rapids-tpu") -> Dict[str, Any]:
    """Chrome trace-event JSON (the "JSON object format"): open in perfetto
    (ui.perfetto.dev → Open trace) or chrome://tracing. B/E pairs are
    emitted per thread in record order, which our per-thread span stacks
    guarantee to be properly nested. Queries that rode the mesh data plane
    additionally render one track per DEVICE (process "mesh devices") with
    the collective wait of every exchange aligned across tracks and flow
    arrows from producer exchange to consumer read
    (docs/observability.md "Mesh profiling")."""
    evs: List[Dict[str, Any]] = []
    tids = set()
    opened = set()
    for rec in profile["events"]:
        ph = rec[REC_PHASE]
        ts_us = rec[REC_TS] / 1e3
        tids.add(rec[REC_TID])
        if ph == "B":
            opened.add(rec[REC_SPAN])
            args = dict(rec[REC_ARGS] or {})
            if rec[REC_OP]:
                args.setdefault("op", rec[REC_OP])
            evs.append({"ph": "B", "name": rec[REC_NAME],
                        "cat": rec[REC_CAT], "ts": ts_us, "pid": 1,
                        "tid": rec[REC_TID], "args": args})
        elif ph == "E":
            # ring overflow can evict a long-lived span's B while its E
            # survives; a stray E would pop the wrong slice in the viewer
            # (same orphan handling as span_tree)
            if rec[REC_SPAN] not in opened:
                continue
            evs.append({"ph": "E", "ts": ts_us, "pid": 1,
                        "tid": rec[REC_TID]})
        else:
            args = dict(rec[REC_ARGS] or {})
            if rec[REC_OP]:
                args.setdefault("op", rec[REC_OP])
            evs.append({"ph": "i", "s": "t", "name": rec[REC_NAME],
                        "cat": rec[REC_CAT], "ts": ts_us, "pid": 1,
                        "tid": rec[REC_TID], "args": args})
    mesh_evs, device_ids = _mesh_tracks(profile)
    meta = [{"ph": "M", "name": "process_name", "pid": 1,
             "args": {"name": process_name}}]
    meta += [{"ph": "M", "name": "thread_name", "pid": 1, "tid": t,
              "args": {"name": f"thread-{t}"}} for t in sorted(tids)]
    if device_ids:
        meta.append({"ph": "M", "name": "process_name",
                     "pid": MESH_DEVICE_PID,
                     "args": {"name": "mesh devices"}})
        meta += [{"ph": "M", "name": "thread_name",
                  "pid": MESH_DEVICE_PID, "tid": d,
                  "args": {"name": f"device-{d}"}} for d in device_ids]
    return {"traceEvents": meta + evs + mesh_evs,
            "displayTimeUnit": "ms",
            "otherData": {"query": profile.get("name"),
                          "dropped_events": profile.get("dropped", 0),
                          # time.time_ns() at ts 0: the shift onto the
                          # realtime clock of a device trace
                          "t0_unix_ns": profile.get("t0_unix_ns")}}


def _counts(profile: Dict[str, Any]):
    """Aggregate instant events: (by_operator, dispatch_by_kind, sync_total,
    event_counts_by_cat, chaos_events, retry_events)."""
    by_op: Dict[str, Dict[str, Dict[str, int]]] = {}
    disp_by_kind: Dict[str, int] = {}
    by_cat: Dict[str, int] = {}
    chaos: List[Dict[str, Any]] = []
    retries: List[Dict[str, Any]] = []
    sync_total = 0
    for rec in profile["events"]:
        if rec[REC_PHASE] != "i":
            continue
        cat = rec[REC_CAT]
        by_cat[cat] = by_cat.get(cat, 0) + 1
        args = rec[REC_ARGS] or {}
        op = rec[REC_OP] or "<unattributed>"
        slot = by_op.setdefault(op, {})
        if cat == "dispatch":
            kind = str(args.get("kind", "?"))
            d = slot.setdefault("dispatches", {})
            d[kind] = d.get(kind, 0) + 1
            c = slot.setdefault("dispatch_cache", {})
            hit = str(args.get("cache", "?"))
            c[hit] = c.get(hit, 0) + 1
            if args.get("source") == "opjit" or args.get("cache") == "extern":
                # "extern" = launches recorded into calls_by_kind from
                # outside the opjit cache (opjit.record_external_dispatch,
                # e.g. the parquet device-decode programs) — they must
                # count here too or reconciliation would always fail
                disp_by_kind[kind] = disp_by_kind.get(kind, 0) + 1
        elif cat == "sync":
            kind = str(args.get("kind", "?"))
            s = slot.setdefault("syncs", {})
            s[kind] = s.get(kind, 0) + 1
            sync_total += 1
        elif cat == "chaos":
            chaos.append({"span": rec[REC_SPAN], "op": op,
                          "t_ns": rec[REC_TS], **args})
        elif cat == "retry":
            retries.append({"span": rec[REC_SPAN], "op": op,
                            "t_ns": rec[REC_TS], **args})
        else:
            e = slot.setdefault("events", {})
            e[rec[REC_NAME]] = e.get(rec[REC_NAME], 0) + 1
    return by_op, disp_by_kind, sync_total, by_cat, chaos, retries


def build_bundle(profile: Dict[str, Any],
                 plan_tree: Optional[List[Dict[str, Any]]] = None,
                 metrics: Optional[Dict[str, Dict[str, int]]] = None,
                 sync_ledger: Optional[Dict[str, Dict[str, int]]] = None,
                 dispatch_delta: Optional[Dict[str, int]] = None,
                 task_metrics: Optional[Dict[str, int]] = None,
                 mesh_profiles: Optional[List[Dict[str, Any]]] = None,
                 mesh_fallbacks: Optional[List[Dict[str, Any]]] = None,
                 mesh_dropped: int = 0) -> Dict[str, Any]:
    """The machine-readable per-query diagnostics bundle
    (docs/observability.md "Bundle schema"). `sync_ledger` and
    `dispatch_delta` are the SAME-query deltas of the SyncLedger and of
    opjit ``cache_stats()["calls_by_kind"]`` — the bundle's own event
    counts must reconcile with them exactly unless the ring overflowed.
    `mesh_profiles` / `mesh_fallbacks` are this query's collective-
    exchange records (obs/mesh_profile.py) — present only for queries
    that ran on a mesh session."""
    by_op, disp_by_kind, sync_total, by_cat, chaos, retries = \
        _counts(profile)
    dropped = int(profile.get("dropped", 0))
    # exclusive: no other query (traced or not) overlapped this one, so
    # process-wide counter deltas were attributable; when False the caller
    # passed the tracer's own per-query counters instead (obs/tracer.py)
    reconcile: Dict[str, Any] = {
        "overflow": dropped > 0,
        "exclusive": bool(profile.get("exclusive", True))}
    if dispatch_delta is not None:
        want = {k: v for k, v in dispatch_delta.items() if v}
        reconcile["dispatch_ok"] = dropped > 0 or disp_by_kind == want
        reconcile["dispatch_expected"] = want
    if sync_ledger is not None:
        want_syncs = {op: dict(kinds) for op, kinds in sync_ledger.items()}
        got_syncs = {op: slot["syncs"] for op, slot in by_op.items()
                     if slot.get("syncs")}
        reconcile["sync_ok"] = dropped > 0 or got_syncs == want_syncs
        reconcile["sync_total_expected"] = sum(
            sum(k.values()) for k in want_syncs.values())
    bundle = {
        "schema": "spark-rapids-tpu/query-profile/1",
        "query": profile.get("name"),
        "duration_ms": round(profile.get("duration_ns", 0) / 1e6, 3),
        "t0_unix_ns": profile.get("t0_unix_ns"),
        "dropped_events": dropped,
        "event_counts": by_cat,
        "spans": span_tree(profile),
        "plan": plan_tree or [],
        "metrics": metrics or {},
        "task_metrics": task_metrics or {},
        "by_operator": by_op,
        "dispatches_by_kind": disp_by_kind,
        "sync_events_total": sync_total,
        "chaos_events": chaos,
        "retry_events": retries,
        "reconcile": reconcile,
    }
    if mesh_profiles or mesh_fallbacks or mesh_dropped:
        # mesh section (docs/observability.md "Mesh profiling"): the
        # per-exchange phase breakdown + skew table, the worst-imbalance
        # exchange, the per-map fallback reason counts, and the count of
        # records the bounded profiler rings evicted inside this query's
        # window (never presented as a complete set when it is not)
        reasons: Dict[str, int] = {}
        for f in mesh_fallbacks or []:
            reasons[f["reason"]] = reasons.get(f["reason"], 0) + 1
        worst = max((p for p in mesh_profiles or []),
                    key=lambda p: p["skew"]["imbalance"], default=None)
        bundle["mesh"] = {
            "exchanges": list(mesh_profiles or []),
            "per_map_reasons": reasons,
            "skew_worst": None if worst is None else {
                "exchange": worst["exchange"], "seq": worst["seq"],
                **worst["skew"]},
            "watchdog_fired": any(p.get("watchdog_fired")
                                  for p in mesh_profiles or []),
            "dropped_records": int(mesh_dropped),
        }
    return bundle


def write_artifacts(bundle: Dict[str, Any], profile: Dict[str, Any],
                    out_dir: str, stem: str) -> Dict[str, str]:
    """Write the Chrome trace and the bundle JSON under ``out_dir``;
    returns {"chrome_trace": path, "bundle": path} (also recorded inside
    the bundle as ``artifacts``)."""
    import os
    os.makedirs(out_dir, exist_ok=True)
    trace_path = os.path.join(out_dir, f"{stem}.trace.json")
    bundle_path = os.path.join(out_dir, f"{stem}.profile.json")
    with open(trace_path, "w") as f:
        json.dump(chrome_trace(profile), f)
    paths = {"chrome_trace": trace_path, "bundle": bundle_path}
    bundle["artifacts"] = paths
    with open(bundle_path, "w") as f:
        json.dump(bundle, f, default=str)
    return paths
