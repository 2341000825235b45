"""obs_report: read out the always-on metrics registry, or self-check the
observability plane.

Usage:
    python -m tools.obs_report                # human-readable snapshot
    python -m tools.obs_report --json         # raw JSON (dashboards/diffing)
    python -m tools.obs_report --mesh         # + the mesh section: collective
                                              # stats, recent per-exchange
                                              # profiles (phase walls + skew),
                                              # per-map fallback reasons
    python -m tools.obs_report --self-check   # exercise registry + flight
                                              # recorder + concurrent tracer
                                              # + mesh profiler wiring; exit
                                              # non-zero on any broken
                                              # invariant (CI fast tier)

The snapshot is ``spark_rapids_tpu.obs.metrics.full_snapshot()`` — the same
payload ``session.metrics_snapshot()`` serves: registry counters/gauges/
histograms (with p50/p95/p99 readouts), the per-phase totals of the served
path (``obs.phase``) plus the engine's other process-wide
counters folded in (opjit cache stats, mesh collective_stats, SyncLedger,
task metrics, chaos, shuffle, HBM). Schema: docs/observability.md.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _render_mesh(snap: dict) -> str:
    """The --mesh section: collective launch stats, the recent
    per-exchange profiles (phase walls + skew table + straggler), and the
    per-map fallback reasons — everything the mesh efficiency profiler
    keeps (docs/observability.md "Mesh profiling")."""
    lines = ["", "## mesh (collective data plane)"]
    ext = snap.get("external", {})
    col = ext.get("collective", {}) or {}
    if col and "error" not in col:
        lines.append(
            f"  collectives: launches={col.get('launches', 0)} "
            f"rows={col.get('rows_sent', 0)} "
            f"stage={col.get('stage_ns', 0) / 1e6:.1f}ms "
            f"launch={col.get('launch_ns', 0) / 1e6:.1f}ms "
            f"wait={col.get('wait_ns', 0) / 1e6:.1f}ms "
            f"compact={col.get('compact_ns', 0) / 1e6:.1f}ms")
    mp = ext.get("mesh_profiles", {}) or {}
    reasons = mp.get("per_map_reasons") or {}
    if reasons:
        lines.append("  per-map exchanges (why not collective): "
                     + ", ".join(f"{k}={v}"
                                 for k, v in sorted(reasons.items())))
    recents = mp.get("recent_exchanges") or []
    if not recents:
        lines.append("  no collective exchanges recorded")
    for p in recents:
        ph = p.get("phases_ms", {})
        sk = p.get("skew", {})
        strag = sk.get("straggler_chip")
        lines.append(
            f"  exchange s{p.get('exchange')} seq={p.get('seq')} "
            f"[{p.get('partitioning')}, n_dev={p.get('n_dev')}] "
            f"query={p.get('query') or '-'}"
            + (" WATCHDOG" if p.get("watchdog_fired") else ""))
        lines.append(
            f"    phases_ms: staging={ph.get('staging')} "
            f"launch={ph.get('launch')} "
            f"wait={ph.get('collective_wait')} "
            f"compact={ph.get('compact')}")
        lines.append(
            f"    skew: imbalance={sk.get('imbalance')} "
            f"max={sk.get('max_rows')} median={sk.get('median_rows')}"
            + (f" straggler=chip{strag}" if strag is not None else ""))
        lines.append(f"    recv_rows: {p.get('recv_rows')}")
    return "\n".join(lines)


def _render(snap: dict) -> str:
    lines = ["# spark-rapids-tpu metrics snapshot", ""]
    q = snap.get("queries", {})
    lines.append(f"active queries: {len(q.get('active', []))} "
                 f"{q.get('active', [])} (epoch {q.get('epoch')})")
    for section in ("counters", "gauges"):
        vals = snap.get(section, {})
        if vals:
            lines += ["", f"## {section}"]
            for name in sorted(vals):
                for labels, v in sorted(vals[name].items()):
                    tag = f"{{{labels}}}" if labels else ""
                    lines.append(f"  {name}{tag} = {v}")
    hists = snap.get("histograms", {})
    if hists:
        lines += ["", "## histograms (log2 buckets)"]
        for name in sorted(hists):
            for labels, h in sorted(hists[name].items()):
                tag = f"{{{labels}}}" if labels else ""
                lines.append(
                    f"  {name}{tag}: count={h['count']} sum={h['sum']:.1f} "
                    f"p50={h['p50']:.0f} p95={h['p95']:.0f} "
                    f"p99={h['p99']:.0f}")
    phases = snap.get("phases", {})
    if phases:
        lines += ["", "## phases (totals over the kept query summaries; self = "
                  "wall - nested phases, off-CPU = wall - thread CPU)"]
        for name, t in sorted(phases.items(),
                              key=lambda kv: -kv[1]["wall_ns"]):
            lines.append(
                f"  {name} [{t['cat']}]: queries={t['queries']} "
                f"count={t['count']} wall_ms={t['wall_ns'] / 1e6:.3f} "
                f"self_ms={(t['wall_ns'] - t['child_wall_ns']) / 1e6:.3f} "
                + ("cpu_ms=not-sampled" if t["cpu_ns"] is None
                   else f"cpu_ms={t['cpu_ns'] / 1e6:.3f}"))
    pc = (snap.get("external", {}).get("scheduler", {}) or {}) \
        .get("plan_cache")
    if pc and "error" not in pc:
        lines += ["", "## plan cache (scheduler-owned, docs/serving.md)"]
        lines.append(
            f"  entries={pc.get('entries', 0)}/{pc.get('capacity', 0)} "
            f"hits={pc.get('hits', 0)} misses={pc.get('misses', 0)} "
            f"invalidations={pc.get('invalidations', 0)}")
        per = pc.get("per_entry_hits") or {}
        for label, h in sorted(per.items(), key=lambda kv: -kv[1]):
            lines.append(f"  entry {label}: hits={h}")
    ext = snap.get("external", {})
    if ext:
        lines += ["", "## folded process-wide counters"]
        for k in sorted(ext):
            lines.append(f"  {k}: {json.dumps(ext[k], default=str)}")
    return "\n".join(lines)


def _self_check() -> int:
    """Exercise the plane end-to-end in-process; print PASS/FAIL lines and
    return a process exit code. Deliberately cheap (no session, no device
    work) so the CI fast tier can run it on every commit."""
    from spark_rapids_tpu.obs import flight, metrics
    from spark_rapids_tpu.obs import tracer as obs_tracer

    failures = []

    def check(name, cond, detail=""):
        print(f"  {'PASS' if cond else 'FAIL'}: {name}"
              + (f" ({detail})" if detail and not cond else ""))
        if not cond:
            failures.append(name)

    metrics.MetricsRegistry.reset_for_tests()
    metrics.reset_query_state_for_tests()
    flight.reset_for_tests()
    obs_tracer.QueryTracer.reset_for_tests()

    # registry: counter/gauge/histogram round trip with known quantiles
    metrics.counter_inc("selfcheck.counter", 3, site="a")
    metrics.counter_inc("selfcheck.counter", 2, site="a")
    metrics.gauge_max("selfcheck.gauge", 7)
    metrics.gauge_max("selfcheck.gauge", 5)
    for v in (1, 2, 4, 100, 1000):
        metrics.histogram_observe("selfcheck.hist", v)
    snap = metrics.MetricsRegistry.get().snapshot()
    check("counter accumulates per label set",
          snap["counters"].get("selfcheck.counter", {}).get("site=a") == 5,
          str(snap["counters"]))
    check("gauge_max keeps the high-water",
          snap["gauges"].get("selfcheck.gauge", {}).get("") == 7)
    h = snap["histograms"].get("selfcheck.hist", {}).get("", {})
    check("histogram count/sum", h.get("count") == 5
          and abs(h.get("sum", 0) - 1107) < 1e-9)
    check("histogram p50 within a factor of two of the median",
          2 <= h.get("p50", 0) <= 8, str(h))
    check("histogram p99 reaches the top observation's bucket",
          h.get("p99", 0) >= 1000, str(h))

    # query lifecycle feeds the latency histogram + active gauge
    tok = metrics.query_begin("selfcheck-q")
    check("active query listed",
          "selfcheck-q" in metrics.active_queries())
    metrics.query_end(tok, rows=1000)
    snap = metrics.MetricsRegistry.get().snapshot()
    lat = snap["histograms"].get("query.latency_ms", {})
    check("query latency histogram populated",
          any(c.get("count") for c in lat.values()), str(lat))

    # concurrent tracing: two tracers on two threads, zero silent drops
    import threading
    results = {}

    def trace_one(key):
        tr = obs_tracer.begin_query(f"selfcheck-{key}")
        results[key] = tr
        if tr is not None:
            with obs_tracer.span("op", cat="op"):
                # the path profiling.SyncLedger.record takes: ring event
                # plus the tracer's per-query sync counter
                obs_tracer.sync_event("X", "rows")
            results[f"{key}-profile"] = obs_tracer.end_query(tr)

    t = threading.Thread(target=trace_one, args=("bg",))
    tr_fg = obs_tracer.begin_query("selfcheck-fg")
    t.start()
    t.join()
    check("two queries trace concurrently",
          tr_fg is not None and results.get("bg") is not None)
    prof_bg = results.get("bg-profile") or {}
    check("concurrent tracer records its own events",
          prof_bg.get("sync_counts", {}).get("X", {}).get("rows") == 1,
          str(prof_bg.get("sync_counts")))
    if tr_fg is not None:
        obs_tracer.end_query(tr_fg)

    # capacity drop is counted, never silent
    tr1 = obs_tracer.begin_query("cap-owner", max_concurrent=1)

    def try_over_capacity():
        results["over"] = obs_tracer.begin_query("cap-over",
                                                 max_concurrent=1)

    t2 = threading.Thread(target=try_over_capacity)
    t2.start()
    t2.join()
    snap = metrics.MetricsRegistry.get().snapshot()
    drops = snap["counters"].get("trace.dropped_queries", {})
    check("capacity drop returns None and increments "
          "trace.dropped_queries",
          results.get("over") is None and sum(drops.values()) >= 1,
          str(drops))
    if tr1 is not None:
        obs_tracer.end_query(tr1)

    # mesh efficiency profiler: skew math, profile recording, registry
    # histograms, fallback reasons, the watchdog timer, and the --mesh
    # rendering over the resulting snapshot
    from spark_rapids_tpu.obs import mesh_profile
    mesh_profile.reset_for_tests()
    seq = mesh_profile.alloc_seq()
    prof = mesh_profile.record_exchange(
        seq, shuffle_id=7, partitioning="hash", n_dev=4,
        send_rows=[100, 100, 100, 100], recv_rows=[370, 10, 10, 10],
        recv_bytes=[3700, 100, 100, 100], stage_ns=2_000_000,
        launch_ns=1_000_000, wait_ns=4_000_000, compact_ns=500_000)
    check("mesh profile records phase walls",
          prof is not None
          and prof["phases_ms"]["collective_wait"] == 4.0, str(prof))
    check("skew report names the heavy chip",
          prof["skew"]["straggler_chip"] == 0
          and prof["skew"]["imbalance"] > 2.0, str(prof["skew"]))
    mesh_profile.record_fallback(8, "string_or_nested_payload")
    snap = metrics.MetricsRegistry.get().snapshot()
    check("mesh.skew_imbalance histogram populated",
          any(c.get("count")
              for c in snap["histograms"].get("mesh.skew_imbalance",
                                              {}).values()))
    check("mesh.straggler_wait_ms histogram populated",
          any(c.get("count")
              for c in snap["histograms"].get("mesh.straggler_wait_ms",
                                              {}).values()))
    check("per-map fallback reason counted",
          mesh_profile.fallback_counts()
          .get("string_or_nested_payload") == 1)
    import time as _time
    wd_holder = {}
    # arm with an explicitly tiny threshold through maybe_configure
    from spark_rapids_tpu.config import RapidsConf
    mesh_profile.maybe_configure(RapidsConf({
        "spark.rapids.tpu.obs.collectiveWatchdogMs": "5"}))
    with mesh_profile.collective_watchdog(9, 4) as wd:
        _time.sleep(0.08)
        wd_holder["fired"] = wd.fired
    snap = metrics.MetricsRegistry.get().snapshot()
    fired = snap["counters"].get("mesh.watchdog_fired", {})
    check("collective watchdog trips while the wait is blocked",
          wd_holder.get("fired") and sum(fired.values()) >= 1,
          str(fired))
    check("watchdog note lands in the flight ring",
          any(r.get("event") == "mesh.watchdog"
              for r in flight.snapshot()))
    mesh_render = _render_mesh(metrics.full_snapshot())
    check("--mesh rendering shows the exchange + straggler",
          "exchange s7" in mesh_render and "straggler=chip0" in mesh_render,
          mesh_render[:200])
    mesh_profile.reset_for_tests()

    # flight recorder: notes land in the ring and in a postmortem bundle
    flight.note("selfcheck.note", value=42)
    pm = flight.build_postmortem("selfcheck", RuntimeError("boom"),
                                 last_k=16)
    check("flight note in postmortem last-K",
          any(r.get("event") == "selfcheck.note"
              for r in pm["flight_events"]))
    check("postmortem carries a registry snapshot",
          pm.get("metrics", {}).get("schema")
          == "spark-rapids-tpu/metrics/1")
    check("postmortem carries engine state",
          "hbm" in pm.get("engine_state", {}))

    metrics.MetricsRegistry.reset_for_tests()
    metrics.reset_query_state_for_tests()
    flight.reset_for_tests()
    obs_tracer.QueryTracer.reset_for_tests()
    if failures:
        print(f"self-check FAILED: {failures}")
        return 1
    print("self-check ok")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="obs_report", description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="raw JSON instead of the human rendering")
    ap.add_argument("--mesh", action="store_true",
                    help="append the mesh section (collective stats, "
                         "recent per-exchange profiles, fallback reasons)")
    ap.add_argument("--self-check", action="store_true",
                    help="exercise the observability plane; exit non-zero "
                         "on a broken invariant")
    args = ap.parse_args(argv)
    if args.self_check:
        return _self_check()
    from spark_rapids_tpu.obs import metrics
    snap = metrics.full_snapshot()
    if args.json:
        print(json.dumps(snap, indent=2, default=str))
    else:
        out = _render(snap)
        if args.mesh:
            out += "\n" + _render_mesh(snap)
        print(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
