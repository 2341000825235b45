"""Mesh efficiency profiler (ISSUE 13 tentpole): per-exchange wall
attribution, skew/straggler reporting, the collective watchdog, and the
efficiency-attribution summary.

Covers the bars the issue names: a forced-skew dataset produces a skew
report naming the heavy partition; chaos `mesh.link` latency trips the
watchdog (flight event + counter; no postmortem below the fatal
threshold, one at it); the multi-chip Chrome trace is well-formed
(per-device tracks, balanced B/E, flow events resolve); the profile's
phase walls sum to within tolerance of the `mesh.exchange` span; the
registry keys land in `metrics_snapshot()`; profiling adds ZERO device
syncs/dispatches to the hot path; the per-map "why not collective"
reasons surface in the bundle and `explain("metrics")`; and the sharded
runner attributes ≥90% of the mesh wall to named phases."""

import glob
import json
import os

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.chaos import FaultInjector
from spark_rapids_tpu.obs import flight, mesh_profile
from spark_rapids_tpu.obs import metrics as obs_metrics
from spark_rapids_tpu.obs.tracer import QueryTracer
from spark_rapids_tpu.session import TpuSession

N_DEV = 8


def _mesh_conf(**extra):
    base = {
        "spark.rapids.shuffle.mode": "ICI",
        "spark.rapids.tpu.mesh.enabled": "true",
        "spark.sql.shuffle.partitions": str(N_DEV),
        "spark.rapids.tpu.dispatch.partitionBatch": str(N_DEV),
        "spark.sql.autoBroadcastJoinThreshold": "0",
        "spark.rapids.tpu.agg.compiledStage.enabled": "false",
        "spark.rapids.tpu.join.compiledStage.enabled": "false",
    }
    base.update(extra)
    return base


@pytest.fixture(autouse=True)
def _fresh_profiler():
    mesh_profile.reset_for_tests()
    yield
    mesh_profile.reset_for_tests()
    flight.reset_for_tests()
    QueryTracer.reset_for_tests()


def _skew_tables(n=4000, heavy_frac=0.9, seed=11):
    """90% of the fact rows carry ONE join key: the fact-side join
    exchange lands ~90% of its rows on the chip that key hashes to."""
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 60, n)
    heavy = rng.random(n) < heavy_frac
    k[heavy] = 5
    fact = pa.table({"k": k, "v": rng.integers(-100, 100, n)})
    dim = pa.table({"k2": np.arange(60), "r": rng.integers(0, 9, 60)})
    return fact, dim


def _skew_query(s, fact, dim):
    fd = s.createDataFrame(fact, num_partitions=4)
    dd = s.createDataFrame(dim, num_partitions=2)
    return (fd.join(dd, on=fd["k"] == dd["k2"])
            .groupBy("k")
            .agg(F.sum(F.col("v")).alias("sv"),
                 F.max(F.col("r")).alias("mr"))
            .sort("k"))


# ---------------------------------------------------------------------------
# skew: a forced-skew dataset produces a report naming the heavy partition
# ---------------------------------------------------------------------------

def test_forced_skew_names_heavy_partition():
    fact, dim = _skew_tables()
    s = TpuSession(_mesh_conf(**{"spark.rapids.tpu.trace.enabled": "true"}))
    _skew_query(s, fact, dim).collect()
    prof = s.last_query_profile()
    assert prof is not None
    mesh = prof.get("mesh")
    assert mesh is not None and mesh["exchanges"], \
        "traced mesh query carries no mesh section"
    worst = max(mesh["exchanges"], key=lambda p: p["skew"]["imbalance"])
    skew = worst["skew"]
    recv = worst["recv_rows"]
    # the report names the chip that actually received the heavy key
    assert skew["straggler_chip"] == int(np.argmax(recv))
    assert recv[skew["straggler_chip"]] > 0.5 * sum(recv)
    assert skew["imbalance"] >= 2.0
    assert skew["max_rows"] == max(recv)
    # the bundle's one-line summary points at the same exchange
    assert mesh["skew_worst"]["straggler_chip"] == skew["straggler_chip"]
    # phase walls present for every exchange, all non-negative
    for p in mesh["exchanges"]:
        ph = p["phases_ms"]
        assert set(ph) == {"staging", "launch", "collective_wait",
                           "compact"}
        assert all(v >= 0 for v in ph.values())
        assert len(p["send_rows"]) == N_DEV
        assert len(p["recv_rows"]) == N_DEV
        assert len(p["recv_bytes"]) == N_DEV


# ---------------------------------------------------------------------------
# collective watchdog: chaos mesh.link latency trips it
# ---------------------------------------------------------------------------

def test_chaos_slow_link_trips_watchdog(tmp_path):
    fact, dim = _skew_tables(n=1500, heavy_frac=0.0, seed=3)
    pdir = str(tmp_path / "pm")
    s = TpuSession(_mesh_conf(**{
        "spark.rapids.tpu.obs.collectiveWatchdogMs": "5",
        "spark.rapids.tpu.obs.postmortemDir": pdir,
        "spark.rapids.tpu.test.chaos.enabled": "true",
        "spark.rapids.tpu.test.chaos.sites": "mesh.link",
        "spark.rapids.tpu.test.chaos.kinds": "latency",
        "spark.rapids.tpu.test.chaos.probability": "1.0",
        "spark.rapids.tpu.test.chaos.latencyMs": "60",
    }))
    try:
        reg0 = obs_metrics.MetricsRegistry.get().snapshot()
        fired0 = sum(reg0["counters"].get("mesh.watchdog_fired",
                                          {}).values())
        _skew_query(s, fact, dim).collect()
        reg = obs_metrics.MetricsRegistry.get().snapshot()
        fired = sum(reg["counters"].get("mesh.watchdog_fired",
                                        {}).values())
        assert fired > fired0, "slow link did not trip the watchdog"
        notes = [r for r in flight.snapshot()
                 if r.get("event") == "mesh.watchdog"]
        assert notes, "no mesh.watchdog flight-recorder event"
        assert notes[0]["threshold_ms"] == 5.0
        # below the fatal threshold (disabled): NO postmortem bundle
        assert not glob.glob(os.path.join(pdir, "*.json"))
        # the completed exchange's profile records that the watchdog fired
        recents = mesh_profile.recent()
        assert any(p["watchdog_fired"] for p in recents)
    finally:
        FaultInjector.reset_for_tests()


def test_watchdog_fatal_threshold_writes_postmortem(tmp_path):
    fact, dim = _skew_tables(n=1500, heavy_frac=0.0, seed=4)
    pdir = str(tmp_path / "pm")
    s = TpuSession(_mesh_conf(**{
        "spark.rapids.tpu.obs.collectiveWatchdogMs": "5",
        "spark.rapids.tpu.obs.collectiveWatchdogFatalMs": "15",
        "spark.rapids.tpu.obs.postmortemDir": pdir,
        "spark.rapids.tpu.test.chaos.enabled": "true",
        "spark.rapids.tpu.test.chaos.sites": "mesh.link",
        "spark.rapids.tpu.test.chaos.kinds": "latency",
        "spark.rapids.tpu.test.chaos.probability": "1.0",
        "spark.rapids.tpu.test.chaos.latencyMs": "80",
    }))
    try:
        _skew_query(s, fact, dim).collect()
        paths = glob.glob(
            os.path.join(pdir, "postmortem-collective_watchdog-*.json"))
        assert paths, "fatal watchdog threshold wrote no postmortem"
        with open(paths[0]) as f:
            pm = json.load(f)
        assert pm["reason"] == "collective_watchdog"
        assert any(r.get("event") == "mesh.watchdog_fatal"
                   for r in pm["flight_events"])
        assert pm["metrics"]["schema"] == "spark-rapids-tpu/metrics/1"
    finally:
        FaultInjector.reset_for_tests()


# ---------------------------------------------------------------------------
# Chrome trace: per-device tracks, balanced B/E, flow events resolve
# ---------------------------------------------------------------------------

def test_multichip_chrome_trace_well_formed(tmp_path):
    from spark_rapids_tpu.obs.export import MESH_DEVICE_PID
    fact, dim = _skew_tables(n=2000, heavy_frac=0.5, seed=7)
    s = TpuSession(_mesh_conf(**{
        "spark.rapids.tpu.trace.enabled": "true",
        "spark.rapids.tpu.trace.dir": str(tmp_path)}))
    _skew_query(s, fact, dim).collect()
    paths = glob.glob(os.path.join(str(tmp_path), "*.trace.json"))
    assert paths
    with open(paths[0]) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    # one track per device under the synthetic "mesh devices" process
    dev_names = {m["tid"]: m["args"]["name"] for m in evs
                 if m.get("ph") == "M" and m.get("name") == "thread_name"
                 and m.get("pid") == MESH_DEVICE_PID}
    assert dev_names == {d: f"device-{d}" for d in range(N_DEV)}
    assert any(m.get("ph") == "M" and m.get("name") == "process_name"
               and m.get("pid") == MESH_DEVICE_PID
               and m["args"]["name"] == "mesh devices" for m in evs)
    # collective spans aligned across tracks: each exchange_seq appears
    # once per device with identical ts/dur
    xs = [e for e in evs if e.get("ph") == "X"
          and e.get("pid") == MESH_DEVICE_PID]
    assert xs
    by_seq = {}
    for e in xs:
        by_seq.setdefault(e["args"]["exchange_seq"], []).append(e)
    for seq, group in by_seq.items():
        assert len(group) == N_DEV
        assert sorted(e["tid"] for e in group) == list(range(N_DEV))
        assert len({(e["ts"], e["dur"]) for e in group}) == 1
    # balanced B/E per engine thread (pid 1)
    for tid in {e["tid"] for e in evs
                if e.get("ph") in ("B", "E") and e.get("pid") == 1}:
        b = sum(1 for e in evs if e.get("ph") == "B" and e["tid"] == tid)
        en = sum(1 for e in evs if e.get("ph") == "E" and e["tid"] == tid)
        assert b == en, f"unbalanced B/E on tid {tid}"
    # flow events resolve: every producer start has a consumer finish at
    # or after it, with a matching id
    starts = [e for e in evs if e.get("ph") == "s"]
    finishes = [e for e in evs if e.get("ph") == "f"]
    assert starts, "no producer→consumer flow events in a mesh trace"
    for st in starts:
        match = [fi for fi in finishes if fi["id"] == st["id"]]
        assert match, f"flow {st['id']} never finishes"
        assert all(fi["ts"] >= st["ts"] for fi in match)


# ---------------------------------------------------------------------------
# phase walls vs the mesh.exchange span
# ---------------------------------------------------------------------------

def test_phase_walls_sum_to_span_duration():
    fact, dim = _skew_tables(n=3000, heavy_frac=0.3, seed=9)
    s = TpuSession(_mesh_conf(**{"spark.rapids.tpu.trace.enabled": "true"}))
    _skew_query(s, fact, dim).collect()
    prof = s.last_query_profile()
    assert prof is not None and prof.get("mesh")
    spans = []

    def find(node):
        if isinstance(node, dict):
            if "mesh.exchange" in str(node.get("name", "")):
                spans.append(node)
            for c in node.get("children", []):
                find(c)

    find(prof["spans"])
    assert spans
    profiles = {p["seq"]: p for p in prof["mesh"]["exchanges"]}
    checked = 0
    for sp in spans:
        seq = sp["args"].get("exchange_seq")
        if seq not in profiles or sp.get("dur_ns") is None:
            continue
        ph = profiles[seq]["phases_ms"]
        # the span covers launch → wait → compact (staging precedes it
        # and rides the span args); the walls must account for the span
        covered = ph["launch"] + ph["collective_wait"] + ph["compact"]
        dur_ms = sp["dur_ns"] / 1e6
        assert abs(covered - dur_ms) <= max(2.0, 0.25 * dur_ms), \
            f"phase walls {covered}ms vs span {dur_ms}ms"
        assert sp["args"]["staging_ms"] >= 0
        checked += 1
    assert checked >= 1


# ---------------------------------------------------------------------------
# registry keys + metrics_snapshot folding
# ---------------------------------------------------------------------------

def test_registry_keys_in_metrics_snapshot():
    fact, dim = _skew_tables(n=2500, heavy_frac=0.9, seed=13)
    s = TpuSession(_mesh_conf())
    _skew_query(s, fact, dim).collect()
    snap = s.metrics_snapshot()
    hists = snap["histograms"]
    assert any(c.get("count")
               for c in hists.get("mesh.collective_wait_ms", {}).values())
    assert any(c.get("count")
               for c in hists.get("mesh.skew_imbalance", {}).values())
    # the forced skew guarantees a straggler fired at least once
    assert any(c.get("count")
               for c in hists.get("mesh.straggler_wait_ms", {}).values())
    mp = snap["external"]["mesh_profiles"]
    assert mp["recent_exchanges"], "snapshot folds no recent exchanges"
    rec = mp["recent_exchanges"][-1]
    assert set(rec["phases_ms"]) == {"staging", "launch",
                                     "collective_wait", "compact"}


# ---------------------------------------------------------------------------
# zero additional device syncs / dispatches on the hot path
# ---------------------------------------------------------------------------

def test_profiler_adds_zero_syncs_and_dispatches():
    from spark_rapids_tpu.execs import opjit
    from spark_rapids_tpu.profiling import SyncLedger
    fact, dim = _skew_tables(n=2000, heavy_frac=0.5, seed=17)
    s = TpuSession(_mesh_conf())
    q = _skew_query(s, fact, dim)
    q.collect()  # warm: compiles everything

    def one_collect_delta():
        led0 = SyncLedger.get().total()
        d0 = dict(opjit.cache_stats()["calls_by_kind"])
        q.collect()
        led1 = SyncLedger.get().total()
        d1 = opjit.cache_stats()["calls_by_kind"]
        return led1 - led0, {k: d1.get(k, 0) - d0.get(k, 0)
                             for k in set(d0) | set(d1)}

    syncs_on, disp_on = one_collect_delta()
    assert mesh_profile.recent(), "profiler recorded nothing while on"
    mesh_profile.set_enabled(False)
    try:
        syncs_off, disp_off = one_collect_delta()
    finally:
        mesh_profile.set_enabled(True)
    # recording per-exchange profiles must not change EITHER ground-truth
    # counter: same blocking syncs, same dispatches by kind
    assert syncs_on == syncs_off
    assert disp_on == disp_off
    assert disp_on.get("mesh_collective", 0) >= 1


# ---------------------------------------------------------------------------
# "why not collective" reasons: bundle, registry, explain("metrics")
# ---------------------------------------------------------------------------

def test_per_map_reason_surfaces_everywhere():
    rng = np.random.default_rng(2)
    t = pa.table({"k": rng.integers(0, 10, 800),
                  "s": pa.array([f"x{i % 5}" for i in range(800)])})
    # dictionary encode OFF: this test exercises the per-map REASON
    # surfaces (with it on, a string payload rides the collective)
    s = TpuSession(_mesh_conf(**{
        "spark.rapids.tpu.trace.enabled": "true",
        "spark.rapids.tpu.exchange.dictionaryEncode.enabled": "false"}))
    df = (s.createDataFrame(t, num_partitions=4)
          .groupBy("k").agg(F.max(F.col("s")).alias("ms")))
    df.collect()
    # bundle: the mesh section's reason table
    prof = s.last_query_profile()
    assert prof is not None
    reasons = (prof.get("mesh") or {}).get("per_map_reasons") or {}
    assert reasons.get("string_or_nested_payload", 0) >= 1, reasons
    # registry: the always-on counter with the reason label
    snap = s.metrics_snapshot()
    cells = snap["counters"].get("mesh.per_map_exchange", {})
    assert any("string_or_nested_payload" in labels for labels in cells)
    # explain("metrics"): the plan says why the exchange rode per-map
    rendered = s.explain("metrics")
    assert "per_map=string_or_nested_payload" in rendered


def test_collective_exchange_shows_no_reason():
    fact, dim = _skew_tables(n=1500, heavy_frac=0.0, seed=23)
    s = TpuSession(_mesh_conf())
    _skew_query(s, fact, dim).collect()
    rendered = s.explain("metrics")
    # fixed-width exchanges rode the collective: no per_map annotation
    assert "per_map=" not in rendered


# ---------------------------------------------------------------------------
# sharded runner: efficiency attribution ≥90% of the mesh wall
# ---------------------------------------------------------------------------

def test_sharded_attribution_covers_mesh_wall():
    from spark_rapids_tpu.parallel.sharded import (attribute_efficiency,
                                                   run_mesh_query,
                                                   summarize)
    fact, dim = _skew_tables(n=2500, heavy_frac=0.6, seed=29)

    def build(s):
        return _skew_query(s, fact, dim)

    rec = run_mesh_query("skewq", build, n_devices=N_DEV, iters=1)
    assert rec["bit_identical"]
    assert rec["collective_launches"] >= 1
    assert rec["exchange_profiles"], "measured collect kept no profiles"
    ea = attribute_efficiency(rec)
    # a value above ~100 would mean the phase walls overcounted the wall
    # they were measured against (attributed_pct is deliberately unclamped)
    assert 90.0 <= ea["attributed_pct"] <= 110.0
    summary = summarize([rec], N_DEV, {"skewq": 2500})
    q = summary["queries"]["skewq"]
    # the compact line drops zero-valued phase percentages (size budget)
    # but always carries compute + the total attributed share
    assert set(q["efficiency_attribution"]) <= {
        "staging", "launch", "collective_wait", "compact", "compute",
        "attributed_pct"}
    assert 90.0 <= q["efficiency_attribution"]["attributed_pct"] <= 110.0
    assert "collective_phases_ms_total" in summary
    assert "collective_ms_total" not in summary  # an older name of it
    assert set(q["phases_ms"]) == {"staging", "launch", "collective_wait",
                                   "compact"}
    assert q["skew"] is not None and "imbalance" in q["skew"]
    assert q["per_map_exchanges"] == {}
    assert summary["watchdog_fired_any"] is False
    # the phase walls the attribution is built from came from the SAME
    # collect as the wall they are divided by
    assert rec["wall_ms_profiled"] > 0
