"""The one traffic generator. It reads a traffic file (`traffic/<name>.json`)
and drives `send(tenant, template)` callables either as a closed loop (each
client sends its next query when the last one answered) or as an open loop
(queries are due on a schedule made from the seed, whether or not earlier ones
have finished). A new mix is a new data file; nothing here names a cell.

Traffic file keys:
  loop       "closed" | "open"
  tenants    how many tenants share the table (each gets an even slice)
  clients    closed loop: clients (threads), each bound to tenant i % tenants
  templates  [{"query": <queries/NAME.py>, "share": w, "class": <SLO class>|null}]
  tenant_zipf  exponent of the tenant popularity (0 = uniform)
  rate_per_s   open loop: arrivals per second (fixed; found once by a sweep)
  schedule_seed  open loop: fixes the one arrival sequence every run rotates
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

import numpy as np

from .stats import apportion, percentile


@dataclass
class Record:
    """One query of the window, on the harness's clock (seconds from window start)."""
    tenant: int
    template: int
    due: float
    sent: float = math.nan
    done: float = math.nan
    free_at: float = 0.0         # when this query's tenant had answered its previous one
    result: Any = None           # rows, or the exception / shed object
    failed: bool = False
    extra: dict = field(default_factory=dict)


def mix(traffic: dict) -> List[tuple]:
    """[(tenant, template, weight)] of the joint popularity."""
    n_t = int(traffic.get("tenants", 1))
    z = float(traffic.get("tenant_zipf", 0.0))
    tw = [1.0 / (r + 1) ** z for r in range(n_t)]
    return [(t, q, tw[t] * float(tpl["share"]))
            for t in range(n_t) for q, tpl in enumerate(traffic["templates"])]


def open_schedule(traffic: dict, seconds: float, seed: int) -> List[Record]:
    """The arrivals of an open-loop window. The count, the gaps (the quantiles
    of the exponential distribution at the cell's rate, shuffled once by the
    traffic file's own `schedule_seed`) and the (tenant, template) of each
    arrival are one fixed sequence; the run's seed only rotates it. So every
    seed offers the same work with the same bursts, starting at another point.
    Measured on the chip (PR 23): with the order drawn afresh from each seed,
    two seeds' p95 differed by up to 30 % while two runs of one seed agreed
    within 4 % — the order of the bursts, not the system, was being measured."""
    rate = float(traffic["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    fixed = np.random.default_rng([int(traffic.get("schedule_seed", 0)), 0x0A11])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= seconds / gaps.sum() * (n / (n + 1.0))     # the last arrival stays inside
    gaps = fixed.permutation(gaps)
    cells = mix(traffic)
    counts = apportion([w for _, _, w in cells], n)
    kinds = np.repeat(np.arange(len(cells)), counts)
    fixed.shuffle(kinds)
    k = int(np.random.default_rng([int(seed), 0x0A11]).integers(n))
    due = np.cumsum(np.roll(gaps, -k))
    return [Record(tenant=cells[c][0], template=cells[c][1], due=float(d))
            for c, d in zip(np.roll(kinds, -k), due)]


def closed_order(traffic: dict, seed: int, client: int, length: int = 4096) -> List[tuple]:
    """The (tenant, template) sequence a closed-loop client walks through."""
    n_t = int(traffic.get("tenants", 1))
    tenant = client % n_t
    cells = [(t, q, w) for t, q, w in mix(traffic) if t == tenant]
    counts = apportion([w for _, _, w in cells], length)
    kinds = np.repeat(np.arange(len(cells)), counts)
    np.random.default_rng([int(seed), 0xC105, client]).shuffle(kinds)
    return [(cells[k][0], cells[k][1]) for k in kinds]


Send = Callable[[Record], None]


def run_closed(traffic: dict, seconds: float, seed: int, send: Send, t0: float,
               on_query_end: Optional[Callable[[Record], None]] = None) -> List[Record]:
    """Each client sends back to back until `seconds` have passed since `t0`
    (a `time.perf_counter()` reading, the window's start); the query in flight
    then finishes and counts. Returns the records in send order."""
    clients = int(traffic.get("clients", 1))
    out: List[List[Record]] = [[] for _ in range(clients)]

    def client(i: int) -> None:
        free_at = 0.0
        for tenant, template in closed_order(traffic, seed, i):
            now = time.perf_counter() - t0
            if now >= seconds:
                return
            rec = Record(tenant=tenant, template=template, due=now, sent=now,
                         free_at=free_at)
            send(rec)
            rec.done = free_at = time.perf_counter() - t0
            out[i].append(rec)
            if on_query_end is not None:
                on_query_end(rec)

    if clients == 1:         # one client: no thread, the caller's thread sends
        client(0)
    else:
        _run_threads([threading.Thread(target=client, args=(i,), name=f"client-{i}")
                      for i in range(clients)])
    return sorted((r for recs in out for r in recs), key=lambda r: r.sent)


def run_open(traffic: dict, seconds: float, seed: int, send: Send,
             t0: float) -> List[Record]:
    """Every tenant is one connection: a thread that sends each of its queries
    when it is due (counted from `t0`, the window's start), or as soon as its
    previous one has answered. Latency is counted from the due time, so a
    stall delays what queues behind it."""
    schedule = open_schedule(traffic, seconds, seed)
    n_t = int(traffic.get("tenants", 1))
    per_tenant = [[r for r in schedule if r.tenant == t] for t in range(n_t)]

    def tenant(recs: List[Record]) -> None:
        free_at = 0.0
        for rec in recs:
            wait = rec.due - (time.perf_counter() - t0)
            if wait > 0:
                time.sleep(wait)
            rec.free_at = free_at
            rec.sent = time.perf_counter() - t0
            send(rec)
            rec.done = free_at = time.perf_counter() - t0

    _run_threads([threading.Thread(target=tenant, args=(recs,), name=f"tenant-{t}")
                  for t, recs in enumerate(per_tenant) if recs])
    return schedule


def open_summary(records: List[Record], wall: float) -> dict:
    """What an open-loop window looked like from the generator's side, for the
    log: latency from due time at several quantiles, and for each tenant its
    queries, the share of the window its one connection was busy, its own p95
    and how many of the slowest twentieth of all queries were its."""
    ok = [r for r in records if not r.failed]
    lat = sorted((r.done - r.due) * 1e3 for r in ok)
    if not lat:
        return {}
    cut = percentile(lat, 0.95)
    out = {"mean_ms": sum(lat) / len(lat), "max_ms": lat[-1], "tenants": []}
    for q in (0.5, 0.75, 0.9, 0.95, 0.99):
        out[f"p{int(q * 100)}_ms"] = percentile(lat, q)
    for t in sorted({r.tenant for r in ok}):
        mine = [r for r in ok if r.tenant == t]
        out["tenants"].append({
            "n": len(mine), "busy": sum(r.done - r.sent for r in mine) / wall,
            "p95_ms": percentile([(r.done - r.due) * 1e3 for r in mine], 0.95),
            "behind_max_ms": max((r.sent - r.due) * 1e3 for r in mine),
            "in_tail": sum((r.done - r.due) * 1e3 >= cut for r in mine)})
    return out


def _run_threads(threads) -> None:
    for t in threads:
        t.start()
    for t in threads:
        t.join()
