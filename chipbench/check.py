"""The comparison that decides `correct`: every answer the window produced
against the plain reference (`queries/<name>.py::reference`) over the same
generated columns. Keys, counts and integer sums must be equal; a DOUBLE may
differ by the limit in `limits.json`, which PERF.md derives from the readings of
sound runs and of the lower-precision control. Nothing here imports the program."""

from __future__ import annotations

import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def limits() -> dict:
    with open(os.path.join(HERE, "limits.json")) as f:
        return json.load(f)


def compare_rows(got, want) -> dict:
    """Worst relative gap over the DOUBLE values, and how many values that
    must be exact (keys, counts, integers, row count, nulls) are not."""
    if not isinstance(got, list) or len(got) != len(want):
        return {"max_rel_err": math.inf, "inexact": 1 + abs(
            (len(got) if isinstance(got, list) else 0) - len(want))}
    worst, inexact = 0.0, 0
    for g, w in zip(got, want):
        if list(g) != list(w):
            inexact += 1
            continue
        for k, wv in w.items():
            gv = g[k]
            if isinstance(wv, float) and isinstance(gv, float):
                if math.isnan(gv) or math.isinf(gv):
                    worst = math.inf
                elif gv != wv:
                    worst = max(worst, abs(gv - wv) / max(abs(wv), abs(gv)))
            elif gv != wv or type(gv) is not type(wv):
                inexact += 1
    return {"max_rel_err": worst, "inexact": inexact}


def _doubles(tables: dict, fn) -> dict:
    return {t: {k: (fn(v) if v.dtype == np.float64 else v) for k, v in cols.items()}
            for t, cols in tables.items()}


def lower_precision(tables: dict) -> dict:
    """The control's input: every DOUBLE column of every table handed over as
    FLOAT (rounded to float32), the step below what the configurations state."""
    return _doubles(tables, lambda v: v.astype(np.float32).astype(np.float64))


def all_float32(tables: dict) -> dict:
    """A harsher control: the columns stay float32, so the reference's own
    products and sums are float32 too."""
    return _doubles(tables, lambda v: v.astype(np.float32))


def reference_columns(cell, seed: int, rows: int = 0) -> dict:
    """tenant -> table -> the numpy columns the cell's templates read, made
    from the seed alone: what a run of the cell keeps for its reference."""
    from . import datagen
    n_t = int(cell.traffic.get("tenants", 1))
    out = {t: {} for t in range(n_t)}
    for name, table in datagen.tables(cell.config, rows).items():
        needed = cell.columns_read().get(name, ())
        for t, (lo, hi) in enumerate(datagen.tenant_slices(table.rows, n_t)):
            out[t][name] = table.kept(table.generate(seed, needed, lo, hi), needed)
    return out


class Checker:
    """Caches one reference per (tenant, template), a parameter set being a
    template entry of its own; compares every record."""

    def __init__(self, cell, tenant_columns):
        self.cell = cell
        self.tenant_columns = tenant_columns       # tenant -> table -> numpy columns
        self._ref = {}

    def reference(self, tenant: int, template: int):
        key = (tenant, template)
        if key not in self._ref:
            tpl = self.cell.traffic["templates"][template]
            self._ref[key] = self.cell.query(tpl["query"]).reference(
                self.tenant_columns[tenant], **tpl.get("params", {}))
        return self._ref[key]

    def check(self, records, extra: dict) -> dict:
        """{name: {"value": v, "limit": l}} for every number compared; the run
        is correct when no value passes its limit."""
        lim = limits()
        worst, inexact, compared, unanswered = 0.0, 0, 0, 0
        for r in records:
            if r.failed:
                unanswered += 1
                continue
            c = compare_rows(r.result, self.reference(r.tenant, r.template))
            worst = max(worst, c["max_rel_err"])
            inexact += c["inexact"]
            compared += 1
        out = {
            "double_max_rel_err": {"value": worst, "limit": lim["double_max_rel_err"]},
            "inexact_values": {"value": inexact, "limit": 0},
            "unanswered": {"value": unanswered, "limit": 0},
            "answers_compared": {"value": compared, "at_least": 1},
        }
        for k, v in extra.items():
            out[k] = {"value": v, "limit": 0}
        return out


def verdict(checks: dict) -> bool:
    for c in checks.values():
        if "limit" in c and not c["value"] <= c["limit"]:
            return False
        if "at_least" in c and not c["value"] >= c["at_least"]:
            return False
    return True
