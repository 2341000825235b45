"""Everything the harness knows about a cell it finds by name: the cell in
`BENCHMARK.json`, its configuration (`configs[].file`), its traffic mix
(`traffic/<traffic>.json`), its query templates (`queries/<name>.py`), its
metrics (`metrics/<name>.json`) and their readers (`readers/<reader>.py`).
A later PR adds a cell, a mix, a template or a metric as new files and new
`BENCHMARK.json` entries; no file here names one.

Configuration file keys the harness reads (everything else is for the reader):
  table, rows    the main table and its rows (`--rows` of a rehearsal replaces them)
  session_conf   the confs every tenant's session is made with
  storage, columns   without `tables`: PR 23's one LINEITEM, "parquet" (all 14
                 columns, PR 23's writer) or "resident" (the `columns` cached)
  tables         {name: {"rows": n | {"of": table, "ratio": [a, b]},
                         "columns": {column: generator spec (datagen.py: key,
                                     suppkey, int, double, char1, date, choice,
                                     seq, fk, after, text)},
                         "storage": "parquet" | "resident"}}
                 A Parquet table is written as PR 23's file is (snappy, REQUIRED,
                 dictionary on, 2^20-row groups); a resident one goes through
                 createDataFrame(...).device_cache(). With `tables`, the traffic
                 may have one tenant only.
Traffic file keys: loadgen.py; a `templates` entry may carry `params`, the
keyword arguments its template's `build` and `reference` both receive.
A template (`queries/<name>.py`): COLUMNS {table: columns it reads},
build(F, {table: DataFrame}, **params), reference({table: {column: numpy}},
**params)."""

from __future__ import annotations

import importlib
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class ManifestError(ValueError):
    pass


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


class Cell:
    """One entry of `workloads`, with the files it names loaded."""

    def __init__(self, name: str, root: str = ROOT):
        self.root = root
        self.bench = benchmark(root)
        hits = [w for w in self.bench["workloads"] if w["name"] == name]
        if not hits:
            raise ManifestError(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{[w['name'] for w in self.bench['workloads']]}")
        self.entry = hits[0]
        self.name = name
        self.chips = int(self.entry["chips"])
        conf = [c for c in self.bench["configs"] if c["name"] == self.entry["config"]]
        if not conf:
            raise ManifestError(f"{name}: no config {self.entry['config']!r}")
        self.config = _json(os.path.join(root, conf[0]["file"]))
        self.bench_dir = os.path.join(root, self.bench["paths"][0])
        self.traffic = _json(os.path.join(self.bench_dir, "traffic",
                                          self.entry["traffic"] + ".json"))

    def metrics(self, group: str) -> list:
        """The `end_to_end` or `per_layer` entries this cell reports."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def reader(self, metric: str):
        """(read function, its arguments) of a metric, from metrics/<name>.json."""
        spec = _json(os.path.join(self.bench_dir, "metrics", metric + ".json"))
        mod = importlib.import_module(f"chipbench.readers.{spec['reader']}")
        return mod.read, spec.get("args", {})

    def query(self, name: str):
        return importlib.import_module(f"chipbench.queries.{name}")

    def columns_read(self) -> dict:
        """{table: sorted columns} over every template of the traffic mix."""
        out = {}
        for tpl in self.traffic["templates"]:
            for table, columns in self.query(tpl["query"]).COLUMNS.items():
                out[table] = sorted(set(out.get(table, ())) | set(columns))
        return out


def _deployment_faults(cell: Cell) -> list:
    """Faults of a cell's configuration against its traffic: tables that
    cannot be generated, columns a template reads and no table has, tenants
    over a multi-table configuration (no rule slices a dimension table)."""
    from . import datagen
    bad = []
    schema = datagen.tables(cell.config)
    if len(schema) > 1 and int(cell.traffic.get("tenants", 1)) > 1:
        bad.append(f"workload {cell.name}: {len(schema)} tables and more than one tenant")
    for tpl in cell.traffic["templates"]:
        for table, columns in cell.query(tpl["query"]).COLUMNS.items():
            held = schema[table].held() if table in schema else ()
            missing = [c for c in columns if c not in held]
            if missing:
                bad.append(f"workload {cell.name}: template {tpl['query']} reads "
                           f"{table}.{missing}, which the configuration does not hold")
    return bad


def validate(root: str = ROOT) -> list:
    """Faults of the manifest against the names, units and files the contract
    allows; an empty list when it is sound."""
    b = benchmark(root)
    bad = []

    def name_ok(what, n):
        if not NAME.match(str(n)):
            bad.append(f"{what}: name {n!r} has characters outside [A-Za-z0-9_.-]")

    bench_dir = os.path.join(root, b["paths"][0])
    for c in b["configs"]:
        name_ok("config", c["name"])
        for k in c["reduced"]:
            name_ok(f"config {c['name']} reduced", k)
        if not os.path.isfile(os.path.join(root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
    seen = set()
    for w in b["workloads"]:
        name_ok("workload", w["name"])
        name_ok("traffic", w["traffic"])
        if (w["config"], w["traffic"]) in seen:
            bad.append(f"workload {w['name']}: config and traffic appear twice")
        seen.add((w["config"], w["traffic"]))
        if len(w["why"]) > 200:
            bad.append(f"workload {w['name']}: why is over 200 characters")
        if not os.path.isfile(os.path.join(bench_dir, "traffic", w["traffic"] + ".json")):
            bad.append(f"workload {w['name']}: no traffic file {w['traffic']}.json")
    for w in b["workloads"]:
        try:
            bad += _deployment_faults(Cell(w["name"], root))
        except (ManifestError, OSError, ValueError, KeyError) as e:
            bad.append(f"workload {w['name']}: {type(e).__name__}: {e}")
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    for group in ("end_to_end", "per_layer"):
        for m in b[group]:
            name_ok(group, m["name"])
            if not UNIT.match(m["unit"]):
                bad.append(f"{m['name']}: unit {m['unit']!r} not allowed")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better is {m['better']!r}")
            if m["source"] not in SOURCES:
                bad.append(f"{m['name']}: source {m['source']!r}")
            for w in m.get("workloads", ()):
                if w not in cells:
                    bad.append(f"{m['name']}: lists unknown cell {w!r}")
            if group == "per_layer" and m["moves"] not in e2e:
                bad.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
            path = os.path.join(bench_dir, "metrics", m["name"] + ".json")
            if not os.path.isfile(path):
                bad.append(f"{m['name']}: no metrics/{m['name']}.json")
                continue
            reader = _json(path)["reader"]
            if not os.path.isfile(os.path.join(bench_dir, "readers", reader + ".py")):
                bad.append(f"{m['name']}: no readers/{reader}.py")
    return bad
