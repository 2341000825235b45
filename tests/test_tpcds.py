"""TPC-DS suite: every benchmark query must produce CPU-oracle-equal results
through the TPU plan (reference tier-2 net: integration_tests tpcds suite vs
CPU, asserts.py:479; BASELINE.md 99-query north star — 38 queries here)."""

import numpy as np
import pytest

import benchmarks.tpcds as tpcds

ROWS = 12_000
#: the 99 queries run as two modules, every other one here and the rest in
#: test_tpcds_b.py: `--dist loadfile` gives a module one worker, and as one
#: module this suite alone was the whole of tier-1's wall time
NAMES = sorted(tpcds.QUERIES)

_done = [0]


@pytest.fixture(autouse=True)
def _bound_xla_within_module():
    """99 queries x 2 sessions compile thousands of executables in ONE
    module; the conftest's per-module cache drop never fires inside it and
    the unbounded live-executable set has segfaulted the allocator deep
    into the run. Drop caches every 12 queries."""
    yield
    _done[0] += 1
    if _done[0] % 12 == 0:
        import gc
        import jax
        jax.clear_caches()
        gc.collect()


@pytest.fixture(scope="module")
def suites():
    tpu_s = tpcds.make_session(tpu=True)
    cpu_s = tpcds.make_session(tpu=False)
    return (tpu_s, tpcds.load_tables(tpu_s, ROWS),
            cpu_s, tpcds.load_tables(cpu_s, ROWS))


def _canon(table):
    """Sort-insensitive canonical form with float rounding."""
    cols = sorted(table.column_names)
    rows = []
    for i in range(table.num_rows):
        row = []
        for c in cols:
            v = table.column(c)[i].as_py()
            if isinstance(v, float):
                v = round(v, 4)
            row.append(v)
        rows.append(tuple(row))
    none_low = [tuple((x is None, x if x is not None else 0) for x in r)
                for r in rows]
    return [rows[i] for i in np.argsort(
        np.array([str(r) for r in none_low]))]


def check_query(name, suites):
    tpu_s, tpu_t, cpu_s, cpu_t = suites
    fn = tpcds.QUERIES[name]
    tpu_out = fn(tpu_s, tpu_t).to_arrow()
    cpu_out = fn(cpu_s, cpu_t).to_arrow()
    assert cpu_out.num_rows > 0, f"{name}: oracle returned no rows"
    assert tpu_out.num_rows == cpu_out.num_rows, (
        f"{name}: {tpu_out.num_rows} vs oracle {cpu_out.num_rows} rows")
    assert sorted(tpu_out.column_names) == sorted(cpu_out.column_names)
    got, want = _canon(tpu_out), _canon(cpu_out)
    for g, w in zip(got, want):
        for gv, wv in zip(g, w):
            if isinstance(gv, float) and isinstance(wv, float):
                assert gv == pytest.approx(wv, rel=1e-4, abs=1e-4), (
                    f"{name}: {g} != {w}")
            else:
                assert gv == wv, f"{name}: {g} != {w}"


@pytest.mark.parametrize("name", NAMES[0::2])
def test_query_matches_cpu_oracle(name, suites):
    check_query(name, suites)
