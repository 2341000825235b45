"""Mergeable t-digest approx_percentile (reference
GpuApproximatePercentile.scala): error bounds vs the exact
percentile, partial/final merge, and engine parity across partitions."""

import numpy as np
import pyarrow as pa
import pytest

import spark_rapids_tpu.functions as F
from spark_rapids_tpu.kernels.tdigest import (build_digest_np,
                                              compression_for,
                                              merge_digests, quantile)
from spark_rapids_tpu.session import TpuSession


def test_digest_quantile_error_bound():
    rng = np.random.default_rng(0)
    for dist in (rng.random(50_000), rng.normal(0, 100, 50_000),
                 rng.exponential(5.0, 50_000)):
        v = np.sort(dist)
        means, w = build_digest_np(v, compression_for(10000))
        assert len(means) <= compression_for(10000)
        for p in (0.01, 0.25, 0.5, 0.75, 0.99):
            got = quantile(means, w, p)
            exact = np.quantile(v, p)
            spread = v[-1] - v[0]
            assert abs(got - exact) <= 0.005 * spread + 1e-9, (p, got, exact)


def test_digest_merge_matches_single_build():
    """Partial/final merge: digests built on slices and merged must answer
    within the error bound of a single whole-data digest."""
    rng = np.random.default_rng(1)
    v = rng.normal(0, 10, 40_000)
    comp = compression_for(10000)
    whole = build_digest_np(np.sort(v), comp)
    parts = [build_digest_np(np.sort(chunk), comp)
             for chunk in np.array_split(v, 7)]
    merged = merge_digests(parts, comp)
    assert len(merged[0]) <= comp
    assert merged[1].sum() == pytest.approx(len(v))
    for p in (0.05, 0.5, 0.95):
        a, b = quantile(*whole, p), quantile(*merged, p)
        spread = v.max() - v.min()
        assert abs(a - b) <= 0.01 * spread, (p, a, b)


def test_approx_percentile_distributed_matches_oracle():
    """approx_percentile through the full engine across >=2 partitions:
    TPU == CPU oracle exactly (same digest construction), and both within
    the accuracy bound of the exact percentile."""
    rng = np.random.default_rng(2)
    n = 20_000
    t = pa.table({"g": rng.integers(0, 5, n), "v": rng.normal(50, 20, n)})

    res = {}
    for en in ("true", "false"):
        s = TpuSession({"spark.rapids.sql.enabled": en,
                        "spark.sql.shuffle.partitions": "3"})
        df = s.createDataFrame(t, num_partitions=4)
        out = df.groupBy("g").agg(
            F.approx_percentile(F.col("v"), 0.5).alias("p50"))
        res[en] = {r["g"]: r["p50"] for r in out.collect()}
    assert set(res["true"]) == set(res["false"])
    import pandas as pd
    pdf = t.to_pandas()
    for g, v_tpu in res["true"].items():
        v_cpu = res["false"][g]
        assert v_tpu == pytest.approx(v_cpu, rel=1e-9), (g, v_tpu, v_cpu)
        exact = pdf[pdf.g == g].v.quantile(0.5)
        spread = pdf[pdf.g == g].v.max() - pdf[pdf.g == g].v.min()
        assert abs(v_tpu - exact) <= 0.01 * spread, (g, v_tpu, exact)


def test_approx_percentile_int_and_array_forms():
    t = pa.table({"g": [1] * 100 + [2] * 100,
                  "v": list(range(100)) + list(range(0, 1000, 10))})
    res = {}
    for en in ("true", "false"):
        s = TpuSession({"spark.rapids.sql.enabled": en})
        df = s.createDataFrame(t, num_partitions=2)
        out = df.groupBy("g").agg(
            F.approx_percentile(F.col("v"), [0.0, 0.5, 1.0]).alias("ps"))
        res[en] = {r["g"]: r["ps"] for r in out.collect()}
    assert res["true"] == res["false"]
    for g, ps in res["true"].items():
        assert all(isinstance(x, int) for x in ps), ps  # input-typed
        assert ps[0] <= ps[1] <= ps[2]
