"""Static validation tier (reference SURVEY §4 tier 4): api_validation tool
+ generated-docs drift checks."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")


def _remove_tools_path():
    # the tools themselves insert ROOT at index 0, so pop(0) would remove
    # the wrong entry — remove our insertion by value
    while TOOLS in sys.path:
        sys.path.remove(TOOLS)


def test_api_validation_passes():
    sys.path.insert(0, TOOLS)
    try:
        import api_validation
        violations = api_validation.validate()
    finally:
        _remove_tools_path()
    assert violations == []


def test_key_spelled_only_in_prose_is_reported_dead(monkeypatch):
    """A declared key that only a docstring spells — this one spells
    `spark.rapids.tpu.test.plantedProseOnlyKey` — is read by no code and
    is reported dead; the key beside it, set like a session conf, is not."""
    from spark_rapids_tpu.config import REGISTRY, ConfEntry
    settings = {"spark.rapids.tpu.test.plantedLiveKey": "true"}
    entries = dict(REGISTRY.entries)
    # joined here so that no literal of this file is the prose-only key
    for key in ("spark.rapids.tpu.test." + "plantedProseOnlyKey", *settings):
        entries[key] = ConfEntry(key, "planted", None, str, internal=True)
    monkeypatch.setattr(REGISTRY, "entries", entries)
    sys.path.insert(0, TOOLS)
    try:
        import api_validation
        dead = [v for v in api_validation.conf_consistency()
                if "dead conf" in v]
    finally:
        _remove_tools_path()
    assert len(dead) == 1 and "plantedProseOnlyKey" in dead[0], dead


def test_docs_not_drifted():
    """docs/configs.md and docs/supported_ops.md must match the registries
    (reference: generated-docs drift is a premerge failure)."""
    sys.path.insert(0, TOOLS)
    try:
        import gen_docs
        want_cfg = gen_docs.gen_configs_md()
        want_ops = gen_docs.gen_supported_ops_md()
    finally:
        _remove_tools_path()
    with open(os.path.join(ROOT, "docs", "configs.md")) as f:
        assert f.read() == want_cfg, \
            "docs/configs.md drifted — run python tools/gen_docs.py"
    with open(os.path.join(ROOT, "docs", "supported_ops.md")) as f:
        assert f.read() == want_ops, \
            "docs/supported_ops.md drifted — run python tools/gen_docs.py"


def test_exec_toggles_disable_ops():
    """Spot-check that toggle configs force CPU fallbacks (key existence for
    EVERY rule is covered by api_validation's registry check)."""
    import pyarrow as pa
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.session import TpuSession

    t = pa.table({"a": pa.array([3, 1, 2]), "b": pa.array([1.0, 2.0, 3.0])})

    s = TpuSession({"spark.rapids.sql.exec.ProjectExec": "false"})
    df = s.createDataFrame(t).select((F.col("a") + 1).alias("x"))
    assert "TpuProject" not in df.explain()
    assert sorted(r["x"] for r in df.collect()) == [2, 3, 4]

    s = TpuSession({"spark.rapids.sql.exec.SortExec": "false"})
    df = s.createDataFrame(t).orderBy(F.col("a"))
    assert "TpuSort" not in df.explain()
    assert [r["a"] for r in df.collect()] == [1, 2, 3]

    s = TpuSession({"spark.rapids.sql.exec.SampleExec": "false"})
    df = s.createDataFrame(t).sample(fraction=0.9, seed=1)
    assert "TpuSample" not in df.explain()

    s = TpuSession({"spark.rapids.sql.exec.TakeOrderedAndProjectExec":
                    "false"})
    df = s.createDataFrame(t).orderBy(F.col("a")).limit(2)
    assert "TpuTopN" not in df.explain()
    assert [r["a"] for r in df.collect()] == [1, 2]
