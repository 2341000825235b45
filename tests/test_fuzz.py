"""Fuzz tier.

Reference: integration_tests regexp fuzzers (regexp_test.py,
RegularExpressionFuzzSuite) and json_fuzz_test.py. All generators are
seeded — failures reproduce exactly. Three properties:

  * regex: for random patterns the transpiler either REJECTS (tagging keeps
    the op on the host oracle — no silent divergence) or ACCEPTS, in which
    case device and oracle paths must agree on random subject strings;
  * JSON: get_json_object over random nested documents matches the oracle
    for random JSONPaths; from_json(to_json(x)) round-trips;
  * LIKE: the device segment matcher agrees with the oracle for random
    %._-escaped patterns (the fuzz companion to the directed tests).
"""

import json
import random
import string

import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.expressions.base import AttributeReference, Literal
from spark_rapids_tpu.expressions.regex import (Like, RLike, RegexpReplace,
                                                transpile)
from spark_rapids_tpu.expressions.json import GetJsonObject

SEED = 20260730


# ---------------------------------------------------------------------------
# generators
# ---------------------------------------------------------------------------

_REGEX_ATOMS = ["a", "b", "c", "1", "2", " ", ".", r"\d", r"\w", r"\s",
                "[ab]", "[^c]", "[a-z]", "(a)", "(a|b)", "(?:ab)"]
_REGEX_SUFFIX = ["", "*", "+", "?", "{1,3}", "{2}"]
_REGEX_EXOTIC = [r"\p{Alpha}", "a*+", "b?+", "(?<=a)", r"\G", r"\Z"]


def _rand_pattern(rng: random.Random) -> str:
    n = rng.randint(1, 6)
    parts = []
    if rng.random() < 0.2:
        parts.append("^")
    for _ in range(n):
        if rng.random() < 0.08:
            parts.append(rng.choice(_REGEX_EXOTIC))
        else:
            parts.append(rng.choice(_REGEX_ATOMS)
                         + rng.choice(_REGEX_SUFFIX))
    if rng.random() < 0.2:
        parts.append("$")
    return "".join(parts)


def _rand_subjects(rng: random.Random, n: int):
    alpha = "abc12 xyz"
    out = []
    for _ in range(n):
        if rng.random() < 0.08:
            out.append(None)
        else:
            out.append("".join(rng.choice(alpha)
                               for _ in range(rng.randint(0, 12))))
    return out


def _rand_json(rng: random.Random, depth: int = 0):
    r = rng.random()
    if depth >= 3 or r < 0.3:
        return rng.choice([rng.randint(-100, 100), rng.random() * 10,
                           "".join(rng.choice(string.ascii_lowercase)
                                   for _ in range(rng.randint(0, 6))),
                           True, False, None])
    if r < 0.65:
        return {rng.choice("abcde"): _rand_json(rng, depth + 1)
                for _ in range(rng.randint(1, 3))}
    return [_rand_json(rng, depth + 1) for _ in range(rng.randint(0, 3))]


def _rand_path(rng: random.Random, doc) -> str:
    path = "$"
    cur = doc
    for _ in range(rng.randint(1, 3)):
        if isinstance(cur, dict) and cur:
            k = rng.choice(sorted(cur))
            path += f".{k}"
            cur = cur[k]
        elif isinstance(cur, list) and cur:
            i = rng.randrange(len(cur))
            path += f"[{i}]"
            cur = cur[i]
        else:
            # step off the document on purpose sometimes
            path += "." + rng.choice("xyz")
            break
    return path


def _str_batch(vals):
    arr = pa.array(vals, pa.string())
    col = TpuColumnVector.from_arrow(arr)
    batch = TpuColumnarBatch([col], len(vals), names=["s"])
    ref = AttributeReference("s", col.dtype, ordinal=0)
    return batch, pa.table({"s": arr}), ref


# ---------------------------------------------------------------------------
# regex fuzz
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("round_seed", range(8))
def test_regex_fuzz_rlike(round_seed):
    rng = random.Random(SEED + round_seed)
    rejected = accepted = 0
    for _ in range(40):
        pat = _rand_pattern(rng)
        t = transpile(pat)
        subjects = _rand_subjects(rng, 24)
        batch, tbl, ref = _str_batch(subjects)
        expr = RLike(ref, pat)
        if t is None:
            rejected += 1
            # rejection correctness: tagging must refuse the device path
            assert not expr.tpu_supported, pat
            continue
        accepted += 1
        got = expr.eval_tpu(batch).to_arrow().to_pylist()[: len(subjects)]
        want = expr.eval_cpu(tbl).to_pylist()
        assert got == want, (pat, subjects, got, want)
    # the generator must exercise both branches to mean anything
    assert accepted > 0
    # exotic constructs appear with p≈0.4/round; across rounds both branches
    # stay covered (seeded, so this is deterministic)


@pytest.mark.parametrize("round_seed", range(4))
def test_regex_fuzz_replace(round_seed):
    rng = random.Random(SEED * 3 + round_seed)
    for _ in range(20):
        pat = _rand_pattern(rng)
        if transpile(pat) is None:
            continue
        repl = "".join(rng.choice("xy_") for _ in range(rng.randint(0, 3)))
        subjects = _rand_subjects(rng, 16)
        batch, tbl, ref = _str_batch(subjects)
        try:
            expr = RegexpReplace(ref, pat, repl)
        except Exception:
            continue  # constructor-level rejection is a valid outcome
        if not expr.tpu_supported:
            continue
        got = expr.eval_tpu(batch).to_arrow().to_pylist()[: len(subjects)]
        want = expr.eval_cpu(tbl).to_pylist()
        assert got == want, (pat, repl, subjects)


@pytest.mark.parametrize("round_seed", range(4))
def test_like_fuzz(round_seed):
    rng = random.Random(SEED * 7 + round_seed)
    alpha = "ab%_c\\"
    for _ in range(60):
        pat = "".join(rng.choice(alpha) for _ in range(rng.randint(0, 8)))
        if pat.endswith("\\") and not pat.endswith("\\\\"):
            pat += "a"  # dangling escape is illegal in both engines
        subjects = _rand_subjects(rng, 16)
        batch, tbl, ref = _str_batch(subjects)
        expr = Like(ref, pat)
        try:
            want = expr.eval_cpu(tbl).to_pylist()
        except Exception:
            continue  # oracle rejects the pattern — nothing to compare
        got = expr.eval_tpu(batch).to_arrow().to_pylist()[: len(subjects)]
        assert got == want, (pat, subjects, got, want)


# ---------------------------------------------------------------------------
# JSON fuzz
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("round_seed", range(6))
def test_json_fuzz_get_json_object(round_seed):
    rng = random.Random(SEED * 11 + round_seed)
    docs, paths = [], []
    for _ in range(30):
        doc = _rand_json(rng)
        docs.append(json.dumps(doc))
        paths.append(_rand_path(rng, doc))
    # some malformed documents too
    docs += ['{"a":', "", "not json", '{"a" 1}', None]
    paths += ["$.a"] * 5
    batch, tbl, ref = _str_batch(docs)
    for path in sorted(set(paths)):
        expr = GetJsonObject(ref, Literal(path))
        got = expr.eval_tpu(batch).to_arrow().to_pylist()[: len(docs)]
        want = expr.eval_cpu(tbl).to_pylist()
        assert got == want, (path, docs, got, want)


@pytest.mark.parametrize("round_seed", range(3))
def test_json_fuzz_roundtrip(round_seed):
    """to_json/from_json stability over random flat structs via the session."""
    from spark_rapids_tpu.session import TpuSession
    import spark_rapids_tpu.functions as F
    rng = random.Random(SEED * 13 + round_seed)
    rows = []
    for i in range(40):
        rows.append({"j": json.dumps(
            {"a": rng.randint(-5, 5),
             "b": "".join(rng.choice("xyz") for _ in range(rng.randint(0, 4))),
             "c": rng.random() < 0.5})})
    tpu = TpuSession({"spark.rapids.sql.enabled": "true"})
    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})

    def q(sess):
        df = sess.createDataFrame(rows)
        parsed = F.from_json(F.col("j"), "a bigint, b string, c boolean")
        return df.select(F.to_json(parsed).alias("out"))

    assert q(tpu).collect() == q(cpu).collect()
