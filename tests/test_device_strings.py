"""Device-resident string kernels vs the CPU oracle.

The hot string ops must run on device (no
device→arrow→device hop) for ASCII columns, and byte-safe ops for any UTF-8.
The `_poison_host_hop` fixture makes any host materialization of the input
column raise, proving the op never left HBM.
Reference surface: stringFunctions.scala (GpuSubstring, GpuConcat, GpuTrim,
GpuStringRepeat, GpuStringReplace, GpuStringLocate, GpuStringLPad/RPad,
GpuTranslate, GpuSubstringIndex, GpuContains, GpuLike, GpuInitCap,
GpuStringReverse).
"""

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.expressions.base import AttributeReference, Literal
from spark_rapids_tpu.expressions import strings as S
from spark_rapids_tpu.expressions.regex import Like

ASCII_VALS = ["hello world", "", None, "  spaced  ", "aAbBcC", "aaaa",
              "x,y,z,w", "pad", "  ", "ab,cd", "hello", "wxyz", "\tmix ed\n",
              "%odd_chars$", "trailing   ", "   leading", None, "a"]

UNI_VALS = ["héllo wörld", "日本語テスト", None, "  ünïcode  ", "Ça va",
            "αβγαβγ", "", "a👍b,c👍d"]


def _batch_and_table(vals):
    arr = pa.array(vals, pa.string())
    col = TpuColumnVector.from_arrow(arr)
    return (TpuColumnarBatch([col], len(vals), names=["s"]), pa.table({"s": arr}),
            AttributeReference("s", col.dtype, ordinal=0))


def _check(expr, vals, poison=False, monkeypatch=None):
    batch, tbl, _ = _batch_and_table(vals)
    if poison:
        def _no_hop(x, b):
            raise AssertionError("host hop on the device path")
        monkeypatch.setattr(S, "_to_arrow_side", _no_hop)
    dev = expr.eval_tpu(batch)
    if poison:
        monkeypatch.undo()
    host = expr.eval_cpu(tbl)
    got = dev.to_arrow().to_pylist()[: len(vals)]
    want = host.to_pylist()
    assert got == want, f"{expr.pretty()}: {got} != {want}"


def _ref():
    return AttributeReference("s", TpuColumnVector.from_arrow(
        pa.array(["x"], pa.string())).dtype, ordinal=0)


ASCII_CASES = [
    ("trim", lambda r: S.Trim(r)),
    ("ltrim", lambda r: S.LTrim(r)),
    ("rtrim", lambda r: S.RTrim(r)),
    ("reverse", lambda r: S.Reverse(r)),
    ("initcap", lambda r: S.InitCap(r)),
    ("upper", lambda r: S.Upper(r)),
    ("lower", lambda r: S.Lower(r)),
    ("substring_2_3", lambda r: S.Substring(r, Literal(2), Literal(3))),
    ("substring_neg", lambda r: S.Substring(r, Literal(-3), Literal(2))),
    ("substring_0", lambda r: S.Substring(r, Literal(0), Literal(4))),
    ("substring_past_end", lambda r: S.Substring(r, Literal(50), Literal(4))),
    ("concat", lambda r: S.ConcatStr(r, Literal("!"), r)),
    ("contains", lambda r: S.Contains(r, Literal("a"))),
    ("contains_multi", lambda r: S.Contains(r, Literal("llo"))),
    ("contains_empty", lambda r: S.Contains(r, Literal(""))),
    ("repeat", lambda r: S.StringRepeat(r, Literal(3))),
    ("repeat_0", lambda r: S.StringRepeat(r, Literal(0))),
    ("replace", lambda r: S.StringReplace(r, Literal("a"), Literal("XY"))),
    ("replace_overlap", lambda r: S.StringReplace(r, Literal("aa"), Literal("b"))),
    ("replace_delete", lambda r: S.StringReplace(r, Literal("l"), Literal(""))),
    ("locate", lambda r: S.StringLocate(Literal("l"), r)),
    ("locate_from_3", lambda r: S.StringLocate(Literal("a"), r, Literal(3))),
    ("locate_empty", lambda r: S.StringLocate(Literal(""), r, Literal(2))),
    ("locate_from_0", lambda r: S.StringLocate(Literal("a"), r, Literal(0))),
    ("lpad", lambda r: S.LPad(r, Literal(6), Literal("*#"))),
    ("rpad", lambda r: S.RPad(r, Literal(6), Literal("*#"))),
    ("lpad_truncate", lambda r: S.LPad(r, Literal(3), Literal("*"))),
    ("lpad_empty_pad", lambda r: S.LPad(r, Literal(6), Literal(""))),
    ("translate", lambda r: S.StringTranslate(r, Literal("abc"), Literal("AB"))),
    ("substr_index_2", lambda r: S.SubstringIndex(r, Literal(","), Literal(2))),
    ("substr_index_neg", lambda r: S.SubstringIndex(r, Literal(","), Literal(-2))),
    ("substr_index_0", lambda r: S.SubstringIndex(r, Literal("a"), Literal(0))),
    ("concat_ws", lambda r: S.ConcatWs(Literal("-"), r, r)),
]


@pytest.mark.parametrize("name,make", ASCII_CASES, ids=[c[0] for c in ASCII_CASES])
def test_ascii_device(name, make, monkeypatch):
    """ASCII corpus: device path, no host hop allowed."""
    _, _, ref = _batch_and_table(ASCII_VALS)
    _check(make(ref), ASCII_VALS, poison=True, monkeypatch=monkeypatch)


@pytest.mark.parametrize("name,make", ASCII_CASES, ids=[c[0] for c in ASCII_CASES])
def test_unicode_parity(name, make):
    """Unicode corpus: device where byte-safe, host fallback otherwise —
    results must match the oracle either way."""
    _, _, ref = _batch_and_table(UNI_VALS)
    _check(make(ref), UNI_VALS)


LIKE_PATTERNS = ["hello%", "%world", "%l_o%", "a_b%", "%", "", "wxyz",
                 "h%o%d", "%a%a%", "_", "__", "%,%,%", r"\%odd%", "%$"]


@pytest.mark.parametrize("pat", LIKE_PATTERNS)
def test_like_device(pat, monkeypatch):
    _, _, ref = _batch_and_table(ASCII_VALS)
    _check(Like(ref, pat), ASCII_VALS)


def test_like_unicode_falls_back():
    _, _, ref = _batch_and_table(UNI_VALS)
    _check(Like(ref, "héllo%"), UNI_VALS)
    _check(Like(ref, "%テスト"), UNI_VALS)


def test_all_null_and_empty_columns(monkeypatch):
    vals = [None, None, None]
    _, _, ref = _batch_and_table(vals)
    for make in (lambda r: S.Trim(r), lambda r: S.ConcatStr(r, r),
                 lambda r: S.StringReplace(r, Literal("a"), Literal("b"))):
        _check(make(ref), vals)


def test_replace_self_overlapping_pattern(monkeypatch):
    """'aaaa' replace 'aa'→'b' must be greedy left-to-right ('bb', not 'bbb')."""
    vals = ["aaaa", "aaa", "aaaaa", "baab"]
    _, _, ref = _batch_and_table(vals)
    _check(S.StringReplace(ref, Literal("aa"), Literal("b")), vals,
           poison=True, monkeypatch=monkeypatch)
    batch, _, _ = _batch_and_table(vals)
    out = S.StringReplace(ref, Literal("aa"), Literal("b")).eval_tpu(batch)
    assert out.to_arrow().to_pylist()[:4] == ["bb", "ba", "bba", "bbb"]


def test_substring_index_split_semantics(monkeypatch):
    """Counting must use non-overlapping occurrences (split semantics)."""
    vals = ["aaaa", "aaaaaa"]
    _, _, ref = _batch_and_table(vals)
    _check(S.SubstringIndex(ref, Literal("aa"), Literal(2)), vals,
           poison=True, monkeypatch=monkeypatch)


def test_initcap_at_exact_byte_capacity(monkeypatch):
    """Total bytes == bucketed char capacity: trailing padding offsets equal
    nbytes and must not wrap onto the last real byte (falsely marking it a
    word start)."""
    vals = ["abcdefgh", "ijklmnop"]  # 16 bytes == bucket_capacity(16)
    _, _, ref = _batch_and_table(vals)
    _check(S.InitCap(ref), vals, poison=True, monkeypatch=monkeypatch)


def test_concat_ws_fallback_single_eval(monkeypatch):
    """Non-device arg: the fallback must not re-evaluate child expressions."""
    import pyarrow as pa
    batch, tbl, ref = _batch_and_table(ASCII_VALS)
    calls = {"n": 0}
    orig = S.ConcatWs.eval_tpu

    class Counting(AttributeReference):
        def eval_tpu(self, b, ctx=None):
            calls["n"] += 1
            return super().eval_tpu(b) if ctx is None else super().eval_tpu(b, ctx)

    cref = Counting("s", ref.dtype, ordinal=0)
    expr = S.ConcatWs(Literal("-"), cref, cref)
    expr.eval_tpu(batch)
    assert calls["n"] == 2  # once per argument, not twice per argument


def test_host_assisted_string_count_shrunk():
    """Exit criterion: host-assisted registry entries ≤ 45
    after the device string sweep (was 62)."""
    import spark_rapids_tpu.plan.overrides  # trigger registration
    from spark_rapids_tpu.plan.typechecks import all_expr_rules
    ha = [c.__name__ for c, r in all_expr_rules().items() if r.host_assisted]
    assert len(ha) <= 45, ha
    for name in ("Substring", "ConcatStr", "Trim", "LPad", "RPad", "Contains",
                 "StringReplace", "StringLocate", "SubstringIndex", "Like",
                 "StringTranslate", "InitCap", "Reverse", "StringRepeat",
                 "ConcatWs"):
        assert name not in ha, f"{name} should be device now"
