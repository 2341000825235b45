"""Device regex DFA (kernels/regex_dfa.py): compile-or-reject coverage,
device-vs-host engine equality, and proof the device path actually fires
(reference RegexParser.scala transpile-or-reject)."""

import re

import numpy as np
import pyarrow as pa
import pytest

from spark_rapids_tpu.columnar.batch import TpuColumnarBatch
from spark_rapids_tpu.columnar.vector import TpuColumnVector
from spark_rapids_tpu.expressions.base import AttributeReference
from spark_rapids_tpu.expressions.regex import RLike
from spark_rapids_tpu.kernels.regex_dfa import compile_dfa

SUBJECTS = ["", "a", "abc", "xabcy", "123", "a1b2c3", "hello world",
            "HELLO", "h\nt", "hat", "ab" * 40, "a@b.com", "x@y.org",
    "café", "éé", "naïve33", "  spaced  ", "a-b_c.d"]

DEVICE_PATTERNS = [
    "abc", "^abc", "abc$", "^abc$", "a*", "a+b", "ab?c", "[a-c]+x",
    "a|bc|def", r"\d{2,3}", "h.t", "[^0-9]+", "(ab)+c", r"\w+@\w+",
    r"^\w+@\w+\.(com|org)$", r"\s\s", r"[aeiou]{2}", "x{0,2}y",
    "(a|b)(c|d)e?", r"\.", "a{3,}",
]

REJECT_PATTERNS = ["a(?=b)", r"(a)\1", r"\p{L}", "a*+", "café",
                   r"\bword\b", "a$b", "(?<=x)y", "[[:alpha:]]",
                   # Java scopes anchors to one branch of a top-level
                   # alternation; this parser cannot model that -> host
                   # (r3 advisor high finding)
                   "a|b$", "^a|b", "^a|b$", "a|b|c$"]


def _batch(vals):
    arr = pa.array(vals, pa.string())
    col = TpuColumnVector.from_arrow(arr)
    batch = TpuColumnarBatch([col], len(vals), names=["s"])
    return batch, col, AttributeReference("s", col.dtype, ordinal=0)


@pytest.mark.parametrize("pat", DEVICE_PATTERNS)
def test_device_dfa_matches_python_re(pat):
    batch, col, ref = _batch(SUBJECTS)
    expr = RLike(ref, pat)
    out = expr._device_dfa_match(col, batch)
    dfa = compile_dfa(pat)
    assert dfa is not None, f"{pat} should compile"
    if not dfa.ascii_atoms:
        # non-ASCII data present -> the gate must punt to host
        assert out is None
        batch, col, ref = _batch([s for s in SUBJECTS if s.isascii()])
        out = RLike(ref, pat)._device_dfa_match(col, batch)
        subjects = [s for s in SUBJECTS if s.isascii()]
    else:
        subjects = SUBJECTS
    assert out is not None, f"device path must fire for {pat}"
    got = out.to_arrow().to_pylist()[:len(subjects)]
    want = [re.search(pat, s) is not None for s in subjects]
    assert got == want, (pat, list(zip(subjects, got, want)))


@pytest.mark.parametrize("pat", REJECT_PATTERNS)
def test_out_of_subset_rejects_to_host(pat):
    assert compile_dfa(pat) is None


def test_ascii_atom_pattern_runs_on_utf8_data():
    """All-ASCII atoms are byte/char exact on any UTF-8 input — the device
    path must fire even with non-ASCII rows present."""
    batch, col, ref = _batch(["café 42", "café", "x42"])
    out = RLike(ref, r"\d{2}")._device_dfa_match(col, batch)
    assert out is not None
    assert out.to_arrow().to_pylist()[:3] == [True, False, True]


def test_nulls_propagate():
    batch, col, ref = _batch(["abc", None, "xyz"])
    out = RLike(ref, "b")._device_dfa_match(col, batch)
    assert out is not None
    assert out.to_arrow().to_pylist()[:3] == [True, None, False]


def test_long_rows_fall_back():
    from spark_rapids_tpu.kernels.regex_dfa import MAX_DEVICE_ROW_BYTES
    batch, col, ref = _batch(["x" * (MAX_DEVICE_ROW_BYTES + 1), "ab"])
    assert RLike(ref, "ab")._device_dfa_match(col, batch) is None


def test_rlike_full_expression_uses_dfa_result():
    """End-to-end through eval_tpu (non-rewritable pattern so the literal
    fast path cannot shadow the DFA)."""
    batch, col, ref = _batch(SUBJECTS)
    pat = r"[a-z]+\d"
    got = RLike(ref, pat).eval_tpu(batch).to_arrow().to_pylist()
    want = [re.search(pat, s) is not None for s in SUBJECTS]
    assert got[:len(SUBJECTS)] == want


def test_dollar_matches_before_final_line_terminator():
    """Java (non-MULTILINE) '$' matches before a trailing \\n, \\r, or
    \\r\\n (r3 review finding)."""
    batch, col, ref = _batch(["abc", "abc\n", "abc\r\n", "abc\r",
                              "abc\nx", "ab"])
    out = RLike(ref, "c$")._device_dfa_match(col, batch)
    assert out is not None
    assert out.to_arrow().to_pylist()[:6] == [
        True, True, True, True, False, False]
    # python re agrees for \n (its $ handles only \n; the wider terminator
    # set is Java's — asserted explicitly above)
    assert re.search("c$", "abc\n") is not None


def test_octal_escape():
    batch, col, ref = _batch(["a\x07b", "a0b", "a\x00" + "7b"])
    out = RLike(ref, r"\07")._device_dfa_match(col, batch)
    assert out is not None
    # \07 is BEL, not NUL followed by literal 7 (r3 review finding)
    assert out.to_arrow().to_pylist()[:3] == [True, False, False]
    assert compile_dfa("\\0") is None  # bare \0 is illegal in java


def test_anchored_group_alternation_still_compiles():
    """'^(a|b)$' keeps its '|' inside a group — anchors scope over the whole
    pattern exactly as in Java, so the device path must keep serving it."""
    batch, col, ref = _batch(["a", "b", "ab", "xa", ""])
    out = RLike(ref, "^(a|b)$")._device_dfa_match(col, batch)
    assert out is not None
    assert out.to_arrow().to_pylist()[:5] == [True, True, False, False, False]


def test_top_level_alternation_with_anchor_is_host_correct():
    """End-to-end: 'a|b$' on 'ax' must be True (Java: (a)|(b$)) — served by
    the host fallback after the device reject."""
    batch, col, ref = _batch(["ax", "b", "cb", "c"])
    got = RLike(ref, "a|b$").eval_tpu(batch).to_arrow().to_pylist()
    assert got[:4] == [True, True, True, False]


def test_escaped_range_start_in_class():
    batch, col, ref = _batch(["C", "-", "F", "A", "E"])
    out = RLike(ref, r"[\x41-\x45]")._device_dfa_match(col, batch)
    assert out is not None
    # \x41-\x45 is the range A-E, not the literals {A, -, E}
    assert out.to_arrow().to_pylist()[:5] == [True, False, False, True, True]


# --- span matching: device regexp_replace / regexp_extract ------------------

REPLACE_PATTERNS = [
    (r"\d+", "#"), ("l+", "L"), (r"\s+", "_"), ("x", "yy"),
    (r"[0-9]{2,3}", "<n>"), (r"[aeiou]", ""), ("ab", "ba"),
    (r"\w\d", "*"), ("h.t", "HAT"), (r"[a-c]{2}", "Z"),
]

SPAN_SUBJECTS = ["", "a", "abc", "xabcy", "123", "a1b2c3", "hello world",
                 "hat hit hot", "ab" * 30, "  spaced  ", "999", "x1x22x333x",
                 "aaa bbb ccc", "tail123", None, "no match here!"]


@pytest.mark.parametrize("pat,repl", REPLACE_PATTERNS)
def test_device_regexp_replace_matches_python(pat, repl):
    import re as _re

    from spark_rapids_tpu.expressions.regex import RegexpReplace
    batch, col, ref = _batch(SPAN_SUBJECTS)
    e = RegexpReplace(ref, pat, repl)
    c = e.children[0].eval_tpu(batch)
    dev = e._device_replace(c, batch)
    assert dev is not None, f"device path must fire for {pat}"
    got = dev.to_arrow().to_pylist()[:len(SPAN_SUBJECTS)]
    want = [None if v is None else _re.sub(pat, repl, v)
            for v in SPAN_SUBJECTS]
    assert got == want, (pat, list(zip(SPAN_SUBJECTS, got, want)))


@pytest.mark.parametrize("pat", [r"\d+", "l+", r"[a-c]+", "h.t", r"\w{3}"])
def test_device_regexp_extract_matches_python(pat):
    import re as _re

    from spark_rapids_tpu.expressions.regex import RegexpExtract
    batch, col, ref = _batch(SPAN_SUBJECTS)
    e = RegexpExtract(ref, pat, 0)
    c = e.children[0].eval_tpu(batch)
    dev = e._device_extract(c, batch)
    assert dev is not None, f"device path must fire for {pat}"
    got = dev.to_arrow().to_pylist()[:len(SPAN_SUBJECTS)]

    def want_of(v):
        if v is None:
            return None
        m = _re.search(pat, v)
        return m.group(0) if m else ""
    want = [want_of(v) for v in SPAN_SUBJECTS]
    assert got == want, (pat, list(zip(SPAN_SUBJECTS, got, want)))


def test_span_subset_rejections():
    """Outside the span subset -> host engine (alternation, lazy, anchors,
    nullable patterns, group refs in the replacement)."""
    from spark_rapids_tpu.kernels.regex_dfa import compile_exact_dfa
    for pat in ["a|b", "a*?b", "^ab", "ab$", "a*", "x?", "(a|b)c"]:
        assert compile_exact_dfa(pat) is None, pat
    # group-ref replacement must not take the device path
    from spark_rapids_tpu.expressions.regex import RegexpReplace
    batch, col, ref = _batch(["abc"])
    e = RegexpReplace(ref, "b", "$0x")
    c = e.children[0].eval_tpu(batch)
    assert e._device_replace(c, batch) is None


def test_ambiguous_greedy_span_rejected():
    """ADVICE r4 high: greedy backtracking (Java) is not leftmost-longest
    when a variable segment is followed by an overlapping variable segment
    with a multi-byte atom — those patterns must fall back to host. The
    canonical case: re.sub('xa{0,2}(ab)?', 'R', 'xaab') == 'Rb' (Java
    matches 'xaa'), while a longest-match DFA would take 'xaab'."""
    from spark_rapids_tpu.kernels.regex_dfa import compile_exact_dfa
    for pat in ["a+(ab)?", "xa{0,2}(ab)?", "a*(ab)*", "(ab)?(aba)?",
                "(a*b)+", "[ab]+(ba)?"]:
        assert compile_exact_dfa(pat) is None, pat
    # single-byte-atom chains stay on device (greedy == longest for them)
    for pat in ["a{2,4}", "x[ab]{0,3}", "[0-9]{1,3}", "a+b*", "abc[0-9]*"]:
        assert compile_exact_dfa(pat) is not None, pat


def test_overlap_structure_fuzz_vs_python():
    """Fuzz with patterns that HAVE the overlap structure (ADVICE r4): any
    such pattern either rejects (host path) or, if admitted, must agree
    with python re on every row."""
    import re as _re

    import numpy.random as npr

    from spark_rapids_tpu.expressions.regex import RegexpReplace
    rng = npr.default_rng(11)
    alpha = "aabx"
    subjects = ["".join(rng.choice(list(alpha), size=rng.integers(0, 10)))
                for _ in range(150)]
    pats = ["a+(ab)?", "xa{0,2}(ab)?", "a*(ab)*b", "(ab)?(aba)?x",
            "a+(ba)?", "[ab]{1,2}(bx)?", "a{1,3}b?", "x?a+", "(ab)+x?",
            "a+(ab){1,2}"]
    for pat in pats:
        batch, col, ref = _batch(subjects)
        e = RegexpReplace(ref, pat, "R")
        c = e.children[0].eval_tpu(batch)
        dev = e._device_replace(c, batch)
        if dev is None:
            continue  # host fallback: correct by construction
        got = dev.to_arrow().to_pylist()[:len(subjects)]
        want = [_re.sub(pat, "R", v) for v in subjects]
        assert got == want, (pat, [x for x in zip(subjects, got, want)
                                   if x[1] != x[2]][:3])


def test_device_replace_fuzz_vs_python():
    """Random short strings over a small alphabet: device replace must agree
    with python re.sub (which matches Java for this subset) on every row."""
    import re as _re

    import numpy.random as npr
    rng = npr.default_rng(7)
    alpha = "ab1 x"
    subjects = ["".join(rng.choice(list(alpha), size=rng.integers(0, 12)))
                for _ in range(200)]
    from spark_rapids_tpu.expressions.regex import RegexpReplace
    for pat, repl in [(r"\d", "N"), ("a+", "A"), ("ab", "-"),
                      (r"[ax]{2}", "!"), (r"\s", ".")]:
        batch, col, ref = _batch(subjects)
        e = RegexpReplace(ref, pat, repl)
        c = e.children[0].eval_tpu(batch)
        dev = e._device_replace(c, batch)
        assert dev is not None
        got = dev.to_arrow().to_pylist()[:len(subjects)]
        want = [_re.sub(pat, repl, v) for v in subjects]
        assert got == want, (pat, [x for x in zip(subjects, got, want)
                                   if x[1] != x[2]][:3])
