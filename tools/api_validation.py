"""Static API validation of the exec/expression registries.

Reference: api_validation/ (ApiValidation.scala, 175 LoC) — compares each
GpuExec's constructor signature against the corresponding Spark exec per
version to catch shim drift. Here the analogue checks, per registered rule:

  * every exec rule names a config key that exists in the config registry;
  * every CPU exec class implements the physical-plan contract
    (execute_partition, output);
  * every registered expression either has a device kernel (eval_tpu
    overridden) or is explicitly flagged host-assisted / CPU-fallback — an
    unflagged expression without a kernel would be tagged onto the device
    and crash at runtime;
  * every expression with a type signature can answer a check() call.

Run as a script (exits non-zero on violations) or through
`validate() -> List[str]` from the test suite (SURVEY §4 tier 4).
"""

import ast
import inspect
import os
import sys
import textwrap

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _resolve_tpu_cls(dotted: str):
    """'execs.sort.TpuSortExec' → class, imported under spark_rapids_tpu."""
    import importlib
    mod_path, _, cls_name = dotted.rpartition(".")
    mod = importlib.import_module(f"spark_rapids_tpu.{mod_path}")
    return getattr(mod, cls_name)


def _metric_names_of(cls) -> set:
    """Metric names the class registers: the base set from
    PhysicalPlan._register_metrics plus every string key its
    `additional_metrics` overrides mention, collected by AST along the MRO
    (the methods build literal dicts / subscript-assign literal keys, and
    instantiating every exec generically is not possible)."""
    from spark_rapids_tpu.execs.base import TpuExec
    names = {"numOutputRows", "numOutputBatches", "opTime"}
    if issubclass(cls, TpuExec):
        names |= {"opJitCacheHits", "opJitCacheMisses", "opJitTraceTime"}
    for k in cls.__mro__:
        fn = k.__dict__.get("additional_metrics")
        if fn is None:
            continue
        try:
            tree = ast.parse(textwrap.dedent(inspect.getsource(fn)))
        except (OSError, SyntaxError, TypeError):
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Dict):
                for key in node.keys:
                    if isinstance(key, ast.Constant) \
                            and isinstance(key.value, str):
                        names.add(key.value)
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Subscript) \
                            and isinstance(t.slice, ast.Constant) \
                            and isinstance(t.slice.value, str):
                        names.add(t.slice.value)
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id == "dict":
                # dict(buildTime="MODERATE", ...) kwargs ARE metric names;
                # kwargs of arbitrary calls are not
                for kw in node.keywords:
                    if kw.arg is not None:
                        names.add(kw.arg)
    return names


_CONF_KEY_RE = None


def _conf_keys_in_text(text: str):
    """spark.rapids.tpu.* / spark.rapids.shuffle.* key candidates mentioned
    in a string (f-string fragments and doc prose included)."""
    global _CONF_KEY_RE
    import re
    if _CONF_KEY_RE is None:
        _CONF_KEY_RE = re.compile(
            r"spark\.rapids\.(?:tpu|shuffle)\.[A-Za-z0-9_.]+")
    return [m.rstrip(".") for m in _CONF_KEY_RE.findall(text)]


def _config_constant_names():
    """config.py module-level NAME -> conf key, from the builder DSL
    (``NAME = conf("key").doc(...)...``)."""
    import spark_rapids_tpu.config as cfg
    root = os.path.dirname(cfg.__file__)
    out = {}
    with open(cfg.__file__) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)):
            continue
        # innermost conf("key") of the builder chain
        # (conf("k").doc(...).booleanConf.createWithDefault(...))
        for sub in ast.walk(node.value):
            if not (isinstance(sub, ast.Call) and sub.args
                    and isinstance(sub.args[0], ast.Constant)
                    and isinstance(sub.args[0].value, str)):
                continue
            f_ = sub.func
            fname = f_.id if isinstance(f_, ast.Name) else (
                f_.attr if isinstance(f_, ast.Attribute) else "")
            if fname in ("conf", "_conf") \
                    and sub.args[0].value.startswith("spark."):
                out[node.targets[0].id] = sub.args[0].value
                break
    return out, root


def _conf_reads(tree, constants):
    """What one module's CODE does with conf keys: (every key a string
    mentions, the keys it reads, the config constants it references).

    A key is read where a string literal IS the key — the argument of a
    conf `get` / `set`, a key of a settings dict, in the dotted spelling or
    the tests' `spark__rapids__…` keyword one — or where its config
    constant is referenced. Prose does not read: a docstring, or a message
    that spells the key among other words, keeps no option alive."""
    prose = {id(n.value) for n in ast.walk(tree)
             if isinstance(n, ast.Expr) and isinstance(n.value, ast.Constant)}
    mentioned, read, consts = set(), set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            mentioned.update(_conf_keys_in_text(node.value))
            if id(node) not in prose and node.value.startswith("spark"):
                read.add(node.value.replace("__", "."))
        elif isinstance(node, ast.keyword) and (node.arg or "").startswith(
                "spark__"):
            read.add(node.arg.replace("__", "."))
        elif isinstance(node, ast.Name) and node.id in constants:
            consts.add(node.id)
        elif isinstance(node, ast.Attribute) and node.attr in constants:
            consts.add(node.attr)
    return mentioned, read, consts


def conf_consistency():
    """Conf-consistency check (the tracelint-adjacent registry contract):

    * every ``spark.rapids.tpu.*`` / ``spark.rapids.shuffle.*`` key
      mentioned anywhere in ``spark_rapids_tpu/`` must be declared in
      config.py's registry (a candidate that is a strict prefix of a
      registered key — ``spark.rapids.tpu.test.chaos`` in prose — is fine);
    * every registered key must appear in the regenerated docs/configs.md;
    * every key documented in the configs.md TABLE must be registered (no
      documented-but-dead keys);
    * every registered tpu/shuffle key must actually be READ by code
      outside config.py (`_conf_reads`) in the package, tests,
      benchmarks/, chipbench/ or chip_smoke.py (no declared-but-dead
      keys); a comment or docstring that spells the key reads nothing.
    """
    from spark_rapids_tpu.config import REGISTRY
    registered = set(REGISTRY.entries)
    scoped = {k for k in registered
              if k.startswith(("spark.rapids.tpu.", "spark.rapids.shuffle."))}
    constants, pkg_root = _config_constant_names()
    key_to_consts = {}
    for name, key in constants.items():
        key_to_consts.setdefault(key, set()).add(name)
    violations = []

    read_literals = set()
    used_consts = set()
    repo_root = os.path.dirname(pkg_root)
    paths = [os.path.join(repo_root, "chip_smoke.py")]
    for root in (pkg_root, *(os.path.join(repo_root, d)
                             for d in ("tests", "benchmarks", "chipbench"))):
        for dirpath, _dirs, files in os.walk(root):
            if "__pycache__" not in dirpath:
                paths.extend(os.path.join(dirpath, f) for f in files
                             if f.endswith(".py"))
    config_path = os.path.join(pkg_root, "config.py")
    for path in paths:
        if path == config_path:
            continue
        with open(path) as f:
            src = f.read()
        try:
            tree = ast.parse(src)
        except SyntaxError:
            continue
        mentioned, read, consts = _conf_reads(tree, constants)
        read_literals |= read
        used_consts |= consts
        if path.startswith(pkg_root):
            rel = os.path.relpath(path, repo_root)
            for key in sorted(mentioned - registered):
                if not any(r.startswith(key + ".") for r in registered):
                    violations.append(
                        f"conf: {rel} reads undeclared key "
                        f"{key!r} — declare it in config.py "
                        f"(and regenerate docs/configs.md)")

    # registry ↔ docs
    docs_path = os.path.join(repo_root, "docs", "configs.md")
    with open(docs_path) as f:
        doc_lines = f.read().splitlines()
    doc_keys = {line.split("|")[1].strip() for line in doc_lines
                if line.startswith("| spark.rapids")}
    # gen_docs.py documents the non-internal spark.rapids.* surface
    # (passthrough spark.sql.* compatibility keys are Spark's docs, not
    # ours; internal() test hooks are deliberately undocumented)
    documentable = {k for k in registered
                    if k.startswith("spark.rapids.")
                    and not REGISTRY.entries[k].internal}
    for key in sorted(documentable - doc_keys):
        violations.append(
            f"conf: registered key {key!r} missing from docs/configs.md — "
            f"run tools/gen_docs.py")
    for key in sorted(doc_keys - registered):
        violations.append(
            f"conf: docs/configs.md documents {key!r} but config.py does "
            f"not declare it (documented-but-dead)")

    # declared-but-dead: no code reads the literal or the constant
    for key in sorted(scoped - read_literals):
        if not (key_to_consts.get(key, set()) & used_consts):
            violations.append(
                f"conf: key {key!r} is declared in config.py but read "
                f"nowhere (package, tests, benchmarks, chipbench, "
                f"chip_smoke.py) — dead conf")
    return violations


_METRIC_EMITTERS = ("counter_inc", "gauge_set", "gauge_max",
                    "histogram_observe")


def _emitted_metric_names():
    """Every registry key emitted in the package, with the file that emits
    it: literal first args of the obs/metrics.py emission functions, plus
    both branches of a literal conditional (`"a" if ok else "b"`).  A
    non-literal key defeats both this check and dashboard grep-ability, so
    it is reported as a violation rather than silently skipped."""
    import spark_rapids_tpu as pkg
    pkg_root = os.path.dirname(pkg.__file__)
    repo_root = os.path.dirname(pkg_root)
    names = {}
    non_literal = []
    for dirpath, _dirs, files in os.walk(pkg_root):
        if "__pycache__" in dirpath:
            continue
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            with open(path) as f:
                try:
                    tree = ast.parse(f.read())
                except SyntaxError:
                    continue
            rel = os.path.relpath(path, repo_root)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                fn = node.func
                callee = fn.id if isinstance(fn, ast.Name) else (
                    fn.attr if isinstance(fn, ast.Attribute) else "")
                if callee not in _METRIC_EMITTERS or not node.args:
                    continue
                arg0 = node.args[0]
                literals = []
                if isinstance(arg0, ast.Constant) \
                        and isinstance(arg0.value, str):
                    literals = [arg0.value]
                elif isinstance(arg0, ast.IfExp) and all(
                        isinstance(b, ast.Constant)
                        and isinstance(b.value, str)
                        for b in (arg0.body, arg0.orelse)):
                    literals = [arg0.body.value, arg0.orelse.value]
                else:
                    non_literal.append(f"{rel}:{node.lineno}")
                for name in literals:
                    names.setdefault(name, set()).add(rel)
    return names, non_literal, repo_root


def _documented_metric_names(repo_root):
    """Names from the docs/observability.md metrics REGISTRY table (the one
    whose header is `| metric | type | ... |`) — backticked, multi-name
    rows joined with ' / '.  The doc has other `|`-tables (event names,
    snapshot keys); only the registry table states the emission contract."""
    import re
    path = os.path.join(repo_root, "docs", "observability.md")
    names = set()
    in_table = False
    with open(path) as f:
        for line in f:
            if line.startswith("| metric |"):
                in_table = True
                continue
            if in_table:
                if not line.startswith("|"):
                    in_table = False
                    continue
                if line.startswith("|---"):
                    continue
                cell = line.split("|")[1].strip()
                names.update(re.findall(r"`([^`]+)`", cell))
    return names, path


def metrics_consistency():
    """Metrics-name consistency (the conf-consistency mirror for the
    observability registry): every counter/gauge/histogram key the package
    emits is documented in docs/observability.md's registry table, and
    every documented key is actually emitted — a dashboard built from the
    docs must never watch a dead name, and a new emission site must
    publish its name."""
    violations = []
    emitted, non_literal, repo_root = _emitted_metric_names()
    for loc in non_literal:
        violations.append(
            f"metrics: {loc} emits a registry key that is not a string "
            f"literal (or a literal conditional) — literal names keep the "
            f"registry grep-able and this check exact")
    documented, docs_path = _documented_metric_names(repo_root)
    docs_rel = os.path.relpath(docs_path, repo_root)
    for name in sorted(set(emitted) - documented):
        files = ", ".join(sorted(emitted[name]))
        violations.append(
            f"metrics: {name!r} (emitted by {files}) is missing from the "
            f"{docs_rel} registry table")
    for name in sorted(documented - set(emitted)):
        violations.append(
            f"metrics: {docs_rel} documents {name!r} but nothing in the "
            f"package emits it (documented-but-dead)")
    return violations


def validate():
    import jax
    jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.config import REGISTRY
    from spark_rapids_tpu.execs.base import CpuExec, PhysicalPlan
    from spark_rapids_tpu.expressions.base import Expression
    from spark_rapids_tpu.plan.overrides import exec_rules
    from spark_rapids_tpu.plan.typechecks import all_expr_rules

    violations = []

    # exec rules ----------------------------------------------------------
    for cls, rule in exec_rules().items():
        if rule.conf_key and rule.conf_key not in REGISTRY.entries:
            violations.append(
                f"exec {cls.__name__}: conf key {rule.conf_key!r} is not a "
                f"registered config entry")
        if not issubclass(cls, CpuExec):
            violations.append(
                f"exec rule for {cls.__name__} is not keyed by a CpuExec "
                f"subclass")
        if cls.execute_partition is PhysicalPlan.execute_partition:
            violations.append(
                f"exec {cls.__name__} does not implement execute_partition")
        if cls.output is PhysicalPlan.output:
            violations.append(
                f"exec {cls.__name__} does not implement output")
        if rule._convert is None:  # rule.convert is a bound wrapper — check
            violations.append(     # the actual registered callable
                f"exec {cls.__name__}: rule has no convert fn")
        if rule.metrics and not rule.tpu_cls:
            violations.append(
                f"exec {cls.__name__}: rule declares metrics "
                f"{rule.metrics} but no tpu_cls to check them against")
        if rule.tpu_cls:
            try:
                tpu_cls = _resolve_tpu_cls(rule.tpu_cls)
            except (ImportError, AttributeError) as e:
                violations.append(
                    f"exec {cls.__name__}: tpu_cls {rule.tpu_cls!r} does "
                    f"not resolve ({e})")
            else:
                have = _metric_names_of(tpu_cls)
                for m in rule.metrics:
                    if m not in have:
                        violations.append(
                            f"exec {cls.__name__}: declared metric {m!r} "
                            f"is not registered by {rule.tpu_cls} "
                            f"(has: {sorted(have)})")

    # expression rules ----------------------------------------------------
    base_eval_tpu = Expression.eval_tpu
    base_eval_cpu = Expression.eval_cpu
    for cls, rule in all_expr_rules().items():
        if getattr(cls, "unevaluable", False):
            # structural: driven by its exec (reference Unevaluable) — it
            # must not ALSO claim a kernel: an eval_tpu override or a
            # host_assisted flag on an unevaluable expression is dead code
            # that would mislead the tagging/pricing layers
            if "eval_tpu" in cls.__dict__:  # own override only — inheriting
                violations.append(         # an evaluable base is not a claim
                    f"expression {cls.__name__}: unevaluable but overrides "
                    f"eval_tpu — the kernel can never run (drop one)")
            if rule.host_assisted:
                violations.append(
                    f"expression {cls.__name__}: unevaluable but flagged "
                    f"host_assisted — the flag implies an eval path that "
                    f"does not exist")
            continue
        has_tpu = cls.eval_tpu is not base_eval_tpu
        has_cpu = cls.eval_cpu is not base_eval_cpu
        supported = getattr(cls, "tpu_supported", True)
        if supported and not (has_tpu or rule.host_assisted):
            violations.append(
                f"expression {cls.__name__}: registered as device-supported "
                f"but neither overrides eval_tpu nor is flagged "
                f"host_assisted")
        if not has_cpu and not has_tpu:
            violations.append(
                f"expression {cls.__name__}: no evaluation path at all")
        if rule.type_sig is not None:
            try:
                rule.type_sig.check  # noqa: B018 — attribute must exist
            except AttributeError:
                violations.append(
                    f"expression {cls.__name__}: type_sig lacks check()")

    violations.extend(conf_consistency())
    violations.extend(metrics_consistency())
    return violations


def main() -> int:
    violations = validate()
    if violations:
        print(f"{len(violations)} API validation failure(s):")
        for v in violations:
            print(f"  - {v}")
        return 1
    print("API validation passed: "
          "all exec/expression registry contracts hold")
    return 0


if __name__ == "__main__":
    sys.exit(main())
