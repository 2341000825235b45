"""Observability lint (rule **TL012**): emission discipline for the whole
obs plane — tracer spans/events, metrics-registry increments, and flight-
recorder notes.

The obs layer (docs/observability.md) is only trustworthy if engine code
follows two rules, checked statically here over ``execs/``, ``shuffle/``,
``memory/`` and ``parallel/`` (the mesh.exchange spans):

1. **Route through the obs API.** Emission sites must use the public
   helpers (``obs.span`` / ``obs.phase`` / ``obs.phase_add`` /
   ``obs.event`` / ``obs.dispatch_event`` / ``obs.sync_event`` /
   ``obs.current_span``; ``metrics.counter_inc`` /
   ``gauge_set`` / ``gauge_max`` / ``histogram_observe``;
   ``flight.note``) — not the tracer internals (``QueryTracer``,
   ``_Span``, the ring-buffer ``_append``), not the registry internals
   (``MetricsRegistry`` cells), and not raw ``jax.profiler`` annotations
   (those belong in profiling.py's ``trace_scope``, which carries the
   off-fast-path). A bypass would skip the ``_ACTIVE``/enabled gates
   (overhead when off), the category filter, and the thread-local span
   stacks (corrupting the tree for every later span on that thread).

2. **Instrumentation must not introduce unaudited blocking syncs.** An
   emission ARGUMENT — a span/event arg, a registry label or value, a
   flight-note field — that forces a device value to host
   (``np.asarray(...)``, ``.item()``, ``jax.device_get(...)``, or
   ``int()``/``float()`` of a jnp expression) is a hidden blocking
   device→host round trip that fires exactly when the observability
   plane is on — the observer would perturb the observed, and the sync
   would bypass the audited ledger gate (TL011's contract). Emission args
   must be values the caller already has on host; the always-on registry
   makes this non-negotiable (the sync would fire on EVERY query, not
   just traced ones).

3. **The fused collective dataplane stays one dispatch.** The post-
   collective compact of ``parallel/mesh.py`` runs INSIDE the cached
   exchange program (scatter to ``bases[src] + pos`` under the host-known
   sizing counts — ISSUE 16's fused compact): a call to the host-compact
   idiom (``columnar.batch._compact_plan`` / ``gather``) in that module
   re-introduces the per-partition host round-trips the fusion removed,
   so it fails static analysis here rather than waiting for a bench round
   to notice the compact wall is back.

All are errors; the baseline stays EMPTY — our own instrumentation
complies, and new emission sites must too.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Tuple

from .registry_check import Finding

#: packages the lint covers (relative to the spark_rapids_tpu package root)
OBS_SUBPACKAGES: Tuple[str, ...] = ("execs", "shuffle", "memory", "parallel",
                                    "serving")

#: individual modules additionally covered: obs/mesh_profile.py is part of
#: the obs package but is itself an EMITTER (registry histograms, flight
#: notes, the watchdog) — its emission arguments obey the same
#: no-blocking-sync contract as engine code. io/device_decode.py emits
#: scan.page/scan.fallback events per staged page/demoted column (the
#: BYTE_ARRAY string staging added more of them) — same contract.
OBS_MODULES: Tuple[str, ...] = ("obs/mesh_profile.py", "io/device_decode.py")

#: names that count as obs emission entry points when bound from the obs
#: package (rule 2 scans their call arguments): tracer spans/events,
#: per-query counter events, metrics-registry increments, flight notes,
#: mesh-profiler records
_EMIT_NAMES = ("span", "phase", "phase_add", "event", "dispatch_event",
               "sync_event", "counter_inc", "gauge_set", "gauge_max",
               "histogram_observe", "note", "record_exchange",
               "record_fallback")

#: obs submodules whose attribute calls are emission sites when imported
#: (``from ..obs import tracer as obs`` / ``metrics`` / ``flight`` /
#: ``mesh_profile``)
_OBS_MODULE_NAMES = ("tracer", "metrics", "flight", "obs", "mesh_profile")

#: tracer/registry internals whose use outside obs/ is a rule-1 finding
_INTERNAL_NAMES = ("QueryTracer", "_Span", "_NullSpan", "MetricsRegistry")
_INTERNAL_ATTRS = ("_append", "_alloc_span", "_ring", "_cells",
                   "_counters", "_gauges", "_hists")

#: rule 3 — the fused one-dispatch surface: modules whose post-collective
#: consumption must stay inside the ONE cached exchange program; calling
#: the host-compact idiom there is the regression the fusion removed
_FUSED_DISPATCH_MODULES: Tuple[str, ...] = ("parallel/mesh.py",)
_HOST_COMPACT_CALLS: Tuple[str, ...] = ("_compact_plan", "gather")


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted name of an expression ('jax.profiler.start_trace',
    'obs.event', ...)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _is_blocking_call(call: ast.Call) -> Optional[str]:
    """The blocking-sync shapes of TL011, syntactically: raw transfer calls
    plus int()/float() coercion of a jnp/jax expression."""
    name = _dotted(call.func)
    if name.endswith(("np.asarray", "numpy.asarray", "np.array",
                      "numpy.array")):
        return name
    if name in ("jax.device_get", "device_get") \
            or name.endswith(".device_get"):
        return name
    if isinstance(call.func, ast.Attribute) and call.func.attr == "item" \
            and not call.args:
        return _dotted(call.func)
    if name in ("int", "float") and call.args:
        inner = _dotted(call.args[0].func) if isinstance(
            call.args[0], ast.Call) else _dotted(call.args[0])
        if inner.startswith(("jnp.", "jax.")):
            return f"{name}({inner})"
    return None


class _Visitor(ast.NodeVisitor):
    def __init__(self, relpath: str):
        self.relpath = relpath
        self.stack: List[str] = []
        self.obs_modules: set = set()   # names bound to the obs pkg/tracer
        self.obs_helpers: set = set()   # emission helpers imported by name
        self.hits: List[Tuple[str, int, str]] = []  # (qual, line, msg)

    # --- import tracking ---------------------------------------------------
    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        mod = node.module or ""
        # a module inside obs/ itself imports siblings relatively
        # (``from . import metrics``) — same binding rules apply
        in_obs_pkg = self.relpath.startswith("obs/") and not mod
        if in_obs_pkg or mod.endswith("obs") or ".obs." in f".{mod}." or \
                mod.endswith(("obs.tracer", "obs.metrics", "obs.flight",
                              "obs.mesh_profile")):
            for a in node.names:
                bound = a.asname or a.name
                if a.name in _EMIT_NAMES:
                    self.obs_helpers.add(bound)
                elif a.name in _OBS_MODULE_NAMES:
                    self.obs_modules.add(bound)
        self.generic_visit(node)

    def visit_Import(self, node: ast.Import) -> None:
        for a in node.names:
            if a.name.endswith((".obs", ".obs.tracer", ".obs.metrics",
                                ".obs.flight")):
                self.obs_modules.add(a.asname or a.name.split(".")[-1])
        self.generic_visit(node)

    # --- qualname tracking --------------------------------------------------
    def _qual(self) -> str:
        return ".".join(self.stack) or "<module>"

    def visit_FunctionDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self.stack.append(node.name)
        self.generic_visit(node)
        self.stack.pop()

    # --- the rules -----------------------------------------------------------
    def visit_Attribute(self, node: ast.Attribute) -> None:
        name = _dotted(node)
        if name.startswith("jax.profiler."):
            self.hits.append((
                self._qual(), node.lineno,
                f"raw jax.profiler use ({name}) — emission sites route "
                f"through the obs API (obs.span/obs.event) or "
                f"profiling.trace_scope, which carry the tracing-off "
                f"fast path"))
        elif node.attr in _INTERNAL_ATTRS and self._is_obs_value(node.value):
            self.hits.append((
                self._qual(), node.lineno,
                f"tracer internal ({name}) — use the public obs helpers; "
                f"bypassing them skips the _ACTIVE gate and the "
                f"thread-local span stacks"))
        self.generic_visit(node)

    def _is_obs_value(self, node: ast.AST) -> bool:
        name = _dotted(node)
        head = name.split(".")[0]
        return head in self.obs_modules or "QueryTracer" in name

    def visit_Name(self, node: ast.Name) -> None:
        if node.id in _INTERNAL_NAMES and isinstance(node.ctx, ast.Load):
            self.hits.append((
                self._qual(), node.lineno,
                f"tracer internal ({node.id}) — construct spans/events "
                f"through the public obs helpers only"))
        self.generic_visit(node)

    def _is_emit_call(self, call: ast.Call) -> bool:
        f = call.func
        if isinstance(f, ast.Name):
            return f.id in self.obs_helpers
        if isinstance(f, ast.Attribute) and f.attr in _EMIT_NAMES:
            return self._is_obs_value(f.value)
        return False

    def visit_Call(self, node: ast.Call) -> None:
        if self.relpath in _FUSED_DISPATCH_MODULES:
            last = _dotted(node.func).split(".")[-1]
            if last in _HOST_COMPACT_CALLS:
                self.hits.append((
                    self._qual(), node.lineno,
                    f"host-side compact ({_dotted(node.func)}) in the "
                    f"fused collective dataplane — the post-collective "
                    f"compact is part of the ONE cached exchange dispatch "
                    f"(scatter to bases[src]+pos under the host-known "
                    f"sizing counts); a host _compact_plan/gather here "
                    f"regresses the compact wall the fusion removed"))
        if self._is_emit_call(node):
            for arg in list(node.args) + [k.value for k in node.keywords]:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Call):
                        blocked = _is_blocking_call(sub)
                        if blocked:
                            self.hits.append((
                                self._qual(), sub.lineno,
                                f"blocking device→host sync ({blocked}) "
                                f"inside a span/event argument — "
                                f"instrumentation must not sync; pass a "
                                f"value the caller already holds on host"))
        self.generic_visit(node)


def lint_obs_module(source: str, relpath: str) -> List[Finding]:
    """TL012 findings for one module's source."""
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return []
    v = _Visitor(relpath)
    v.visit(tree)
    findings: List[Finding] = []
    seen = set()
    for qual, line, msg in v.hits:
        key = f"{relpath}::{qual}"
        if (key, msg) in seen:
            continue
        seen.add((key, msg))
        findings.append(Finding("TL012", "error", key,
                                f"{msg} (line {line})"))
    return findings


def lint_obs_tree(root: Optional[str] = None,
                  subpackages: Tuple[str, ...] = OBS_SUBPACKAGES,
                  modules: Tuple[str, ...] = OBS_MODULES
                  ) -> List[Finding]:
    """Lint the shipped tree (root defaults to the spark_rapids_tpu pkg)."""
    from .astwalk import iter_module_sources
    findings: List[Finding] = []
    for relpath, src in iter_module_sources(root, subpackages,
                                            modules=modules):
        findings.extend(lint_obs_module(src, relpath))
    return findings
