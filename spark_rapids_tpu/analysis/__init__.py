"""Trace-safety & registry-consistency static analysis (tracelint).

Public surface for tools/tracelint.py, tools/gen_docs.py and the tests:

* :func:`analyze_registry` — classify every registered expression's
  ``eval_tpu`` and cross-check against plan/typechecks.py (TL001–TL004).
* :func:`lint_tree` — concurrency lint over shuffle/, memory/, execs/
  (TL010).
* :func:`lint_sync_tree` — blocking device→host syncs outside the audited
  ledger gate in execs/ and shuffle/ (TL011).
* :func:`lint_obs_tree` — span/event emission discipline in execs/,
  shuffle/ and memory/: route through the obs API, never sync inside an
  event argument (TL012).
* :func:`lint_lifecycle_tree` — resource-lifetime pass over execs/,
  shuffle/, memory/, parallel/, io/ and session.py: leak-freedom on all
  paths incl. exceptions (TL020) and chaos coverage of the unwind paths
  (TL023).
* :func:`lint_locks_tree` — lock discipline: no blocking op under a
  process-wide lock (TL021), global lock graph vs the declared partial
  order (TL022).
* :func:`lint_jit_tree` — program-cache & dispatch discipline over the
  cached-program surfaces: cache-key stability (TL030), static-shape
  bucketing (TL031), trace purity (TL032), donated-buffer safety
  (TL033).
* :func:`lint_plan_key_tree` — plan-cache key stability over serving/:
  unpinned identity, per-query values, live conf reads and bare schema
  objects inside fingerprint/``*_sig`` builders (TL034).
* :func:`corroborate` — dynamic ``jax.eval_shape`` probe vs the static
  verdicts (TL005).
* :func:`scan_source` / :func:`scan_function` — detector layer over raw
  source (test fixtures, kernel modules).
* :func:`execution_modes` — per-expression execution-mode strings for
  docs/supported_ops.md.

See docs/analysis.md for the verdict classes and the baseline workflow.
"""

from .astwalk import (CONDITIONAL_HOST, DEVICE, HOST, UNTRACEABLE, Detection,
                      FunctionReport, ModuleIndex, worst)
from .concurrency import lint_module_source, lint_tree
from .detectors import DETECTOR_IDS, scan_function, scan_source
from .jitlint import (lint_jit_module, lint_jit_tree, lint_plan_key_module,
                      lint_plan_key_tree)
from .lifecycle import lint_lifecycle_module, lint_lifecycle_tree
from .locks import LOCK_ORDER, lint_locks_module, lint_locks_tree
from .obslint import lint_obs_module, lint_obs_tree
from .registry_check import (ExprReport, Finding, analyze_registry,
                             classify_class, execution_modes)
from .syncs import lint_sync_module, lint_sync_tree

__all__ = [
    "CONDITIONAL_HOST", "DEVICE", "HOST", "LOCK_ORDER", "UNTRACEABLE",
    "Detection", "DETECTOR_IDS", "ExprReport", "Finding", "FunctionReport",
    "ModuleIndex", "analyze_registry", "classify_class", "corroborate",
    "execution_modes", "lint_jit_module", "lint_jit_tree",
    "lint_lifecycle_module", "lint_lifecycle_tree",
    "lint_locks_module", "lint_locks_tree", "lint_module_source",
    "lint_obs_module", "lint_obs_tree", "lint_plan_key_module",
    "lint_plan_key_tree", "lint_sync_module",
    "lint_sync_tree", "lint_tree", "scan_function", "scan_source", "worst",
]


def corroborate(reports):
    # jax import deferred: the static passes must work without touching jax
    from .probe import corroborate as _c
    return _c(reports)
