"""cordumlint — control-plane-aware static analysis for cordum-tpu.

A small AST-based rule engine encoding this codebase's correctness
invariants: deterministic clocks in deadline logic (CL001), no silently
swallowed exceptions (CL002), no blocking calls in async services (CL003),
job-state writes only through the legal-transition table (CL004), and bus
subjects from ``protocol/subjects.py`` constants (CL005).

Run it as ``python -m tools.cordumlint cordum_tpu`` or via ``make lint``.
See ``docs/static_analysis.md`` for the rule catalogue and suppression /
baseline workflow.
"""
from __future__ import annotations

from .core import Finding, LintContext, Rule, all_rules, lint_paths

__version__ = "2.0.0"

__all__ = ["Finding", "LintContext", "Rule", "all_rules", "lint_paths", "__version__"]
