"""jaxlint — AST-based tracing-safety analyzer for this repo's JAX
invariants (see tools/jaxlint/core.py for the framework and
tools/jaxlint/rules/ for the rule set).

Public API::

    from tools.jaxlint import run_paths, check_source, REGISTRY
    findings = run_paths(["deeplearning4j_tpu", "tools"])

CLI: ``python -m tools.jaxlint [paths...]`` (see cli.py).
"""

from tools.jaxlint import rules  # noqa: F401 — registers the rule set
from tools.jaxlint.core import (  # noqa: F401
    Finding, REGISTRY, Rule, check_source, register, run_paths,
)

__all__ = ["Finding", "REGISTRY", "Rule", "check_source", "register",
           "run_paths"]
