"""spec-without-divisibility-guard: a spec factory naming ``model``
must validate divisibility.

Sharding a weight axis over ``model`` only works when the axis length
divides the mesh's model degree — otherwise jax raises deep inside
``NamedSharding`` consumption with a shape error that names neither
the config knob nor the factory that chose the layout.  PR 12's
convention is that the ``shard_specs`` factories validate up front and
raise with the REAL constraint (``"n_heads=12 not divisible by model
degree 8 — attention heads shard over `model`"``,
``transformer.shard_specs``); this rule keeps every future family
honest.

A module-level (or method) factory whose name ends in ``specs`` and
whose body names the ``model`` axis in a ``P(...)`` literal must
either

- contain a divisibility check (any ``%`` — the ``if cfg.n_heads %
  model_degree: raise`` idiom, or a ``vocab_ok = ... % ... == 0``
  predicate), or
- delegate to another ``*specs`` factory (``gpt.shard_specs`` is
  ``transformer.shard_specs`` re-exported — the delegatee carries the
  guard), or
- carry an inline suppression explaining where the validation lives
  (``gpt.paged_specs``: the DecodeEngine validates at construction).
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.jaxlint import astutil
from tools.jaxlint.core import Finding, Rule, register

_SCOPE_HINTS = ("models/", "parallel/sharded_fit.py")
_own_body = astutil.walk_own_body


@register
class SpecWithoutDivisibilityGuardRule(Rule):
    name = "spec-without-divisibility-guard"
    severity = "error"
    family = "sharding-layout"
    description = ("a *specs factory names the `model` axis without a "
                   "divisibility check or delegation to a guarded "
                   "factory — bad (conf, mesh) pairings fail inside XLA "
                   "partitioning instead of at build time")

    def applies_to(self, posix_path: str) -> bool:
        return any(h in posix_path for h in _SCOPE_HINTS)

    def check(self, tree: ast.Module, posix_path: str) -> Iterable[Finding]:
        chain = astutil.enclosing_chain(tree)
        aliases = astutil.partition_spec_aliases(tree)
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    or not fn.name.endswith("specs"):
                continue
            names_model = False
            has_mod = False
            delegates = False
            for node in _own_body(fn):
                if isinstance(node, ast.BinOp) \
                        and isinstance(node.op, ast.Mod):
                    has_mod = True
                elif isinstance(node, ast.Call):
                    name = astutil.dotted_name(node.func)
                    if name is not None and name != fn.name \
                            and name.rsplit(".", 1)[-1].endswith("specs"):
                        delegates = True
            for node in _own_body(fn):
                if not isinstance(node, ast.Call):
                    continue
                name = astutil.dotted_name(node.func)
                if name is None:
                    continue
                leaf = name.rsplit(".", 1)[-1]
                if leaf != "PartitionSpec" and name not in aliases:
                    continue
                for entry in astutil.partition_spec_entries(node):
                    values = astutil.resolve_axis_entry(
                        entry, tree, chain.get(id(entry), []))
                    if values and "model" in values:
                        names_model = True
            if names_model and not has_mod and not delegates:
                yield self.finding(
                    posix_path, fn,
                    f"{fn.name}() shards over the `model` axis but "
                    "neither checks divisibility (no `%` in the body) "
                    "nor delegates to a *specs factory that does — a "
                    "model degree that does not divide the sharded axis "
                    "fails deep inside XLA partitioning; validate up "
                    "front with the real constraint, or suppress with "
                    "a pointer to where the validation lives")
