"""host-sync-in-hot-path: jitted step functions must not sync the host.

Inside a traced ("hot" — see ``astutil.hot_functions``) function,
``.item()``, ``float(x)``/``int(x)``/``bool(x)`` on a tracer,
``np.asarray``/``np.array``, and Python ``if``/``while`` on a traced
value either fail tracing outright (ConcretizationTypeError at best) or
— worse, when the value happens to be concrete at trace time — silently
bake a constant into the compiled program and force a device→host
round-trip per call, which stalls the dispatch queue the device runs
ahead on (the dispatch-latency wall PR 1 exists to remove).

Parameters declared static (``static_argnums``/``static_argnames``
literals on the jit call or decorator, and keyword-only params) are NOT
treated as tracers, so shape-style branching on statics stays clean.
"""

from __future__ import annotations

import ast
from typing import Iterable, Set

from tools.jaxlint import astutil
from tools.jaxlint.core import Finding, Rule, register

_CASTS = {"float", "int", "bool"}
_NP_NAMES = {"np", "numpy", "onp"}
_NP_MATERIALIZERS = {"asarray", "array"}


@register
class HostSyncRule(Rule):
    name = "host-sync-in-hot-path"
    severity = "warning"
    description = ("device→host sync (.item(), float()/int()/bool() on a "
                   "tracer, np.asarray, if-on-tracer) inside a jitted "
                   "step function")

    def check(self, tree: ast.Module, posix_path: str) -> Iterable[Finding]:
        hot = astutil.hot_functions(tree)
        if not hot:
            return
        owner = astutil.enclosing_function_params(tree)
        # tracer params per hot function (statics excluded)
        tracers = {fn: astutil.dynamic_param_names(
            fn, info.static_argnums, info.static_argnames)
            for fn, info in hot.items()}

        for root, _ in astutil.hot_roots(hot):
            for node in ast.walk(root):
                yield from self._check_node(node, posix_path, hot, owner,
                                            tracers)

    def _check_node(self, node, posix_path, hot, owner, tracers
                    ) -> Iterable[Finding]:
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Attribute) and fn.attr == "item" \
                    and not node.args and not node.keywords:
                yield self.finding(
                    posix_path, node,
                    ".item() forces a device→host sync inside a traced "
                    "function")
            elif isinstance(fn, ast.Name) and fn.id in _CASTS \
                    and len(node.args) == 1 and not node.keywords \
                    and self._tracer_in_test(
                        node.args[0],
                        tracers.get(owner.get(node), set())) is not None:
                # only casts whose argument READS a tracer param — a
                # float() of a host scalar in a hot function is fine
                yield self.finding(
                    posix_path, node,
                    f"{fn.id}() on a traced value syncs the host (use "
                    f"jnp casts / lax.convert_element_type on device)")
            elif isinstance(fn, ast.Attribute) \
                    and fn.attr in _NP_MATERIALIZERS \
                    and isinstance(fn.value, ast.Name) \
                    and fn.value.id in _NP_NAMES:
                yield self.finding(
                    posix_path, node,
                    f"np.{fn.attr}() materializes a device array on host "
                    "inside a traced function (use jnp)")
        elif isinstance(node, (ast.If, ast.While)):
            enclosing = owner.get(node)
            if enclosing not in hot:
                return
            params = tracers.get(enclosing, set())
            hit = self._tracer_in_test(node.test, params)
            if hit is not None:
                kw = "if" if isinstance(node, ast.If) else "while"
                yield self.finding(
                    posix_path, node,
                    f"Python `{kw}` on traced value {hit!r} — branch on "
                    "device with jnp.where/lax.cond instead")

    @staticmethod
    def _tracer_in_test(test: ast.AST, params: Set[str]):
        """First parameter name the expression reads as a traced VALUE.
        Reads reached only through metadata attributes (``.shape``/
        ``.ndim``/... — astutil.METADATA_ATTRS) are static at trace
        time and don't count."""
        nodes = list(ast.walk(test))
        static_bases = astutil.metadata_only_names(nodes)
        for sub in nodes:
            if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Load) \
                    and sub.id in params and id(sub) not in static_bases:
                return sub.id
        return None
