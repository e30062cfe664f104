"""raw-shard-map: ``shard_map`` is only reached via ``compat.py``.

``deeplearning4j_tpu/compat.py`` is the one import site: it spells the
call convention (keyword placement arguments, ``check_vma``) once, so a
change in JAX's ``shard_map`` surface is a one-file edit.  ``compat.py``
itself carries a file-wide ``# jaxlint: disable-file=raw-shard-map``
(it IS the import site) rather than a path exemption baked in here.
"""

from __future__ import annotations

import ast
from typing import Iterable

from tools.jaxlint.core import Finding, Rule, register

_MSG = ("direct shard_map import bypasses deeplearning4j_tpu/compat.py "
        "(the one shard_map import site); use "
        "'from deeplearning4j_tpu.compat import shard_map'")


@register
class RawShardMapRule(Rule):
    name = "raw-shard-map"
    severity = "error"
    description = ("shard_map imported from jax instead of the "
                   "compat.py shim")

    def check(self, tree: ast.Module, posix_path: str) -> Iterable[Finding]:
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod == "jax.experimental.shard_map":
                    yield self.finding(posix_path, node, _MSG)
                elif mod in ("jax", "jax.experimental") and any(
                        a.name == "shard_map" for a in node.names):
                    yield self.finding(posix_path, node, _MSG)
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.startswith("jax.experimental.shard_map"):
                        yield self.finding(posix_path, node, _MSG)
            elif isinstance(node, ast.Attribute) \
                    and node.attr == "shard_map":
                # expression use: jax.shard_map / jax.experimental.shard_map
                base = node.value
                if (isinstance(base, ast.Name) and base.id == "jax") or (
                        isinstance(base, ast.Attribute)
                        and base.attr == "experimental"
                        and isinstance(base.value, ast.Name)
                        and base.value.id == "jax"):
                    yield self.finding(posix_path, node, _MSG)
