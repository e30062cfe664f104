"""The one ``shard_map`` import site.

Every module takes ``shard_map`` from here (jaxlint's ``raw-shard-map``
rule enforces it), so the call convention — keyword ``mesh`` /
``in_specs`` / ``out_specs``, optional ``check_vma`` — is spelled once.
"""

from __future__ import annotations

# jaxlint: disable-file=raw-shard-map — this module IS the designated
# import site every other shard_map user is required to route through

from typing import Any, Callable

from jax import shard_map as _shard_map


def shard_map(f: Callable, *, mesh: Any, in_specs: Any, out_specs: Any,
              check_vma: "bool | None" = None, **kwargs: Any) -> Callable:
    """``jax.shard_map`` with keyword-only placement arguments;
    ``check_vma=None`` leaves JAX's default.  Extra kwargs pass through
    untouched."""
    if check_vma is not None:
        kwargs["check_vma"] = check_vma
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, **kwargs)
