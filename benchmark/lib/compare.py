"""The arithmetic of ``correct``: what is compared, never a limit (those
are data, ``limits/<workload>.json``)."""

from __future__ import annotations

import functools
import statistics
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

#: a leaf whose reference gradient is under this share of the median
#: leaf's is nought to rounding (a key bias under softmax, the unread
#: segment embedding): its change is round-off and is not compared
ZERO_GRAD_SHARE = 1e-3


def leaf_names(tree: Any) -> List[str]:
    import jax

    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@functools.lru_cache(maxsize=None)
def _jitted():
    """The two reductions, jitted once a process: leaf-by-leaf L2 norms
    of a tree and of the difference of two (fused, so that no tree of
    differences is ever held beside the others)."""
    import jax
    import jax.numpy as jnp

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x)))

    @jax.jit
    def tree(x):
        return jnp.stack([norm(p.astype(jnp.float32))
                          for p in jax.tree.leaves(x)])

    @jax.jit
    def diff(x, y):
        return jnp.stack([norm(p.astype(jnp.float32) - q.astype(jnp.float32))
                          for p, q in zip(jax.tree.leaves(x),
                                          jax.tree.leaves(y))])

    return tree, diff


def diff_norms(a: Any, b: Any) -> Dict[str, float]:
    """L2 norm of ``a - b``, leaf by leaf, computed on the device in one
    call."""
    return dict(zip(leaf_names(a), np.asarray(_jitted()[1](a, b)).tolist()))


def tree_norms(a: Any) -> Dict[str, float]:
    return dict(zip(leaf_names(a), np.asarray(_jitted()[0](a)).tolist()))


def leaf_gaps(prog: Dict[str, float], ref: Dict[str, float],
              skip: Sequence[str] = ()) -> Dict[str, float]:
    """Leaf by leaf, the gap between the program's norm and the
    reference's (not the norm of their difference), against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    median = statistics.median(ref.values())
    return {k: abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
            for k in ref if k not in skip}


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   skip: Sequence[str] = ()) -> Tuple[float, str]:
    """The widest of ``leaf_gaps``.  Returns (gap, leaf)."""
    gaps = leaf_gaps(prog, ref, skip)
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def zero_grad_leaves(ref_grad: Dict[str, float]) -> List[str]:
    median = statistics.median(ref_grad.values())
    return [k for k, v in ref_grad.items() if v < ZERO_GRAD_SHARE * median]


def train_numbers(prog: Dict[str, Any], ref: Dict[str, Any]
                  ) -> Dict[str, float]:
    """``prog`` / ``ref``: {"losses": [per step], "change": {leaf: norm}};
    ``ref`` also "grad": {leaf: norm} of its first gradient, which names
    the leaves left out of the change.  A loss that is not finite, or a
    norm that is not, reads as infinite."""
    out: Dict[str, float] = {}
    lp, lr = np.asarray(prog["losses"], float), np.asarray(ref["losses"],
                                                           float)
    n = min(len(lp), len(lr))
    out["loss_gap"] = float(np.max(np.abs(lp[:n] - lr[:n]) / np.abs(lr[:n]))
                            ) if n and len(lp) >= len(lr) else float("inf")
    out["change_gap"] = worst_leaf_gap(prog["change"], ref["change"],
                                       zero_grad_leaves(ref["grad"]))[0]
    return {k: (v if np.isfinite(v) else float("inf"))
            for k, v in out.items()}


def served_gap(ref_logits: np.ndarray, served: np.ndarray) -> float:
    """The widest gap by which a served token's reference logit lies
    below the reference's best: ``ref_logits`` [n, V] at the positions
    that predicted ``served`` [n]."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, served[:, None].astype(np.int64),
                             axis=-1)[:, 0]
    return float(np.max(best - got))


def verdict(compared: Dict[str, float], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, List[float]]]:
    """Each number beside its limit; a number with no limit in the
    cell's file is a fault of the benchmark, not a pass."""
    rows = {k: [v, limits[k]] for k, v in compared.items()}
    return all(v <= lim for v, lim in rows.values()), rows
