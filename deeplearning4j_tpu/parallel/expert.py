"""Expert parallelism — Mixture-of-Experts with all_to_all dispatch.

New capability with no reference counterpart (SURVEY.md §2.9: expert
parallelism absent from the reference).  GShard/Switch-style design, built
for the TPU torus:

- Top-k router with capacity factor; dispatch/combine are dense one-hot
  einsums (MXU-friendly — no scatters, no dynamic shapes under jit).
- Experts are sharded over the mesh ``expert`` axis; tokens travel to their
  experts and back via two ``lax.all_to_all`` collectives (ICI), each shard
  batch-applying only its resident experts.
- Load-balance auxiliary loss (Switch Transformer form): E * Σ_e f_e · p_e
  where f_e is the fraction of tokens routed to expert e and p_e the mean
  router probability.
- Single-shard path (no ``expert`` axis in the mesh) runs the same
  dispatch/combine math without collectives, so the layer is
  topology-agnostic.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from deeplearning4j_tpu.compat import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, EXPERT_AXIS

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    d_model: int = 64
    d_ff: int = 256
    aux_loss_weight: float = 1e-2


def init_moe_params(key: Array, cfg: MoEConfig) -> dict:
    kr, k1, k2 = jax.random.split(key, 3)
    E, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": jax.random.normal(kr, (d, E)) * 0.02,
        "wi": jax.random.normal(k1, (E, d, f)) * (1.0 / jnp.sqrt(d)),
        "wo": jax.random.normal(k2, (E, f, d)) * (1.0 / jnp.sqrt(f)),
    }


def compute_capacity(n_tokens: int, n_experts: int, top_k: int,
                     capacity_factor: float) -> int:
    c = int(capacity_factor * top_k * n_tokens / n_experts)
    return max(c, 1)


def route_topk(gates: Array, top_k: int, capacity: int,
               stat_axes: Tuple[str, ...] = ()
               ) -> Tuple[Array, Array, Array]:
    """Top-k routing with per-expert capacity.

    gates: [N, E] router probabilities.  Returns (dispatch [N,E,C] {0,1},
    combine [N,E,C] gate-weighted, aux_loss scalar).

    ``stat_axes``: mesh axes the token batch is sharded over.  The Switch
    aux loss is NONLINEAR in the routing statistics (f_e · p_e), so a
    mean of per-shard aux values is not the global aux; pmean-ing f_e and
    p_e over the token shards first (equal shard sizes → global means)
    makes the sharded aux exactly equal the pooled-token computation.
    """
    N, E = gates.shape
    topv, topi = lax.top_k(gates, top_k)                # [N, k]
    topv = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)

    # Slot accounting is COUNTING, not math on probabilities: keep it in
    # int32.  In bf16 (the usual compute dtype) a cumsum cannot represent
    # counts above 256 exactly, silently colliding tokens into one slot.
    masks = jax.nn.one_hot(topi, E, dtype=jnp.int32)    # [N, k, E]
    # positions: choice-major cumulative count per expert (choice 0 of every
    # token outranks choice 1, GShard-style priority)
    flat = jnp.swapaxes(masks, 0, 1).reshape(top_k * N, E)
    pos_flat = jnp.cumsum(flat, axis=0) - flat          # 0-based slot
    pos = jnp.swapaxes(pos_flat.reshape(top_k, N, E), 0, 1)  # [N, k, E]

    dispatch = jnp.zeros((N, E, capacity), gates.dtype)
    combine = jnp.zeros((N, E, capacity), gates.dtype)
    for j in range(top_k):
        m = masks[:, j]                                  # [N, E] int
        slot = jnp.sum(pos[:, j] * m, axis=-1)           # [N] int32
        sel = (m * (slot < capacity)[:, None]).astype(gates.dtype)
        slot_oh = jax.nn.one_hot(slot, capacity, dtype=gates.dtype)
        d_j = sel[:, :, None] * slot_oh[:, None, :]      # [N, E, C]
        dispatch = dispatch + d_j
        combine = combine + d_j * topv[:, j][:, None, None]

    # Switch aux loss: E * sum_e (token fraction to e) * (mean prob of e);
    # accumulated in f32 (a bf16 sum over N tokens is equally lossy).
    f_e = jnp.sum(masks.sum(1), axis=0).astype(jnp.float32) / (N * top_k)
    p_e = jnp.mean(gates.astype(jnp.float32), axis=0)        # [E]
    for ax in stat_axes:
        f_e = lax.pmean(f_e, ax)
        p_e = lax.pmean(p_e, ax)
    aux = E * jnp.sum(f_e * p_e)
    return dispatch, combine, aux


def _expert_ffn(wi: Array, wo: Array, x: Array) -> Array:
    """Batched expert FFN: x [E_local, C', d] through per-expert weights."""
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, wi,
                               preferred_element_type=jnp.float32))
    return jnp.einsum("ecf,efd->ecd", h.astype(x.dtype), wo,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def moe_ffn(params: dict, x: Array, cfg: MoEConfig,
            axis_name: Optional[str] = None,
            stat_axes: Tuple[str, ...] = ()) -> Tuple[Array, Array]:
    """MoE FFN over tokens x [N, d] -> (y [N, d], aux_loss).

    When ``axis_name`` is given (running inside shard_map), x holds this
    shard's N local tokens and params hold the LOCAL experts
    ``[E/ep, ...]``; dispatch crosses shards via all_to_all.  The router
    table is replicated.  ``stat_axes`` reduces the aux-loss routing
    statistics across token shards first (see route_topk) so the sharded
    aux equals the pooled computation exactly.
    """
    N, d = x.shape
    E = cfg.n_experts
    gates = jax.nn.softmax(
        jnp.einsum("nd,de->ne", x, params["router"],
                   preferred_element_type=jnp.float32), axis=-1
    ).astype(x.dtype)
    C = compute_capacity(N, E, cfg.top_k, cfg.capacity_factor)
    dispatch, combine, aux = route_topk(gates, cfg.top_k, C, stat_axes)

    # [N,E,C] x [N,d] -> [E,C,d] expert inboxes
    inbox = jnp.einsum("nec,nd->ecd", dispatch, x)

    if axis_name is None:
        out = _expert_ffn(params["wi"], params["wo"], inbox)
    else:
        # [E, C, d] -> each shard holds every source shard's slots for its
        # local experts: [E/ep, ep*C, d] (slot axis blocked by source shard)
        inbox = lax.all_to_all(inbox, axis_name,
                               split_axis=0, concat_axis=1, tiled=True)
        out = _expert_ffn(params["wi"], params["wo"], inbox)
        # route results back to source shards: [E, C, d]
        out = lax.all_to_all(out, axis_name,
                             split_axis=1, concat_axis=0, tiled=True)

    y = jnp.einsum("nec,ecd->nd", combine, out)
    return y, aux.astype(jnp.float32)


def expert_param_specs(cfg: MoEConfig) -> dict:
    """PartitionSpecs: experts sharded over ``expert``, router replicated."""
    return {"router": P(), "wi": P(EXPERT_AXIS), "wo": P(EXPERT_AXIS)}


def make_moe_layer(mesh: Mesh, cfg: MoEConfig):
    """Build ``f(params, x) -> (y, aux)`` for token batch x [N, d], with
    experts sharded over the mesh ``expert`` axis and tokens over
    ``data`` x ``expert`` (falling back to replicated when those axes are
    absent/size-1)."""
    ep = mesh.shape.get(EXPERT_AXIS, 1)
    if cfg.n_experts % ep != 0:
        raise ValueError(f"n_experts={cfg.n_experts} not divisible by "
                         f"expert degree {ep}")
    if ep == 1:
        def apply(params, x):
            return moe_ffn(params, x, cfg, axis_name=None)
        return apply

    # Tokens shard over BOTH data and expert axes: with tokens only on
    # ``data``, every expert shard would route the identical token set and
    # do the full single-device FFN FLOPs — expert parallelism would save
    # weight memory but zero compute.  Splitting tokens across the expert
    # axis cuts per-device routing + FFN work by the expert degree; the
    # all_to_alls then move each sub-batch's slots to their expert owners.
    tok_axes = tuple(a for a in (DATA_AXIS, EXPERT_AXIS)
                     if mesh.shape.get(a, 1) > 1)
    tok_spec = P(tok_axes) if tok_axes else P()
    pspec = expert_param_specs(cfg)

    def inner(params, x):
        # aux forms from routing stats pmean-ed across the token shards
        # (route_topk docstring: the aux is nonlinear in them, so this —
        # not a pmean of per-shard aux values — matches the pooled-token
        # computation); the returned scalar is already identical on all
        # shards.
        y, aux = moe_ffn(params, x, cfg, axis_name=EXPERT_AXIS,
                         stat_axes=tok_axes)
        return y, aux

    return shard_map(inner, mesh=mesh, in_specs=(pspec, tok_spec),
                     out_specs=(tok_spec, P()), check_vma=False)


def make_gspmd_moe_ffn(mesh: Optional[Mesh], cfg: MoEConfig):
    """The per-layer MoE dispatch for the GSPMD fit spine: a callable
    ``(layer_params, tok) -> (y, aux)`` with ``layer_params =
    {"router", "wi" [E,H,F], "wo" [E,F,H]}`` and ``tok [N, H]``, legal
    to call from INSIDE a jitted global-view program (the sharded-fit
    scanned-epoch step calls it from the layer ``lax.scan`` body via
    ``models/moe.encode(..., ffn_fn=...)``).

    With an ``expert`` axis of size > 1 in ``mesh`` this is a nested
    ``shard_map``: tokens shard over (``data``, ``expert``), expert
    tables over ``expert``, and the two ``lax.all_to_all`` dispatch
    collectives from ``moe_ffn`` run on the ``expert`` axis exactly as
    in the standalone ``make_moe_layer`` path.  Without one it degrades
    to the single-shard dispatch math (GSPMD still shards the einsums
    over whatever the specs say).  The aux scalar comes back replicated
    and already globally pmean-ed over the token shards."""
    ep = 1 if mesh is None else int(mesh.shape.get(EXPERT_AXIS, 1))
    if ep == 1:
        def apply(params, x):
            return moe_ffn(params, x, cfg, axis_name=None)
        return apply
    if cfg.n_experts % ep != 0:
        raise ValueError(f"n_experts={cfg.n_experts} not divisible by "
                         f"expert degree {ep}")
    tok_axes = tuple(a for a in (DATA_AXIS, EXPERT_AXIS)
                     if mesh.shape.get(a, 1) > 1)
    tok_spec = P(tok_axes) if tok_axes else P()
    pspec = expert_param_specs(cfg)

    def inner(params, x):
        return moe_ffn(params, x, cfg, axis_name=EXPERT_AXIS,
                       stat_axes=tok_axes)

    return shard_map(inner, mesh=mesh, in_specs=(pspec, tok_spec),
                     out_specs=(tok_spec, P()), check_vma=False)


# ---------------------------------------------------------------------------
# One expert-parallel rank on the decode path: routing over every expert
# (group-limited, or plain top-k), no capacity, and the part of the result
# the experts HELD HERE give.  What the absent ranks' experts would add is left out (it
# would arrive by the all-to-all this rank is not part of); nothing here
# stands in for them.
# ---------------------------------------------------------------------------

def route_group_limited(scores: Array, n_group: int, topk_group: int,
                        top_k: int, scale: float
                        ) -> Tuple[Array, Array]:
    """DeepSeek-V2's device-limited routing (``group_limited_greedy``).

    ``scores`` [N, E] float32 router probabilities over ALL experts, E
    in ``n_group`` consecutive groups (one group a device).  A group's
    score is its largest; the ``topk_group`` best groups stay, and among
    their experts the ``top_k`` best are taken, each weighted
    ``scale * score`` (``norm_topk_prob`` false: no renormalising, no
    capacity, no token dropped).  Returns (weights [N, E] float32, zero
    off the chosen experts; chosen [N, E] bool)."""
    N, E = scores.shape
    g = scores.reshape(N, n_group, E // n_group)
    _, top_groups = lax.top_k(g.max(axis=-1), topk_group)       # [N, kg]
    keep = jnp.zeros((N, n_group), jnp.bool_).at[
        jnp.arange(N)[:, None], top_groups].set(True)
    kept = jnp.where(keep[:, :, None], g, 0.0).reshape(N, E)
    _, top_experts = lax.top_k(kept, top_k)                     # [N, k]
    chosen = jnp.zeros((N, E), jnp.bool_).at[
        jnp.arange(N)[:, None], top_experts].set(True)
    return jnp.where(chosen, scores * scale, 0.0), chosen


def route_topk_renorm(scores: Array, top_k: int) -> Tuple[Array, Array]:
    """Plain top-k routing with renormalisation (``norm_topk_prob``
    true): of ``scores`` [N, E] float32 router probabilities the
    ``top_k`` largest a token are taken, each weighted by its share of
    their sum; no groups, no capacity, no token dropped.  Returns what
    :func:`route_group_limited` does: (weights [N, E] float32, zero off
    the chosen experts and summing to 1 a token; chosen [N, E] bool)."""
    N, E = scores.shape
    top, top_experts = lax.top_k(scores, top_k)                 # [N, k]
    chosen = jnp.zeros((N, E), jnp.bool_).at[
        jnp.arange(N)[:, None], top_experts].set(True)
    return jnp.where(chosen, scores / top.sum(axis=-1, keepdims=True),
                     0.0), chosen


def route_sigmoid_bias(scores: Array, bias: Array, top_k: int, scale: float,
                       n_group: int = 1, topk_group: int = 1
                       ) -> Tuple[Array, Array]:
    """DeepSeek-V3's routing (``scoring_func`` sigmoid, ``norm_topk_prob``
    true): ``scores`` [N, E] float32 are the SIGMOIDS of the router's
    logits, each expert's own and no distribution over them.  With
    ``n_group`` groups of consecutive experts, a group's score is the
    sum of its two largest ``scores + bias`` and only the ``topk_group``
    best groups stay (their experts' ``scores + bias`` kept, the others'
    set to 0, as ``modeling_deepseek_v3.py`` does); one group keeps
    everything and takes no group step at all.  The ``top_k`` largest of
    ``scores + bias`` a token are taken (``bias`` [E], the selection
    bias that balances load and takes no part in the result), each
    weighted ``scale`` times its UNBIASED score's share of the taken
    ones' sum.  Returns what :func:`route_group_limited` does: (weights
    [N, E] float32, zero off the chosen experts and summing to ``scale``
    a token; chosen [N, E] bool)."""
    N, E = scores.shape
    choice = scores + bias[None, :]
    if n_group > 1:
        g = choice.reshape(N, n_group, E // n_group)
        _, top_groups = lax.top_k(lax.top_k(g, 2)[0].sum(axis=-1),
                                  topk_group)                   # [N, kg]
        keep = jnp.zeros((N, n_group), jnp.bool_).at[
            jnp.arange(N)[:, None], top_groups].set(True)
        choice = jnp.where(keep[:, :, None], g, 0.0).reshape(N, E)
    _, top_experts = lax.top_k(choice, top_k)                   # [N, k]
    chosen = jnp.zeros((N, E), jnp.bool_).at[
        jnp.arange(N)[:, None], top_experts].set(True)
    taken = jnp.where(chosen, scores, 0.0)
    return taken * (scale / (taken.sum(axis=-1, keepdims=True) + 1e-20)
                    ), chosen


def gated_ffn(x: Array, w_gate: Array, w_up: Array, w_down: Array,
              limit: float = 0.0) -> Array:
    """``W_down(silu(W_gate x) * W_up x)``: operands in ``x``'s type,
    products accumulated and returned in float32.  A ``limit`` ``L > 0``
    is the SwiGLU clamp (``expert_swiglu_limit_list``): ``silu(min(W_gate
    x, L)) * clip(W_up x, -L, L)``; 0 clamps nothing."""
    g = jnp.einsum("nh,hf->nf", x, w_gate,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("nh,hf->nf", x, w_up,
                   preferred_element_type=jnp.float32)
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return jnp.einsum("nf,fh->nh", (jax.nn.silu(g) * u).astype(x.dtype),
                      w_down, preferred_element_type=jnp.float32)


def held_experts_ffn(x: Array, weights: Array, chosen: Array,
                     experts: dict, limit: float = 0.0
                     ) -> Tuple[Array, Array]:
    """The routed part of an expert layer that the experts held here
    give.  ``x`` [N, H]; ``weights``/``chosen`` [N, E_held]: the held
    columns of :func:`route_group_limited`'s result (rows of tokens that
    do not count — an idle slot, a chunk's padding — all zero/False);
    ``experts`` ``{"w_gate": [E_held, H, F], "w_up": [E_held, H, F],
    "w_down": [E_held, F, H]}``; ``limit`` each expert's SwiGLU clamp
    (:func:`gated_ffn`).  Returns (y [N, H] float32, hits: how many held
    experts some token chose).

    Only the experts HIT are touched: they are put first and a loop of
    ``hits`` steps runs each over all N rows (a handful, on the decode
    path), the rows that did not choose it weighted 0.  An expert's
    47 MB are then read once a dispatch, and an expert nobody chose
    costs nothing, which is what makes a decode step's bytes follow the
    routing and not the layer's size."""
    hit = chosen.any(axis=0)                                   # [E_held]
    hits = hit.sum().astype(jnp.int32)
    order = jnp.argsort(~hit, stable=True).astype(jnp.int32)

    def one(i, acc):
        e = order[i]
        w = [lax.dynamic_index_in_dim(experts[k], e, keepdims=False)
             for k in ("w_gate", "w_up", "w_down")]
        mine = lax.dynamic_index_in_dim(weights, e, axis=1, keepdims=True)
        return acc + mine * gated_ffn(x, *w, limit=limit)

    y = lax.fori_loop(0, hits, one,
                      jnp.zeros(x.shape, jnp.float32))
    return y, hits
