"""Ling-3.0-flash (inclusionAI; ``model_type`` ``bailing_hybrid``) on the
serving spine, as ONE expert-parallel rank: Kimi Delta Attention (KDA,
arXiv 2510.26692) in five of every six layers, whose per-sequence state
is a fixed matrix held in a SLOT and not in pages, multi-head latent
attention (MLA) in the sixth over ``models/deepseek_v2.py``'s latent
rows, leading dense layers, and expert layers routed by grouped sigmoid
scores with a selection bias plus a shared expert.

The layers, ``x`` the residual stream in float32, pre-norm (every norm
RMSNorm in float32, no bias anywhere); the kept layer ``l`` is the
published layer ``i = cfg.layer_ids[l]``, MLA where ``(i + 1) %
layer_group_size == 0`` and KDA otherwise, dense where ``i <
first_k_dense_replace``:

* KDA, per head ``h`` of ``D = head_dim`` lanes (``d_k = d_v = D``):
  ``q, k, v = SiLU(ShortConv_K(x W_{q,k,v}))``, the convolution causal
  and depthwise over the last ``K = short_conv_kernel_size`` input rows;
  ``q``, ``k`` L2-normalised a head and ``q`` scaled by ``D^-1/2``; a
  per-channel log-decay ``g = kda_lower_bound * sigmoid(exp(A_log) *
  (x W_f + dt_bias))`` in ``[kda_lower_bound, 0]``; ``beta =
  sigmoid(x W_b)`` a head; the state ``S [D, D]`` float32, ``S_t = (I -
  beta k k^T) Diag(exp g) S_{t-1} + beta k v^T``; ``o = S_t^T q``; ``y =
  W_o (RMSNorm_head(o) * sigmoid(x W_g))``.
* MLA: ``q = x W_q`` [NH, nope + rope] (no low-rank step); the cached row
  ``[RMSNorm(x W_dkv[:R]) ; RoPE(x W_dkv[R:])]`` with the rotation on
  interleaved pairs at ``rope_theta``; scores ``(q_nope . k_nope + q_rope
  . k_rope) / sqrt(nope + rope)``, causal softmax in float32, folded
  against the cached rows as ``deepseek_v2.attention_folded`` does; head
  ``h``'s output times ``sigmoid(x W_ga)[h]`` before ``W_o``.
* the feed-forward: dense ``W_down(silu(W_gate x) * W_up x)``; or ``s =
  sigmoid(x W_r)`` over all ``num_experts``, the group step and the top
  ``num_experts_per_tok`` of ``s + b`` (:func:`parallel.expert
  .route_sigmoid_bias`), ``w_e = routed_scaling_factor * s_e / sum of the
  taken s``, and ``sum over taken e HELD HERE of w_e F_e(x) +
  F_shared(x)``, each ``F`` under its layer's SwiGLU limit
  (``parallel.expert.gated_ffn``).  What the other ranks' experts would
  add is left out.
* final RMSNorm, untied head over the rows held here.

Two forms of the KDA state update, the same mathematics:

* CHUNKED (a prefill dispatch, :func:`kda_chunked`): chunks of
  ``CHUNK`` rows in the WY form, ``u = (I + A)^-1 (beta v - beta
  (Gamma k) S_0)`` with ``A_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)``
  below the diagonal (``G`` the decay's running sum in the chunk: a
  difference is exponentiated only where ``i >= j``, masked before the
  ``exp``, on the chunk's diagonal blocks of ``SUB_CHUNK`` rows, and the
  blocks below them are matrix products of factors whose exponents are
  <= 0, so nothing overflows over a chunk), the state carried from chunk
  to chunk and from dispatch to dispatch.
* RECURRENT (a decode dispatch, :func:`kda_step`): the one-row update.

THE SLOT STATE.  What a KDA layer keeps of a sequence does not grow with
it: ``S`` [NH, D, D] float32 and the convolution's last ``K - 1`` input
rows.  The family declares it (:func:`slot_state`) and
``serving.decode.DecodeEngine`` holds a slab of ``n_slots`` of them
beside the page pool; the paged functions take the pool as ``(pages,
state)``.  A prefill dispatch is handed its slot's index and, at
``start`` 0 (a join's first), starts that slot's state from zero; a
decode dispatch leaves the state of a slot it does not run as it was.

Weights stay in the type they are given in (the checkpoint's bfloat16):
nothing here casts a weight.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.models.deepseek_v2 import (PagedLatent, _attn_out,
                                                   _mm, attention_expanded,
                                                   attention_folded, rms_norm)
from deeplearning4j_tpu.models.gpt import (_read_pages, _slot_key,
                                           _write_rows, sample_token)
from deeplearning4j_tpu.parallel.expert import (gated_ffn, held_experts_ffn,
                                                route_sigmoid_bias)

Array = jax.Array
PyTree = Any

KDA, MLA = "kda", "mla"

#: what ``paged_decode`` appends to its [S] tokens, summed over the expert
#: layers of the dispatch: assignments made for active slots (token x
#: layer x top_k), those that fell on an expert held here, distinct held
#: experts touched, and expert layers run
DECODE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                   "moe_expert_hits", "moe_layer_dispatches")

#: ``DecodeEngine`` options this family has no code for: a prefix hit
#: would need a snapshot of the KDA state at a page edge, a speculative
#: round the state after each draft row
UNSUPPORTED_ENGINE_OPTIONS = ("mesh", "kv_dtype", "quantize", "draft",
                              "prefix_cache")

#: weights arrive in the compute type: nothing to hold cast
COMPUTE_DTYPE_LEAVES = ()

_HI = lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class LingHybridConfig:
    """Published key names where the model's ``config.json`` has one."""
    vocab_size: int = 157184
    max_len: int = 262144                # max_position_embeddings
    hidden: int = 2560
    #: the published index of each layer this rank runs
    layer_ids: Tuple[int, ...] = tuple(range(42))
    layer_group_size: int = 6
    n_heads: int = 32                    # KDA and MLA alike
    head_dim: int = 128                  # a KDA head's d_k = d_v
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 6e6
    intermediate_size: int = 6144        # the dense layers' width
    moe_intermediate_size: int = 768     # one expert's width
    moe_shared_expert_intermediate_size: int = 768
    first_k_dense_replace: int = 2
    num_experts: int = 512               # the ROUTER's width, never cut
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    #: each kept layer's SwiGLU limit of its routed and its shared
    #: experts (0: no clamp); empty: none anywhere
    expert_swiglu_limits: Tuple[float, ...] = ()
    shared_swiglu_limits: Tuple[float, ...] = ()
    rms_norm_eps: float = 1e-6
    #: (first expert id, how many) of each layer's routed experts that
    #: live on this rank; the default holds them all
    held_experts: Tuple[int, int] = (0, 512)
    compute_dtype: str = "bfloat16"
    causal: ClassVar[bool] = True
    #: the module under ``models/`` whose paged functions serve this config
    family: ClassVar[str] = "ling_hybrid"

    def __post_init__(self):
        first, count = self.held_experts
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f"held_experts {self.held_experts} is not a "
                             f"range of the {self.num_experts} experts")
        if self.num_experts % self.n_group:
            raise ValueError("num_experts must divide into n_group")
        for limits in (self.expert_swiglu_limits, self.shared_swiglu_limits):
            if limits and len(limits) != self.n_layers:
                raise ValueError(f"{len(limits)} SwiGLU limits for "
                                 f"{self.n_layers} layers")

    @property
    def n_layers(self) -> int:
        return len(self.layer_ids)

    def kind_of(self, layer: int) -> str:
        i = self.layer_ids[layer]
        return MLA if (i + 1) % self.layer_group_size == 0 else KDA

    def dense(self, layer: int) -> bool:
        return self.layer_ids[layer] < self.first_k_dense_replace

    def layers_of(self, kind: str) -> int:
        return sum(self.kind_of(l) == kind for l in range(self.n_layers))

    def limits(self, layer: int) -> Tuple[float, float]:
        """(routed, shared) SwiGLU limit of a kept layer."""
        return tuple(float(ls[layer]) if ls else 0.0 for ls in (
            self.expert_swiglu_limits, self.shared_swiglu_limits))

    @property
    def kda_width(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def cache_width(self) -> int:
        """Values an MLA layer caches a token: the latent and the shared
        rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def row_width(self) -> int:
        """Lanes a cached row takes in the pool: ``cache_width`` rounded up
        to a whole number of 128-lane tiles, the rest zeros.  A page of
        576-lane rows is laid out by XLA with its 128 rows minor, so every
        dispatch that writes and gathers it copied the whole pool into
        that layout and back (2 x 0.6 GB a step at 16k); 640 lanes keep
        the pool's own layout."""
        return -(-self.cache_width // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5


def tiny_config(**over) -> LingHybridConfig:
    """Small widths with every mechanism present (CPU tests): a period of
    three, published layers 0 (dense, KDA), 2 (MLA), 3 and 4 (KDA), 16
    experts in 4 groups, the best 2 groups, the best 3 experts."""
    base = dict(vocab_size=96, max_len=128, hidden=32, layer_ids=(0, 2, 3, 4),
                layer_group_size=3, n_heads=4, head_dim=8, kv_lora_rank=16,
                qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
                rope_theta=10000.0, intermediate_size=48,
                moe_intermediate_size=12,
                moe_shared_expert_intermediate_size=12,
                first_k_dense_replace=1, num_experts=16,
                num_experts_per_tok=3, n_group=4, topk_group=2,
                held_experts=(0, 16))
    base.update(over)
    return LingHybridConfig(**base)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: LingHybridConfig) -> PyTree:
    """The tree's shapes: ``layers`` is a list, one dict a layer (a KDA
    and an MLA layer, a dense and an expert layer, differ in shape)."""
    H, NH, D = cfg.hidden, cfg.n_heads, cfg.head_dim
    F, E = cfg.moe_intermediate_size, cfg.held_experts[1]
    HD = cfg.kda_width

    def ffn(width):
        return {"w_gate": (H, width), "w_up": (H, width),
                "w_down": (width, H)}

    layers = []
    for l in range(cfg.n_layers):
        if cfg.kind_of(l) == KDA:
            attn = {"w_q": (H, HD), "w_k": (H, HD), "w_v": (H, HD),
                    "conv": (cfg.short_conv_kernel_size, 3 * HD),
                    "w_f": (H, HD), "A_log": (NH,), "dt_bias": (HD,),
                    "w_b": (H, NH), "w_g": (H, HD), "o_norm": (D,),
                    "w_o": (HD, H)}
        else:
            attn = {"w_q": (H, NH, cfg.qk_nope_head_dim
                            + cfg.qk_rope_head_dim),
                    "w_dkv": (H, cfg.cache_width),
                    "kv_norm": (cfg.kv_lora_rank,),
                    "w_ukv": (cfg.kv_lora_rank, NH,
                              cfg.qk_nope_head_dim + cfg.v_head_dim),
                    "w_ga": (H, NH), "w_o": (NH, cfg.v_head_dim, H)}
        layer = {"attn_norm": (H,), "ffn_norm": (H,), "attn": attn}
        if cfg.dense(l):
            layer["mlp"] = ffn(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "router": (H, cfg.num_experts), "bias": (cfg.num_experts,),
                "experts": {"w_gate": (E, H, F), "w_up": (E, H, F),
                            "w_down": (E, F, H)},
                "shared": ffn(cfg.moe_shared_expert_intermediate_size)}
        layers.append(layer)
    return {"embed": (cfg.vocab_size, H), "layers": layers,
            "final_norm": (H,), "head": (H, cfg.vocab_size)}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def init_params(key: Array, cfg: LingHybridConfig, std: float = 0.02,
                dtype: Any = None) -> PyTree:
    """Every matrix, the router's selection bias, ``A_log`` and
    ``dt_bias`` N(0, ``std``), norm gains 1 + N(0, ``std``), drawn in
    float32 and rounded to ``dtype`` (the compute type unless given)."""
    dtype = jnp.dtype(dtype or cfg.compute_dtype)
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(cfg), is_leaf=_is_shape)
    keys = jax.random.split(key, len(paths))
    leaves = []
    for k, (path, shape) in zip(keys, paths):
        w = std * jax.random.normal(k, shape, jnp.float32)
        gain = str(path[-1].key).endswith("norm")
        leaves.append((1.0 + w if gain else w).astype(dtype))
    return jax.tree.unflatten(treedef, leaves)


def hold_experts(cfg: LingHybridConfig, params: PyTree, first: int,
                 count: int) -> Tuple[LingHybridConfig, PyTree]:
    """The share of a rank that holds experts ``first .. first + count -
    1``: the config that says so and the tree with the other experts'
    weights left out (``params`` must hold them all)."""
    if cfg.held_experts != (0, cfg.num_experts):
        raise ValueError("hold_experts cuts a tree that holds every expert")

    def cut(layer):
        if "moe" not in layer:
            return layer
        experts = {k: v[first:first + count]
                   for k, v in layer["moe"]["experts"].items()}
        return {**layer, "moe": {**layer["moe"], "experts": experts}}

    return (dataclasses.replace(cfg, held_experts=(first, count)),
            {**params, "layers": [cut(l) for l in params["layers"]]})


# ---------------------------------------------------------------------------
# KDA: the two forms of the state update, and the layer around them
# ---------------------------------------------------------------------------

def _f32(spec: str, a: Array, b: Array) -> Array:
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


def kda_step(q: Array, k: Array, v: Array, g: Array, beta: Array,
             S: Array) -> Tuple[Array, Array]:
    """One row of every sequence: ``q``, ``k``, ``v``, ``g`` [B, NH, D]
    float32, ``beta`` [B, NH], ``S`` [B, NH, D, D].  Returns (o [B, NH, D],
    S').  ``o = S'^T q = S^T (e q) + (k . q) delta`` with ``e = exp(g)``,
    so the two products with the old state share one read of it and the
    update a second: products elementwise with float32 sums."""
    eg = jnp.exp(g)
    kv_mem = jnp.sum(S * (eg * k)[..., None], axis=-2)
    q_mem = jnp.sum(S * (eg * q)[..., None], axis=-2)
    delta = beta[..., None] * (v - kv_mem)
    o = q_mem + jnp.sum(k * q, axis=-1, keepdims=True) * delta
    return o, eg[..., None] * S + k[..., None] * delta[..., None, :]


#: rows of a chunk of the prefill's chunked form (a dispatch's rows
#: whole: ``gcd(rows, CHUNK)``)
CHUNK = 64

#: rows of a sub-chunk of the chunked form: its diagonal blocks take the
#: masked exponent of every (i, j, lane), the blocks below them a product
SUB_CHUNK = 16


def _decayed_products(a: Array, k: Array, G: Array, strict: bool) -> Array:
    """``P_ij = sum_d a_id k_jd exp(G_id - G_jd)`` for ``j < i`` (and ``j =
    i`` unless ``strict``), 0 above: a, k, G [..., C, D] -> [..., C, C].
    A block of ``SUB_CHUNK`` rows on the diagonal exponentiates each
    difference where ``i >= j`` only, masked before the ``exp``; a block
    below it is a matrix product of ``a_i exp(G_i - G_r)`` and ``k_j
    exp(G_r - G_j)``, ``r`` the row before the block's first: both
    exponents <= 0, nothing overflows over a chunk."""
    C = a.shape[-2]
    c = math.gcd(C, SUB_CHUNK)
    at = jnp.arange(c)
    below = (at[:, None] > at[None, :]) if strict else (
        at[:, None] >= at[None, :])
    rows = []
    for I in range(C // c):
        lo, hi = I * c, (I + 1) * c
        aI, kI, GI = a[..., lo:hi, :], k[..., lo:hi, :], G[..., lo:hi, :]
        diff = jnp.where(below[..., None],
                         GI[..., :, None, :] - GI[..., None, :, :], -jnp.inf)
        diag = jnp.einsum("...id,...jd,...ijd->...ij", aI, kI, jnp.exp(diff),
                          precision=_HI)
        parts = [diag, jnp.zeros(diag.shape[:-1] + (C - hi,), diag.dtype)]
        if lo:
            ref = G[..., lo - 1:lo, :]
            parts.insert(0, _f32("...id,...jd->...ij",
                                 aI * jnp.exp(GI - ref),
                                 k[..., :lo, :] * jnp.exp(ref - G[..., :lo, :])))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def kda_chunked(q: Array, k: Array, v: Array, g: Array, beta: Array,
                S: Array, chunk: int) -> Tuple[Array, Array]:
    """Rows [B, T, NH, D] float32 (``T`` a whole number of ``chunk``s),
    ``beta`` [B, T, NH], the state ``S`` [B, NH, D, D] before the first
    row.  Returns (o [B, T, NH, D], S after the last row).  A row with
    ``beta = 0`` and ``g = 0`` (padding) leaves the state as it was."""
    B, T, NH, D = q.shape
    n, C = T // chunk, chunk

    def blocks(x):                       # [B, T, NH, ...] -> [n, B, NH, C, ...]
        x = x.reshape((B, n, C, NH) + x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g = (blocks(x) for x in (q, k, v, g))
    beta = blocks(beta)[..., None]                          # [n, B, NH, C, 1]
    G = jnp.cumsum(g, axis=-2)                              # running decay
    A = beta * _decayed_products(k, k, G, strict=True)
    Aqk = _decayed_products(q, k, G, strict=False)
    # (I + A)^-1 of a strictly lower A: (I + N)(I + N^2)(I + N^4)... for
    # N = -A, nilpotent of order C
    N = -A
    Tinv = jnp.eye(C, dtype=jnp.float32) + N
    for _ in range(max(0, math.ceil(math.log2(C)) - 1)):
        N = _f32("...ij,...jk->...ik", N, N)
        Tinv = Tinv + _f32("...ij,...jk->...ik", Tinv, N)
    gamma = jnp.exp(G)                                      # decay to row i
    w = _f32("...ij,...jd->...id", Tinv, beta * gamma * k)  # [.., C, D]
    u0 = _f32("...ij,...jd->...id", Tinv, beta * v)
    q_s = gamma * q
    last = G[..., -1:, :]                                   # [.., 1, D]
    k_end = jnp.exp(last - G) * k                           # decay to the end

    def one(S, blk):
        w, u0, q_s, Aqk, k_end, last = blk
        u = u0 - _f32("bhid,bhde->bhie", w, S)
        o = _f32("bhid,bhde->bhie", q_s, S) + _f32("bhij,bhje->bhie", Aqk, u)
        S = (jnp.exp(last[..., 0, :])[..., None] * S
             + _f32("bhid,bhie->bhde", k_end, u))
        return S, o

    S, o = lax.scan(one, S, (w, u0, q_s, Aqk, k_end, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)           # [B, n, C, NH, D]
    return o.reshape(B, T, NH, D), S


def _l2norm(x: Array, eps: float = 1e-6) -> Array:
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _kda(cfg: LingHybridConfig, a: dict, h: Array, S: Array, conv: Array,
         ok: Array) -> Tuple[Array, Array, Array]:
    """One KDA layer on the normed rows ``h`` [B, W, H] (compute type) of
    B sequences whose rows ``ok`` [B, W] (a prefix of each) count, from
    their states ``S`` [B, NH, D, D] and ``conv`` [B, K - 1, 3 NH D].
    Returns (y [B, W, H] float32, S', conv'): a sequence with no row
    that counts keeps its state."""
    cdt = h.dtype
    B, W, _ = h.shape
    NH, D, K = cfg.n_heads, cfg.head_dim, cfg.short_conv_kernel_size
    keep = ok[..., None]
    with jax.named_scope("kda_attention"):
        with jax.named_scope("short_conv"):
            fresh = jnp.concatenate([_mm(h, a[n]) for n in ("w_q", "w_k",
                                                            "w_v")], axis=-1)
            fresh = jnp.where(keep, fresh, 0.0).astype(conv.dtype)
            rows = jnp.concatenate([conv, fresh], axis=1)   # [B, K-1+W, F]
            wc = a["conv"].astype(jnp.float32)
            mixed = sum(rows[:, j:j + W].astype(jnp.float32) * wc[j]
                        for j in range(K))
            qkv = jax.nn.silu(mixed).reshape(B, W, 3, NH, D)
            # the last K - 1 input rows behind each sequence's last row
            n_ok = ok.sum(axis=1).astype(jnp.int32)
            conv = jax.vmap(lambda r, n: lax.dynamic_slice_in_dim(
                r, n, K - 1, axis=0))(rows, n_ok)
        q = _l2norm(qkv[:, :, 0]) * D ** -0.5
        k = jnp.where(keep[..., None], _l2norm(qkv[:, :, 1]), 0.0)
        v = qkv[:, :, 2]
        f = _mm(h, a["w_f"]).reshape(B, W, NH, D)
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(a["A_log"].astype(jnp.float32))[:, None]
            * (f + a["dt_bias"].astype(jnp.float32).reshape(NH, D)))
        g = jnp.where(keep[..., None], g, 0.0)
        beta = jnp.where(keep, jax.nn.sigmoid(_mm(h, a["w_b"])), 0.0)
        with jax.named_scope("state_update"):
            if W == 1:
                o, S1 = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                 beta[:, 0], S)
                S = jnp.where(ok[:, 0, None, None, None], S1, S)
                o = o[:, None]
            else:
                o, S = kda_chunked(q, k, v, g, beta, S,
                                   math.gcd(W, CHUNK))
        o = rms_norm(o, a["o_norm"], cfg.rms_norm_eps) * jax.nn.sigmoid(
            _mm(h, a["w_g"])).reshape(B, W, NH, D)
        y = _mm(o.reshape(B, W, NH * D).astype(cdt), a["w_o"])
    return y, S, conv


# ---------------------------------------------------------------------------
# MLA: the query side and the cached row, then the folded product
# ---------------------------------------------------------------------------

def rope_tables(cfg: LingHybridConfig, n: int) -> Tuple[np.ndarray,
                                                        np.ndarray]:
    """cos/sin [n, rope / 2] float32 for positions 0..n-1: pair ``j`` of
    lanes ``(2j, 2j + 1)`` turns at ``rope_theta^(-2j / rope)``."""
    d = cfg.qk_rope_head_dim
    inv = 1.0 / cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(n, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """Interleaved rotation over the last axis (float32 in and out);
    ``cos``/``sin`` [..., rope / 2] broadcast against ``x``'s pairs."""
    p = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = p[..., 0], p[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _mla_queries_and_row(cfg: LingHybridConfig, a: dict, h: Array,
                         cos: Array, sin: Array, pad: int = 0):
    """From normed rows ``h`` [..., H] at positions whose tables are
    ``cos``/``sin`` [..., rope / 2]: (q_nope [..., NH, nope], q_rope [...,
    NH, rope + pad], the row to cache [..., cache_width + pad]), in the
    compute type; ``pad`` zero lanes behind the rotated ones, where the
    row is cached ``row_width`` wide (a zero lane adds nothing to a
    score)."""
    cdt = h.dtype
    R, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    q = jnp.einsum("...h,hnd->...nd", h, a["w_q"],
                   preferred_element_type=jnp.float32)
    q_rope = apply_rope(q[..., dn:], cos[..., None, :], sin[..., None, :])
    ckv = _mm(h, a["w_dkv"])
    row = jnp.concatenate([rms_norm(ckv[..., :R], a["kv_norm"],
                                    cfg.rms_norm_eps),
                           apply_rope(ckv[..., R:], cos, sin)], axis=-1)
    widen = lambda t: jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, pad)])  # noqa: E731
    return (q[..., :dn].astype(cdt), widen(q_rope).astype(cdt),
            widen(row).astype(cdt))


def _mla_out(a: dict, h: Array, o: Array) -> Array:
    """Each head's output [B, W, NH, v] gated by ``sigmoid(h W_ga)``, then
    ``W_o``: [B, W, H] float32."""
    gate = jax.nn.sigmoid(_mm(h, a["w_ga"]))[..., None]
    return _attn_out(a, o * gate, h.dtype)


# ---------------------------------------------------------------------------
# The feed-forward half of a layer
# ---------------------------------------------------------------------------

def moe_routed(cfg: LingHybridConfig, p: dict, x: Array, limit: float,
               counted: Optional[Array] = None) -> Tuple[Array, Array]:
    """The routed experts' part alone, ``x`` [N, H] in the compute type:
    (sum over the taken experts held here [N, H] float32, counts
    [len(DECODE_COUNTERS)] int32).  Rows where ``counted`` [N] is False
    are routed nowhere and counted nowhere."""
    first, n_held = cfg.held_experts
    with jax.named_scope("moe_route"):
        # operands as stored (a product of two bfloat16 values is exact
        # in float32); logits, sigmoid, groups and top-k in float32
        scores = jax.nn.sigmoid(
            jnp.einsum("nh,he->ne", x, p["router"], precision=_HI,
                       preferred_element_type=jnp.float32))
        weights, chosen = route_sigmoid_bias(
            scores, p["bias"].astype(jnp.float32), cfg.num_experts_per_tok,
            cfg.routed_scaling_factor, cfg.n_group, cfg.topk_group)
        if counted is not None:
            chosen = chosen & counted[:, None]
            weights = jnp.where(chosen, weights, 0.0)
        held = chosen[:, first:first + n_held]
        w_held = weights[:, first:first + n_held]
    with jax.named_scope("moe_experts"):
        y, hits = held_experts_ffn(x, w_held, held, p["experts"], limit)
    counts = jnp.stack([chosen.sum(), held.sum(), hits,
                        jnp.int32(1)]).astype(jnp.int32)
    return y, counts


def _ffn(cfg: LingHybridConfig, l: int, layer: dict, x: Array,
         counted: Optional[Array]) -> Tuple[Array, Array]:
    """Feed-forward half of kept layer ``l`` on [N, H] compute-type rows:
    (float32 result, counts)."""
    if "mlp" in layer:
        m = layer["mlp"]
        return (gated_ffn(x, m["w_gate"], m["w_up"], m["w_down"]),
                jnp.zeros((len(DECODE_COUNTERS),), jnp.int32))
    routed_limit, shared_limit = cfg.limits(l)
    routed, counts = moe_routed(cfg, layer["moe"], x, routed_limit, counted)
    with jax.named_scope("shared_expert"):
        s = layer["moe"]["shared"]
        shared = gated_ffn(x, s["w_gate"], s["w_up"], s["w_down"],
                           shared_limit)
    return routed + shared, counts


# ---------------------------------------------------------------------------
# Full forward, no cache
# ---------------------------------------------------------------------------

def forward_logits(cfg: LingHybridConfig, params: PyTree, token_ids: Array,
                   folded: bool = False) -> Array:
    """Logits [B, T, V] float32 of whole rows ``token_ids`` [B, T], no
    cache: KDA in its chunked form from a zero state (T a whole number of
    ``CHUNK`` rows or of their divisor), MLA expanded or ``folded``."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, T = token_ids.shape
    cos, sin = (jnp.asarray(t) for t in rope_tables(cfg, T))
    valid = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), jnp.bool_)),
                             (B, T, T))
    ok = jnp.ones((B, T), jnp.bool_)
    S0, conv0 = _zero_state(cfg, B)
    x = params["embed"][token_ids].astype(jnp.float32)
    for l, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps).astype(cdt)
        a = layer["attn"]
        if cfg.kind_of(l) == KDA:
            y, _, _ = _kda(cfg, a, h, S0, conv0, ok)
        else:
            with jax.named_scope("mla_attention"):
                q_nope, q_rope, rows = _mla_queries_and_row(cfg, a, h, cos,
                                                            sin)
                attend = attention_folded if folded else attention_expanded
                y = _mla_out(a, h, attend(cfg, a, q_nope, q_rope, rows,
                                          valid))
        x = x + y
        h = rms_norm(x, layer["ffn_norm"], cfg.rms_norm_eps).astype(cdt)
        f, _ = _ffn(cfg, l, layer, h.reshape(B * T, -1), None)
        x = x + f.reshape(B, T, -1)
    return _readout(cfg, params, x)


def _zero_state(cfg: LingHybridConfig, B: int) -> Tuple[Array, Array]:
    NH, D = cfg.n_heads, cfg.head_dim
    return (jnp.zeros((B, NH, D, D), jnp.float32),
            jnp.zeros((B, cfg.short_conv_kernel_size - 1, 3 * NH * D),
                      jnp.dtype(cfg.compute_dtype)))


def _readout(cfg: LingHybridConfig, params: PyTree, x: Array) -> Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(cdt)
    return _mm(h, params["head"])


# ---------------------------------------------------------------------------
# The pool: latent pages for the MLA layers, a slot state for the KDA ones
# ---------------------------------------------------------------------------

def slot_state(cfg: LingHybridConfig) -> Dict[str, List[jax.ShapeDtypeStruct]]:
    """What ONE slot keeps of its sequence in the KDA layers, a leaf a
    layer: ``S`` [NH, D, D] float32 and ``conv``, the convolution's last
    ``K - 1`` input rows [K - 1, 3 NH D] in the compute type
    (``DecodeEngine`` holds ``n_slots`` of each)."""
    S0, conv0 = _zero_state(cfg, 1)
    n = cfg.layers_of(KDA)
    return {"S": [jax.ShapeDtypeStruct(S0.shape[1:], S0.dtype)] * n,
            "conv": [jax.ShapeDtypeStruct(conv0.shape[1:], conv0.dtype)] * n}


def state_bytes_per_slot(cfg: LingHybridConfig) -> int:
    return sum(math.prod(s.shape) * s.dtype.itemsize
               for s in jax.tree.leaves(slot_state(cfg)))


def init_pages(cfg: LingHybridConfig, n_pages: int, page_tokens: int,
               kv_dtype: Optional[str] = None) -> PagedLatent:
    """Pages of latent rows [L_mla, P, C, row_width] (the slot state is
    the engine's: :func:`slot_state`)."""
    _no_kv_dtype(kv_dtype)
    return PagedLatent(jnp.zeros(
        (cfg.layers_of(MLA), n_pages, page_tokens, cfg.row_width),
        jnp.dtype(cfg.compute_dtype)))


def pages_bytes(cfg: LingHybridConfig, n_pages: int, page_tokens: int,
                kv_dtype: Optional[str] = None) -> int:
    _no_kv_dtype(kv_dtype)
    return (cfg.layers_of(MLA) * n_pages * page_tokens * cfg.row_width
            * jnp.dtype(cfg.compute_dtype).itemsize)


def slots_bytes_per_slot(cfg: LingHybridConfig, t_max: int,
                         kv_dtype: Optional[str] = None) -> int:
    """What one sequence of ``t_max`` positions holds: its latent rows and
    its slot state."""
    return pages_bytes(cfg, 1, t_max, kv_dtype) + state_bytes_per_slot(cfg)


def _no_kv_dtype(kv_dtype: Optional[str]) -> None:
    if kv_dtype is not None:
        raise ValueError(f"ling_hybrid has no {kv_dtype!r} latent pool")


def _paged_stack(cfg: LingHybridConfig, params: PyTree, pages: PagedLatent,
                 S: List[Array], conv: List[Array], ptab: Array,
                 toks_w: Array, posw: Array, row_ok: Array):
    """The layer stack, W rows a sequence: row w of sequence s feeds
    ``toks_w[s, w]`` at position ``posw[s, w]`` (decode: S slots, W = 1; a
    prefill dispatch: S = 1, W rows).  An MLA layer writes the fresh rows
    at (layer, page, offset) and reads the sequence's pages back through
    ``ptab`` [S, TBL], as ``deepseek_v2._paged_stack``; a KDA layer moves
    the sequences' states ``S``/``conv`` (a list a KDA layer, [S, ...]).
    Rows where ``row_ok`` [S, W] is False go to the trash page, fold
    nothing into a state, are routed to no expert and counted nowhere.
    Returns (pages', S', conv', hidden [S, W, H] float32, counts)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    Sq, TBL = ptab.shape
    W = toks_w.shape[1]
    C = pages.rows.shape[2]
    T = TBL * C
    cos_t, sin_t = (jnp.asarray(t) for t in rope_tables(cfg, T))
    pw = jnp.clip(posw, 0, T - 1)
    cos, sin = cos_t[pw], sin_t[pw]
    ok = (posw >= 0) & (posw < T) & row_ok
    pids = jnp.where(ok, jnp.take_along_axis(ptab, pw // C, axis=1), 0)
    lp0 = jnp.stack([jnp.zeros_like(ptab), ptab], axis=-1)
    lpo0 = jnp.stack([jnp.zeros_like(pids), pids, pw % C], axis=-1)
    valid = jnp.arange(T)[None, None, :] <= posw[:, :, None]
    counted = ok.reshape(Sq * W)
    counts = jnp.zeros((len(DECODE_COUNTERS),), jnp.int32)
    S, conv = list(S), list(conv)
    rows_a = pages.rows
    x = params["embed"][toks_w].astype(jnp.float32)
    seen = {KDA: 0, MLA: 0}
    for l, layer in enumerate(params["layers"]):
        kind = cfg.kind_of(l)
        at, seen[kind] = seen[kind], seen[kind] + 1
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps).astype(cdt)
        a = layer["attn"]
        if kind == KDA:
            y, S[at], conv[at] = _kda(cfg, a, h, S[at], conv[at], ok)
        else:
            with jax.named_scope("mla_attention"):
                q_nope, q_rope, fresh = _mla_queries_and_row(
                    cfg, a, h, cos, sin, cfg.row_width - cfg.cache_width)
                with jax.named_scope("row_write"):
                    rows_a = _write_rows(
                        rows_a, lpo0 + jnp.array([at, 0, 0], jnp.int32),
                        fresh)
                with jax.named_scope("page_read"):
                    read = _read_pages(
                        rows_a, lp0 + jnp.array([at, 0], jnp.int32)
                    ).reshape(Sq, T, cfg.row_width)
                y = _mla_out(a, h, attention_folded(cfg, a, q_nope, q_rope,
                                                    read, valid))
        x = x + y
        h = rms_norm(x, layer["ffn_norm"], cfg.rms_norm_eps).astype(cdt)
        f, c = _ffn(cfg, l, layer, h.reshape(Sq * W, -1), counted)
        x = x + f.reshape(Sq, W, -1)
        counts = counts + c
    return PagedLatent(rows_a), S, conv, x, counts


def paged_prefill(cfg: LingHybridConfig, params: PyTree, pool, ptab_s: Array,
                  toks: Array, start: Array, n_valid: Array,
                  temperature: Array, seed: Array, slot: Array):
    """One prefill dispatch's rows ``toks`` [W] of the sequence in slot
    ``slot``, whose page table is ``ptab_s`` [TBL], at page-aligned
    ``start``; ``pool`` is ``(pages, state)``.  The slot's KDA state is
    read, carried over the dispatch's rows (those past ``n_valid`` fold
    nothing) and written back; at ``start`` 0 it starts from ZERO, so
    nothing of the slot's previous sequence leaks into a join.  Returns
    ((pages', state'), the token sampled after row ``n_valid - 1``)."""
    pages, state = pool
    W = toks.shape[0]
    at = jnp.arange(W, dtype=jnp.int32)
    fresh = start == 0

    def mine(a):
        a = lax.dynamic_slice_in_dim(a, slot, 1, axis=0)
        return jnp.where(fresh, jnp.zeros_like(a), a)

    pages, S, conv, x, _ = _paged_stack(
        cfg, params, pages, [mine(a) for a in state["S"]],
        [mine(a) for a in state["conv"]], ptab_s[None, :], toks[None, :],
        (start + at)[None, :], (at < n_valid)[None, :])

    def put(slab, a):
        return lax.dynamic_update_slice_in_dim(slab, a, slot, axis=0)

    state = {"S": [put(*p) for p in zip(state["S"], S)],
             "conv": [put(*p) for p in zip(state["conv"], conv)]}
    with jax.named_scope("readout"):
        last = lax.dynamic_slice_in_dim(x[0], n_valid - 1, 1, axis=0)
        logits = _readout(cfg, params, last)[0]
        first = sample_token(logits, _slot_key(seed, start + n_valid - 1),
                             temperature)
    return (pages, state), first


def paged_decode(cfg: LingHybridConfig, params: PyTree, pool, ptab: Array,
                 tokens: Array, pos: Array, active: Array,
                 temperature: Array, seeds: Array):
    """One token for every active slot; an inactive slot's KDA state is
    left as it was.  Returns ((pages', state'), int32 [S +
    len(DECODE_COUNTERS)]: the slots' next tokens, then the dispatch's
    routing counts)."""
    pages, state = pool
    pages, S, conv, x, counts = _paged_stack(
        cfg, params, pages, state["S"], state["conv"], ptab,
        tokens[:, None], pos[:, None], active[:, None])
    with jax.named_scope("readout"):
        logits = _readout(cfg, params, x[:, 0, :])
        keys = jax.vmap(_slot_key)(seeds, pos)
        nxt = jax.vmap(sample_token)(logits, keys, temperature)
    return (pages, {"S": S, "conv": conv}), jnp.concatenate(
        [jnp.where(active, nxt, tokens), counts])
