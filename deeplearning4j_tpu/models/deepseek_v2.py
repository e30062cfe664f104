"""DeepSeek-V2 (arXiv 2405.04434) on the serving spine: multi-head
latent attention over a paged pool of latent rows, and an expert layer
that is ONE expert-parallel rank — it routes over every expert and
computes the part its own experts give.

The layers, ``h`` the residual stream (all norms RMSNorm, pre-norm, no
bias anywhere):

* MLA: ``cQ = RMSNorm(W_DQ x)``; ``[q_nope | q_rope] = W_UQ cQ`` a head;
  ``[cKV | k_r] = W_DKV x``; ``cKV = RMSNorm(cKV)``; ``k_rope =
  RoPE(k_r)`` (one for all heads), ``q_rope = RoPE(q_rope)``;
  ``[k_nope | v] = W_UKV cKV`` a head; ``score = (q_nope . k_nope +
  q_rope . k_rope) * softmax_scale``; causal softmax in float32; ``out =
  W_O concat(softmax . v)``.  RoPE is YaRN (:func:`rope_tables`).  What
  is cached of a token is ``(cKV, k_rope)``: ``kv_lora_rank +
  qk_rope_head_dim`` values a layer, the same for every head.
* layer 0 feed-forward: ``W_down(silu(W_gate x) * W_up x)``.
* later layers: ``s = softmax(W_r x)`` over all experts; group-limited
  top-k (:func:`parallel.expert.route_group_limited`); ``y = sum_i w_i
  E_i(x) + Shared(x)``, the sum over the chosen experts HELD HERE
  (``cfg.held_experts``: first id and count).
* final RMSNorm, untied head.

Two forms of the attention product, the same mathematics:

* EXPANDED (:func:`forward_logits`, no cache): ``k_nope`` and ``v`` are
  made from ``cKV`` for every position, heads attend as usual.
* FOLDED (:func:`paged_prefill`, :func:`paged_decode`): ``W_UK`` goes
  into the query (``q_lat = q_nope W_UK^T``, 512 wide) and ``W_UV``
  after the weighted sum, so scores and values are taken against the
  cached rows themselves, [T, 576] read once for all 128 heads.  A
  prefill chunk's C x 128 query rows share that read as a decode
  step's 128 do, so both paged paths fold.

Weights stay in the type they are given in (the checkpoint's bfloat16):
nothing here casts a weight, so a dispatch holds no converted copy.

The paged functions have the signatures ``serving.decode.DecodeEngine``
calls by family (``models/gpt.py`` is the other implementer);
``paged_decode`` returns its tokens with :data:`DECODE_COUNTERS`
appended, so the expert layers' routing counts ride in the one fetch a
step makes anyway.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.models.gpt import (_every_layer, _read_pages,
                                           _slot_key, _write_rows,
                                           sample_token)
from deeplearning4j_tpu.parallel.expert import (gated_ffn, held_experts_ffn,
                                                route_group_limited)

Array = jax.Array
PyTree = Any

#: what ``paged_decode`` appends to its [S] tokens, in this order, summed
#: over the expert layers of the dispatch: assignments made for active
#: slots (token x layer x top_k), those that fell on an expert held
#: here, distinct held experts touched, and expert layers run
DECODE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                   "moe_expert_hits", "moe_layer_dispatches")

#: ``DecodeEngine`` options this family has no code for yet; the engine
#: raises at construction rather than fall through to another family's
UNSUPPORTED_ENGINE_OPTIONS = ("mesh", "kv_dtype", "quantize", "draft",
                              "prefix_cache")


@dataclasses.dataclass(frozen=True)
class DeepSeekV2Config:
    """Published key names where the model's ``config.json`` has one."""
    vocab_size: int = 102400
    max_len: int = 163840                # max_position_embeddings
    hidden: int = 5120
    n_layers: int = 60
    n_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 12288       # the dense layers' width
    moe_intermediate_size: int = 1536    # one expert's width
    first_k_dense_replace: int = 1
    n_routed_experts: int = 160          # the ROUTER's width, never cut
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    compute_dtype: str = "bfloat16"
    #: (first expert id, how many) of each layer's routed experts that
    #: live on this rank; the default holds them all
    held_experts: Tuple[int, int] = (0, 160)
    causal: ClassVar[bool] = True
    #: the module under ``models/`` whose paged functions serve this config
    family: ClassVar[str] = "deepseek_v2"

    def __post_init__(self):
        first, count = self.held_experts
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held_experts {self.held_experts} is not a "
                             f"range of the {self.n_routed_experts} experts")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")

    @property
    def cache_width(self) -> int:
        """Values cached a token a layer: the latent and the shared
        rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def tiny_config(**over) -> DeepSeekV2Config:
    """Small widths with every mechanism present (CPU tests): 16
    experts in 4 groups, top 2 groups, top 3 experts."""
    base = dict(vocab_size=96, max_len=64, hidden=32, n_layers=3, n_heads=4,
                q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
                qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
                moe_intermediate_size=12, n_routed_experts=16,
                n_shared_experts=2, num_experts_per_tok=3, n_group=4,
                topk_group=2, routed_scaling_factor=4.0,
                rope_original_max_len=16, held_experts=(0, 16))
    base.update(over)
    return DeepSeekV2Config(**base)


# ---------------------------------------------------------------------------
# Pieces
# ---------------------------------------------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(cfg: DeepSeekV2Config, n: int) -> Tuple[np.ndarray,
                                                        np.ndarray]:
    """YaRN cos/sin [n, qk_rope_head_dim] float32 for positions 0..n-1:
    each frequency interpolated (divided by ``rope_factor``) or kept, by
    a linear ramp between the dimensions that turn ``beta_fast`` and
    ``beta_slow`` times over the original context; scaled by
    ``mscale / mscale_all_dim`` (1 as published)."""
    d, base = cfg.qk_rope_head_dim, cfg.rope_theta
    orig = cfg.rope_original_max_len
    exponent = np.arange(0, d, 2, dtype=np.float64) / d
    extra = 1.0 / base ** exponent
    inter = extra / cfg.rope_factor

    def correction_dim(rotations: float) -> float:
        return (d * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = inter * ramp + extra * (1.0 - ramp)
    angles = np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([angles, angles], axis=-1)
    scale = (yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))
    return ((np.cos(emb) * scale).astype(np.float32),
            (np.sin(emb) * scale).astype(np.float32))


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate-half over the last axis (float32 in, float32 out);
    ``cos``/``sin`` broadcast against ``x``."""
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def rms_norm(x: Array, gain: Array, eps: float) -> Array:
    """Float32 statistics, float32 out."""
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * gain.astype(jnp.float32)


def _mm(x: Array, w: Array) -> Array:
    """``x @ w`` over the last axis, operands as given, float32 out."""
    return jnp.einsum("...h,hf->...f", x, w,
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: DeepSeekV2Config) -> PyTree:
    """The tree's shapes: ``layers`` is a list, one dict a layer, so a
    layer's weights are leaves of their own and no dispatch slices a
    stack."""
    H, NH = cfg.hidden, cfg.n_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    F, E = cfg.moe_intermediate_size, cfg.held_experts[1]
    Fs = F * cfg.n_shared_experts

    def ffn(width):
        return {"w_gate": (H, width), "w_up": (H, width),
                "w_down": (width, H)}

    layers = []
    for i in range(cfg.n_layers):
        layer = {
            "attn_norm": (H,), "ffn_norm": (H,),
            "attn": {"w_dq": (H, cfg.q_lora_rank),
                     "q_norm": (cfg.q_lora_rank,),
                     "w_uq": (cfg.q_lora_rank, NH, qk),
                     "w_dkv": (H, cfg.cache_width),
                     "kv_norm": (cfg.kv_lora_rank,),
                     "w_ukv": (cfg.kv_lora_rank, NH,
                               cfg.qk_nope_head_dim + cfg.v_head_dim),
                     "w_o": (NH, cfg.v_head_dim, H)}}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = ffn(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "router": (H, cfg.n_routed_experts),
                "experts": {"w_gate": (E, H, F), "w_up": (E, H, F),
                            "w_down": (E, F, H)},
                "shared": ffn(Fs)}
        layers.append(layer)
    return {"embed": (cfg.vocab_size, H), "layers": layers,
            "final_norm": (H,), "head": (H, cfg.vocab_size)}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def init_params(key: Array, cfg: DeepSeekV2Config, std: float = 0.02,
                dtype: Any = None) -> PyTree:
    """Every matrix N(0, ``std``), norm gains 1 + N(0, ``std``), drawn in
    float32 and rounded to ``dtype`` (the compute type unless given)."""
    dtype = jnp.dtype(dtype or cfg.compute_dtype)
    shapes, treedef = jax.tree.flatten(param_shapes(cfg), is_leaf=_is_shape)
    keys = jax.random.split(key, len(shapes))
    leaves = []
    for k, shape in zip(keys, shapes):
        w = std * jax.random.normal(k, shape, jnp.float32)
        leaves.append((1.0 + w if len(shape) == 1 else w).astype(dtype))
    return jax.tree.unflatten(treedef, leaves)


def hold_experts(cfg: DeepSeekV2Config, params: PyTree, first: int,
                 count: int) -> Tuple[DeepSeekV2Config, PyTree]:
    """The share of a rank that holds experts ``first .. first + count -
    1``: the config that says so and the tree with the other experts'
    weights left out (``params`` must hold them all)."""
    if cfg.held_experts != (0, cfg.n_routed_experts):
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
# The feed-forward half of a layer
# ---------------------------------------------------------------------------

def moe_routed(cfg: DeepSeekV2Config, p: dict, x: Array,
               counted: Optional[Array] = None) -> Tuple[Array, Array]:
    """The routed experts' part alone, ``x`` [N, H] in the compute type:
    (sum over the chosen experts held here [N, H] float32, counts
    [len(DECODE_COUNTERS)] int32).  Rows where ``counted`` [N] is False
    are routed nowhere and counted nowhere."""
    first, n_held = cfg.held_experts
    with jax.named_scope("moe_route"):
        # operands as stored (a product of two bfloat16 values is exact
        # in float32), logits, softmax and top-k in float32
        scores = jax.nn.softmax(
            jnp.einsum("nh,he->ne", x, p["router"],
                       precision=lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32), axis=-1)
        weights, chosen = route_group_limited(
            scores, cfg.n_group, cfg.topk_group, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor)
        if counted is not None:
            chosen = chosen & counted[:, None]
            weights = jnp.where(chosen, weights, 0.0)
        held = chosen[:, first:first + n_held]
        w_held = weights[:, first:first + n_held]
    with jax.named_scope("moe_experts"):
        y, hits = held_experts_ffn(x, w_held, held, p["experts"])
    counts = jnp.stack([chosen.sum(), held.sum(), hits,
                        jnp.int32(1)]).astype(jnp.int32)
    return y, counts


def _ffn(cfg: DeepSeekV2Config, layer: dict, x: Array,
         counted: Optional[Array]) -> Tuple[Array, Array]:
    """Feed-forward half of one layer on [N, H] compute-type rows:
    (float32 result, counts)."""
    if "mlp" in layer:
        m = layer["mlp"]
        return (gated_ffn(x, m["w_gate"], m["w_up"], m["w_down"]),
                jnp.zeros((len(DECODE_COUNTERS),), jnp.int32))
    routed, counts = moe_routed(cfg, layer["moe"], x, counted)
    with jax.named_scope("shared_expert"):
        s = layer["moe"]["shared"]
        shared = gated_ffn(x, s["w_gate"], s["w_up"], s["w_down"])
    return routed + shared, counts


# ---------------------------------------------------------------------------
# Attention: the query side (both forms), then the two products
# ---------------------------------------------------------------------------

def _queries_and_row(cfg: DeepSeekV2Config, a: dict, h: Array, cos: Array,
                     sin: Array) -> Tuple[Array, Array, Array]:
    """From normed rows ``h`` [..., H] (compute type) at positions whose
    tables are ``cos``/``sin`` [..., rope]: (q_nope [..., NH, nope],
    q_rope [..., NH, rope], the row to cache [..., cache_width]), all in
    the compute type."""
    cdt = h.dtype
    eps, R = cfg.rms_norm_eps, cfg.kv_lora_rank
    cq = rms_norm(_mm(h, a["w_dq"]), a["q_norm"], eps).astype(cdt)
    q = jnp.einsum("...r,rnd->...nd", cq, a["w_uq"],
                   preferred_element_type=jnp.float32)
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_rope = apply_rope(q[..., cfg.qk_nope_head_dim:], cos[..., None, :],
                        sin[..., None, :])
    ckv = _mm(h, a["w_dkv"])
    row = jnp.concatenate([rms_norm(ckv[..., :R], a["kv_norm"], eps),
                           apply_rope(ckv[..., R:], cos, sin)], axis=-1)
    return q_nope.astype(cdt), q_rope.astype(cdt), row.astype(cdt)


def attention_expanded(cfg: DeepSeekV2Config, a: dict, q_nope: Array,
                       q_rope: Array, rows: Array, valid: Array) -> Array:
    """``q_*`` [B, W, NH, .] over cached-form ``rows`` [B, T, cache_width]
    with ``k_nope`` and ``v`` made for every row; ``valid`` [B, W, T].
    Returns [B, W, NH, v] float32."""
    R, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    kv = jnp.einsum("btr,rnd->btnd", rows[..., :R], a["w_ukv"],
                    preferred_element_type=jnp.float32).astype(rows.dtype)
    s = (jnp.einsum("bwnd,btnd->bnwt", q_nope, kv[..., :dn],
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bwnd,btd->bnwt", q_rope, rows[..., R:],
                      preferred_element_type=jnp.float32))
    s = jnp.where(valid[:, None], s * cfg.softmax_scale, -1e9)
    probs = jax.nn.softmax(s, axis=-1).astype(rows.dtype)
    return jnp.einsum("bnwt,btnd->bwnd", probs, kv[..., dn:],
                      preferred_element_type=jnp.float32)


def attention_folded(cfg: DeepSeekV2Config, a: dict, q_nope: Array,
                     q_rope: Array, rows: Array, valid: Array) -> Array:
    """The same product with ``W_UK`` folded into the query and ``W_UV``
    applied after the weighted sum: scores and values against ``rows``
    themselves, read once for every head."""
    R, dn = cfg.kv_lora_rank, cfg.qk_nope_head_dim
    cdt = rows.dtype
    q_lat = jnp.einsum("bwnd,rnd->bwnr", q_nope, a["w_ukv"][..., :dn],
                       preferred_element_type=jnp.float32).astype(cdt)
    B, W, NH = q_lat.shape[:3]
    T = rows.shape[1]
    # every head's every query row against the one [T, cache] block: two
    # plain batched matrix products over the merged (head, row) axis
    q_cat = jnp.moveaxis(jnp.concatenate([q_lat, q_rope], axis=-1), 1, 2
                         ).reshape(B, NH * W, -1)
    s = jnp.einsum("bqc,btc->bqt", q_cat, rows,
                   preferred_element_type=jnp.float32).reshape(B, NH, W, T)
    s = jnp.where(valid[:, None], s * cfg.softmax_scale, -1e9)
    probs = jax.nn.softmax(s, axis=-1).astype(cdt).reshape(B, NH * W, T)
    o_lat = jnp.einsum("bqt,btr->bqr", probs, rows[..., :R],
                       preferred_element_type=jnp.float32).astype(cdt)
    o_lat = jnp.moveaxis(o_lat.reshape(B, NH, W, R), 1, 2)
    return jnp.einsum("bwnr,rnd->bwnd", o_lat, a["w_ukv"][..., dn:],
                      preferred_element_type=jnp.float32)


def _attn_out(a: dict, o: Array, cdt) -> Array:
    return jnp.einsum("bwnd,ndh->bwh", o.astype(cdt), a["w_o"],
                      preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Full forward, no cache (the expanded form)
# ---------------------------------------------------------------------------

def forward_logits(cfg: DeepSeekV2Config, params: PyTree, token_ids: Array,
                   folded: bool = False) -> Array:
    """Logits [B, T, V] float32 of whole rows ``token_ids`` [B, T], no
    cache.  ``folded`` runs the paged paths' form of the attention
    product instead (the two must agree)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, T = token_ids.shape
    cos, sin = (jnp.asarray(t) for t in rope_tables(cfg, T))
    valid = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), jnp.bool_)),
                             (B, T, T))
    attend = attention_folded if folded else attention_expanded
    x = params["embed"][token_ids].astype(jnp.float32)
    for layer in params["layers"]:
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps).astype(cdt)
        with jax.named_scope("mla_attention"):
            q_nope, q_rope, rows = _queries_and_row(cfg, layer["attn"], h,
                                                    cos, sin)
            o = attend(cfg, layer["attn"], q_nope, q_rope, rows, valid)
            x = x + _attn_out(layer["attn"], o, cdt)
        h = rms_norm(x, layer["ffn_norm"], cfg.rms_norm_eps).astype(cdt)
        f, _ = _ffn(cfg, layer, h.reshape(B * T, -1), None)
        x = x + f.reshape(B, T, -1)
    return _readout(cfg, params, x)


def _readout(cfg: DeepSeekV2Config, params: PyTree, x: Array) -> Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(cdt)
    return _mm(h, params["head"])


# ---------------------------------------------------------------------------
# The paged pool of latent rows, and the two dispatches over it
# ---------------------------------------------------------------------------

class PagedLatent(NamedTuple):
    """Pool of pages of cached rows [L, P, C, cache_width]: a token's
    normalised latent and its rotated shared key, the same row for every
    head (1,152 B in bfloat16 at the published widths).  Pages, tables,
    the trash page 0 and the allocator are :class:`models.gpt.PagedKV`'s;
    only what a page holds differs."""
    rows: Array


def init_pages(cfg: DeepSeekV2Config, n_pages: int, page_tokens: int,
               kv_dtype: Optional[str] = None) -> PagedLatent:
    _no_kv_dtype(kv_dtype)
    return PagedLatent(jnp.zeros(
        (cfg.n_layers, n_pages, page_tokens, cfg.cache_width),
        jnp.dtype(cfg.compute_dtype)))


def pages_bytes(cfg: DeepSeekV2Config, n_pages: int, page_tokens: int,
                kv_dtype: Optional[str] = None) -> int:
    _no_kv_dtype(kv_dtype)
    return (cfg.n_layers * n_pages * page_tokens * cfg.cache_width
            * jnp.dtype(cfg.compute_dtype).itemsize)


def slots_bytes_per_slot(cfg: DeepSeekV2Config, t_max: int,
                         kv_dtype: Optional[str] = None) -> int:
    """Cache bytes of one sequence of ``t_max`` positions."""
    return pages_bytes(cfg, 1, t_max, kv_dtype)


def paged_specs(cfg: DeepSeekV2Config,
                kv_dtype: Optional[str] = None) -> PagedLatent:  # jaxlint: disable=spec-without-divisibility-guard — nothing is divided: one row serves every head
    """The pool is replicated over a model mesh: its rows are shared by
    all heads, so there is no head axis to divide."""
    _no_kv_dtype(kv_dtype)
    return PagedLatent(rows=P())


def _no_kv_dtype(kv_dtype: Optional[str]) -> None:
    if kv_dtype is not None:
        raise ValueError(f"deepseek_v2 has no {kv_dtype!r} latent pool")


def _paged_stack(cfg: DeepSeekV2Config, params: PyTree, pool: PagedLatent,
                 ptab: Array, toks_w: Array, posw: Array, row_ok: Array
                 ) -> Tuple[PagedLatent, Array, Array]:
    """The layer stack over the pool, W rows a sequence: row w of
    sequence s feeds ``toks_w[s, w]`` at position ``posw[s, w]`` (decode:
    S slots, W = 1; a prefill dispatch: S = 1, W rows of one or more
    pages).  Layer by layer the
    fresh rows are written at (layer, page, offset) and the sequence's
    pages of that layer read back through ``ptab`` [S, TBL], the fresh
    rows among them, as in :func:`models.gpt._paged_stack`.  Rows where
    ``row_ok`` [S, W] is False (an idle slot, a chunk's padding) go to
    the trash page, are routed to no expert and counted nowhere.
    Returns (pool', hidden [S, W, H] float32, counts)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    S, TBL = ptab.shape
    W = toks_w.shape[1]
    C = pool.rows.shape[2]
    T = TBL * C
    cos_t, sin_t = (jnp.asarray(t) for t in rope_tables(cfg, T))
    pw = jnp.clip(posw, 0, T - 1)
    cos, sin = cos_t[pw], sin_t[pw]                          # [S, W, rope]
    ok = (posw >= 0) & (posw < T) & row_ok
    pids = jnp.where(ok, jnp.take_along_axis(ptab, pw // C, axis=1), 0)
    lp0 = jnp.stack([jnp.zeros_like(ptab), ptab], axis=-1)
    lpo0 = jnp.stack([jnp.zeros_like(pids), pids, pw % C], axis=-1)
    valid = jnp.arange(T)[None, None, :] <= posw[:, :, None]  # [S, W, T]
    counted = ok.reshape(S * W)
    counts = jnp.zeros((len(DECODE_COUNTERS),), jnp.int32)
    x = params["embed"][toks_w].astype(jnp.float32)          # [S, W, H]
    rows_a = pool.rows
    for i, layer in enumerate(params["layers"]):
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps).astype(cdt)
        with jax.named_scope("mla_attention"):
            q_nope, q_rope, fresh = _queries_and_row(cfg, layer["attn"], h,
                                                     cos, sin)
            with jax.named_scope("row_write"):
                rows_a = _write_rows(
                    rows_a, lpo0 + jnp.array([i, 0, 0], jnp.int32), fresh)
            with jax.named_scope("page_read"):
                read = _read_pages(
                    rows_a, lp0 + jnp.array([i, 0], jnp.int32)
                ).reshape(S, T, cfg.cache_width)
            o = attention_folded(cfg, layer["attn"], q_nope, q_rope, read,
                                 valid)
            x = x + _attn_out(layer["attn"], o, cdt)
        h = rms_norm(x, layer["ffn_norm"], cfg.rms_norm_eps).astype(cdt)
        f, c = _ffn(cfg, layer, h.reshape(S * W, -1), counted)
        x = x + f.reshape(S, W, -1)
        counts = counts + c
    return PagedLatent(rows_a), x, counts


def paged_prefill(cfg: DeepSeekV2Config, params: PyTree, pool: PagedLatent,
                  ptab_s: Array, toks: Array, start: Array, n_valid: Array,
                  temperature: Array, seed: Array
                  ) -> Tuple[PagedLatent, Array]:
    """One prefill dispatch's rows ``toks`` [W] (any number of rows: the
    engine sends a whole number of pages, ``DecodeEngine.prefill_rows``)
    of the sequence whose page table is ``ptab_s`` [TBL], at
    page-aligned ``start``: each row is written at the page and offset
    of its own position (those past ``n_valid``, and those past the
    table's end, into the trash page) and attends its context through
    the table.  Returns (pool', the token sampled after row
    ``n_valid - 1``)."""
    W = toks.shape[0]
    at = jnp.arange(W, dtype=jnp.int32)
    pool, x, _ = _paged_stack(cfg, params, pool, ptab_s[None, :],
                              toks[None, :], (start + at)[None, :],
                              (at < n_valid)[None, :])
    with jax.named_scope("readout"):
        last = lax.dynamic_slice_in_dim(x[0], n_valid - 1, 1, axis=0)
        logits = _readout(cfg, params, last)[0]
        first = sample_token(logits, _slot_key(seed, start + n_valid - 1),
                             temperature)
    return pool, first


def paged_decode(cfg: DeepSeekV2Config, params: PyTree, pool: PagedLatent,
                 ptab: Array, tokens: Array, pos: Array, active: Array,
                 temperature: Array, seeds: Array
                 ) -> Tuple[PagedLatent, Array]:
    """One token for every active slot.  Returns (pool', int32 [S +
    len(DECODE_COUNTERS)]: the slots' next tokens, then the dispatch's
    routing counts)."""
    pool, x, counts = _paged_stack(cfg, params, pool, ptab, tokens[:, None],
                                   pos[:, None], active[:, None])
    with jax.named_scope("readout"):
        logits = _readout(cfg, params, x[:, 0, :])            # [S, V]
        keys = jax.vmap(_slot_key)(seeds, pos)
        nxt = jax.vmap(sample_token)(logits, keys, temperature)
    return pool, jnp.concatenate([jnp.where(active, nxt, tokens), counts])


def paged_read_pages(cfg: DeepSeekV2Config, pool: PagedLatent, pids: Array):
    """Pages ``pids`` [TBL] of every layer, [L, TBL, C, cache_width]."""
    return (_read_pages(pool.rows, _every_layer(pool.rows.shape[0], pids)),)


def paged_write_pages(cfg: DeepSeekV2Config, pool: PagedLatent, pids: Array,
                      rows: Array) -> PagedLatent:
    """Pages [L, TBL, C, cache_width] into pool pages ``pids`` [TBL]."""
    lidx = jnp.arange(pool.rows.shape[0])[:, None]
    return PagedLatent(pool.rows.at[lidx, pids].set(rows))
