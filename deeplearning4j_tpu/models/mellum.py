"""Mellum 2 (JetBrains ``Mellum2-12B-A2.5B``; ``model_type`` ``mellum``)
on the serving spine: sliding-window and full-attention layers side by
side, each kind over its own slab of a paged pool and its own page
table, grouped-query K/V rows, and an expert layer that takes the 8
best of 64 experts a token, all of them held here.

The layers, ``x`` the residual stream (every norm RMSNorm in float32,
pre-norm, no bias anywhere); layer ``l`` is of kind ``cfg.period[l %
len(cfg.period)]`` (published: three ``sliding_attention`` layers, then
one ``full_attention`` layer):

* attention: ``q = n W_q`` [NH, D], ``k = n W_k``, ``v = n W_v`` [KV, D];
  ``q``, ``k`` RMS-normalised a head (gains ``q_norm``, ``k_norm`` [D]),
  then rotated over all D lanes (rotate-half; :func:`rope_tables`: plain
  on a window layer, YaRN on a full one).  Query head ``h`` reads K/V
  head ``h // (NH / KV)``.  Scores ``q . k / sqrt(D)``, softmax in
  float32 over keys ``j <= i`` and, on a window layer, ``j > i -
  sliding_window``.  Out ``concat(heads) W_o``.
* experts: ``p = softmax(n W_r)`` over all experts in float32; the
  ``num_experts_per_tok`` largest, each weighted by its share of their
  sum (:func:`parallel.expert.route_topk_renorm`); ``sum_e w_e
  W_down,e (silu(W_gate,e n) * W_up,e n)``.  No shared expert, no dense
  layer, no capacity.
* final RMSNorm, untied head.

What is cached of a token is its rotated ``k`` and its ``v``, ``KV * D``
values each a layer: rows as wide as the K/V heads, not the query
heads.  The pool is TWO slabs (:class:`PagedGQA`): ``full``
[L_full, P_full, C, KV*D] for the full-attention layers and ``window``
[L_win, P_win, C, KV*D] for the sliding-window ones, K and V each,
because their rows live differently long: a full layer reads every row
of a sequence for as long as it runs, a window layer never reads one
more than ``sliding_window - 1`` positions back.  :func:`page_kinds`
tells ``serving.decode.DecodeEngine`` so; it keeps an allocator and a
page table a kind, and a slot's window table is a RING of
``ceil((sliding_window - 1) / C) + RING_PREFILL_PAGES`` columns (the
page of positions ``j C ..`` in column ``j % columns``), so a window
layer holds and reads at most that many pages a slot however long the
sequence, and both paged paths find a column's positions from the
slot's own.

The layer loop is a ``lax.scan`` over periods (one traced period
whatever the depth); each layer's fresh rows are written in place at
(layer of its kind, page, offset) and its pages read back through its
own kind's table.  All layers' experts are ONE stack ``[L * E, H, F]``
and a layer's routing addresses its own block of it, so that
:func:`parallel.expert.held_experts_ffn`'s loop slices the parameter
itself and no layer's 64 experts are ever copied out of a stack.

Weights stay in the type they are given in (the checkpoint's
bfloat16): nothing here casts a weight.
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
from deeplearning4j_tpu.parallel.expert import (held_experts_ffn,
                                                route_topk_renorm)

Array = jax.Array
PyTree = Any

WINDOW, FULL = "sliding_attention", "full_attention"

#: what ``paged_decode`` appends to its [S] tokens, as
#: ``models/deepseek_v2.py`` does (every expert is held here, so the
#: first two are equal)
DECODE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                   "moe_expert_hits", "moe_layer_dispatches")

#: ``DecodeEngine`` options this family has no code for; the engine
#: raises at construction rather than fall through to another family's
UNSUPPORTED_ENGINE_OPTIONS = ("mesh", "kv_dtype", "quantize", "draft",
                              "prefix_cache")

#: weights arrive in the compute type: nothing to hold cast
COMPUTE_DTYPE_LEAVES = ()

#: pages of ONE prefill dispatch the window ring leaves room for
#: (:func:`page_kinds`).  A dispatch reads every weight once whatever its
#: rows (all 64 experts of every layer are hit from 128 rows on: 10 GB at
#: the published widths), so a second page halves the reads a join
#: makes; its price is one ring column, 2.36 MB a slot at those widths
#: and pages of 128, which every decode step's window layers read too.
#: No third: two pages of 128 are ``serving.decode.PREFILL_ROWS_MAX``.
RING_PREFILL_PAGES = 2


@dataclasses.dataclass(frozen=True)
class MellumConfig:
    """Published key names where the model's ``config.json`` has one."""
    vocab_size: int = 98304
    max_len: int = 131072                # max_position_embeddings
    hidden: int = 2304
    n_layers: int = 28
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    moe_intermediate_size: int = 896
    num_experts: int = 64
    num_experts_per_tok: int = 8
    sliding_window: int = 1024
    #: the kinds of one period of ``layer_types``; the layers repeat it
    period: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL)
    rms_norm_eps: float = 1e-6
    rope_theta: float = 500000.0
    #: ``rope_parameters.full_attention`` (YaRN); the window layers'
    #: section is the plain table at ``rope_theta``
    rope_factor: float = 16.0
    rope_original_max_len: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_attention_factor: float = 1.2772588722239782
    compute_dtype: str = "bfloat16"
    causal: ClassVar[bool] = True
    #: the module under ``models/`` whose paged functions serve this config
    family: ClassVar[str] = "mellum"

    def __post_init__(self):
        if self.n_layers % len(self.period):
            raise ValueError(f"{self.n_layers} layers are not whole periods "
                             f"of {len(self.period)}")
        if set(self.period) - {WINDOW, FULL}:
            raise ValueError(f"unknown layer kind in {self.period}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")

    @property
    def n_periods(self) -> int:
        return self.n_layers // len(self.period)

    @property
    def kv_width(self) -> int:
        """Values of K (and of V) cached a token a layer."""
        return self.n_kv_heads * self.head_dim

    def layers_of(self, kind: str) -> int:
        return self.n_periods * self.period.count(kind)


def tiny_config(**over) -> MellumConfig:
    """Small widths with every mechanism present (CPU tests): 2 periods,
    a window of 16, 8 experts with the best 2 taken."""
    base = dict(vocab_size=96, max_len=128, hidden=32, n_layers=8, n_heads=4,
                n_kv_heads=2, head_dim=8, moe_intermediate_size=12,
                num_experts=8, num_experts_per_tok=2, sliding_window=16,
                rope_theta=10000.0, rope_original_max_len=32)
    base.update(over)
    return MellumConfig(**base)


# ---------------------------------------------------------------------------
# Pieces
# ---------------------------------------------------------------------------

def rope_tables(cfg: MellumConfig, kind: str, n: int
                ) -> Tuple[np.ndarray, np.ndarray]:
    """cos/sin [n, head_dim] float32 for positions 0..n-1 of a layer of
    ``kind``.  Window layers: ``inv_freq = theta^(-2i/D)``.  Full layers,
    YaRN: each frequency kept or divided by ``rope_factor``, by a linear
    ramp between the dimensions that turn ``beta_fast`` and ``beta_slow``
    times over the original context, and cos and sin scaled by
    ``rope_attention_factor``."""
    d = cfg.head_dim
    inv_freq = 1.0 / cfg.rope_theta ** (np.arange(0, d, 2, dtype=np.float64)
                                        / d)
    scale = 1.0
    if kind == FULL:
        def correction_dim(rotations: float) -> float:
            return (d * math.log(cfg.rope_original_max_len
                                 / (rotations * 2 * math.pi))
                    / (2 * math.log(cfg.rope_theta)))

        low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
        high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
        ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                       0, 1)
        inv_freq = inv_freq / cfg.rope_factor * ramp + inv_freq * (1.0 - ramp)
        scale = cfg.rope_attention_factor
    angles = np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([angles, angles], axis=-1)
    return ((np.cos(emb) * scale).astype(np.float32),
            (np.sin(emb) * scale).astype(np.float32))


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """Rotate-half over the last axis (float32 in, float32 out)."""
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

def param_shapes(cfg: MellumConfig) -> PyTree:
    """The tree's shapes.  ``layers``: every leaf stacked over the
    layers (the scan over periods reads it ``[periods, period, ...]``).
    ``experts``: all layers' experts in one stack, layer ``l``'s at
    ``l * num_experts ..``."""
    H, L, D = cfg.hidden, cfg.n_layers, cfg.head_dim
    F, E = cfg.moe_intermediate_size, cfg.num_experts
    return {"embed": (cfg.vocab_size, H),
            "layers": {"attn_norm": (L, H), "ffn_norm": (L, H),
                       "w_q": (L, H, cfg.n_heads * D),
                       "w_k": (L, H, cfg.kv_width),
                       "w_v": (L, H, cfg.kv_width),
                       "w_o": (L, cfg.n_heads * D, H),
                       "q_norm": (L, D), "k_norm": (L, D),
                       "router": (L, H, E)},
            "experts": {"w_gate": (L * E, H, F), "w_up": (L * E, H, F),
                        "w_down": (L * E, F, H)},
            "final_norm": (H,), "head": (H, cfg.vocab_size)}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def init_params(key: Array, cfg: MellumConfig, std: float = 0.02,
                dtype: Any = None) -> PyTree:
    """Every matrix N(0, ``std``), norm gains 1 + N(0, ``std``), drawn in
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


# ---------------------------------------------------------------------------
# The two halves of a layer
# ---------------------------------------------------------------------------

def _qkv(cfg: MellumConfig, layer: dict, h: Array, cos: Array, sin: Array
         ) -> Tuple[Array, Array, Array]:
    """From normed rows ``h`` [..., H] (compute type) at positions whose
    tables are ``cos``/``sin`` [..., D]: (q [..., NH, D], the K row and
    the V row to cache [..., KV*D]), all in the compute type."""
    cdt = h.dtype
    D, eps = cfg.head_dim, cfg.rms_norm_eps
    lead = h.shape[:-1]
    cos, sin = cos[..., None, :], sin[..., None, :]
    q = rms_norm(_mm(h, layer["w_q"]).reshape(lead + (cfg.n_heads, D)),
                 layer["q_norm"], eps)
    k = rms_norm(_mm(h, layer["w_k"]).reshape(lead + (cfg.n_kv_heads, D)),
                 layer["k_norm"], eps)
    k = apply_rope(k, cos, sin).reshape(lead + (cfg.kv_width,))
    return (apply_rope(q, cos, sin).astype(cdt), k.astype(cdt),
            _mm(h, layer["w_v"]).astype(cdt))


def _attend(cfg: MellumConfig, q: Array, k: Array, v: Array, valid: Array
            ) -> Array:
    """``q`` [B, W, NH, D] over K/V rows as the pool stores them
    [B, T, KV*D], ``valid`` [B, W, T]: each K/V head's rows are read once
    for the NH / KV query heads that share it.  Returns [B, W, NH*D]
    float32.

    Two forms of the same products.  GROUPED: the rows seen [B, T, KV, D],
    a batched product a K/V head; it moves the head axis over the rows
    (a pass over K and over V), which a prefill chunk's one sequence
    pays once for C query rows.  BY LANES (:func:`models.gpt
    ._rows_attention`'s form): q laid out [B, KV*D, NH*W] with head n's
    values on its own K/V head's lanes and zeros elsewhere, so ONE
    product over all KV*D lanes gives every head's scores (the terms it
    adds are exact zeros) and the rows are never re-tiled.  That costs
    KV times the multiply-adds, free where all NH*W query rows fit one
    128-wide pass of the matrix unit anyway: a decode step's."""
    B, W, NH, D = q.shape
    T, G = k.shape[1], cfg.n_kv_heads
    if NH * W > 128:
        qg = q.reshape(B, W, G, NH // G, D)
        s = jnp.einsum("bwgrd,btgd->bgrwt", qg, k.reshape(B, T, G, D),
                       preferred_element_type=jnp.float32) * D ** -0.5
        s = jnp.where(valid[:, None, None], s, -1e9)
        probs = jax.nn.softmax(s, axis=-1).astype(k.dtype)
        o = jnp.einsum("bgrwt,btgd->bwgrd", probs, v.reshape(B, T, G, D),
                       preferred_element_type=jnp.float32)
        return o.reshape(B, W, NH * D)
    # [G, NH]: query head n reads K/V head n // (NH / G)
    mine = np.arange(G)[:, None] == np.arange(NH)[None, :] // (NH // G)
    q_lanes = jnp.where(mine[None, :, None, :, None],
                        jnp.transpose(q, (0, 3, 2, 1))[:, None],
                        0)                               # [B, G, D, NH, W]
    s = jnp.einsum("btf,bfc->bct", k, q_lanes.reshape(B, G * D, NH * W),
                   preferred_element_type=jnp.float32) * D ** -0.5
    s = jnp.where(valid[:, None], s.reshape(B, NH, W, T), -1e9)
    probs = jax.nn.softmax(s, axis=-1).astype(k.dtype)
    every = jnp.einsum("bct,btf->bcf", probs.reshape(B, NH * W, T), v,
                       preferred_element_type=jnp.float32)
    o = jnp.where(mine.T[None, :, None, :, None],
                  every.reshape(B, NH, W, G, D), 0.0).sum(axis=3)
    return jnp.moveaxis(o, 1, 2).reshape(B, W, NH * D)


def moe(cfg: MellumConfig, router: Array, experts: dict, first: Array,
        x: Array, counted: Optional[Array] = None) -> Tuple[Array, Array]:
    """The expert layer on ``x`` [N, H] (compute type): (float32 result
    [N, H], counts [len(DECODE_COUNTERS)] int32).  ``experts`` is the
    stack of EVERY layer's experts and ``first`` where this layer's
    begin in it.  Rows where ``counted`` [N] is False are routed nowhere
    and counted nowhere."""
    with jax.named_scope("moe_route"):
        # operands as stored (a product of two bfloat16 values is exact
        # in float32), logits, softmax and top-k in float32
        scores = jax.nn.softmax(
            jnp.einsum("nh,he->ne", x, router,
                       precision=lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32), axis=-1)
        weights, chosen = route_topk_renorm(scores, cfg.num_experts_per_tok)
        if counted is not None:
            chosen = chosen & counted[:, None]
            weights = jnp.where(chosen, weights, 0.0)
        # this layer's columns among all the stack's
        n_all = experts["w_gate"].shape[0]
        N = x.shape[0]
        in_stack = [lax.dynamic_update_slice(
            jnp.zeros((N, n_all), a.dtype), a, (0, first))
            for a in (weights, chosen)]
    with jax.named_scope("moe_experts"):
        y, hits = held_experts_ffn(x, *in_stack, experts)
    made = chosen.sum()
    return y, jnp.stack([made, made, hits, jnp.int32(1)]).astype(jnp.int32)


def _readout(cfg: MellumConfig, params: PyTree, x: Array) -> Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    h = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(cdt)
    return _mm(h, params["head"])


# ---------------------------------------------------------------------------
# Full forward, no cache
# ---------------------------------------------------------------------------

def forward_logits(cfg: MellumConfig, params: PyTree, token_ids: Array
                   ) -> Array:
    """Logits [B, T, V] float32 of whole rows ``token_ids`` [B, T], no
    cache: a window layer's mask is the causal one less the keys
    ``sliding_window`` or more behind."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, T = token_ids.shape
    at = jnp.arange(T)
    causal = at[None, :] <= at[:, None]
    masks = {FULL: causal,
             WINDOW: causal & (at[None, :] > at[:, None] - cfg.sliding_window)}
    tables = {kind: tuple(jnp.asarray(t) for t in rope_tables(cfg, kind, T))
              for kind in dict.fromkeys(cfg.period)}
    x = params["embed"][token_ids].astype(jnp.float32)
    for l in range(cfg.n_layers):
        kind = cfg.period[l % len(cfg.period)]
        layer = jax.tree.map(lambda a, l=l: a[l], params["layers"])
        h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps).astype(cdt)
        q, k, v = _qkv(cfg, layer, h, *tables[kind])
        o = _attend(cfg, q, k, v, jnp.broadcast_to(masks[kind], (B, T, T)))
        x = x + _mm(o.astype(cdt), layer["w_o"])
        h = rms_norm(x, layer["ffn_norm"], cfg.rms_norm_eps).astype(cdt)
        f, _ = moe(cfg, layer["router"], params["experts"],
                   l * cfg.num_experts, h.reshape(B * T, -1))
        x = x + f.reshape(B, T, -1)
    return _readout(cfg, params, x)


# ---------------------------------------------------------------------------
# The paged pool of two slabs, and the two dispatches over it
# ---------------------------------------------------------------------------

class PagedGQA(NamedTuple):
    """Pool of pages of grouped-query K/V rows, a slab a kind of layer:
    ``full_*`` [L_full, P_full, C, KV*D] and ``window_*`` [L_win, P_win,
    C, KV*D] (1,024 B a row a layer, K and V each, at the published
    widths in bfloat16).  Page 0 of each slab is the trash page; pages,
    tables and allocators are ``serving.decode``'s, one of each a
    kind."""
    full_k: Array
    full_v: Array
    window_k: Array
    window_v: Array


def page_kinds(cfg: MellumConfig, page_tokens: int
               ) -> Tuple[Tuple[Any, ...], ...]:
    """The kinds of page of this family's pool, in the order its paged
    functions take page counts and tables: ``full``, a rung's worth a
    slot; ``window``, a ring of ``cap = ceil((sliding_window - 1) / C) +
    RING_PREFILL_PAGES`` pages (the pages the frontier's row reads back
    to, less its own, and those of one prefill dispatch), and behind the
    bound the rows a dispatch may write AHEAD of a slot's committed
    frontier into that ring (``DecodeEngine``'s ring rule, as
    ``exaone_moe.page_kinds`` states it): ``(cap - 1) C -
    (sliding_window - 1)``, from which the engine derives the pages of a
    prefill dispatch, ``1 + ahead // C``."""
    cap = -(-(cfg.sliding_window - 1) // page_tokens) + RING_PREFILL_PAGES
    return (("full", None),
            ("window", cap,
             (cap - 1) * page_tokens - (cfg.sliding_window - 1)))


def _slab_shapes(cfg: MellumConfig, n_pages: Tuple[int, int],
                 page_tokens: int) -> Tuple[Tuple[int, ...], ...]:
    return tuple((cfg.layers_of(kind), n, page_tokens, cfg.kv_width)
                 for kind, n in zip((FULL, WINDOW), n_pages))


def init_pages(cfg: MellumConfig, n_pages: Tuple[int, int], page_tokens: int,
               kv_dtype: Optional[str] = None) -> PagedGQA:
    """``n_pages``: pages of the (full, window) slabs."""
    _no_kv_dtype(kv_dtype)
    cdt = jnp.dtype(cfg.compute_dtype)
    full, window = _slab_shapes(cfg, n_pages, page_tokens)
    return PagedGQA(jnp.zeros(full, cdt), jnp.zeros(full, cdt),
                    jnp.zeros(window, cdt), jnp.zeros(window, cdt))


def pages_bytes(cfg: MellumConfig, n_pages: Tuple[int, int],
                page_tokens: int, kv_dtype: Optional[str] = None) -> int:
    _no_kv_dtype(kv_dtype)
    return sum(2 * math.prod(shape) for shape in _slab_shapes(
        cfg, n_pages, page_tokens)) * jnp.dtype(cfg.compute_dtype).itemsize


def slots_bytes_per_slot(cfg: MellumConfig, t_max: int,
                         kv_dtype: Optional[str] = None) -> int:
    """Cache bytes of one sequence of ``t_max`` positions: every row on
    the full layers, a window's worth on the others."""
    return pages_bytes(cfg, (t_max, min(t_max, cfg.sliding_window)), 1,
                       kv_dtype)


def paged_specs(cfg: MellumConfig,
                kv_dtype: Optional[str] = None) -> PagedGQA:  # jaxlint: disable=spec-without-divisibility-guard — nothing is divided: the family serves on no mesh yet
    _no_kv_dtype(kv_dtype)
    return PagedGQA(P(), P(), P(), P())


def _no_kv_dtype(kv_dtype: Optional[str]) -> None:
    if kv_dtype is not None:
        raise ValueError(f"mellum has no {kv_dtype!r} K/V pool")


def _paged_stack(cfg: MellumConfig, params: PyTree, pool: PagedGQA,
                 ptabs: Tuple[Array, Array], toks_w: Array, posw: Array,
                 row_ok: Array) -> Tuple[PagedGQA, Array, Array]:
    """The layer stack over the pool, W rows a sequence: row w of
    sequence s feeds ``toks_w[s, w]`` at position ``posw[s, w]`` (decode:
    S slots, W = 1; a prefill dispatch: S = 1, W rows of whole pages
    from a page-aligned start, as many as the ring leaves room for).
    ``ptabs``: the full kind's table [S, TBL] (column j the page of
    positions ``j C ..``) and the window kind's [S, R], a ring (that page
    in column ``j % R``).  What a ring column holds is read off the
    dispatch's NEWEST row of the slot: the newest page ``<=`` that row's
    which falls in the column.  Where a dispatch's rows lie on several
    pages, an older one's page is then the newest in ITS column; and
    where its newest page was not written (the padding of a last
    dispatch goes to the trash page, yet counts as the newest row), what
    that page's column still holds of the page ``R`` before it is
    labelled with positions past every valid row, so the mask by
    position leaves it out, and by the ring rule no valid row reads back
    to that page.
    Layer by layer the fresh rows are written at (layer of its kind,
    page, offset) and that kind's pages of the sequence read back, the
    fresh rows among them.  Rows where ``row_ok`` [S, W] is False (an
    idle slot, a chunk's padding) go to the trash pages, are routed to
    no expert and counted nowhere.  Returns (pool', hidden [S, W, H]
    float32, counts)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    ptab_f, ptab_w = ptabs
    S, TBL = ptab_f.shape
    R = ptab_w.shape[1]
    W = toks_w.shape[1]
    C = pool.full_k.shape[2]
    T = TBL * C
    pw = jnp.clip(posw, 0, T - 1)
    ok = (posw >= 0) & (posw < T) & row_ok
    page, off = pw // C, pw % C
    pids = {FULL: jnp.where(ok, jnp.take_along_axis(ptab_f, page, axis=1), 0),
            WINDOW: jnp.where(ok, jnp.take_along_axis(ptab_w, page % R,
                                                      axis=1), 0)}
    tabs = {FULL: ptab_f, WINDOW: ptab_w}
    # the position each gathered row holds: a full table's in order; a
    # ring column's page is the newest one <= the slot's newest page
    # that falls in it (below 0: never written)
    newest = pw.max(axis=1, keepdims=True) // C                  # [S, 1]
    ring_page = newest - (newest - jnp.arange(R)[None, :]) % R   # [S, R]
    kpos_w = (ring_page[:, :, None] * C + jnp.arange(C)[None, None, :]
              ).reshape(S, 1, R * C)
    kpos_f = jnp.arange(T)[None, None, :]
    q_at = posw[:, :, None]
    valid = {FULL: kpos_f <= q_at,
             WINDOW: ((kpos_w <= q_at) & (kpos_w >= 0)
                      & (kpos_w > q_at - cfg.sliding_window))}
    # (in the period's own order: a set's would change from process to
    # process, and the traced program and its compile-cache key with it)
    rope = {kind: tuple(jnp.asarray(t)[pw]
                        for t in rope_tables(cfg, kind, T))
            for kind in dict.fromkeys(cfg.period)}
    counted = ok.reshape(S * W)
    per = len(cfg.period)

    def period(carry, xs):
        x, slabs, counts = carry
        layers, p = xs
        seen = {FULL: 0, WINDOW: 0}
        for i, kind in enumerate(cfg.period):
            layer = jax.tree.map(lambda a, i=i: a[i], layers)
            at = p * cfg.period.count(kind) + seen[kind]
            seen[kind] += 1
            h = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps).astype(cdt)
            with jax.named_scope("window_attention" if kind == WINDOW
                                 else "full_attention"):
                q, k1, v1 = _qkv(cfg, layer, h, *rope[kind])
                k_a, v_a = slabs[kind]
                with jax.named_scope("row_write"):
                    lpo = jnp.stack([jnp.zeros_like(page) + at, pids[kind],
                                     off], axis=-1)
                    k_a = _write_rows(k_a, lpo, k1)
                    v_a = _write_rows(v_a, lpo, v1)
                with jax.named_scope("page_read"):
                    lp = jnp.stack([jnp.zeros_like(tabs[kind]) + at,
                                    tabs[kind]], axis=-1)
                    k_r = _read_pages(k_a, lp).reshape(S, -1, cfg.kv_width)
                    v_r = _read_pages(v_a, lp).reshape(S, -1, cfg.kv_width)
                slabs = {**slabs, kind: (k_a, v_a)}
                o = _attend(cfg, q, k_r, v_r, valid[kind])
                x = x + _mm(o.astype(cdt), layer["w_o"])
            h = rms_norm(x, layer["ffn_norm"], cfg.rms_norm_eps).astype(cdt)
            f, c = moe(cfg, layer["router"], params["experts"],
                       (p * per + i) * cfg.num_experts,
                       h.reshape(S * W, -1), counted)
            x = x + f.reshape(S, W, -1)
            counts = counts + c
        return (x, slabs, counts), None

    by_period = jax.tree.map(
        lambda a: a.reshape((cfg.n_periods, per) + a.shape[1:]),
        params["layers"])
    x = params["embed"][toks_w].astype(jnp.float32)              # [S, W, H]
    slabs = {FULL: (pool.full_k, pool.full_v),
             WINDOW: (pool.window_k, pool.window_v)}
    (x, slabs, counts), _ = lax.scan(
        period, (x, slabs, jnp.zeros((len(DECODE_COUNTERS),), jnp.int32)),
        (by_period, jnp.arange(cfg.n_periods, dtype=jnp.int32)))
    return PagedGQA(*slabs[FULL], *slabs[WINDOW]), x, counts


def paged_prefill(cfg: MellumConfig, params: PyTree, pool: PagedGQA,
                  ptab_s: Tuple[Array, Array], toks: Array, start: Array,
                  n_valid: Array, temperature: Array, seed: Array
                  ) -> Tuple[PagedGQA, Array]:
    """One prefill dispatch's rows ``toks`` [W] of the sequence whose
    page tables are ``ptab_s`` ([TBL] full, [R] window), at page-aligned
    ``start``.  W is a whole number of pages, no more than the window
    ring leaves room for (``DecodeEngine.prefill_rows`` derives it from
    what :func:`page_kinds` declares; the full kind's row scatter takes
    any W).  Its rows are written into their pages of each kind (those
    past ``n_valid`` into the trash pages; on the window kind over the
    slot's oldest pages once the ring is full) and it attends its
    context through the tables.  Returns (pool', the token sampled after
    row ``n_valid - 1``)."""
    W = toks.shape[0]
    at = jnp.arange(W, dtype=jnp.int32)
    pool, x, _ = _paged_stack(cfg, params, pool,
                              tuple(t[None, :] for t in ptab_s),
                              toks[None, :], (start + at)[None, :],
                              (at < n_valid)[None, :])
    with jax.named_scope("readout"):
        last = lax.dynamic_slice_in_dim(x[0], n_valid - 1, 1, axis=0)
        logits = _readout(cfg, params, last)[0]
        first = sample_token(logits, _slot_key(seed, start + n_valid - 1),
                             temperature)
    return pool, first


def paged_decode(cfg: MellumConfig, params: PyTree, pool: PagedGQA,
                 ptab: Tuple[Array, Array], tokens: Array, pos: Array,
                 active: Array, temperature: Array, seeds: Array
                 ) -> Tuple[PagedGQA, Array]:
    """One token for every active slot; a window layer reads its ring's
    at most R pages a slot whatever the full table's width.  Returns
    (pool', int32 [S + len(DECODE_COUNTERS)]: the slots' next tokens,
    then the dispatch's routing counts)."""
    pool, x, counts = _paged_stack(cfg, params, pool, ptab, tokens[:, None],
                                   pos[:, None], active[:, None])
    with jax.named_scope("readout"):
        logits = _readout(cfg, params, x[:, 0, :])            # [S, V]
        keys = jax.vmap(_slot_key)(seeds, pos)
        nxt = jax.vmap(sample_token)(logits, keys, temperature)
    return pool, jnp.concatenate([jnp.where(active, nxt, tokens), counts])


def paged_read_pages(cfg: MellumConfig, pool: PagedGQA,
                     pids: Tuple[Array, Array]):
    """Pages ``pids`` ([n] of the full slab, [m] of the window slab) of
    every layer of their slab: ([L_full, n, C, KV*D] K and V, [L_win, m,
    C, KV*D] K and V)."""
    return tuple(_read_pages(a, _every_layer(a.shape[0], p))
                 for a, p in zip(pool, (pids[0], pids[0], pids[1], pids[1])))


def paged_write_pages(cfg: MellumConfig, pool: PagedGQA,
                      pids: Tuple[Array, Array], *pages: Array) -> PagedGQA:
    """What :func:`paged_read_pages` gives, written into pages ``pids``."""
    return PagedGQA(*(
        a.at[jnp.arange(a.shape[0])[:, None], p].set(rows)
        for a, p, rows in zip(pool, (pids[0], pids[0], pids[1], pids[1]),
                              pages)))
