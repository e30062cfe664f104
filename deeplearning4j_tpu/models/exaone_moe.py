"""K-EXAONE (LG AI Research ``K-EXAONE-236B-A23B``; ``model_type``
``exaone_moe``) on the serving spine, as ONE expert-parallel rank:
sliding-window and full-attention layers over ``models/mellum.py``'s
two-slab pool (:class:`PagedGQA`: a full table and a window ring), a
leading dense layer, expert layers routed by sigmoid scores with a
selection bias plus a shared expert, and the model's own multi-token
-prediction (MTP) block as the draft of a speculative round.

The layers, ``x`` the residual stream in float32 (every norm RMSNorm in
float32, on each sublayer's OUTPUT, no bias anywhere); layer ``l`` is of
kind ``cfg.period[l % len(cfg.period)]`` (published: three
``sliding_attention`` layers of 128 keys, then one ``full_attention``):

* attention: ``q = x W_q`` [NH, D], ``k = x W_k``, ``v = x W_v`` [KV, D];
  ``q``, ``k`` RMS-normalised a head (gains [D]); rotated over all D
  lanes (rotate-half, plain table at ``rope_theta``) on a SLIDING layer
  only: a full layer has no positions at all.  Query head ``h`` reads
  K/V head ``h // (NH / KV)``; scores ``q . k / sqrt(D)``, softmax in
  float32 over keys ``j <= i`` and, on a sliding layer, ``j > i -
  sliding_window``.  ``x <- x + RMS(concat(heads) W_o)``.
* layer 0 (``first_k_dense_replace`` of them): ``x <- x + RMS(F(x))``,
  ``F(v) = W_down (silu(W_gate v) * W_up v)`` at ``intermediate_size``.
* later layers: ``s = sigmoid(x W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + b`` are taken (``b`` the
  selection bias), ``w_e = routed_scaling_factor * s_e / sum of the
  taken s`` (:func:`parallel.expert.route_sigmoid_bias`); ``x <- x +
  RMS(sum over taken e HELD HERE of w_e F_e(x) + F_shared(x))``.  What
  the other ranks' experts would add is left out.
* final RMSNorm, untied head over the rows held here.
* the MTP block (DeepSeek-V3, arXiv 2412.19437, section 2.2): at
  position ``i``, with ``h_i`` the last main layer's output (before the
  final norm) and ``t_{i+1}`` the token that follows, ``u_i =
  [RMS(h_i) ; RMS(Emb(t_{i+1}))] W_eh``, one more block of kind
  ``full_attention`` with a sparse MLP over a cache of its own, and
  ``draft_{i+2} = argmax(RMS(block(u)) W_head)``.  Embedding and head
  are the main model's.

The pool is :class:`models.mellum.PagedGQA`: the full slab holds the
full-attention layers of the main stack and, behind them, the MTP
block's; the window slab the sliding layers'.  The layers are a Python
loop over a list (a layer's weights are leaves of their own: the leading
dense layer has another shape than the rest, and no dispatch slices a
stack).

A SPECULATIVE ROUND (:func:`paged_self_draft_round`, ``k = 1``) is ONE
dispatch: the main stack over each slot's current token at ``p`` and
its draft at ``p + 1`` gives the model's own tokens ``g_0`` (for ``p +
1``) and ``g_1`` (for ``p + 2``) under the position-keyed sampling of
:func:`paged_decode`; ``1 + [g_0 == draft]`` of them commit; the MTP
block runs on ``(h_p, g_0)`` and ``(h_{p+1}, g_1)`` and the next draft
is the one behind the last committed row.  A row of a rejected draft is
written over by the next round's row at its position and is never
attended before (every mask is by position).  The committed stream is,
token for token, :func:`paged_decode`'s.

Weights stay in the type they are given in (the checkpoint's bfloat16):
nothing here casts a weight.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.models.gpt import (_read_pages, _slot_key,
                                           _write_rows, sample_token)
from deeplearning4j_tpu.models.mellum import (FULL, WINDOW,  # noqa: F401 — the pool's functions are this family's too: DecodeEngine takes them from here
                                              PagedGQA, _attend, _mm,
                                              apply_rope, init_pages,
                                              paged_read_pages, paged_specs,
                                              paged_write_pages, pages_bytes,
                                              rms_norm, rope_tables,
                                              slots_bytes_per_slot)
from deeplearning4j_tpu.parallel.expert import (gated_ffn, held_experts_ffn,
                                                route_sigmoid_bias)

Array = jax.Array
PyTree = Any

#: what ``paged_decode`` and a speculative round append to their tokens,
#: summed over the MAIN stack's expert layers and over every row of the
#: dispatch that counts (a round's two rows a running slot, the rejected
#: draft's among them): assignments made (row x layer x top_k), those
#: that fell on an expert held here, distinct held experts touched,
#: expert layers run.  The MTP block's expert layer is not in them (its
#: device time is the ``mtp_block`` scope's).
DECODE_COUNTERS = ("moe_assignments", "moe_assignments_held",
                   "moe_expert_hits", "moe_layer_dispatches")

#: ``DecodeEngine`` options this family has no code for; ``draft`` is
#: not among them: ``draft="self"`` is the MTP block
UNSUPPORTED_ENGINE_OPTIONS = ("mesh", "kv_dtype", "quantize", "prefix_cache")

#: weights arrive in the compute type: nothing to hold cast
COMPUTE_DTYPE_LEAVES = ()


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """Published key names where the model's ``config.json`` has one."""
    vocab_size: int = 153600
    max_len: int = 262144                # max_position_embeddings
    hidden: int = 6144
    n_layers: int = 48                   # of the main stack
    n_heads: int = 64
    n_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 18432       # the dense layers' width
    moe_intermediate_size: int = 2048    # one expert's width
    first_k_dense_replace: int = 1
    num_experts: int = 128               # the ROUTER's width, never cut
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    sliding_window: int = 128
    #: the kinds of one period of ``layer_types``; the layers repeat it
    period: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL)
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1000000.0
    #: ``num_nextn_predict_layers``: MTP blocks behind the main stack
    n_mtp: int = 1
    #: (first expert id, how many) of each layer's routed experts that
    #: live on this rank; the default holds them all
    held_experts: Tuple[int, int] = (0, 128)
    compute_dtype: str = "bfloat16"
    causal: ClassVar[bool] = True
    #: the module under ``models/`` whose paged functions serve this config
    family: ClassVar[str] = "exaone_moe"

    def __post_init__(self):
        first, count = self.held_experts
        if not (0 <= first and count >= 1
                and first + count <= self.num_experts):
            raise ValueError(f"held_experts {self.held_experts} is not a "
                             f"range of the {self.num_experts} experts")
        if set(self.period) - {WINDOW, FULL}:
            raise ValueError(f"unknown layer kind in {self.period}")
        if self.n_heads % self.n_kv_heads:
            raise ValueError("n_heads must be a multiple of n_kv_heads")
        if self.n_mtp not in (0, 1):
            raise ValueError(f"{self.n_mtp} MTP blocks: one at most is "
                             f"chained here")

    @property
    def kv_width(self) -> int:
        """Values of K (and of V) cached a token a layer."""
        return self.n_kv_heads * self.head_dim

    def kind_of(self, layer: int) -> str:
        return self.period[layer % len(self.period)]

    def main_layers_of(self, kind: str) -> int:
        return sum(self.kind_of(l) == kind for l in range(self.n_layers))

    def layers_of(self, kind: str) -> int:
        """Layers of the pool's slab of ``kind``: the main stack's, and
        behind the full ones the MTP block's."""
        return self.main_layers_of(kind) + (self.n_mtp if kind == FULL else 0)


def tiny_config(**over) -> ExaoneMoeConfig:
    """Small widths with every mechanism present (CPU tests): a dense
    layer and four expert layers (sliding, sliding, sliding, full,
    sliding), a window of 8, 16 experts with the best 3 taken, the MTP
    block."""
    base = dict(vocab_size=96, max_len=128, hidden=32, n_layers=5, n_heads=4,
                n_kv_heads=2, head_dim=8, intermediate_size=48,
                moe_intermediate_size=12, num_experts=16,
                num_experts_per_tok=3, sliding_window=8, rope_theta=10000.0,
                held_experts=(0, 16))
    base.update(over)
    return ExaoneMoeConfig(**base)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: ExaoneMoeConfig) -> PyTree:
    """The tree's shapes: ``layers`` is a list, one dict a layer; ``mtp``
    (where the config has the block) its two input norms, the joining
    matrix, one more expert layer and the norm before the shared head."""
    H, D, NH = cfg.hidden, cfg.head_dim, cfg.n_heads
    F, E = cfg.moe_intermediate_size, cfg.held_experts[1]

    def ffn(width):
        return {"w_gate": (H, width), "w_up": (H, width),
                "w_down": (width, H)}

    def block(dense: bool):
        layer = {"attn": {"w_q": (H, NH * D), "w_k": (H, cfg.kv_width),
                          "w_v": (H, cfg.kv_width), "w_o": (NH * D, H),
                          "q_norm": (D,), "k_norm": (D,)},
                 "attn_out_norm": (H,), "ffn_out_norm": (H,)}
        if dense:
            layer["mlp"] = ffn(cfg.intermediate_size)
        else:
            layer["moe"] = {
                "router": (H, cfg.num_experts), "bias": (cfg.num_experts,),
                "experts": {"w_gate": (E, H, F), "w_up": (E, H, F),
                            "w_down": (E, F, H)},
                "shared": ffn(F * cfg.num_shared_experts)}
        return layer

    shapes = {"embed": (cfg.vocab_size, H),
              "layers": [block(i < cfg.first_k_dense_replace)
                         for i in range(cfg.n_layers)],
              "final_norm": (H,), "head": (H, cfg.vocab_size)}
    if cfg.n_mtp:
        shapes["mtp"] = {"h_norm": (H,), "e_norm": (H,), "w_eh": (2 * H, H),
                         "block": block(False), "out_norm": (H,)}
    return shapes


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(d, int) for d in x)


def init_params(key: Array, cfg: ExaoneMoeConfig, std: float = 0.02,
                dtype: Any = None) -> PyTree:
    """Every matrix and the router's selection bias N(0, ``std``), norm
    gains 1 + N(0, ``std``), drawn in float32 and rounded to ``dtype``
    (the compute type unless given)."""
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


def hold_experts(cfg: ExaoneMoeConfig, params: PyTree, first: int,
                 count: int) -> Tuple[ExaoneMoeConfig, PyTree]:
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

    out = {**params, "layers": [cut(l) for l in params["layers"]]}
    if "mtp" in params:
        out["mtp"] = {**params["mtp"], "block": cut(params["mtp"]["block"])}
    return dataclasses.replace(cfg, held_experts=(first, count)), out


# ---------------------------------------------------------------------------
# The two halves of a block
# ---------------------------------------------------------------------------

def _qkv(cfg: ExaoneMoeConfig, a: dict, h: Array,
         rope: Optional[Tuple[Array, Array]]) -> Tuple[Array, Array, Array]:
    """From rows ``h`` [..., H] (compute type): (q [..., NH, D], the K
    row and the V row to cache [..., KV*D]), all in the compute type.
    ``rope``: cos/sin [..., D] of the rows' positions on a sliding layer,
    ``None`` on a full one, which rotates nothing."""
    cdt = h.dtype
    D, eps = cfg.head_dim, cfg.rms_norm_eps
    lead = h.shape[:-1]
    q = rms_norm(_mm(h, a["w_q"]).reshape(lead + (cfg.n_heads, D)),
                 a["q_norm"], eps)
    k = rms_norm(_mm(h, a["w_k"]).reshape(lead + (cfg.n_kv_heads, D)),
                 a["k_norm"], eps)
    if rope is not None:
        cos, sin = (t[..., None, :] for t in rope)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
    return (q.astype(cdt), k.reshape(lead + (cfg.kv_width,)).astype(cdt),
            _mm(h, a["w_v"]).astype(cdt))


def moe_routed(cfg: ExaoneMoeConfig, p: dict, x: Array,
               counted: Optional[Array] = None) -> Tuple[Array, Array]:
    """The routed experts' part alone, ``x`` [N, H] in the compute type:
    (sum over the taken experts held here [N, H] float32, counts
    [len(DECODE_COUNTERS)] int32).  Rows where ``counted`` [N] is False
    are routed nowhere and counted nowhere."""
    first, n_held = cfg.held_experts
    with jax.named_scope("moe_route"):
        # operands as stored (a product of two bfloat16 values is exact
        # in float32); logits, sigmoid and top-k in float32
        scores = jax.nn.sigmoid(
            jnp.einsum("nh,he->ne", x, p["router"],
                       precision=lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32))
        weights, chosen = route_sigmoid_bias(
            scores, p["bias"].astype(jnp.float32), cfg.num_experts_per_tok,
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


def _ffn(cfg: ExaoneMoeConfig, layer: dict, x: Array,
         counted: Optional[Array]) -> Tuple[Array, Array]:
    """Feed-forward half of one block on [N, H] compute-type rows:
    (float32 result before its norm, counts)."""
    if "mlp" in layer:
        m = layer["mlp"]
        return (gated_ffn(x, m["w_gate"], m["w_up"], m["w_down"]),
                jnp.zeros((len(DECODE_COUNTERS),), jnp.int32))
    routed, counts = moe_routed(cfg, layer["moe"], x, counted)
    with jax.named_scope("shared_expert"):
        s = layer["moe"]["shared"]
        shared = gated_ffn(x, s["w_gate"], s["w_up"], s["w_down"])
    return routed + shared, counts


def _block(cfg: ExaoneMoeConfig, layer: dict, x: Array, attend,
           counted: Optional[Array]) -> Tuple[Array, Array]:
    """One block on the residual stream ``x`` [B, W, H] float32, the norm
    on each sublayer's output; ``attend(h)`` gives the heads' output [B,
    W, NH*D] for the compute-type rows ``h``."""
    cdt = jnp.dtype(cfg.compute_dtype)
    B, W, H = x.shape
    o = attend(x.astype(cdt))
    x = x + rms_norm(_mm(o.astype(cdt), layer["attn"]["w_o"]),
                     layer["attn_out_norm"], cfg.rms_norm_eps)
    f, counts = _ffn(cfg, layer, x.astype(cdt).reshape(B * W, H), counted)
    return x + rms_norm(f.reshape(B, W, H), layer["ffn_out_norm"],
                        cfg.rms_norm_eps), counts


def _head(cfg: ExaoneMoeConfig, params: PyTree, x: Array, gain: Array
          ) -> Array:
    cdt = jnp.dtype(cfg.compute_dtype)
    return _mm(rms_norm(x, gain, cfg.rms_norm_eps).astype(cdt),
               params["head"])


def _readout(cfg: ExaoneMoeConfig, params: PyTree, x: Array) -> Array:
    return _head(cfg, params, x, params["final_norm"])


def _draft_logits(cfg: ExaoneMoeConfig, params: PyTree, u: Array) -> Array:
    """The shared head over the MTP block's output and its own norm."""
    return _head(cfg, params, u, params["mtp"]["out_norm"])


def _mtp_input(cfg: ExaoneMoeConfig, params: PyTree, hidden: Array,
               nxt: Array) -> Array:
    """``[RMS(h_i) ; RMS(Emb(t_{i+1}))] W_eh`` for ``hidden`` [..., H]
    float32 and the tokens ``nxt`` [...] that follow: float32."""
    m, eps = params["mtp"], cfg.rms_norm_eps
    cdt = jnp.dtype(cfg.compute_dtype)
    e = params["embed"][nxt].astype(jnp.float32)
    both = jnp.concatenate([rms_norm(hidden, m["h_norm"], eps),
                            rms_norm(e, m["e_norm"], eps)], axis=-1)
    return _mm(both.astype(cdt), m["w_eh"])


# ---------------------------------------------------------------------------
# Full forward, no cache
# ---------------------------------------------------------------------------

def _masks(cfg: ExaoneMoeConfig, B: int, T: int) -> Dict[str, Array]:
    at = jnp.arange(T)
    causal = at[None, :] <= at[:, None]
    window = causal & (at[None, :] > at[:, None] - cfg.sliding_window)
    return {FULL: jnp.broadcast_to(causal, (B, T, T)),
            WINDOW: jnp.broadcast_to(window, (B, T, T))}


def forward_hidden(cfg: ExaoneMoeConfig, params: PyTree, token_ids: Array
                   ) -> Array:
    """The last main layer's output [B, T, H] float32 (before the final
    norm) of whole rows ``token_ids`` [B, T], no cache."""
    B, T = token_ids.shape
    masks = _masks(cfg, B, T)
    rope = tuple(jnp.asarray(t) for t in rope_tables(cfg, WINDOW, T))
    x = params["embed"][token_ids].astype(jnp.float32)
    for l, layer in enumerate(params["layers"]):
        kind = cfg.kind_of(l)

        def attend(h, layer=layer, kind=kind):
            q, k, v = _qkv(cfg, layer["attn"], h,
                           rope if kind == WINDOW else None)
            return _attend(cfg, q, k, v, masks[kind])

        x, _ = _block(cfg, layer, x, attend, None)
    return x


def forward_logits(cfg: ExaoneMoeConfig, params: PyTree, token_ids: Array
                   ) -> Array:
    """Logits [B, T, V] float32 of whole rows ``token_ids`` [B, T], no
    cache, no draft."""
    return _readout(cfg, params, forward_hidden(cfg, params, token_ids))


def forward_drafts(cfg: ExaoneMoeConfig, params: PyTree, token_ids: Array
                   ) -> Array:
    """The MTP block's logits [B, T - 1, V] float32 of whole rows, no
    cache: entry ``i`` is over the token at ``i + 2``, from ``h_i`` and
    the token at ``i + 1``."""
    B, T = token_ids.shape
    hidden = forward_hidden(cfg, params, token_ids)[:, :-1]
    u = _mtp_input(cfg, params, hidden, token_ids[:, 1:])
    valid = _masks(cfg, B, T - 1)[FULL]
    block = params["mtp"]["block"]

    def attend(h):
        return _attend(cfg, *_qkv(cfg, block["attn"], h, None), valid)

    u, _ = _block(cfg, block, u, attend, None)
    return _draft_logits(cfg, params, u)


# ---------------------------------------------------------------------------
# The paged pool (mellum's two slabs) and the dispatches over it
# ---------------------------------------------------------------------------

def page_kinds(cfg: ExaoneMoeConfig, page_tokens: int
               ) -> Tuple[Tuple[Any, ...], ...]:
    """The kinds of page of this family's pool, in the order its paged
    functions take page counts and tables: ``full``, a rung's worth a
    slot; ``window``, a ring of ``cap = ceil((sliding_window - 1) / C) +
    1`` pages, and behind the bound the rows a dispatch may write AHEAD
    of a slot's committed frontier into that ring (``DecodeEngine``'s
    ring rule): ``(cap - 1) C - (sliding_window - 1)``, which is 1 where
    C divides the window: the page a row at ``p + a`` opens lies over the
    page ``cap`` before it, whose last row must be further back than the
    ``sliding_window - 1`` rows the frontier ``p`` still reads."""
    cap = -(-(cfg.sliding_window - 1) // page_tokens) + 1
    return (("full", None),
            ("window", cap,
             (cap - 1) * page_tokens - (cfg.sliding_window - 1)))


def self_draft_depth(cfg: ExaoneMoeConfig) -> int:
    """Tokens the model's own MTP blocks draft a round
    (``DecodeEngine(draft="self")``)."""
    return cfg.n_mtp


class _Rows:
    """What every layer of one dispatch shares, for W rows a sequence
    (row w of sequence s at position ``posw[s, w]``; decode: S slots, W =
    1; a speculative round: W = 2, the current token and its draft; a
    prefill dispatch: S = 1, W rows of ONE page): where each row is
    written and which gathered rows it may attend, a kind of page.

    ``ptabs``: the full kind's table [S, TBL] (column j the page of
    positions ``j C ..``) and the window kind's [S, R], a ring (that page
    in column ``j % R``).  What a ring column holds is read off the
    dispatch's NEWEST row of the slot: the newest page ``<=`` that row's
    which falls in the column.  A dispatch's rows may lie on two pages (a
    round's two rows straddle a page edge once in C rounds): the older
    one's page is then the newest in ITS column, and what the column of
    the page just opened still holds of the page ``R`` before it is
    labelled with positions past every query row, so the mask by
    position leaves it out.  Rows where ``row_ok`` [S, W] is False (an
    idle slot, a chunk's padding) and rows past the table's end go to
    the trash pages, are routed to no expert and counted nowhere."""

    def __init__(self, cfg: ExaoneMoeConfig, pool: PagedGQA,
                 ptabs: Tuple[Array, Array], posw: Array, row_ok: Array):
        ptab_f, ptab_w = ptabs
        S, TBL = ptab_f.shape
        R = ptab_w.shape[1]
        C = pool.full_k.shape[2]
        T = TBL * C
        pw = jnp.clip(posw, 0, T - 1)
        self.ok = (posw >= 0) & (posw < T) & row_ok
        self.S, self.W = posw.shape
        self.counted = self.ok.reshape(self.S * self.W)
        self.page, self.off = pw // C, pw % C
        self.pids = {
            FULL: jnp.where(self.ok, jnp.take_along_axis(
                ptab_f, self.page, axis=1), 0),
            WINDOW: jnp.where(self.ok, jnp.take_along_axis(
                ptab_w, self.page % R, axis=1), 0)}
        self.tabs = {FULL: ptab_f, WINDOW: ptab_w}
        newest = pw.max(axis=1, keepdims=True) // C                 # [S, 1]
        ring_page = newest - (newest - jnp.arange(R)[None, :]) % R  # [S, R]
        kpos_w = (ring_page[:, :, None] * C + jnp.arange(C)[None, None, :]
                  ).reshape(S, 1, R * C)
        q_at = posw[:, :, None]
        self.valid = {
            FULL: jnp.arange(T)[None, None, :] <= q_at,
            WINDOW: ((kpos_w <= q_at) & (kpos_w >= 0)
                     & (kpos_w > q_at - cfg.sliding_window))}
        self.rope = tuple(jnp.asarray(t)[pw]
                          for t in rope_tables(cfg, WINDOW, T))


def _paged_block(cfg: ExaoneMoeConfig, layer: dict, kind: str, at: int,
                 x: Array, slabs: Dict[str, Tuple[Array, Array]], r: _Rows
                 ) -> Tuple[Array, Dict[str, Tuple[Array, Array]], Array]:
    """One block over the pool: the rows' fresh K/V written at (layer
    ``at`` of the slab of ``kind``, page, offset), that layer's pages of
    every sequence read back through the kind's table, the fresh rows
    among them."""
    slabs = dict(slabs)

    def attend(h):
        with jax.named_scope("window_attention" if kind == WINDOW
                             else "full_attention"):
            q, k1, v1 = _qkv(cfg, layer["attn"], h,
                             r.rope if kind == WINDOW else None)
            k_a, v_a = slabs[kind]
            with jax.named_scope("row_write"):
                lpo = jnp.stack([jnp.zeros_like(r.page) + at, r.pids[kind],
                                 r.off], axis=-1)
                k_a = _write_rows(k_a, lpo, k1)
                v_a = _write_rows(v_a, lpo, v1)
            with jax.named_scope("page_read"):
                lp = jnp.stack([jnp.zeros_like(r.tabs[kind]) + at,
                                r.tabs[kind]], axis=-1)
                k_r = _read_pages(k_a, lp).reshape(r.S, -1, cfg.kv_width)
                v_r = _read_pages(v_a, lp).reshape(r.S, -1, cfg.kv_width)
            slabs[kind] = (k_a, v_a)
            return _attend(cfg, q, k_r, v_r, r.valid[kind])

    x, counts = _block(cfg, layer, x, attend, r.counted)
    return x, slabs, counts


def _slabs(pool: PagedGQA) -> Dict[str, Tuple[Array, Array]]:
    return {FULL: (pool.full_k, pool.full_v),
            WINDOW: (pool.window_k, pool.window_v)}


def _paged_stack(cfg: ExaoneMoeConfig, params: PyTree, pool: PagedGQA,
                 ptabs: Tuple[Array, Array], toks_w: Array, posw: Array,
                 row_ok: Array):
    """The MAIN stack over the pool for the rows :class:`_Rows`
    describes.  Returns (slabs', the last layer's output [S, W, H]
    float32, counts, the rows' geometry for the MTP block behind)."""
    r = _Rows(cfg, pool, ptabs, posw, row_ok)
    slabs = _slabs(pool)
    counts = jnp.zeros((len(DECODE_COUNTERS),), jnp.int32)
    seen = {FULL: 0, WINDOW: 0}
    x = params["embed"][toks_w].astype(jnp.float32)              # [S, W, H]
    for l, layer in enumerate(params["layers"]):
        kind = cfg.kind_of(l)
        x, slabs, c = _paged_block(cfg, layer, kind, seen[kind], x, slabs, r)
        seen[kind] += 1
        counts = counts + c
    return slabs, x, counts, r


def _paged_mtp(cfg: ExaoneMoeConfig, params: PyTree,
               slabs: Dict[str, Tuple[Array, Array]], r: _Rows,
               hidden: Array, nxt: Array
               ) -> Tuple[Dict[str, Tuple[Array, Array]], Array]:
    """The MTP block over its own layer of the full slab (behind the
    main stack's), for the rows ``r`` describes: ``hidden`` [S, W, H] the
    main stack's output at them, ``nxt`` [S, W] the token that follows
    each.  Returns (slabs', the block's output [S, W, H] float32: the
    head over its norm drafts the token two past the row)."""
    with jax.named_scope("mtp_block"):
        u = _mtp_input(cfg, params, hidden, nxt)
        u, slabs, _ = _paged_block(cfg, params["mtp"]["block"], FULL,
                                   cfg.main_layers_of(FULL), u, slabs, r)
    return slabs, u


def _pool_of(slabs: Dict[str, Tuple[Array, Array]]) -> PagedGQA:
    return PagedGQA(*slabs[FULL], *slabs[WINDOW])


def _sample_rows(logits: Array, seeds: Array, posw: Array,
                 temperature: Array) -> Array:
    """The model's own token after every row [S, W], each under the key
    of its position (``gpt._slot_key``): the key the sequential path
    uses there, so a round's tokens are that path's at any
    temperature."""
    keys = jax.vmap(lambda sd, pw: jax.vmap(
        lambda pp: _slot_key(sd, pp))(pw))(seeds, posw)
    return jax.vmap(jax.vmap(sample_token, in_axes=(0, 0, None)))(
        logits, keys, temperature)


def _prefill_rows(cfg, params, pool, ptab_s, toks, start, n_valid,
                  temperature, seed):
    W = toks.shape[0]
    at = jnp.arange(W, dtype=jnp.int32)
    slabs, x, _, r = _paged_stack(cfg, params, pool,
                                  tuple(t[None, :] for t in ptab_s),
                                  toks[None, :], (start + at)[None, :],
                                  (at < n_valid)[None, :])
    with jax.named_scope("readout"):
        last = lax.dynamic_slice_in_dim(x[0], n_valid - 1, 1, axis=0)
        first = sample_token(_readout(cfg, params, last)[0],
                             _slot_key(seed, start + n_valid - 1),
                             temperature)
    return slabs, x, r, first


def paged_prefill(cfg: ExaoneMoeConfig, params: PyTree, pool: PagedGQA,
                  ptab_s: Tuple[Array, Array], toks: Array, start: Array,
                  n_valid: Array, temperature: Array, seed: Array
                  ) -> Tuple[PagedGQA, Array]:
    """One prefill dispatch's rows ``toks`` [W] (ONE page: this
    family's ring of ``page_kinds`` leaves room for no more,
    ``DecodeEngine.prefill_rows``) of the sequence
    whose page tables are ``ptab_s`` ([TBL] full, [R] window), at
    page-aligned ``start``.  The MTP block's cache is left alone: an
    engine without the draft never reads it.  Returns (pool', the token
    sampled after row ``n_valid - 1``)."""
    slabs, _, _, first = _prefill_rows(cfg, params, pool, ptab_s, toks,
                                       start, n_valid, temperature, seed)
    return _pool_of(slabs), first


def paged_self_draft_prefill(cfg: ExaoneMoeConfig, params: PyTree,
                             pool: PagedGQA, ptab_s: Tuple[Array, Array],
                             toks: Array, nxt: Array, start: Array,
                             n_valid: Array, temperature: Array, seed: Array
                             ) -> Tuple[PagedGQA, Array]:
    """:func:`paged_prefill` with the MTP block's cache filled inside
    the join: row ``i`` of it from ``(h_i, t_{i+1})``, ``nxt`` [W] the
    token that follows each row of the dispatch and -1 at the prompt's
    last row, which the token sampled here completes.  Returns (pool',
    int32 [2]: that token, and the draft of the one after it)."""
    slabs, x, r, first = _prefill_rows(cfg, params, pool, ptab_s, toks,
                                       start, n_valid, temperature, seed)
    slabs, u = _paged_mtp(cfg, params, slabs, r, x,
                          jnp.where(nxt < 0, first, nxt)[None, :])
    with jax.named_scope("mtp_block"):
        last = lax.dynamic_slice_in_dim(u[0], n_valid - 1, 1, axis=0)
        draft = jnp.argmax(_draft_logits(cfg, params, last)[0],
                           axis=-1).astype(jnp.int32)
    return _pool_of(slabs), jnp.stack([first, draft])


def paged_decode(cfg: ExaoneMoeConfig, params: PyTree, pool: PagedGQA,
                 ptab: Tuple[Array, Array], tokens: Array, pos: Array,
                 active: Array, temperature: Array, seeds: Array
                 ) -> Tuple[PagedGQA, Array]:
    """One token for every active slot, no draft.  Returns (pool', int32
    [S + len(DECODE_COUNTERS)]: the slots' next tokens, then the
    dispatch's routing counts)."""
    slabs, x, counts, _ = _paged_stack(cfg, params, pool, ptab,
                                       tokens[:, None], pos[:, None],
                                       active[:, None])
    with jax.named_scope("readout"):
        nxt = _sample_rows(_readout(cfg, params, x), seeds, pos[:, None],
                           temperature)[:, 0]
    return _pool_of(slabs), jnp.concatenate(
        [jnp.where(active, nxt, tokens), counts])


def paged_self_draft_round(cfg: ExaoneMoeConfig, params: PyTree,
                           pool: PagedGQA, ptab: Tuple[Array, Array],
                           tokens: Array, pos: Array, active: Array,
                           temperature: Array, seeds: Array, drafts: Array
                           ) -> Tuple[PagedGQA, Array]:
    """One SPECULATIVE round for every active slot, verify and next
    draft in this one dispatch.  Slot s feeds its current token at
    ``pos[s]`` and ``drafts[s]`` [k] behind it (k = 1: one MTP block);
    row w yields the model's own token ``g_w`` for ``pos + w + 1`` under
    :func:`paged_decode`'s key of that position; with ``n_acc`` the
    leading drafts that equal ``g``, ``g_0 .. g_{n_acc}`` commit.  The
    MTP block then runs on ``(h_w, g_w)`` at ``pos + w`` and the next
    draft is the one behind the last committed row.  Returns (pool',
    int32 [S (k + 1) + S + S k + len(DECODE_COUNTERS)]: ``g`` row by row,
    the commit counts (0 where inactive), the next drafts, the routing
    counts of ALL k + 1 rows of every active slot)."""
    S, k = drafts.shape
    toks_w = jnp.concatenate([tokens[:, None], drafts], axis=1)
    posw = pos[:, None] + jnp.arange(k + 1, dtype=pos.dtype)    # [S, W]
    slabs, x, counts, r = _paged_stack(
        cfg, params, pool, ptab, toks_w, posw,
        jnp.broadcast_to(active[:, None], posw.shape))
    with jax.named_scope("readout"):
        g = _sample_rows(_readout(cfg, params, x), seeds, posw, temperature)
    matches = (g[:, :k] == drafts).astype(jnp.int32)
    n_commit = jnp.where(active, 1 + jnp.sum(jnp.cumprod(matches, axis=1),
                                             axis=1), 0)
    slabs, u = _paged_mtp(cfg, params, slabs, r, x, g)
    with jax.named_scope("mtp_block"):
        d = jnp.argmax(_draft_logits(cfg, params, u),
                       axis=-1).astype(jnp.int32)               # [S, W]
        # the draft behind the last committed row is k = 1 token deep
        nxt = jnp.take_along_axis(
            d, jnp.maximum(n_commit - 1, 0)[:, None], axis=1)
    return _pool_of(slabs), jnp.concatenate([
        jnp.where(active[:, None], g, toks_w).reshape(-1), n_commit,
        nxt.reshape(-1), counts]).astype(jnp.int32)
