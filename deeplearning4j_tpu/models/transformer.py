"""Transformer encoder — the attention stack the reference never had.

SURVEY.md §5.7: the reference has NO attention (closest: LSTM.java,
moving-window featurization).  BERT-base is the driver-defined north star
(BASELINE.json), so attention is built here as a first-class TPU-native
component rather than a port of anything:

- All matmuls run in bfloat16 (MXU-native) with fp32 accumulation
  (``preferred_element_type``) and fp32 softmax/layernorm.
- Per-layer parameters are STACKED along a leading ``[n_layers, ...]`` axis
  and the block stack runs under ``lax.scan`` — one compiled block body
  regardless of depth (compile time O(1) in layers), remat-able with
  ``jax.checkpoint`` to trade FLOPs for HBM.
- Sharding is expressed as a pytree of ``PartitionSpec`` rules
  (``param_specs``/``act_spec``) against the package-wide mesh axis names
  (parallel/mesh.py): tensor-parallel attention heads + column/row-parallel
  MLP over ``model``, sequence over ``seq``, batch over ``data``.  Under
  ``jit`` XLA inserts the psum/all-gather collectives — the scaling-book
  recipe, not hand-written NCCL (reference's four RPC stacks, SURVEY §5.8).
- Long context: ``attention`` dispatches to ring attention
  (parallel/ring_attention.py — ppermute blockwise over ICI) when a ``seq``
  axis is present in the active shard_map context.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                              PIPE_AXIS, SEQ_AXIS)

Array = jax.Array
PyTree = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 30522          # BERT wordpiece vocab
    max_len: int = 512
    type_vocab_size: int = 2
    hidden: int = 768
    n_layers: int = 12
    n_heads: int = 12
    ffn_dim: int = 3072
    dropout: float = 0.1
    layer_norm_eps: float = 1e-12
    compute_dtype: str = "bfloat16"
    remat: bool = True               # jax.checkpoint each block (HBM saver)
    causal: bool = False             # BERT is bidirectional; GPT-style sets True

    @property
    def head_dim(self) -> int:
        assert self.hidden % self.n_heads == 0
        return self.hidden // self.n_heads


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _trunc_normal(key, shape, stddev=0.02, dtype=jnp.float32):
    return stddev * jax.random.truncated_normal(key, -2.0, 2.0, shape, dtype)


def init_params(key: Array, cfg: TransformerConfig) -> PyTree:
    """Stacked-block parameter pytree. Layout chosen for scan + TP sharding."""
    ks = jax.random.split(key, 16)
    H, L, F, NH, D = cfg.hidden, cfg.n_layers, cfg.ffn_dim, cfg.n_heads, cfg.head_dim

    def stack(fn, k):
        return jax.vmap(fn)(jax.random.split(k, L))

    embed = {
        "tok": _trunc_normal(ks[0], (cfg.vocab_size, H)),
        "pos": _trunc_normal(ks[1], (cfg.max_len, H)),
        "type": _trunc_normal(ks[2], (cfg.type_vocab_size, H)),
        "ln_g": jnp.ones((H,)), "ln_b": jnp.zeros((H,)),
    }
    blocks = {
        # attention — [L, H, NH, D] so the head axis is shardable over `model`
        "wq": stack(lambda k: _trunc_normal(k, (H, NH, D)), ks[3]),
        "wk": stack(lambda k: _trunc_normal(k, (H, NH, D)), ks[4]),
        "wv": stack(lambda k: _trunc_normal(k, (H, NH, D)), ks[5]),
        "wo": stack(lambda k: _trunc_normal(k, (NH, D, H)), ks[6]),
        "bq": jnp.zeros((L, NH, D)), "bk": jnp.zeros((L, NH, D)),
        "bv": jnp.zeros((L, NH, D)), "bo": jnp.zeros((L, H)),
        "ln1_g": jnp.ones((L, H)), "ln1_b": jnp.zeros((L, H)),
        # MLP — column-parallel w1, row-parallel w2
        "w1": stack(lambda k: _trunc_normal(k, (H, F)), ks[7]),
        "b1": jnp.zeros((L, F)),
        "w2": stack(lambda k: _trunc_normal(k, (F, H)), ks[8]),
        "b2": jnp.zeros((L, H)),
        "ln2_g": jnp.ones((L, H)), "ln2_b": jnp.zeros((L, H)),
    }
    return {"embed": embed, "blocks": blocks}


def param_specs(cfg: TransformerConfig) -> PyTree:  # jaxlint: disable=spec-without-divisibility-guard — degree-independent rule tree; shard_specs is the validated degree-parameterized entry point
    """PartitionSpec rules: TP over `model` (heads / ffn), everything else
    replicated over `data`/`seq`.  Matches init_params layout exactly.
    Degree-independent by design — ``shard_specs`` layers the
    divisibility validation on top and is the entry point every
    degree-parameterized caller (sharded fit, decode engine) uses."""
    m = MODEL_AXIS
    embed = {"tok": P(None, None), "pos": P(None, None), "type": P(None, None),
             "ln_g": P(None), "ln_b": P(None)}
    blocks = {
        "wq": P(None, None, m, None), "wk": P(None, None, m, None),
        "wv": P(None, None, m, None), "wo": P(None, m, None, None),
        "bq": P(None, m, None), "bk": P(None, m, None), "bv": P(None, m, None),
        "bo": P(None, None),
        "ln1_g": P(None, None), "ln1_b": P(None, None),
        "w1": P(None, None, m), "b1": P(None, m),
        "w2": P(None, m, None), "b2": P(None, None),
        "ln2_g": P(None, None), "ln2_b": P(None, None),
    }
    return {"embed": embed, "blocks": blocks}


def pipe_stage_specs(block_specs: PyTree, cfg, pipe_degree: int) -> PyTree:
    """Lay the stacked ``[n_layers, ...]`` block leaves out over the
    ``pipe`` axis: each pipe shard holds a contiguous group of
    ``n_layers / pipe_degree`` layers — the GPipe stage slicing
    expressed as a ``NamedSharding`` layout instead of a hand-written
    schedule (the layer ``lax.scan`` walks the stages in order; XLA
    owns the stage-boundary transfers).  Validates the real constraint
    up front: layers must split evenly into stages."""
    if cfg.n_layers % pipe_degree:
        raise ValueError(
            f"n_layers={cfg.n_layers} not divisible by pipe degree "
            f"{pipe_degree} — stacked layers split into equal "
            f"contiguous pipeline stages over `pipe`")
    return jax.tree.map(lambda s: P(PIPE_AXIS, *tuple(s)[1:]), block_specs,
                        is_leaf=lambda s: isinstance(s, P))


def shard_specs(cfg: TransformerConfig, model_degree: int = 1,
                pipe_degree: int = 1) -> PyTree:
    """Per-layer weight sharding specs for data×model(×pipe) GSPMD
    training and serving (parallel/sharded_fit GSPMD mode,
    serving/decode model sharding): ``param_specs``'s tensor-parallel
    rules — attention heads and MLP hidden over ``model`` — PLUS the
    token embedding (and, via weight tying, the output projection)
    sharded over vocab when the degree divides it, PLUS the stacked
    layer axis split into contiguous pipeline stages over ``pipe`` when
    ``pipe_degree > 1``.  Validates divisibility up front so a bad
    (cfg, mesh) pairing fails at build time with the real constraint,
    not deep inside XLA partitioning."""
    if model_degree > 1:
        if cfg.n_heads % model_degree:
            raise ValueError(
                f"n_heads={cfg.n_heads} not divisible by model degree "
                f"{model_degree} — attention heads shard over `model`")
        if cfg.ffn_dim % model_degree:
            raise ValueError(
                f"ffn_dim={cfg.ffn_dim} not divisible by model degree "
                f"{model_degree} — the MLP hidden shards over `model`")
    specs = param_specs(cfg)
    if model_degree > 1 and cfg.vocab_size % model_degree == 0:
        specs["embed"]["tok"] = P(MODEL_AXIS, None)
    if pipe_degree > 1:
        specs["blocks"] = pipe_stage_specs(specs["blocks"], cfg, pipe_degree)
    return specs


def act_spec() -> P:
    """[B, T, H] activations: batch over data, sequence over seq."""
    return P(DATA_AXIS, SEQ_AXIS, None)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def layer_norm(x: Array, g: Array, b: Array, eps: float) -> Array:
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


@jax.named_scope("attention")
def attention(q: Array, k: Array, v: Array, mask: Optional[Array],
              causal: bool = False) -> Array:
    """Plain fused attention: [B, T, NH, D] -> [B, T, NH, D].

    fp32 softmax, bf16 matmuls with fp32 accumulation.  For sequence-parallel
    execution use parallel/ring_attention.ring_attention (same signature plus
    axis_name) — this function is the single-shard block it rings over.
    """
    cdt = q.dtype
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        # mask: [B, Tk] attention (1=keep) -> additive
        logits = logits + (1.0 - mask[:, None, None, :]) * jnp.float32(-1e9)
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((tq, tk), jnp.bool_))
        logits = jnp.where(cm[None, None], logits, jnp.float32(-1e9))
    probs = jax.nn.softmax(logits, axis=-1).astype(cdt)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v,
                      preferred_element_type=jnp.float32).astype(cdt)


def _attention_sublayer(cfg, x: Array, p: Dict[str, Array],
                        mask: Optional[Array],
                        dropout_key: Optional[Array],
                        attn_fn=attention) -> Tuple[Array, Optional[Array]]:
    """Attention + residual + post-LN — the first half of an encoder
    block, shared by the dense-FFN block below and the MoE-FFN block
    (models/moe.py).  ``cfg`` needs compute_dtype/causal/dropout/
    layer_norm_eps (TransformerConfig or MoETransformerConfig).  Returns
    (x', ffn_dropout_key)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    h = x.astype(cdt)

    q = jnp.einsum("bth,hnd->btnd", h, p["wq"].astype(cdt),
                   preferred_element_type=jnp.float32) + p["bq"]
    k = jnp.einsum("bth,hnd->btnd", h, p["wk"].astype(cdt),
                   preferred_element_type=jnp.float32) + p["bk"]
    v = jnp.einsum("bth,hnd->btnd", h, p["wv"].astype(cdt),
                   preferred_element_type=jnp.float32) + p["bv"]
    a = attn_fn(q.astype(cdt), k.astype(cdt), v.astype(cdt), mask,
                cfg.causal)
    a = jnp.einsum("btnd,ndh->bth", a.astype(cdt), p["wo"].astype(cdt),
                   preferred_element_type=jnp.float32) + p["bo"]
    if dropout_key is not None and cfg.dropout > 0.0:
        dk1, dk2 = jax.random.split(dropout_key)
        keep = 1.0 - cfg.dropout
        a = a * jax.random.bernoulli(dk1, keep, a.shape) / keep
    else:
        dk2 = None
    return layer_norm(x + a, p["ln1_g"], p["ln1_b"],
                      cfg.layer_norm_eps), dk2


def _block(cfg: TransformerConfig, x: Array, p: Dict[str, Array],
           mask: Optional[Array], dropout_key: Optional[Array],
           attn_fn=attention) -> Array:
    """One post-LN encoder block (BERT convention): x [B, T, H] fp32."""
    cdt = jnp.dtype(cfg.compute_dtype)
    x, dk2 = _attention_sublayer(cfg, x, p, mask, dropout_key, attn_fn)

    h = x.astype(cdt)
    f = jnp.einsum("bth,hf->btf", h, p["w1"].astype(cdt),
                   preferred_element_type=jnp.float32) + p["b1"]
    f = jax.nn.gelu(f).astype(cdt)
    f = jnp.einsum("btf,fh->bth", f, p["w2"].astype(cdt),
                   preferred_element_type=jnp.float32) + p["b2"]
    if dk2 is not None and cfg.dropout > 0.0:
        keep = 1.0 - cfg.dropout
        f = f * jax.random.bernoulli(dk2, keep, f.shape) / keep
    return layer_norm(x + f, p["ln2_g"], p["ln2_b"], cfg.layer_norm_eps)


def embed(cfg: TransformerConfig, params: PyTree, token_ids: Array,
          type_ids: Optional[Array] = None,
          position_offset: int | Array = 0) -> Array:
    """[B, T] ids -> [B, T, H] fp32 embeddings (tok + pos + type, LN).

    ``position_offset`` supports sequence-parallel shards embedding their
    slice of a long sequence with correct absolute positions."""
    e = params["embed"]
    T = token_ids.shape[-1]
    x = e["tok"][token_ids]
    idx = jnp.arange(T) + position_offset
    x = x + jnp.take(e["pos"], idx, axis=0)
    if type_ids is not None:
        x = x + e["type"][type_ids]
    return layer_norm(x, e["ln_g"], e["ln_b"], cfg.layer_norm_eps)


def encode(cfg: TransformerConfig, params: PyTree, token_ids: Array,
           mask: Optional[Array] = None, type_ids: Optional[Array] = None,
           dropout_key: Optional[Array] = None,
           position_offset: int | Array = 0,
           attn_fn=attention) -> Array:
    """Full encoder: ids [B, T] -> hidden states [B, T, H] (fp32).

    Scans one remat-ed block body over the stacked [L, ...] params."""
    x = embed(cfg, params, token_ids, type_ids, position_offset)

    blocks = params["blocks"]
    L = cfg.n_layers
    use_dropout = dropout_key is not None and cfg.dropout > 0.0
    dkeys = (jax.random.split(dropout_key, L) if use_dropout
             else jnp.zeros((L, 2), jnp.uint32))

    def body(x, inputs):
        p, dk = inputs
        return _block(cfg, x, p, mask, dk if use_dropout else None,
                      attn_fn), None

    if cfg.remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, (blocks, dkeys))
    return x
