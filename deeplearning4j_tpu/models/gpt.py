"""GPT-style causal language model + KV-cache autoregressive decoding.

New capability (the reference's only generative sequence model is the
char-LSTM, models/classifiers/lstm/LSTM.java); the causal LM reuses the
transformer encoder stack with ``causal=True`` and adds the TPU-native
decode path:

- Training: next-token cross-entropy over the full sequence (one MXU-dense
  forward, shifted labels) — ``make_train_step`` shards dp/tp over the
  mesh exactly like models/bert.
- Generation: a KV cache [L, B, T_max, NH, D] carried through a
  ``lax.scan`` — one compiled program generates N tokens with no
  per-token retracing or host round trips; each step attends over the
  cache prefix with a position mask (static shapes, as XLA wants).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS

Array = jax.Array
PyTree = Any


def gpt_config(vocab_size: int = 50257, max_len: int = 1024,
               hidden: int = 768, n_layers: int = 12, n_heads: int = 12
               ) -> TransformerConfig:
    return TransformerConfig(vocab_size=vocab_size, max_len=max_len,
                             hidden=hidden, n_layers=n_layers,
                             n_heads=n_heads, ffn_dim=4 * hidden,
                             causal=True, type_vocab_size=1)


def gpt_tiny(vocab_size: int = 256, max_len: int = 128) -> TransformerConfig:
    return TransformerConfig(vocab_size=vocab_size, max_len=max_len,
                             hidden=64, n_layers=2, n_heads=4, ffn_dim=128,
                             dropout=0.0, causal=True, type_vocab_size=1)


def init_params(key: Array, cfg: TransformerConfig) -> PyTree:
    if not cfg.causal:
        raise ValueError("GPT config must be causal")
    return tfm.init_params(key, cfg)


def shard_specs(cfg: TransformerConfig, model_degree: int = 1,
                pipe_degree: int = 1) -> PyTree:
    """data×model(×pipe) sharding specs for the GPT family: attention
    heads + MLP hidden over ``model``, the tied token embedding (= the
    LM output projection) over vocab when the degree divides it, and
    the stacked layer axis split into contiguous pipeline stages over
    ``pipe``.  The GPT param tree IS the transformer tree, so this is
    ``transformer.shard_specs`` re-exported under the family name the
    sharded-fit/serving plumbing asks for."""
    return tfm.shard_specs(cfg, model_degree, pipe_degree)


def lm_logits(cfg: TransformerConfig, params: PyTree, hidden: Array) -> Array:
    """Tied-embedding readout [B, T, H] -> [B, T, vocab]."""
    cdt = jnp.dtype(cfg.compute_dtype)
    return jnp.einsum("bth,vh->btv", hidden.astype(cdt),
                      params["embed"]["tok"].astype(cdt),
                      preferred_element_type=jnp.float32)


def lm_loss(cfg: TransformerConfig, params: PyTree, token_ids: Array,
            mask: Optional[Array] = None,
            dropout_key: Optional[Array] = None,
            attn_fn=tfm.attention) -> Array:
    """Next-token CE: predict token_ids[:, 1:] from positions [:, :-1]."""
    hidden = tfm.encode(cfg, params, token_ids, mask, None, dropout_key,
                        attn_fn=attn_fn)
    targets = token_ids[:, 1:]
    with jax.named_scope("readout"):
        logits = lm_logits(cfg, params, hidden[:, :-1])
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    if mask is not None:
        w = mask[:, 1:]
        return -jnp.sum(ll * w) / jnp.maximum(jnp.sum(w), 1.0)
    return -jnp.mean(ll)


class TrainState(NamedTuple):
    params: PyTree
    opt_state: PyTree
    step: Array


def make_train_step(cfg: TransformerConfig, mesh: Mesh,
                    optimizer: Optional[optax.GradientTransformation] = None,
                    attn_fn=None) -> Tuple[Callable, Callable]:
    """Same sharding scheme as models/bert.make_train_step: params over
    the model axis (tp), batch over data.  ``attn_fn=None`` defaults to
    the ``make_attn_fn`` auto policy (causal flash attention on TPU when
    it wins, XLA otherwise — see models/bert.make_train_step)."""
    if attn_fn is None:
        from deeplearning4j_tpu.ops.pallas_attention import make_attn_fn
        attn_fn = make_attn_fn("auto", mesh=mesh)
    optimizer = optimizer or optax.adamw(3e-4, weight_decay=0.01)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                          tfm.param_specs(cfg))
    dsh = NamedSharding(mesh, P(DATA_AXIS, None))
    repl = NamedSharding(mesh, P())

    def init_fn(key: Array) -> TrainState:
        params = init_params(key, cfg)
        return TrainState(params, optimizer.init(params),
                          jnp.zeros((), jnp.int32))

    def _step(state: TrainState, token_ids: Array, key: Array):
        loss, grads = jax.value_and_grad(
            lambda p: lm_loss(cfg, p, token_ids, None, key, attn_fn)
        )(state.params)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    cache: Dict[str, Callable] = {}

    def step_fn(state: TrainState, token_ids: Array, key: Array):
        if "fn" not in cache:
            osh = jax.tree.map(
                lambda x: repl,
                jax.eval_shape(optimizer.init,
                               jax.eval_shape(lambda: state.params)))
            st_sh = TrainState(pshard, osh, repl)
            cache["fn"] = jax.jit(_step,
                                  in_shardings=(st_sh, dsh, repl),
                                  out_shardings=(st_sh, repl),
                                  donate_argnums=(0,))
        return cache["fn"](state, token_ids, key)

    return init_fn, step_fn


# ---------------------------------------------------------------------------
# KV-cache decoding
# ---------------------------------------------------------------------------

class KVCache(NamedTuple):
    k: Array            # [L, B, T_max, NH, D]
    v: Array


class QKVCache(NamedTuple):
    """int8 KV cache: same geometry as :class:`KVCache` but the values
    are symmetric int8 with one fp32 scale per WRITTEN TOKEN ROW
    (amax over that row's heads x head_dim) — ``k_scale``/``v_scale``
    [L, B, T_max].  4x the cache rows per byte vs fp32 (2x vs bf16) at
    a scale overhead of 8 bytes per token row; attention dequantizes
    the rows it reads in-program (the multiply fuses into the score/
    value matmuls), so no fp32 cache copy ever materializes."""
    k: Array            # int8 [L, B, T_max, NH, D]
    v: Array
    k_scale: Array      # fp32 [L, B, T_max]
    v_scale: Array


def _kv_quant(x: Array) -> Tuple[Array, Array]:
    """Quantize fresh K/V rows [..., NH, D] -> (int8 rows, fp32 scale
    [...]) with one symmetric scale per row (amax over NH x D) — the
    same grid as the weight quantizer (runtime/quantize.py QMAX /
    SCALE_EPS), so the two paths can never drift apart."""
    from deeplearning4j_tpu.runtime.quantize import QMAX, SCALE_EPS

    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=(-2, -1))
    scale = jnp.maximum(amax, SCALE_EPS) / QMAX
    q = jnp.clip(jnp.round(x / scale[..., None, None]),
                 -QMAX, QMAX).astype(jnp.int8)
    return q, scale


def _kv_load(q: Array, scale: Array, cdt) -> Array:
    """Dequantize cache rows — [..., NH, D], or [..., NH*D] as a page
    pool stores them — back to the compute dtype (fused into the
    consuming attention matmul under jit)."""
    scale = scale.reshape(scale.shape + (1,) * (q.ndim - scale.ndim))
    return (q.astype(jnp.float32) * scale).astype(cdt)


def init_cache(cfg: TransformerConfig, batch: int,
               max_len: Optional[int] = None) -> KVCache:
    T = max_len or cfg.max_len
    shape = (cfg.n_layers, batch, T, cfg.n_heads, cfg.head_dim)
    cdt = jnp.dtype(cfg.compute_dtype)
    return KVCache(jnp.zeros(shape, cdt), jnp.zeros(shape, cdt))


def _decode_step(cfg: TransformerConfig, params: PyTree, cache: KVCache,
                 token: Array, pos: Array) -> Tuple[KVCache, Array]:
    """One token through the stack, reading/extending the cache.

    token [B] int32; pos scalar int32 (current position).  Returns
    (cache', logits [B, vocab]).
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    B = token.shape[0]
    T_max = cache.k.shape[2]
    x = tfm.embed(cfg, params, token[:, None], None, pos)     # [B, 1, H]

    valid = (jnp.arange(T_max) <= pos)                        # attend <= pos
    new_k, new_v = [], []
    blocks = params["blocks"]
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a, l=layer: a[l], blocks)
        h = x.astype(cdt)
        q = jnp.einsum("bth,hnd->btnd", h, p["wq"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["bq"]
        k1 = jnp.einsum("bth,hnd->btnd", h, p["wk"].astype(cdt),
                        preferred_element_type=jnp.float32) + p["bk"]
        v1 = jnp.einsum("bth,hnd->btnd", h, p["wv"].astype(cdt),
                        preferred_element_type=jnp.float32) + p["bv"]
        k_cache = lax.dynamic_update_slice(
            cache.k[layer], k1.astype(cdt), (0, pos, 0, 0))
        v_cache = lax.dynamic_update_slice(
            cache.v[layer], v1.astype(cdt), (0, pos, 0, 0))
        new_k.append(k_cache)
        new_v.append(v_cache)

        scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
        s = jnp.einsum("bqnd,bknd->bnqk", q.astype(cdt), k_cache,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[None, None, None, :], s, -1e9)
        probs = jax.nn.softmax(s, axis=-1).astype(cdt)
        a = jnp.einsum("bnqk,bknd->bqnd", probs, v_cache,
                       preferred_element_type=jnp.float32)
        a = jnp.einsum("btnd,ndh->bth", a.astype(cdt), p["wo"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["bo"]
        x = tfm.layer_norm(x + a, p["ln1_g"], p["ln1_b"], cfg.layer_norm_eps)

        h = x.astype(cdt)
        f = jnp.einsum("bth,hf->btf", h, p["w1"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["b1"]
        f = jax.nn.gelu(f).astype(cdt)
        f = jnp.einsum("btf,fh->bth", f, p["w2"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["b2"]
        x = tfm.layer_norm(x + f, p["ln2_g"], p["ln2_b"], cfg.layer_norm_eps)

    logits = lm_logits(cfg, params, x)[:, 0, :]
    return KVCache(jnp.stack(new_k), jnp.stack(new_v)), logits


def _embed_rows(cfg: TransformerConfig, params: PyTree, toks: Array,
                pos: Array) -> Array:
    """Token + position rows through the embedding LayerNorm, fp32, for
    ``toks`` at positions ``pos`` (broadcast against it), clipped to the
    model's positions: a padded row or an idle slot may lie past them,
    ``tfm.embed`` (``jnp.take``) would give it a NaN position row, and a
    NaN row, once cached, poisons every row that reads it at weight 0."""
    e = params["embed"]
    pos = jnp.clip(pos, 0, cfg.max_len - 1)
    x = e["tok"][toks] + e["pos"][pos]
    return tfm.layer_norm(x, e["ln_g"], e["ln_b"], cfg.layer_norm_eps)


def _prefill_chunk(cfg: TransformerConfig, params: PyTree, cache: KVCache,
                   toks: Array, start: Array) -> Tuple[KVCache, Array]:
    """One dense prefill chunk: ``toks`` [B, C] int32 at positions
    ``start + [0, C)`` through the stack, K/V written into the cache as
    a C-wide slab (``lax.dynamic_update_slice``), causal attention over
    the cached prefix + the chunk itself.  Returns (cache', logits
    [B, C, vocab]) — the C-token generalization of ``_decode_step``
    (C=1 reduces to it), so prompt ingestion is matmul-bound instead of
    T_prompt sequential steps.  ``cache`` may be a :class:`QKVCache`:
    the slab then quantizes to int8 on write (one scale per token row)
    and attention dequantizes the rows it reads in-program — same
    interface, 1/4 the cache bytes."""
    cdt = jnp.dtype(cfg.compute_dtype)
    quant = isinstance(cache, QKVCache)
    B, C = toks.shape
    T_max = cache.k.shape[2]
    pos_q = start + jnp.arange(C)                             # [C]
    x = _embed_rows(cfg, params, toks, pos_q)                 # [B, C, H]

    # causal over the whole cache row: key col <= query pos.  Stale or
    # padded K/V beyond the written slab sits at col > pos and is never
    # attended; garbage WITHIN the slab from padded prompt rows is
    # excluded the same way (pad rows only ever follow real rows).
    valid = pos_q[:, None] >= jnp.arange(T_max)[None, :]      # [C, T_max]
    new_k, new_v, new_ks, new_vs = [], [], [], []
    blocks = params["blocks"]
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a, l=layer: a[l], blocks)
        h = x.astype(cdt)
        q = jnp.einsum("bth,hnd->btnd", h, p["wq"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["bq"]
        k1 = jnp.einsum("bth,hnd->btnd", h, p["wk"].astype(cdt),
                        preferred_element_type=jnp.float32) + p["bk"]
        v1 = jnp.einsum("bth,hnd->btnd", h, p["wv"].astype(cdt),
                        preferred_element_type=jnp.float32) + p["bv"]
        if quant:
            kq, ks = _kv_quant(k1)                  # [B,C,NH,D]i8, [B,C]
            vq, vs = _kv_quant(v1)
            k_cache = lax.dynamic_update_slice(
                cache.k[layer], kq, (0, start, 0, 0))
            v_cache = lax.dynamic_update_slice(
                cache.v[layer], vq, (0, start, 0, 0))
            ks_cache = lax.dynamic_update_slice(
                cache.k_scale[layer], ks, (0, start))
            vs_cache = lax.dynamic_update_slice(
                cache.v_scale[layer], vs, (0, start))
            new_ks.append(ks_cache)
            new_vs.append(vs_cache)
            k_read = _kv_load(k_cache, ks_cache, cdt)
            v_read = _kv_load(v_cache, vs_cache, cdt)
        else:
            k_cache = lax.dynamic_update_slice(
                cache.k[layer], k1.astype(cdt), (0, start, 0, 0))
            v_cache = lax.dynamic_update_slice(
                cache.v[layer], v1.astype(cdt), (0, start, 0, 0))
            k_read, v_read = k_cache, v_cache
        new_k.append(k_cache)
        new_v.append(v_cache)

        with jax.named_scope("attention"):
            scale = 1.0 / jnp.sqrt(jnp.asarray(cfg.head_dim, jnp.float32))
            s = jnp.einsum("bqnd,bknd->bnqk", q.astype(cdt), k_read,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid[None, None, :, :], s, -1e9)
            probs = jax.nn.softmax(s, axis=-1).astype(cdt)
            a = jnp.einsum("bnqk,bknd->bqnd", probs, v_read,
                           preferred_element_type=jnp.float32)
        a = jnp.einsum("btnd,ndh->bth", a.astype(cdt), p["wo"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["bo"]
        x = tfm.layer_norm(x + a, p["ln1_g"], p["ln1_b"], cfg.layer_norm_eps)

        h = x.astype(cdt)
        f = jnp.einsum("bth,hf->btf", h, p["w1"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["b1"]
        f = jax.nn.gelu(f).astype(cdt)
        f = jnp.einsum("btf,fh->bth", f, p["w2"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["b2"]
        x = tfm.layer_norm(x + f, p["ln2_g"], p["ln2_b"], cfg.layer_norm_eps)

    with jax.named_scope("readout"):
        logits = lm_logits(cfg, params, x)                    # [B, C, V]
    if quant:
        return QKVCache(jnp.stack(new_k), jnp.stack(new_v),
                        jnp.stack(new_ks), jnp.stack(new_vs)), logits
    return KVCache(jnp.stack(new_k), jnp.stack(new_v)), logits


#: default dense-prefill chunk width (positions per slab); prompts are
#: right-padded up to a multiple of this, so the compile count per cache
#: shape is ONE regardless of prompt length
PREFILL_CHUNK = 32

#: the leaves every serving step of this family (prefill, decode, verify,
#: draft) reads ONLY through ``.astype(cfg.compute_dtype)``, by their
#: keys from the root: ``DecodeEngine`` may hold them in that type, cast
#: once per tree, and each step's cast is then no operation.  NOT
#: ``embed.tok``: the readout casts it, but the embedding look-up reads
#: it as stored (float32 into the LayerNorm), so casting it would change
#: the mathematics; biases, LayerNorm leaves and ``embed.pos`` are added
#: in float32.
COMPUTE_DTYPE_LEAVES = tuple(("blocks", name) for name in
                             ("wq", "wk", "wv", "wo", "w1", "w2"))


def prefill_cache(cfg: TransformerConfig, params: PyTree, cache: KVCache,
                  prompt: Array, chunk: int = PREFILL_CHUNK
                  ) -> Tuple[KVCache, Array]:
    """Chunked dense prefill: ingest ``prompt`` [B, T_p] into ``cache``
    in ``chunk``-wide slabs (one ``lax.scan`` over slabs — a single
    compiled chunk body for any prompt length) and return (cache',
    logits [B, vocab] at the LAST prompt position) ready for the first
    sampling step."""
    B, T_p = prompt.shape
    C = min(chunk, T_p)
    n_chunks = -(-T_p // C)
    pad = n_chunks * C - T_p
    toks = jnp.pad(prompt, ((0, 0), (0, pad))) if pad else prompt
    toks = toks.reshape(B, n_chunks, C)

    def body(cache, inp):
        ck, c_start, n_valid = inp
        cache, logits = _prefill_chunk(cfg, params, cache, ck, c_start)
        last = lax.dynamic_slice_in_dim(logits, n_valid - 1, 1, axis=1)
        return cache, last[:, 0]

    starts = jnp.arange(n_chunks) * C
    valids = jnp.minimum(T_p - starts, C)
    cache, lasts = lax.scan(body, cache,
                            (jnp.moveaxis(toks, 1, 0), starts, valids))
    return cache, lasts[-1]


def sample_token(logits: Array, key: Array, temperature: Array) -> Array:
    """One sampling decision [..., vocab] -> [...] int32: categorical at
    ``temperature`` > 0, greedy argmax at ``temperature`` <= 0 (the
    traced ``where`` keeps one compiled program serving both modes, so a
    mixed greedy/sampled slot batch never recompiles)."""
    t = jnp.maximum(jnp.asarray(temperature, jnp.float32), 1e-6)
    sampled = jax.random.categorical(key, logits / t, axis=-1)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(jnp.asarray(temperature) > 0.0, sampled,
                     greedy).astype(jnp.int32)


def _slot_key(seed: Array, pos: Array) -> Array:
    """Per-(request, position) sampling key: deterministic for a given
    request seed regardless of which slot or step the token lands on —
    the property the continuous batcher's reproducibility rests on."""
    return jax.random.fold_in(jax.random.fold_in(jax.random.key(0),
                                                 seed), pos)


def generate(cfg: TransformerConfig, params: PyTree, prompt: Array,
             n_tokens: int, key: Array, temperature: float = 1.0,
             max_len: Optional[int] = None,
             prefill_chunk: int = PREFILL_CHUNK) -> Array:
    """Sample ``n_tokens`` continuations for ``prompt`` [B, T_p] int32.

    Chunked dense prefill ingests the prompt matmul-bound (K/V written
    in slabs), then one lax.scan emits the continuation — the whole
    thing is two compiled programs total.  ``temperature=0`` decodes
    greedily (argmax)."""
    B, T_p = prompt.shape
    T_max = max_len or cfg.max_len
    if T_p + n_tokens > T_max:
        raise ValueError(f"prompt {T_p} + {n_tokens} exceeds max {T_max}")
    cache = init_cache(cfg, B, T_max)
    cache, logits = prefill_cache(cfg, params, cache, prompt,
                                  chunk=prefill_chunk)

    def gen_step(carry, inputs):
        cache, logits = carry
        k, pos = inputs
        nxt = sample_token(logits, k, jnp.float32(temperature))
        cache, logits = _decode_step(cfg, params, cache, nxt, pos)
        return (cache, logits), nxt

    keys = jax.random.split(key, n_tokens)
    _, out = lax.scan(gen_step, (cache, logits),
                      (keys, T_p + jnp.arange(n_tokens)))
    return jnp.moveaxis(out, 0, 1)                            # [B, n_tokens]


def forward_logits(cfg: TransformerConfig, params: PyTree,
                   token_ids: Array) -> Array:
    """Dense (non-cached) forward for parity checks: [B, T] -> [B, T, V]."""
    hidden = tfm.encode(cfg, params, token_ids)
    return lm_logits(cfg, params, hidden)


# ---------------------------------------------------------------------------
# Paged KV storage (serving tier 3)
# ---------------------------------------------------------------------------

class PagedKV(NamedTuple):
    """Pool of fixed-size KV pages [L, P, C, NH*D] (C tokens per page;
    a token row's heads and head width stored as ONE minor dimension,
    so a page is C contiguous lane-dense rows whatever NH and D are —
    [.., 20, 64] minors pad, and the TPU then lays the pool out pages
    -minor, which no page gather or row scatter can use in place).
    A slot's cache row is not a [T_max] slab of its own: a host-side
    page table maps its chunk-aligned position ranges onto pool pages,
    so HBM holds only the pages live tokens occupy — 'slots per chip'
    is bounded by live tokens, not bucket length.  A dispatch never
    holds more of it than one layer's pages of the slots it serves
    (:func:`_read_pages`), and writes only the fresh rows, into the
    donated pool in place (:func:`_write_rows`).  Page 0 is the
    reserved TRASH page: unused page-table entries point at it and
    inactive-slot writes are redirected into it, so a freed page can be
    handed to another slot without scrubbing.  int8 pools carry per-
    token-row scales [L, P, C] (same grid as :class:`QKVCache`)."""
    k: Array
    v: Array
    k_scale: Optional[Array] = None
    v_scale: Optional[Array] = None


def init_pages(cfg: TransformerConfig, n_pages: int, page_tokens: int,
               kv_dtype: Optional[str] = None) -> PagedKV:
    shape = (cfg.n_layers, n_pages, page_tokens, cfg.n_heads * cfg.head_dim)
    if kv_dtype is None:
        cdt = jnp.dtype(cfg.compute_dtype)
        return PagedKV(jnp.zeros(shape, cdt), jnp.zeros(shape, cdt))
    if kv_dtype != "int8":
        raise ValueError(f"kv_dtype must be None or 'int8': {kv_dtype!r}")
    sshape = (cfg.n_layers, n_pages, page_tokens)
    return PagedKV(jnp.zeros(shape, jnp.int8), jnp.zeros(shape, jnp.int8),
                   jnp.zeros(sshape, jnp.float32),
                   jnp.zeros(sshape, jnp.float32))


def pages_bytes(cfg: TransformerConfig, n_pages: int, page_tokens: int,
                kv_dtype: Optional[str] = None) -> int:
    """Persistent pool bytes, an int8 pool's scale rows included — the
    engine's HBM denominator (what a dispatch gathers for attention is
    one layer's pages of its slots at a time, dispatch-transient)."""
    elems = cfg.n_layers * n_pages * page_tokens * cfg.n_heads * cfg.head_dim
    if kv_dtype == "int8":
        return 2 * elems + 2 * cfg.n_layers * n_pages * page_tokens * 4
    return 2 * elems * jnp.dtype(cfg.compute_dtype).itemsize


def slots_bytes_per_slot(cfg: TransformerConfig, t_max: int,
                         kv_dtype: Optional[str] = None) -> int:
    """KV-cache bytes one slot of a ``t_max`` bucket costs when every
    page of it is live — the denominator of 'slots per chip' capacity
    planning (``DecodeEngine.kv_bytes_per_slot``)."""
    return pages_bytes(cfg, 1, t_max, kv_dtype)


def paged_specs(cfg: TransformerConfig,
                kv_dtype: Optional[str] = None) -> "PagedKV":  # jaxlint: disable=spec-without-divisibility-guard — degree-independent; DecodeEngine validates n_heads % model_degree before pinning these specs
    """PartitionSpecs for a model-sharded page pool: the NH*D minor
    dimension over ``model`` in whole heads (each chip holds only its
    heads' cache, as it holds only their weights), scales replicated.
    Page gathers and row scatters index the layer, page and offset axes
    only, so both stay shard-local."""
    h = P(None, None, None, MODEL_AXIS)
    if kv_dtype == "int8":
        return PagedKV(k=h, v=h, k_scale=P(), v_scale=P())
    return PagedKV(k=h, v=h)


def _read_pages(a: Array, lp: Array) -> Array:
    """``a[layer, page]`` for (layer, page) pairs ``lp`` [..., 2]: whole
    pages gathered on the two major axes of a pool array ([L, P, C, F]
    rows or [L, P, C] scales) -> [..., C, F] / [..., C].  One
    ``lax.gather``: ``a[layer, ptab]`` traces a dozen index
    -normalizing ops around the same gather, 72 times a program."""
    n = lp.ndim - 1
    return lax.gather(
        a, lp, lax.GatherDimensionNumbers(
            offset_dims=tuple(range(n, n + a.ndim - 2)),
            collapsed_slice_dims=(0, 1), start_index_map=(0, 1)),
        slice_sizes=(1, 1) + a.shape[2:], mode="clip")


def _every_layer(n_layers: int, pids: Array) -> Array:
    """(layer, page) pairs [L, n, 2] naming pages ``pids`` [n] of every
    layer — what :func:`_read_pages` takes.  A gather that indexes the
    page axis alone (``a[:, pids]``) is compiled as slices of the WHOLE
    pool."""
    return jnp.stack(jnp.broadcast_arrays(
        jnp.arange(n_layers, dtype=pids.dtype)[:, None], pids[None, :]),
        axis=-1)


def _write_rows(a: Array, lpo: Array, rows: Array) -> Array:
    """``a.at[layer, page, offset].set(rows)`` for (layer, page, offset)
    triples ``lpo`` [S, W, 3] and ``rows`` [S, W, F] (or [S, W] scales):
    the one ``lax.scatter``, in place on a donated ``a``."""
    return lax.scatter(
        a, lpo, rows, lax.ScatterDimensionNumbers(
            update_window_dims=tuple(range(2, rows.ndim)),
            inserted_window_dims=(0, 1, 2),
            scatter_dims_to_operand_dims=(0, 1, 2)), mode="clip")


def _rows_attention(q: Array, k: Array, v: Array, valid: Array) -> Array:
    """Attention of ``q`` [S, W, NH, D] over K/V rows as the pool
    stores them, [S, T, NH*D], masked by ``valid`` [S, W, T]; returns
    [S, W, NH, D] fp32.  The rows are never re-tiled per head (a
    [.., NH, D] view of them pads D to the lanes and costs a pass over
    the view a layer): q is laid out block-diagonally, [S, NH*D, NH*W]
    with head n's lanes meeting only head n's columns, so ONE matmul
    over all 1280 lanes gives every head's scores — the terms it adds
    are exact zeros — and the value product's [NH*W, NH*D] result
    keeps its diagonal blocks.  Operands, accumulation and softmax are
    those of :func:`_decode_step`; the NH-fold redundant MXU work is
    free beside the bytes."""
    S, W, NH, D = q.shape
    T = k.shape[1]
    same = np.eye(NH, dtype=np.bool_)[None, :, None, :, None]
    qbd = jnp.where(same, jnp.moveaxis(q, 1, 3)[:, :, :, None, :], 0)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    s = jnp.einsum("btf,bfc->bct", k, qbd.reshape(S, NH * D, NH * W),
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(valid[:, None, :, :], s.reshape(S, NH, W, T), -1e9)
    probs = jax.nn.softmax(s, axis=-1).astype(k.dtype)
    full = jnp.einsum("bct,btf->bcf", probs.reshape(S, NH * W, T), v,
                      preferred_element_type=jnp.float32)
    a = jnp.where(same, full.reshape(S, NH, W, NH, D), 0.0).sum(axis=3)
    return jnp.moveaxis(a, 1, 2)


def _paged_stack(cfg: TransformerConfig, params: PyTree, pool: PagedKV,
                 ptab: Array, toks_w: Array, posw: Array, active: Array
                 ) -> Tuple[PagedKV, Array]:
    """The block stack over a paged pool, ``W`` rows a slot (decode:
    ``W = 1``; verify: ``W = k + 1``; a prefill dispatch does not run
    this: :func:`paged_prefill`): row w of slot s feeds
    ``toks_w[s, w]`` at position ``posw[s, w]``.  Returns (pool',
    hidden [S, W, H]).

    Layer by layer, a strict chain on the one pool: the W fresh rows of
    a layer are written at ``(layer, page, offset)`` — nothing else of
    the pool is — and then the slots' pages of that layer are read back
    through the table, the fresh rows among them (:func:`_read_pages`:
    ``pool.k[layer, ptab]`` → [S, TBL*C, NH*D], S x TBL whole pages
    gathered on the pool's two major axes, never a copy of it), and
    attention runs over ``<= posw`` (:func:`_rows_attention`) — the
    arithmetic of :func:`_decode_step`, row for row.  Every read is of
    the pool as the write before it left it and
    feeds the write after it, so a donated pool is updated in place.
    Writes from inactive slots and out-of-range positions land in the
    trash page (a freed page may ALREADY belong to another live slot,
    so a stale write is not harmless); such
    a slot then attends without its fresh row, and its token is never
    taken."""
    cdt = jnp.dtype(cfg.compute_dtype)
    quant = pool.k_scale is not None
    NH, D = cfg.n_heads, cfg.head_dim
    S, TBL = ptab.shape
    C = pool.k.shape[2]
    T = TBL * C
    x = _embed_rows(cfg, params, toks_w, posw)                # [S, W, H]

    pw = jnp.clip(posw, 0, T - 1)
    ok = (posw >= 0) & (posw < T) & active[:, None]
    pids = jnp.where(ok, jnp.take_along_axis(ptab, pw // C, axis=1), 0)
    # index columns (layer, page[, offset]); the layer is added per layer
    lp0 = jnp.stack([jnp.zeros_like(ptab), ptab], axis=-1)
    lpo0 = jnp.stack([jnp.zeros_like(pids), pids, pw % C], axis=-1)
    valid = jnp.arange(T)[None, None, :] <= posw[:, :, None]  # [S, W, T]
    blocks = params["blocks"]
    for layer in range(cfg.n_layers):
        p = jax.tree.map(lambda a, l=layer: a[l], blocks)
        h = x.astype(cdt)
        q = jnp.einsum("bth,hnd->btnd", h, p["wq"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["bq"]
        k1 = jnp.einsum("bth,hnd->btnd", h, p["wk"].astype(cdt),
                        preferred_element_type=jnp.float32) + p["bk"]
        v1 = jnp.einsum("bth,hnd->btnd", h, p["wv"].astype(cdt),
                        preferred_element_type=jnp.float32) + p["bv"]
        if quant:
            kq, ks = _kv_quant(k1)                  # [S,W,NH,D]i8, [S,W]
            vq, vs = _kv_quant(v1)
            fresh = (kq.reshape(S, -1, NH * D), vq.reshape(S, -1, NH * D),
                     ks, vs)
        else:
            fresh = (k1.astype(cdt).reshape(S, -1, NH * D),
                     v1.astype(cdt).reshape(S, -1, NH * D))
        with jax.named_scope("row_write"):
            lpo = lpo0 + jnp.array([layer, 0, 0], jnp.int32)
            pool = PagedKV(*(_write_rows(a, lpo, r)
                             for a, r in zip(pool, fresh)))
        with jax.named_scope("page_read"):
            lp = lp0 + jnp.array([layer, 0], jnp.int32)
            k_read = _read_pages(pool.k, lp).reshape(S, T, NH * D)
            v_read = _read_pages(pool.v, lp).reshape(S, T, NH * D)
            if quant:
                k_read = _kv_load(
                    k_read, _read_pages(pool.k_scale, lp).reshape(S, T),
                    cdt)
                v_read = _kv_load(
                    v_read, _read_pages(pool.v_scale, lp).reshape(S, T),
                    cdt)
        with jax.named_scope("attention"):
            a = _rows_attention(q.astype(cdt), k_read, v_read, valid)
        a = jnp.einsum("btnd,ndh->bth", a.astype(cdt), p["wo"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["bo"]
        x = tfm.layer_norm(x + a, p["ln1_g"], p["ln1_b"], cfg.layer_norm_eps)

        h = x.astype(cdt)
        f = jnp.einsum("bth,hf->btf", h, p["w1"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["b1"]
        f = jax.nn.gelu(f).astype(cdt)
        f = jnp.einsum("btf,fh->bth", f, p["w2"].astype(cdt),
                       preferred_element_type=jnp.float32) + p["b2"]
        x = tfm.layer_norm(x + f, p["ln2_g"], p["ln2_b"], cfg.layer_norm_eps)
    return pool, x


def paged_prefill(cfg: TransformerConfig, params: PyTree, pool: PagedKV,
                  ptab_s: Array, toks: Array, start: Array, n_valid: Array,
                  temperature: Array, seed: Array) -> Tuple[PagedKV, Array]:
    """Prefill one dispatch's rows ``toks`` [W] of a prompt (W = m C, a
    whole number ``m`` of the pool's C-row pages: the engine derives it,
    ``DecodeEngine.prefill_rows``) into the slot whose page table is
    ``ptab_s`` [TBL], at page-aligned ``start`` (rows past ``n_valid``
    are padding); the other slots' pages ride along untouched — how a
    request joins a RUNNING batch without a barrier.  The rows are the
    ``m`` pages ``ptab_s[start // C : start // C + m]``, persisted as
    ``m`` page writes.  A padded last dispatch may reach PAST the
    table's end (a page-aligned prefix hit starts it anywhere): the
    table is read with ``m - 1`` trash entries behind it, so those
    pages' rows land in the trash page — ``lax.dynamic_slice`` and
    ``dynamic_update_slice`` CLAMP a start that would overrun, which
    would shift the whole slab back onto live rows.  Returns (pool',
    first_token sampled at the last valid row: the final dispatch's
    counts)."""
    L, Pn, C, F = pool.k.shape
    NH, D = cfg.n_heads, cfg.head_dim
    W = toks.shape[0]
    m = W // C
    ptab_v = jnp.concatenate([ptab_s, jnp.zeros((m - 1,), ptab_s.dtype)])
    T = ptab_v.shape[0] * C
    quant = pool.k_scale is not None
    with jax.named_scope("prefill_page_io"):
        lp = _every_layer(L, ptab_v)
        k = _read_pages(pool.k, lp).reshape(L, 1, T, NH, D)
        v = _read_pages(pool.v, lp).reshape(L, 1, T, NH, D)
        if quant:
            cache_in = QKVCache(
                k, v, _read_pages(pool.k_scale, lp).reshape(L, 1, T),
                _read_pages(pool.v_scale, lp).reshape(L, 1, T))
        else:
            cache_in = KVCache(k, v)
    cache, logits = _prefill_chunk(cfg, params, cache_in, toks[None, :],
                                   start)
    with jax.named_scope("readout"):
        last = lax.dynamic_slice_in_dim(logits[0], n_valid - 1, 1,
                                        axis=0)[0]
        first = sample_token(last, _slot_key(seed, start + n_valid - 1),
                             temperature)
    with jax.named_scope("prefill_page_io"):
        lidx = jnp.arange(L)[:, None]
        pids = lax.dynamic_slice_in_dim(ptab_v, start // C, m)
        # the dispatch's rows of each cache leaf, page by page, into
        # the pool leaf they were read from (an int8 pool's scales too)
        pool = PagedKV(*(
            a.at[lidx, pids].set(
                lax.dynamic_slice_in_dim(c, start, W, axis=2).reshape(
                    (L, m, C) + a.shape[3:]))
            for c, a in zip(cache, pool)))
    return pool, first


def paged_decode(cfg: TransformerConfig, params: PyTree, pool: PagedKV,
                 ptab: Array, tokens: Array, pos: Array, active: Array,
                 temperature: Array, seeds: Array
                 ) -> Tuple[PagedKV, Array]:
    """Advance every ACTIVE slot by one token in ONE dispatch: slot s feeds
    ``tokens[s]`` at its own ``pos[s]``, attends over ``<= pos[s]`` and
    samples at ``temperature[s]`` with a key folded from ``seeds[s]`` and
    the position.  A dispatch READS, layer by layer, the pages the table
    names (S x TBL pages of one layer at a time: one rung's rows, never
    the pool) and WRITES each active slot's one new row of every layer
    into the donated pool in place — an inactive slot's into the trash
    page (it computes alongside, fixed shapes, and keeps its token).
    ``tokens``/``pos`` are HOST-tracked (known from the fetched stream),
    so only the pool is device state.  Returns (pool', tokens [S])."""
    pool, x = _paged_stack(cfg, params, pool, ptab, tokens[:, None],
                           pos[:, None], active)
    with jax.named_scope("readout"):
        logits = lm_logits(cfg, params, x)[:, 0, :]           # [S, V]
        keys = jax.vmap(_slot_key)(seeds, pos)
        nxt = jax.vmap(sample_token)(logits, keys, temperature)
    return pool, jnp.where(active, nxt, tokens)


def paged_read_pages(cfg: TransformerConfig, pool: PagedKV, pids: Array):
    """Gather pages ``pids`` [TBL] out of the pool (padded with trash
    ids to the bucket's fixed table width — one traced shape per
    bucket) for the host prefix store, in the store's own row format
    [L, TBL, C, NH, D].  Pure read."""
    L, Pn, C, F = pool.k.shape
    lp = _every_layer(L, pids)
    shape = (L,) + pids.shape + (C, cfg.n_heads, cfg.head_dim)
    k = _read_pages(pool.k, lp).reshape(shape)
    v = _read_pages(pool.v, lp).reshape(shape)
    if pool.k_scale is None:
        return k, v
    return k, v, _read_pages(pool.k_scale, lp), _read_pages(pool.v_scale, lp)


def paged_write_pages(cfg: TransformerConfig, pool: PagedKV, pids: Array,
                      k: Array, v: Array, k_scale: Optional[Array] = None,
                      v_scale: Optional[Array] = None) -> PagedKV:
    """Scatter host prefix pages [L, TBL, C, NH, D] into pool pages
    ``pids`` [TBL] — the host-store HIT path when the prefix is not
    pool-resident.  Pad entries point at the trash page."""
    lidx = jnp.arange(pool.k.shape[0])[:, None]
    flat = k.shape[:3] + pool.k.shape[3:]
    out = pool._replace(k=pool.k.at[lidx, pids].set(k.reshape(flat)),
                        v=pool.v.at[lidx, pids].set(v.reshape(flat)))
    if pool.k_scale is None:
        return out
    return out._replace(k_scale=pool.k_scale.at[lidx, pids].set(k_scale),
                        v_scale=pool.v_scale.at[lidx, pids].set(v_scale))


# ---------------------------------------------------------------------------
# Speculative decoding (serving tier 3)
# ---------------------------------------------------------------------------

def paged_verify(cfg: TransformerConfig, params: PyTree, pool: PagedKV,
                 ptab: Array, tokens: Array, pos: Array, active: Array,
                 temperature: Array, seeds: Array, drafts: Array
                 ) -> Tuple[PagedKV, Array, Array]:
    """Target-model verify: every slot's current token plus its k draft
    proposals, W = k+1 positions, in ONE batched dispatch with the row
    writes and page reads of :func:`paged_decode` (the engine allocates
    pages through ``pos + k``, so rejected rows stay within the slot's
    own pages).  Row w consumes the token at ``pos+w`` (w=0 the current
    token, w>=1 draft w-1) and yields the target's own decision t_w at
    key ``_slot_key(seed, pos+w)`` — the SAME key the sequential path
    uses at that position, so the committed chain is token-for-token
    the non-speculative chain at ANY temperature.  Longest accepted
    prefix: with n_acc = leading matches of t vs drafts, t_0..t_{n_acc}
    commit (drafts 0..n_acc-1 were consumed with exactly the committed
    context; row n_acc's logits are the target's next step after them).
    Returns (pool', t [S, W], n_commit [S]), n_commit=0 where inactive."""
    k_spec = drafts.shape[1]
    toks_w = jnp.concatenate([tokens[:, None], drafts], axis=1)
    posw = pos[:, None] + jnp.arange(k_spec + 1)              # [S, W]
    pool, x = _paged_stack(cfg, params, pool, ptab, toks_w, posw, active)
    with jax.named_scope("readout"):
        logits = lm_logits(cfg, params, x)                    # [S, W, V]
        keys = jax.vmap(lambda sd, pw: jax.vmap(
            lambda pp: _slot_key(sd, pp))(pw))(seeds, posw)   # [S, W]
        t = jax.vmap(jax.vmap(sample_token, in_axes=(0, 0, None)))(
            logits, keys, temperature)                        # [S, W]
    matches = (t[:, :k_spec] == drafts).astype(jnp.int32)
    n_acc = jnp.sum(jnp.cumprod(matches, axis=1), axis=1)     # [S]
    return pool, t, jnp.where(active, n_acc + 1, 0)


def paged_draft_propose(cfg_d: TransformerConfig, params_d: PyTree,
                        dpool: PagedKV, ptab: Array, tokens: Array,
                        pos: Array, active: Array, n_steps: int
                        ) -> Tuple[PagedKV, Array]:
    """Draft-model proposal over a draft pool sharing the TARGET's page
    table (same positions, same page ids — one allocator covers both
    pools): a lax.scan of k greedy :func:`paged_decode` steps from the
    committed frontier the host hands in, each reading the pages the
    step before it wrote.  No re-sync dispatch between rounds: the
    draft's rows at accepted positions consumed exactly the committed
    tokens (that is what acceptance means), so every row below the
    frontier is already correct.  Returns (dpool', proposals [S, k]
    left on device for the verify dispatch)."""
    S = tokens.shape[0]
    zt = jnp.zeros((S,), jnp.float32)
    zs = jnp.zeros((S,), jnp.uint32)

    def body(carry, _):
        pool, toks, ps = carry
        pool, t = paged_decode(cfg_d, params_d, pool, ptab, toks, ps,
                               active, zt, zs)
        return (pool, t, ps + active.astype(jnp.int32)), t

    (dpool, _, _), props = lax.scan(body, (dpool, tokens, pos), None,
                                    length=n_steps)
    return dpool, jnp.moveaxis(props, 0, 1)


def make_serving_apply(cfg: TransformerConfig):
    """(apply_fn, cache_key) for serving/engine.InferenceEngine: token
    ids [B, T] -> next-token logits [B, T, vocab] via the dense forward
    (scoring/classification serving; incremental generation keeps its
    own KV-cache path in ``generate``)."""
    def apply_fn(params, token_ids):
        return forward_logits(cfg, params, token_ids.astype(jnp.int32))

    return apply_fn, ("gpt_serving", repr(cfg))
