"""BERT — masked-language-model pretraining on the transformer encoder.

North-star model (BASELINE.json: BERT-base ≥0.8x per-chip vs the reference's
nd4j-cuda path).  The reference has no attention model at all (SURVEY.md
§5.7); this is a new capability designed TPU-first:

- MLM head shares the token embedding matrix (weight tying) — the big
  [H, vocab] matmul is the single largest FLOP consumer outside the blocks;
  it runs in bf16 on the MXU with fp32 logits.
- Loss masks to the sampled positions only (standard 15% masking), computed
  with a gather-free `where` so shapes stay static under jit.
- ``make_train_step`` returns a jitted step with full dp/tp/sp sharding:
  params sharded by transformer.param_specs, batch by (data, seq) — XLA
  inserts all collectives (psum over `model` for TP matmuls, all-gathers at
  the sharded softmax boundary) per the scaling-book recipe.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, SEQ_AXIS

Array = jax.Array
PyTree = Any


def bert_base() -> TransformerConfig:
    return TransformerConfig(vocab_size=30522, max_len=512, hidden=768,
                             n_layers=12, n_heads=12, ffn_dim=3072)


def bert_tiny(vocab_size: int = 1024, max_len: int = 128) -> TransformerConfig:
    """Test/dryrun-sized config (same code path, toy shapes)."""
    return TransformerConfig(vocab_size=vocab_size, max_len=max_len,
                             hidden=64, n_layers=2, n_heads=4, ffn_dim=128,
                             dropout=0.0)


def init_params(key: Array, cfg: TransformerConfig) -> PyTree:
    k1, k2, k3 = jax.random.split(key, 3)
    params = tfm.init_params(k1, cfg)
    H = cfg.hidden
    params["mlm"] = {
        # transform before the tied-embedding projection (BERT convention)
        "w": tfm._trunc_normal(k2, (H, H)),
        "b": jnp.zeros((H,)),
        "ln_g": jnp.ones((H,)), "ln_b": jnp.zeros((H,)),
        "out_b": jnp.zeros((cfg.vocab_size,)),
    }
    params["pooler"] = {"w": tfm._trunc_normal(k3, (H, H)), "b": jnp.zeros((H,))}
    return params


def param_specs(cfg: TransformerConfig) -> PyTree:
    specs = tfm.param_specs(cfg)
    specs["mlm"] = {"w": P(None, None), "b": P(None),
                    "ln_g": P(None), "ln_b": P(None), "out_b": P(None)}
    specs["pooler"] = {"w": P(None, None), "b": P(None)}
    return specs


def shard_specs(cfg: TransformerConfig, model_degree: int = 1,
                pipe_degree: int = 1) -> PyTree:
    """data×model(×pipe) GSPMD specs for the BERT family: the encoder
    rules from ``transformer.shard_specs`` (heads + MLP hidden over
    ``model``, tied token embedding over vocab when divisible, stacked
    layers split into stages over ``pipe``) plus the MLM head —
    its transform column-parallel over ``model`` and its output bias
    over vocab alongside the tied projection.  LayerNorms and the
    pooler stay replicated (tiny; sharding them buys collectives, not
    memory)."""
    from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS

    specs = tfm.shard_specs(cfg, model_degree, pipe_degree)
    m = MODEL_AXIS if model_degree > 1 else None
    vocab_ok = model_degree > 1 and cfg.vocab_size % model_degree == 0
    specs["mlm"] = {"w": P(None, m), "b": P(m),
                    "ln_g": P(None), "ln_b": P(None),
                    "out_b": P(MODEL_AXIS) if vocab_ok else P(None)}
    specs["pooler"] = {"w": P(None, None), "b": P(None)}
    return specs


class Batch(NamedTuple):
    """MLM batch. ``mlm_mask`` marks the (already-corrupted) predict positions;
    ``labels`` holds original ids everywhere (ignored where mask==0)."""
    token_ids: Array       # [B, T] int32 — corrupted input
    attention_mask: Array  # [B, T] float32, 1 = real token
    type_ids: Array        # [B, T] int32
    labels: Array          # [B, T] int32 — original ids
    mlm_mask: Array        # [B, T] float32, 1 = position to predict


def batch_spec() -> Batch:
    s = P(DATA_AXIS, SEQ_AXIS)
    return Batch(token_ids=s, attention_mask=s, type_ids=s, labels=s,
                 mlm_mask=s)


def forward_hidden(cfg: TransformerConfig, params: PyTree, batch: Batch,
                   dropout_key: Optional[Array] = None,
                   attn_fn=tfm.attention) -> Array:
    return tfm.encode(cfg, params, batch.token_ids, batch.attention_mask,
                      batch.type_ids, dropout_key, attn_fn=attn_fn)


def mlm_logits(cfg: TransformerConfig, params: PyTree, hidden: Array) -> Array:
    """[B, T, H] -> [B, T, vocab] via transform + tied embeddings."""
    cdt = jnp.dtype(cfg.compute_dtype)
    m = params["mlm"]
    h = jax.nn.gelu(hidden.astype(cdt) @ m["w"].astype(cdt) + m["b"])
    h = tfm.layer_norm(h, m["ln_g"], m["ln_b"], cfg.layer_norm_eps)
    logits = jnp.einsum("bth,vh->btv", h.astype(cdt),
                        params["embed"]["tok"].astype(cdt),
                        preferred_element_type=jnp.float32)
    return logits + m["out_b"]


def mlm_loss_from_hidden(cfg: TransformerConfig, params: PyTree,
                         hidden: Array, batch: Batch) -> Array:
    logits = mlm_logits(cfg, params, hidden)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, batch.labels[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(batch.mlm_mask), 1.0)
    return -jnp.sum(ll * batch.mlm_mask) / denom


def mlm_loss(cfg: TransformerConfig, params: PyTree, batch: Batch,
             dropout_key: Optional[Array] = None,
             attn_fn=tfm.attention) -> Array:
    hidden = forward_hidden(cfg, params, batch, dropout_key, attn_fn)
    return mlm_loss_from_hidden(cfg, params, hidden, batch)


class TrainState(NamedTuple):
    params: PyTree
    opt_state: PyTree
    step: Array


def _opt_state_shardings(optimizer, params_shape: PyTree, pshard: PyTree,
                         mesh: Mesh) -> PyTree:
    """Opt-state sharding mirrors param sharding: any subtree of the optax
    state that has the params' tree STRUCTURE (adam mu/nu, momentum
    buffers, ...) gets the params' shardings; remaining leaves (step
    counters etc.) replicate."""
    ostate_shape = jax.eval_shape(optimizer.init, params_shape)
    ptreedef = jax.tree_util.tree_structure(params_shape)

    def assign(node):
        if jax.tree_util.tree_structure(node) == ptreedef:
            return pshard
        if isinstance(node, tuple):
            mapped = [assign(c) for c in node]
            return (type(node)(*mapped) if hasattr(node, "_fields")
                    else tuple(mapped))
        if isinstance(node, list):
            return [assign(c) for c in node]
        if isinstance(node, dict):
            return {k: assign(v) for k, v in node.items()}
        return NamedSharding(mesh, P())

    return assign(ostate_shape)


def make_train_step(cfg: TransformerConfig, mesh: Mesh,
                    optimizer: Optional[optax.GradientTransformation] = None,
                    attn_fn=None, n_steps: int = 1
                    ) -> Tuple[Callable, Callable]:
    """Returns (init_fn(key) -> TrainState, step_fn(state, batch, key)
    -> (state, loss)), both jitted with dp/tp/sp shardings over `mesh`.

    ``attn_fn=None`` (the default) routes attention through the
    ``ops/pallas_attention.make_attn_fn`` auto policy: the Pallas flash
    kernel (autotuned block sizes, shard_map-placed over the mesh) when
    it wins on this device/shape, plain XLA attention otherwise — the
    fast kernel is the DEFAULT training path, not a bench-only opt-in.
    Pass ``attn_fn=tfm.attention`` to force the XLA path.

    ``n_steps > 1`` runs that many optimizer steps per call as one
    ``lax.scan`` dispatch (per-step PRNG keys folded from ``key``) —
    benches use it so measured throughput is device throughput, not
    host->device dispatch latency."""
    if attn_fn is None:
        from deeplearning4j_tpu.ops.pallas_attention import make_attn_fn
        attn_fn = make_attn_fn("auto", mesh=mesh)
    optimizer = optimizer or optax.adamw(1e-4, weight_decay=0.01)

    pspecs = param_specs(cfg)
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), batch_spec(),
                          is_leaf=lambda x: isinstance(x, P))

    def init_fn(key: Array) -> TrainState:
        params = init_params(key, cfg)
        return TrainState(params=params, opt_state=optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    def _one_step(state: TrainState, batch: Batch, key: Array):
        def loss_fn(p):
            return mlm_loss(cfg, p, batch, key, attn_fn)
        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state,
                          state.step + 1), loss

    if n_steps == 1:
        step_fn = _one_step
    else:
        def step_fn(state: TrainState, batch: Batch, key: Array):
            def body(s, i):
                return _one_step(s, batch, jax.random.fold_in(key, i))
            return jax.lax.scan(body, state, jnp.arange(n_steps))
        # loss comes back [n_steps]; callers take the last entry

    params_shape = jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg))
    oshard = _opt_state_shardings(optimizer, params_shape, pshard, mesh)
    state_shard = TrainState(params=pshard, opt_state=oshard,
                             step=NamedSharding(mesh, P()))

    jit_init = jax.jit(init_fn, out_shardings=state_shard)
    jit_step = jax.jit(
        step_fn,
        in_shardings=(state_shard, bshard, NamedSharding(mesh, P())),
        out_shardings=(state_shard, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )
    return jit_init, jit_step


# ---------------------------------------------------------------------------
# pipeline-parallel training — the REAL encoder staged over the `pipe` axis
# ---------------------------------------------------------------------------

def make_pipeline_train_step(cfg: TransformerConfig, mesh: Mesh,
                             n_micro: int,
                             optimizer: Optional[
                                 optax.GradientTransformation] = None
                             ) -> Tuple[Callable, Callable]:
    """GPipe dp×pp training step on the real transformer stack.

    The ``cfg.n_layers`` encoder blocks are split into
    ``mesh.shape['pipe']`` equal stages; each pipe shard scans (and
    remat-s) only its own run of blocks, and activations ring-shift
    between stages via ``lax.ppermute`` with the attention mask riding
    along as a second pytree leaf.  Embedding and the MLM head run outside
    the pipelined region (replicated over ``pipe``, batch sharded over
    ``data``); reverse-mode autodiff through the scan+ppermute yields the
    mirrored backward pipeline.  Dropout is not applied inside the
    pipelined region — pass ``cfg.dropout == 0`` configs (pretraining
    benches run dropout-free; same convention as the bench step).

    Returns ``(init_fn(key) -> TrainState, step_fn(state, batch) ->
    (state, loss))``, both jitted with the dp/pp shardings baked in.
    Parity of rigor with tensor parallelism: ``make_train_step`` stages
    the real BERT over ``model``; this stages the same blocks over
    ``pipe`` (layout documented at parallel/pipeline.py).
    """
    from deeplearning4j_tpu.parallel import pipeline as pl
    from deeplearning4j_tpu.parallel.mesh import PIPE_AXIS

    optimizer = optimizer or optax.adamw(1e-4, weight_decay=0.01)
    n_stages = mesh.shape[PIPE_AXIS]
    if cfg.n_layers % n_stages != 0:
        raise ValueError(f"n_layers={cfg.n_layers} not divisible by pipe "
                         f"degree {n_stages}")
    if cfg.dropout != 0.0:
        raise ValueError(
            f"pipeline train step is dropout-free; got cfg.dropout="
            f"{cfg.dropout} (use dataclasses.replace(cfg, dropout=0.0))")

    def stage_fn(stage_blocks, xm):
        x, mask = xm          # x [mb, T, H] fp32, mask [mb, T] rides along

        def body(h, p):
            return tfm._block(cfg, h, p, mask, None), None

        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, stage_blocks)
        return (x, mask)

    fwd = pl.make_pipeline_fn(mesh, stage_fn, n_micro)

    def loss_of(params, batch: Batch) -> Array:
        x = tfm.embed(cfg, params, batch.token_ids, batch.type_ids)
        hidden, _ = fwd(params["blocks"], (x, batch.attention_mask))
        return mlm_loss_from_hidden(cfg, params, hidden, batch)

    def init_fn(key: Array) -> TrainState:
        params = init_params(key, cfg)
        params["blocks"] = pl.split_layers_into_stages(
            params["blocks"], n_stages)
        return TrainState(params=params, opt_state=optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    def step_fn(state: TrainState, batch: Batch):
        loss, grads = jax.value_and_grad(loss_of)(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    # shardings: stage-stacked blocks over `pipe` (leading axis), everything
    # else replicated; batch over `data` only (no seq axis in a pp mesh).
    base = param_specs(cfg)
    pspecs = dict(base)
    pspecs["blocks"] = jax.tree.map(lambda _: P(PIPE_AXIS), base["blocks"])
    pspecs["embed"] = jax.tree.map(lambda _: P(), base["embed"])
    pspecs["mlm"] = jax.tree.map(lambda _: P(), base["mlm"])
    pspecs["pooler"] = jax.tree.map(lambda _: P(), base["pooler"])
    pshard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                          is_leaf=lambda x: isinstance(x, P))
    bshard = jax.tree.map(lambda _: NamedSharding(mesh, P(DATA_AXIS, None)),
                          Batch(*Batch._fields))

    params_shape = jax.eval_shape(lambda: init_fn(jax.random.key(0)).params)
    oshard = _opt_state_shardings(optimizer, params_shape, pshard, mesh)
    state_shard = TrainState(params=pshard, opt_state=oshard,
                             step=NamedSharding(mesh, P()))

    jit_init = jax.jit(init_fn, out_shardings=state_shard)
    jit_step = jax.jit(step_fn,
                       in_shardings=(state_shard, bshard),
                       out_shardings=(state_shard, NamedSharding(mesh, P())),
                       donate_argnums=(0,))
    return jit_init, jit_step


# ---------------------------------------------------------------------------
# sequence-parallel training — ring attention on the REAL encoder stack
# ---------------------------------------------------------------------------

def make_sp_train_step(cfg: TransformerConfig, mesh: Mesh,
                       optimizer: Optional[
                           optax.GradientTransformation] = None
                       ) -> Tuple[Callable, Callable]:
    """dp×sp training step on the real BERT via ``shard_map``.

    Sequence parallelism for contexts beyond one chip's memory: every
    shard holds ``[B/dp, T/sp]`` tokens, embeds its slice with the
    correct absolute position offset, and attention runs as RING
    attention (parallel/ring_attention.py) — K/V blocks rotate around
    the ``seq`` axis via ppermute while the online softmax accumulates,
    so the full ``[T, T]`` score matrix never exists on any chip.  The
    MLM head's ``[T, vocab]`` matmul also splits across seq shards; the
    masked loss reduces with a psum over (data, seq).  Parameters stay
    replicated (sp shards activations, not weights).

    Dropout must be 0 (same convention as the pipeline step).  Parity of
    rigor across the parallelism axes: tp (``make_train_step``), pp
    (``make_pipeline_train_step``) and sp (this) all train the real
    encoder stack.

    Returns ``(init_fn(key) -> TrainState, step_fn(state, batch) ->
    (state, loss))``, jitted with the dp/sp shardings baked in.
    """
    from deeplearning4j_tpu.compat import shard_map
    from deeplearning4j_tpu.parallel import ring_attention as ra
    from deeplearning4j_tpu.parallel.mesh import SEQ_AXIS

    optimizer = optimizer or optax.adamw(1e-4, weight_decay=0.01)
    if cfg.dropout != 0.0:
        raise ValueError(
            f"sp train step is dropout-free; got cfg.dropout="
            f"{cfg.dropout} (use dataclasses.replace(cfg, dropout=0.0))")

    ring_fn = ra.make_ring_attn_fn(SEQ_AXIS)
    bspec_tree = batch_spec()         # Batch(P(data, seq), ...) everywhere

    def local_loss(params, batch: Batch) -> Array:
        t_loc = batch.token_ids.shape[1]
        off = jax.lax.axis_index(SEQ_AXIS) * t_loc
        hidden = tfm.encode(cfg, params, batch.token_ids,
                            batch.attention_mask, batch.type_ids,
                            position_offset=off, attn_fn=ring_fn)
        logits = mlm_logits(cfg, params, hidden)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, batch.labels[..., None],
                                 axis=-1)[..., 0]
        num = jax.lax.psum(-jnp.sum(ll * batch.mlm_mask),
                           (DATA_AXIS, SEQ_AXIS))
        den = jax.lax.psum(jnp.sum(batch.mlm_mask),
                           (DATA_AXIS, SEQ_AXIS))
        return num / jnp.maximum(den, 1.0)

    sharded_loss = shard_map(
        local_loss, mesh=mesh, in_specs=(P(), bspec_tree),
        out_specs=P(), check_vma=False)

    def init_fn(key: Array) -> TrainState:
        params = init_params(key, cfg)
        return TrainState(params=params, opt_state=optimizer.init(params),
                          step=jnp.zeros((), jnp.int32))

    def step_fn(state: TrainState, batch: Batch):
        loss, grads = jax.value_and_grad(sharded_loss)(state.params, batch)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params)
        params = optax.apply_updates(state.params, updates)
        return TrainState(params, opt_state, state.step + 1), loss

    rshard = NamedSharding(mesh, P())
    params_shape = jax.eval_shape(
        lambda: init_params(jax.random.key(0), cfg))
    pshard = jax.tree.map(lambda _: rshard, params_shape)
    oshard = _opt_state_shardings(optimizer, params_shape, pshard, mesh)
    state_shard = TrainState(params=pshard, opt_state=oshard, step=rshard)
    bshard = jax.tree.map(lambda s: NamedSharding(mesh, s), bspec_tree,
                          is_leaf=lambda s: isinstance(s, P))

    jit_init = jax.jit(init_fn, out_shardings=state_shard)
    jit_step = jax.jit(step_fn,
                       in_shardings=(state_shard, bshard),
                       out_shardings=(state_shard, rshard),
                       donate_argnums=(0,))
    return jit_init, jit_step


# ---------------------------------------------------------------------------
# synthetic MLM batch for tests/bench
# ---------------------------------------------------------------------------

def synthetic_batch(key: Array, cfg: TransformerConfig, batch_size: int,
                    seq_len: int, mask_prob: float = 0.15,
                    mask_token: int = 103) -> Batch:
    k1, k2 = jax.random.split(key)
    labels = jax.random.randint(k1, (batch_size, seq_len), 5, cfg.vocab_size,
                                dtype=jnp.int32)
    mlm = (jax.random.uniform(k2, (batch_size, seq_len)) < mask_prob
           ).astype(jnp.float32)
    token_ids = jnp.where(mlm > 0, mask_token, labels).astype(jnp.int32)
    return Batch(token_ids=token_ids,
                 attention_mask=jnp.ones((batch_size, seq_len), jnp.float32),
                 type_ids=jnp.zeros((batch_size, seq_len), jnp.int32),
                 labels=labels, mlm_mask=mlm)


def make_serving_apply(cfg: TransformerConfig):
    """(apply_fn, cache_key) for serving/engine.InferenceEngine: token
    ids [B, T] -> MLM logits [B, T, vocab] (full attention mask, single
    segment — the plain fill-mask serving shape).  The cache_key ties
    the engine entry to the exact config so replicas share one compile."""
    def apply_fn(params, token_ids):
        B, T = token_ids.shape
        batch = Batch(token_ids=token_ids.astype(jnp.int32),
                      attention_mask=jnp.ones((B, T), jnp.float32),
                      type_ids=jnp.zeros((B, T), jnp.int32),
                      labels=jnp.zeros((B, T), jnp.int32),
                      mlm_mask=jnp.ones((B, T), jnp.float32))
        return mlm_logits(cfg, params, forward_hidden(cfg, params, batch))

    return apply_fn, ("bert_serving", repr(cfg))
