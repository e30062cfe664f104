"""Causal-LM training through the PRODUCTION sharded-fit spine.

``models/gpt.make_train_step`` trains data×model via its own jitted
step, but it lives outside everything PR 1-11 built for the default fit
path: no engine keying, no donation-through-``cached_jit`` accounting,
no collective guard skips, no loss scaling, no ``ResilientFit``
checkpoint/rollback/elastic story.  This module closes that gap — it is
the model-parallel tentpole's training half: a :class:`CausalLM`
trainable whose machinery is built by ``parallel/sharded_fit``'s GSPMD
mode (params laid out with ``NamedSharding`` from
``gpt.shard_specs`` — attention heads and MLP hidden over ``model``,
tied embedding over vocab — instead of replicated), so a GPT whose
parameters exceed one chip's HBM trains with:

- ONE donated dispatch per fit (``build_scanned_epochs`` double scan,
  weight shards resident on their devices across every step);
- the PR 2 in-step guard and the PR 11 dynamic loss scale riding the
  same step — in GSPMD every value is logically global, so the skip
  verdict and the scale transition are replica-consistent across BOTH
  mesh axes by construction;
- the full ``ResilientFit`` surface (``_backprop_machinery`` +
  padding/ustate hooks), so async checkpoints, rollback, preemption,
  and bit-exact resume apply to the sharded LM unchanged;
- ``mesh_signature``-keyed engine entries: the same config on a 2×4
  data×model mesh and an 8×1 data mesh are different executables.

Batches are ``DataSet(token_ids, token_ids)`` — features and labels
both [B, T] int32 (next-token targets are the shifted features; the
labels slot keeps the ``(x, y, n_valid)`` dispatch tuple every DP
driver already speaks).  The loss is the masked-SUM / divide-once
formulation of PR 5, so a data×model fit is numerically equivalent to
the single-device fit at equal effective batch and padding rows are
exactly masked out.
"""

from __future__ import annotations

import math
from typing import Any, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models import gpt
from deeplearning4j_tpu.models import moe as moe_lm
from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models.moe import MoETransformerConfig
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.parallel.mesh import (DATA_AXIS, expert_degree,
                                              mesh_signature, model_degree,
                                              pad_rows, pipe_degree)
from deeplearning4j_tpu.runtime import compile_cache, resilience, telemetry
from deeplearning4j_tpu.runtime.metrics import dp_metrics

Array = jax.Array
PyTree = Any

MIXED_PRECISION_POLICIES = ("off", "bf16")


class _LMConf:
    """The mutable conf surface generic DP drivers expect of a model
    (``ResilientFit`` temporarily overrides ``grad_accum`` during an
    elastic rebuild)."""

    __slots__ = ("grad_accum",)

    def __init__(self, grad_accum: int = 1):
        self.grad_accum = grad_accum


class CausalLM:
    """A GPT-family ``TransformerConfig`` wrapped in the trainable
    surface the sharded-fit/ResilientFit stack drives (the
    ``MultiLayerNetwork`` duck type: ``_backprop_machinery``,
    ``_require_params``, padding hooks, ``conf.grad_accum``).

    The updater is SGD + momentum with fp32 state mirroring the params
    — deliberately simple: the point of this class is the SHARDING and
    resilience plumbing, and a momentum tree shards with exactly the
    weight specs, which keeps the updater-state layout story honest.
    ``mixed_precision="bf16"`` runs the forward/backward in bfloat16
    against fp32 masters with the PR 11 dynamic loss scale threaded
    through the scanned epochs."""

    def __init__(self, cfg: Union[TransformerConfig, MoETransformerConfig],
                 *, lr: float = 0.1,
                 momentum: float = 0.0, mixed_precision: str = "off",
                 grad_accum: int = 1, pipe_microbatches: int = 1):
        if not cfg.causal:
            raise ValueError("CausalLM needs a causal TransformerConfig")
        if mixed_precision not in MIXED_PRECISION_POLICIES:
            raise ValueError(
                f"mixed_precision must be one of "
                f"{MIXED_PRECISION_POLICIES}, got {mixed_precision!r}")
        if pipe_microbatches < 1:
            raise ValueError(
                f"pipe_microbatches must be >= 1, got {pipe_microbatches}")
        self.cfg = cfg
        self.lr = float(lr)
        self.momentum = float(momentum)
        self.mixed_precision = mixed_precision
        #: GPipe microbatch count for pipeline meshes — a CONFIG knob,
        #: not a mesh property: the in-step microbatch schedule is
        #: accum * pipe_microbatches slices regardless of mesh shape, so
        #: the grad-sum association is identical at every shape and a
        #: pipe-degree change is a pure layout change (bit-exact)
        self.pipe_microbatches = int(pipe_microbatches)
        self.conf = _LMConf(grad_accum)
        self.params: Optional[PyTree] = None
        self.listeners: List = []
        self.guard_skips = 0
        self._bp_cache = {}

    @property
    def _is_moe(self) -> bool:
        return isinstance(self.cfg, MoETransformerConfig)

    # -- params ------------------------------------------------------------
    def init(self, seed: int = 0) -> "CausalLM":
        fam = moe_lm if self._is_moe else gpt
        self.params = fam.init_params(jax.random.key(seed), self.cfg)
        return self

    def _require_params(self) -> PyTree:
        if self.params is None:
            self.init()
        return self.params

    def params_flat(self) -> np.ndarray:
        """Flat fp32 HOST view of every leaf (deterministic tree order)
        — the cross-run equality probe tests/benches use.  Each leaf is
        gathered to host BEFORE concatenation: an eager
        ``jnp.concatenate`` over leaves with heterogeneous shardings
        (model-sharded weights next to replicated norms) miscompiles on
        this jax version (replica-summed output), so the probe must
        never mix layouts device-side."""
        return np.concatenate(
            [np.ravel(np.asarray(jax.device_get(leaf))).astype(np.float32)
             for leaf in jax.tree.leaves(self._require_params())])

    def num_param_bytes(self) -> int:
        return sum(math.prod(l.shape) * jnp.dtype(l.dtype).itemsize
                   for l in jax.tree.leaves(self._require_params()))

    # -- machinery ---------------------------------------------------------
    def _conf_signature(self):
        return ("causal_lm", repr(self.cfg), self.lr, self.momentum,
                self.mixed_precision, self.pipe_microbatches)

    def _mp_on(self) -> bool:
        return self.mixed_precision == "bf16"

    @staticmethod
    def _init_ustate(train_step, updaters, params):
        return train_step.init_ustate(params)

    def _backprop_machinery(self, mesh=None):
        """(train_step, train_epochs, updaters) via the MODULE-LEVEL
        engine, keyed on (config signature, mesh signature, accum) —
        same sharing and keying discipline as the MultiLayerNetwork
        bundles.  ``updaters`` is () — the SGD+momentum update is baked
        into the step; ``init_ustate`` on the step builds its state."""
        accum = max(self.conf.grad_accum, 1)
        memo_key = (mesh_signature(mesh), accum)
        if memo_key not in self._bp_cache:
            self._bp_cache[memo_key] = compile_cache.get_or_build(
                ("lm_backprop", self._conf_signature(),
                 mesh_signature(mesh), accum),
                lambda: self._build_machinery(mesh, accum))
        return self._bp_cache[memo_key]

    def _build_machinery(self, mesh, accum: int):
        from deeplearning4j_tpu.parallel import sharded_fit

        cfg = self.cfg
        lr, mu = self.lr, self.momentum
        mp_on = self._mp_on()
        is_moe = self._is_moe
        m_deg = model_degree(mesh)
        p_deg = pipe_degree(mesh)
        e_deg = expert_degree(mesh)
        n_micro = accum * self.pipe_microbatches
        if mesh is None:
            specs = None
        elif is_moe:
            specs = moe_lm.shard_specs(cfg, model_degree=m_deg,
                                       pipe_degree=p_deg,
                                       expert_degree=e_deg)
        else:
            specs = gpt.shard_specs(cfg, model_degree=m_deg,
                                    pipe_degree=p_deg)

        # trace-time attention kernel choice (ops/kernel_select policy +
        # the runtime/autotune cache): flash under data×model, RING when
        # the mesh shards the sequence axis, plain XLA on CPU/short-seq
        if mesh is not None and mesh.size > 1:
            from deeplearning4j_tpu.ops.pallas_attention import make_attn_fn
            attn_fn = make_attn_fn("auto", mesh=mesh)
        else:
            attn_fn = tfm.attention
        # MoE layers dispatch through parallel/expert.py's shard_map on
        # the mesh `expert` axis (all_to_all token routing) from inside
        # the GSPMD program; without an expert axis the same callable is
        # the single-shard dispatch math
        if is_moe:
            from deeplearning4j_tpu.parallel.expert import make_gspmd_moe_ffn
            moe_ffn_fn = make_gspmd_moe_ffn(mesh, cfg.moe)

        def loss_sum(params, ids, rmask, key):
            """Masked next-token NLL SUM over the (global) batch — the
            linear unit shard/microbatch combination preserves.  Under
            mixed precision the fp32 masters cast to bf16 HERE, inside
            the differentiated function, so grads come back fp32.  The
            MoE families add the Switch load-balance aux scaled by the
            slice's valid count, so the final divide-once by the global
            count leaves mean-NLL + aux_weight * (count-weighted) aux."""
            if mp_on:
                params = sharded_fit.mp_cast(params)
            if is_moe:
                hidden, aux = moe_lm.encode(cfg, params, ids,
                                            attn_fn=attn_fn,
                                            ffn_fn=moe_ffn_fn)
            else:
                hidden = tfm.encode(cfg, params, ids, None, None, key,
                                    attn_fn=attn_fn)
            with jax.named_scope("readout"):
                logits = gpt.lm_logits(cfg, params, hidden[:, :-1])
                logp = jax.nn.log_softmax(logits, axis=-1)
                ll = jnp.take_along_axis(logp, ids[:, 1:, None],
                                         axis=-1)[..., 0]
                nll = -jnp.sum(ll * rmask[:, None])
            if is_moe:
                count = jnp.sum(rmask) * (ids.shape[1] - 1)
                nll = nll + cfg.aux_loss_weight * aux * count
            return nll

        def dp_step(params, ustate, batch, key, iteration):
            if mp_on:
                mom, ls = ustate
                scale = ls["scale"]
            else:
                mom, ls, scale = ustate, None, None
            ids, _, n_valid = batch          # labels ARE the ids (shifted)
            key = jax.random.fold_in(key, iteration)
            B, T = ids.shape
            rmask = (jnp.arange(B) < n_valid).astype(jnp.float32)
            count = n_valid.astype(jnp.float32) * (T - 1)

            def scaled_obj(p, xi, mi, ki):
                s = loss_sum(p, xi, mi, ki)
                return (s * scale if mp_on else s), s

            if n_micro == 1:
                (_, lsum), grads = jax.value_and_grad(
                    scaled_obj, has_aux=True)(params, ids, rmask, key)
            else:
                # the in-step GPipe schedule: accum * pipe_microbatches
                # slices walked by a lax.scan whose (grads, loss) carry
                # is donated across iterations — HBM stays flat at one
                # grad tree regardless of the microbatch count, and on a
                # pipe-sharded mesh each slice streams through the
                # stage-laid-out layers while XLA overlaps the
                # stage-boundary transfers of the next slice
                micro = B // n_micro
                xm = ids.reshape(n_micro, micro, T)
                mm = rmask.reshape(n_micro, micro)

                def micro_body(carry, inp):
                    g_acc, s_acc = carry
                    xi, mi, i = inp
                    (_, s), g = jax.value_and_grad(
                        scaled_obj, has_aux=True)(
                            params, xi, mi, jax.random.fold_in(key, i))
                    g_acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32), g_acc, g)
                    return (g_acc, s_acc + s), None

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, lsum), _ = lax.scan(
                    micro_body, (g0, jnp.float32(0.0)),
                    (xm, mm, jnp.arange(n_micro)))
                grads = jax.tree.map(lambda g, p: g.astype(p.dtype),
                                     grads, params)

            denom = jnp.maximum(count, 1.0)
            score = lsum / denom
            # one global divide finishes the mean AND the loss-scale
            # unscaling (PR 11); an overflowed bf16 backward leaves
            # inf/NaN here, which the guard below turns into a skip
            gdenom = denom * scale if mp_on else denom
            grads = jax.tree.map(lambda g: g / gdenom, grads)
            new_mom = jax.tree.map(lambda m, g: mu * m + g, mom, grads)
            new_params = jax.tree.map(lambda p, m: p - lr * m,
                                      params, new_mom)
            # guard verdict from the GLOBAL (score, grads): one logical
            # value under GSPMD, so every shard on both axes skips (and
            # scales) identically
            new_params, new_mom, skipped = resilience.guard_update(
                params, mom, new_params, new_mom, (score, grads))
            if mp_on:
                return (new_params,
                        (new_mom, sharded_fit.next_loss_scale(ls, skipped)),
                        score, skipped)
            return new_params, new_mom, score, skipped

        batch_specs = (P(DATA_AXIS), P(DATA_AXIS), P()) \
            if mesh is not None else None
        ustate_specs = (specs, P()) if (mp_on and specs is not None) \
            else specs
        key_base = ("lm_backprop", self._conf_signature(),
                    mesh_signature(mesh), accum)
        train_step = sharded_fit.build_sharded_step(
            dp_step, mesh, batch_specs=batch_specs, label="lm.train_step",
            engine_key=(key_base, "step"), param_specs=specs,
            ustate_specs=ustate_specs)
        train_epochs = sharded_fit.build_scanned_epochs(
            dp_step, mesh, batch_specs=batch_specs,
            label="lm.train_epochs", engine_key=(key_base, "epochs"),
            param_specs=specs, ustate_specs=ustate_specs)

        def init_ustate(params):
            mom = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            if mp_on:
                from deeplearning4j_tpu.parallel.sharded_fit import \
                    init_loss_scale
                return (mom, init_loss_scale())
            return mom

        for fn in (train_step, train_epochs):
            fn.takes_n_valid = True
            fn.init_ustate = init_ustate
            fn.mixed_precision = mp_on
            fn.pipe_microbatches = self.pipe_microbatches
            fn.pipe_degree = p_deg
            fn.expert_degree = e_deg
        return (train_step, train_epochs, ())

    # -- DP driver hooks (shared with MultiLayerNetwork) -------------------
    def _pad_chunk(self, mesh, accum: int) -> int:
        ndp = mesh.shape[DATA_AXIS] if mesh is not None else 1
        return ndp * max(accum, 1) * self.pipe_microbatches

    @staticmethod
    def _pad_rows(arr: Array, target: int) -> Array:
        return pad_rows(arr, target)

    def _notify_fit_start(self) -> None:
        for ls in self.listeners:
            hook = getattr(ls, "on_fit_start", None)
            if callable(hook):
                hook(self)

    def _note_skips(self, skips) -> None:
        self.guard_skips += resilience.note_skips(skips, where="lm")

    # -- fit ---------------------------------------------------------------
    def fit_backprop(self, data: Union[DataSet, Sequence[DataSet]],
                     num_epochs: int = 1, seed: int = 2,
                     mesh=None) -> None:
        """Scanned-epoch fit: pad every batch to the shard×accum chunk,
        stack, stage pre-sharded onto the mesh, and run the WHOLE fit
        as ONE donated dispatch (mesh=None streams the same step on one
        device, still one dispatch via the scanned builder)."""
        batches = [data] if isinstance(data, DataSet) else list(data)
        if not batches:
            return
        with telemetry.span("lm.fit_backprop", batches=len(batches),
                            epochs=num_epochs):
            self._fit_backprop(batches, num_epochs, seed, mesh)

    def _fit_backprop(self, batches: List[DataSet], num_epochs: int,
                      seed: int, mesh) -> None:
        from deeplearning4j_tpu.parallel import sharded_fit

        self._notify_fit_start()
        accum = max(self.conf.grad_accum, 1)
        chunk = self._pad_chunk(mesh, accum)
        train_step, train_epochs, _ = self._backprop_machinery(mesh)
        with telemetry.span("lm.copy_params"):
            params = jax.tree.map(jnp.copy, self._require_params())
            ustate = train_step.init_ustate(params)
        target = max(-(-b.features.shape[0] // chunk) * chunk
                     for b in batches)
        with telemetry.span("lm.stage", batches=len(batches),
                            sharded=mesh is not None):
            xs = jnp.stack([self._pad_rows(jnp.asarray(b.features,
                                                       jnp.int32), target)
                            for b in batches])
            nvs = jnp.asarray([b.features.shape[0] for b in batches],
                              jnp.int32)
            if mesh is not None:
                xs = jax.device_put(xs, sharded_fit.stacked_sharding(mesh))
        ys = xs                               # next-token targets == inputs
        with telemetry.span("lm.dispatch", scanned=True,
                            data_degree=(mesh.shape[DATA_AXIS]
                                         if mesh is not None else 1),
                            model_degree=model_degree(mesh),
                            pipe_degree=pipe_degree(mesh),
                            expert_degree=expert_degree(mesh),
                            pipe_microbatches=self.pipe_microbatches,
                            steps=num_epochs * len(batches)):
            params, ustate, scores, skips = train_epochs(
                params, ustate, (xs, ys, nvs), jax.random.key(seed), 0,
                num_epochs)
            dp_metrics.note_dispatch(
                steps=num_epochs * len(batches), accum=accum,
                data_degree=(mesh.shape[DATA_AXIS]
                             if mesh is not None else 1))
        with telemetry.span("lm.fetch"):
            # the host's read of the skip flags, then of the scores:
            # both wait for the program
            self._note_skips(skips)
            if self.listeners:
                for j, s in enumerate(np.asarray(scores).ravel()):
                    for ls in self.listeners:
                        ls.iteration_done(self, j, float(s))
        self.params = params
