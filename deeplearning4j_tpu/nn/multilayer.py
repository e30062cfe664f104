"""MultiLayerNetwork — the model: a stack of layers + output layer.

Reference parity (nn/multilayer/MultiLayerNetwork.java):
- ctor from conf ``:82`` / ``init:325`` (builds layers via factories, wires
  nIn/nOut from ``hiddenLayerSizes``)
- ``pretrain(iter):144`` greedy layer-wise unsupervised training
- ``feedForward:462``, ``output:1147``, ``predict:1057``, ``score:1213``
- ``fit(iter):918`` = pretrain -> finetune -> optional backprop
- ``finetune:987`` (trains the output layer on last hidden activations)
- param pack/unpack ``:773/:817``, distributed ``merge:1321``
- serialization = conf JSON + flat param vector ``:93-97``

TPU-native:
- params are a list of per-layer dicts (one pytree) — shardable under pjit;
- the supervised loss is differentiable end-to-end, so "backprop" is
  ``jax.grad`` of ``loss`` (the reference's manual ``doBackWard:941`` chain
  is subsumed);
- ``fit`` on minibatches compiles ONE fused train step (value+grad+update)
  and reuses it across batches/epochs;
- dropout/sampling keys are threaded explicitly.
"""

from __future__ import annotations

import io
import logging
import math
import time
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.configuration import (
    LayerKind, MIXED_PRECISION_POLICIES, MultiLayerConfiguration,
    NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.conf.preprocessors import make_preprocessor
from deeplearning4j_tpu.nn.layers import make_layer
from deeplearning4j_tpu.nn.layers.base import Layer, PretrainLayer
from deeplearning4j_tpu.nn.layers.output import OutputLayer
from deeplearning4j_tpu.nn.params import pack_params, unpack_params
from deeplearning4j_tpu.ops.updaters import apply_updates, dl4j_updater
from deeplearning4j_tpu.optimize.solver import Objective, Solver
from deeplearning4j_tpu.optimize.listeners import IterationListener
from deeplearning4j_tpu.runtime import compile_cache, resilience, telemetry

log = logging.getLogger(__name__)

Array = jax.Array
Params = List[Dict[str, Array]]


class MultiLayerNetwork:
    #: scanned-epoch fast path stacks the dataset on device; above this
    #: budget fit_backprop streams batch-by-batch instead (no OOM)
    SCAN_MAX_DATASET_BYTES = 256 * 1024 * 1024

    def __init__(self, conf: MultiLayerConfiguration,
                 params: Optional[Params] = None):
        self.conf = conf
        self._wire_layer_sizes()
        if conf.use_drop_connect:
            # net-level useDropConnect flips every layer's dropout from
            # activation masking to weight masking (DropConnect)
            for c in conf.confs:
                c.drop_connect = True
        self.layers: List[Layer] = [make_layer(c) for c in conf.confs]
        self.params: Optional[Params] = params
        self.listeners: List[IterationListener] = []
        self._in_pre = {i: make_preprocessor(spec)
                        for i, spec in conf.input_preprocessors.items()}
        self._out_pre = {i: make_preprocessor(spec)
                         for i, spec in conf.output_preprocessors.items()}
        # compiled-step bundles live in the MODULE-LEVEL engine
        # (runtime/compile_cache.py) keyed on the canonical conf JSON —
        # per-instance attrs here only memoize the engine lookup.
        # _bp_cache maps machinery mode (single-device / per-mesh) to the
        # engine bundle: mesh-shape+devices are part of the engine key, so
        # two meshes never silently share a compiled sharded step
        self._bp_cache: Dict = {}
        self._serving_cache = None
        self._serving_engine_memo = None
        #: cumulative in-step guard skips across this network's fits —
        #: exposed so listeners (MetricsListener) can log it per step
        self.guard_skips = 0

    # -- wiring (init:325 parity) ------------------------------------------
    def _wire_layer_sizes(self) -> None:
        confs = self.conf.confs
        sizes = self.conf.hidden_layer_sizes
        if sizes:
            n_in = confs[0].n_in
            if n_in <= 0:
                raise ValueError("first layer needs n_in when using "
                                 "hidden_layer_sizes")
            dims = [n_in] + list(sizes)
            for i, c in enumerate(confs[:-1]):
                if i < len(dims) - 1:
                    c.n_in, c.n_out = dims[i], dims[i + 1]
            out = confs[-1]
            out.n_in = dims[-1]
            if out.n_out <= 0:
                raise ValueError("output layer needs n_out")
        else:
            for prev, cur in zip(confs[:-1], confs[1:]):
                if cur.n_in <= 0 and cur.kind not in (
                        LayerKind.CONVOLUTION, LayerKind.SUBSAMPLING):
                    cur.n_in = prev.n_out

    # -- init --------------------------------------------------------------
    def init(self, seed: Optional[int] = None) -> "MultiLayerNetwork":
        seed = self.conf.confs[0].seed if seed is None else seed
        keys = jax.random.split(jax.random.key(seed), len(self.layers))
        self.params = [layer.init(k) for layer, k in zip(self.layers, keys)]
        return self

    def _require_params(self) -> Params:
        if self.params is None:
            self.init()
        return self.params  # type: ignore[return-value]

    @property
    def output_layer(self) -> OutputLayer:
        last = self.layers[-1]
        if not isinstance(last, OutputLayer):
            raise TypeError("last layer is not an OutputLayer")
        return last

    # -- forward (feedForward:462 parity) ----------------------------------
    def feed_forward(self, params: Params, x: Array,
                     key: Optional[Array] = None, train: bool = False,
                     upto: Optional[int] = None) -> List[Array]:
        """Returns [input, act_0, ..., act_{upto-1}]."""
        n = len(self.layers) if upto is None else upto
        acts = [x]
        keys = (jax.random.split(key, n) if key is not None else [None] * n)
        for i in range(n):
            h = acts[-1]
            if i in self._in_pre:
                h = self._in_pre[i](h, keys[i])
            h = self.layers[i].activate(params[i], h, key=keys[i], train=train)
            if i in self._out_pre:
                h = self._out_pre[i](h, keys[i])
            acts.append(h)
        return acts

    def hidden_activations(self, params: Params, x: Array,
                           key: Optional[Array] = None,
                           train: bool = False) -> Array:
        """Activations entering the output layer (input to finetune)."""
        return self.feed_forward(params, x, key, train,
                                 upto=len(self.layers) - 1)[-1]

    # -- losses ------------------------------------------------------------
    def loss(self, params: Params, x: Array, labels: Array,
             key: Optional[Array] = None, train: bool = False) -> Array:
        """End-to-end supervised loss (differentiable — backprop is
        jax.grad of this)."""
        h = self.hidden_activations(params, x, key, train)
        if len(self.layers) - 1 in self._in_pre:
            h = self._in_pre[len(self.layers) - 1](h, key)
        return self.output_layer.loss(params[-1], h, labels)

    # -- inference (output:1147 / predict:1057 / score:1213) ---------------
    # The reference serves these eagerly, op by op.  Here they route
    # through the serving engine (serving/engine.py): ONE jitted forward
    # per bucket in the ladder, shared across identically-configured
    # networks via the runtime compile engine.  feed_forward stays the
    # raw eager path (training internals + the bucketing-correctness
    # reference in tests).

    def _serving_machinery(self):
        """(forward, scorer) jitted through the MODULE-LEVEL compile
        engine, keyed on the canonical conf signature — same sharing
        and detached-replica rules as ``_backprop_machinery``."""
        if self._serving_cache is None:
            self._serving_cache = compile_cache.get_or_build(
                ("multilayer_serving", self._conf_signature()),
                self._build_serving_machinery)
        return self._serving_cache

    def _build_serving_machinery(self):
        # detached conf-rebuilt replica: the engine entry must neither
        # pin this network nor retrace against later conf mutations
        net = MultiLayerNetwork(
            MultiLayerConfiguration.from_json(self._conf_signature()))

        def forward(p, x):
            return net.feed_forward(p, x)[-1]

        def scorer(p, x, y):
            return net.loss(p, x, y)

        # the padded input buffer is engine-owned and fresh per dispatch
        # — donating it reuses its HBM in place; params serve every
        # request and are NOT donated
        return (compile_cache.cached_jit(
                    forward, label="serving.forward", donate_argnums=(1,)),
                compile_cache.cached_jit(
                    scorer, label="serving.score"))

    def serving_engine(self, buckets: Optional[Sequence[int]] = None,
                       max_batch_size: Optional[int] = None):
        """The bucketed inference engine serving THIS network's live
        params.  Default-configured engines are memoized per instance;
        pass ``buckets``/``max_batch_size`` for a custom ladder (e.g.
        before ``warmup()`` in a serving process)."""
        from deeplearning4j_tpu.serving.engine import (DEFAULT_MAX_BATCH,
                                                       InferenceEngine)
        custom = buckets is not None or max_batch_size is not None
        if not custom and self._serving_engine_memo is not None:
            return self._serving_engine_memo
        forward, _ = self._serving_machinery()
        eng = InferenceEngine(
            forward, params=self._require_params,
            buckets=buckets,
            max_batch_size=max_batch_size or DEFAULT_MAX_BATCH)
        if not custom:
            self._serving_engine_memo = eng
        return eng

    def output(self, x: Array, params: Optional[Params] = None) -> Array:
        if not hasattr(x, "ndim"):
            x = jnp.asarray(x)
        if x.ndim == 1:
            # single unbatched example: no batch dim to bucket — raw
            # eager forward keeps the reference's permissive signature
            p = params if params is not None else self._require_params()
            return self.feed_forward(p, x)[-1]
        return self.serving_engine().infer(x, params=params)

    def predict(self, x: Array) -> Array:
        return jnp.argmax(self.output(x), axis=-1)

    def score(self, data: DataSet, params: Optional[Params] = None) -> float:
        """Mean loss on ``data`` through ONE jitted program.

        Compile contract: unlike ``output`` (bucket-padded — padding a
        MEAN loss would change its value), the scorer specializes per
        (features, labels) shape signature: first call per shape traces,
        repeats are compile-free.  Score fixed-shape eval sets on hot
        paths; a stream of ragged sizes belongs on ``output`` +
        ``Evaluation`` (both bucketed)."""
        params = params if params is not None else self._require_params()
        _, scorer = self._serving_machinery()
        return float(scorer(params, data.features, data.labels))

    # -- pretrain (pretrain:144 parity) ------------------------------------
    def pretrain(self, data: Union[DataSet, Sequence[DataSet]],
                 seed: int = 0) -> None:
        """Greedy layer-wise: train each pretrainable layer on the
        activations of the stack below it, batch by batch.

        For GRADIENT_DESCENT (the default) the step is jitted ONCE per layer
        with the batch as a traced argument — no per-batch recompilation.
        Line-search algorithms (CG/LBFGS) run a full Solver per batch (they
        are full-batch methods; the reference does the same)."""
        from deeplearning4j_tpu.nn.conf.configuration import OptimizationAlgorithm
        # donation guard: the engine's gd_step donates params/ustate, so
        # copy ONCE at the API boundary — caller-held references to the
        # pre-fit params stay valid
        params = jax.tree.map(jnp.copy, self._require_params())
        batches = [data] if isinstance(data, DataSet) else list(data)
        self._notify_fit_start()
        key = jax.random.key(seed)
        for i, layer in enumerate(self.layers):
            if not isinstance(layer, PretrainLayer):
                continue
            conf = self.conf.confs[i]

            # Inputs to layer i under the CURRENT stack params (greedy).
            def layer_input(x: Array) -> Array:
                return self.feed_forward(params, x, upto=i)[-1]

            if conf.optimization_algo in (
                    OptimizationAlgorithm.GRADIENT_DESCENT,
                    OptimizationAlgorithm.ITERATION_GRADIENT_DESCENT):
                # the jitted per-layer step AND its updater live in the
                # module-level engine keyed on (layer index, conf JSON):
                # a fresh closure per pretrain() call would recompile
                # every time (the fit_backprop lesson), and N identically
                # configured replicas share ONE compile.  The ustate init
                # must come from the same updater the cached step closes
                # over.  Like _build_backprop_machinery, the builder
                # closes over a DETACHED conf-rebuilt layer/updater — not
                # this network's live objects — so the entry neither pins
                # this network nor retraces against later conf mutations.
                def _build_gd(_i=i):
                    rep = MultiLayerNetwork(
                        MultiLayerConfiguration.from_json(
                            self._conf_signature()))
                    rlayer = rep.layers[_i]
                    rc = rep.conf.confs[_i]
                    rupdater = dl4j_updater(
                        lr=rc.lr, momentum=rc.momentum,
                        momentum_schedule=rc.momentum_after,
                        use_adagrad=rc.use_adagrad, l2=rc.l2,
                        use_regularization=rc.use_regularization,
                        constrain_unit_norm=rc.constrain_gradient_to_unit_norm,
                    )

                    def gd_step(p, ustate, inputs, k, it):
                        k = jax.random.fold_in(k, it)
                        score, grads = rlayer.pretrain_value_and_grad(
                            p, k, inputs)
                        # batch_size=1: objectives are batch MEANS (the
                        # ÷batch step exists for parity with summed
                        # reference grads)
                        updates, new_ustate = rupdater.update(
                            ustate, grads, p, it, 1)
                        new_p, new_ustate, skipped = resilience.guard_update(
                            p, ustate, apply_updates(p, updates),
                            new_ustate, (score, grads))
                        return new_p, new_ustate, score, skipped
                    # params + updater state update in place on device
                    # (donated); pretrain() copies on entry
                    return (compile_cache.cached_jit(
                        gd_step, label=f"multilayer.pretrain_gd[{_i}]",
                        donate_argnums=(0, 1)), rupdater)
                gd_step, updater = compile_cache.get_or_build(
                    ("multilayer_pretrain_gd", i, self._conf_signature()),
                    _build_gd)

                ustate = updater.init(params[i])
                it = 0
                # distinct key stream per LAYER: fold_in(key, it) alone
                # would replay identical corruption/Gibbs noise in every
                # layer of the stack
                layer_key = jax.random.fold_in(key, i)
                skips = []
                for batch in batches:
                    inputs = layer_input(batch.features)
                    for _ in range(conf.num_iterations):
                        params[i], ustate, score, skipped = gd_step(
                            params[i], ustate, inputs, layer_key, it)
                        skips.append(skipped)
                        if self.listeners:
                            for ls in self.listeners:
                                ls.iteration_done(self, it, float(score))
                        it += 1
                self._note_skips(skips)
            else:
                for b, batch in enumerate(batches):
                    inputs = layer_input(batch.features)
                    objective = Objective(
                        value_and_grad=lambda p, k: layer.pretrain_value_and_grad(
                            p, k, inputs),
                        value=lambda p, k: layer.pretrain_value_and_grad(
                            p, k, inputs)[0],
                        batch_size=1,
                    )
                    solver = Solver(conf, objective, listeners=self.listeners)
                    key, sub = jax.random.split(key)
                    params[i] = solver.optimize(params[i], sub)
                    log.debug("pretrain layer %d batch %d done", i, b)
        self.params = params

    # -- Hessian-free (fit:1006-1009 + backPropGradient2:856 parity) -------
    def fit_hessian_free(self, data: DataSet,
                         num_iterations: Optional[int] = None) -> None:
        """Whole-network Hessian-free optimization: Gauss-Newton products
        through the full stack (the autodiff equivalent of the reference's
        R-operator backPropGradient2/getBackPropRGradient)."""
        from deeplearning4j_tpu.optimize.hessian_free import (
            GNObjective, StochasticHessianFree)

        params = self._require_params()
        out = self.output_layer
        last = len(self.layers) - 1

        def logits_fn(p):
            h = self.hidden_activations(p, data.features)
            if last in self._in_pre:
                h = self._in_pre[last](h, None)
            return out.pre_output(p[last], h)

        obj = GNObjective(
            logits_fn=logits_fn,
            loss_from_logits=lambda z: out.loss_from_logits(z, data.labels))
        hf = StochasticHessianFree(
            obj,
            num_iterations=num_iterations
            or self.conf.confs[-1].num_iterations,
            listeners=self.listeners)
        self.params = hf.optimize(params)

    # -- finetune (finetune:987 parity) ------------------------------------
    def finetune(self, data: DataSet, seed: int = 1) -> None:
        """Train ONLY the output layer on last-hidden activations; with
        HESSIAN_FREE configured, optimize the WHOLE network instead (the
        reference's finetune does exactly this split, fit:1006-1009)."""
        from deeplearning4j_tpu.nn.conf.configuration import (
            OptimizationAlgorithm)

        if (self.conf.confs[-1].optimization_algo
                is OptimizationAlgorithm.HESSIAN_FREE):
            self.fit_hessian_free(data)
            return
        params = self._require_params()
        h = self.hidden_activations(params, data.features)
        # Same boundary transform as loss(): the output layer must train on
        # exactly what it sees at inference.
        last = len(self.layers) - 1
        if last in self._in_pre:
            h = self._in_pre[last](h, None)
        out_conf = self.conf.confs[-1]
        out_layer = self.output_layer
        objective = Objective(
            value_and_grad=lambda p, k: jax.value_and_grad(
                out_layer.loss)(p, h, data.labels),
            value=lambda p, k: out_layer.loss(p, h, data.labels),
            batch_size=1,
        )
        solver = Solver(out_conf, objective, listeners=self.listeners)
        params[-1] = solver.optimize(params[-1], jax.random.key(seed))
        self.params = params

    # -- backprop fine-tuning (doBackWard:941 ≡ jax.grad of loss) ----------
    def _conf_signature(self) -> str:
        """Canonical config signature for the compile engine: the sorted-
        key conf JSON (wired sizes included).  Everything the jitted step
        closes over — layers, preprocessors, updaters, BN indices — is
        derived from exactly this."""
        return self.conf.to_json()

    def _mp_on(self) -> bool:
        """Whether the conf's mixed-precision policy is active (and
        fail-fast validation of the knob — an unknown policy must raise
        at the fit boundary, not silently train fp32)."""
        policy = getattr(self.conf, "mixed_precision", "off")
        if policy not in MIXED_PRECISION_POLICIES:
            raise ValueError(
                f"mixed_precision must be one of "
                f"{MIXED_PRECISION_POLICIES}, got {policy!r}")
        return policy == "bf16"

    @staticmethod
    def _init_ustate(train_step, updaters, params):
        """Fresh updater state for an engine step: the machinery's own
        initializer when it exposes one (the mixed-precision bundle
        threads the dynamic loss-scale state alongside the per-layer
        updater states), else the plain per-layer list."""
        init = getattr(train_step, "init_ustate", None)
        if init is not None:
            return init(params)
        return [u.init(p) for u, p in zip(updaters, params)]

    def _backprop_machinery(self, mesh=None):
        """(train_step, train_epochs, updaters) from the MODULE-LEVEL
        compile engine, keyed on the canonical conf signature (plus the
        mesh signature on the sharded path).

        The jitted step closes over conf-derived state only, so N
        identically-configured networks — e.g. the worker replicas
        ``parallel/scaleout.py`` / ``parallel/data_parallel.py`` spawn
        from one conf JSON — share ONE compiled step instead of paying N
        XLA compiles (tens of seconds each on TPU).  Mutating
        ``self.conf`` after the first fit requires a fresh network (same
        contract as the reference's init()-once lifecycle; the engine
        key would otherwise go stale).

        With ``mesh`` (a Mesh with a ``data`` axis) — or whenever
        ``conf.grad_accum > 1`` — the bundle is the DATA-PARALLEL
        machinery: steps take ``(x, y, n_valid)`` batch tuples (zero-pad
        + mask contract, ``parallel/mesh.pad_global_batch``), shard the
        batch axis over ``data``, psum grads in-graph, and decide guard
        skips from the COLLECTIVE values so replicas never diverge.
        Such steps carry ``takes_n_valid = True`` so generic drivers
        (``ResilientFit``) can adapt.  The engine key grows the mesh
        signature (axis sizes AND device ids): same conf on two meshes
        is two entries, never a silent cross-mesh cache hit.

        Donation contract: ``train_step`` and ``train_epochs`` donate
        params + updater state, so their HBM is reused in place — the
        fit entry points copy caller params once at the API boundary."""
        from deeplearning4j_tpu.parallel.mesh import mesh_signature

        dp = (mesh is not None or self.conf.grad_accum > 1
              or self._mp_on())
        # the accum factor AND the mixed-precision policy join the memo
        # key: ResilientFit's elastic recovery legitimately rebuilds on
        # the same mesh signature with a different grad_accum, and a
        # caller may flip conf.mixed_precision between fits — the engine
        # key below (conf JSON) would catch both while this per-net memo
        # would not, and a stale hit trains with the wrong accumulation
        # or silently with the wrong precision/loss-scaling
        memo_key = (("dp", mesh_signature(mesh),
                     max(self.conf.grad_accum, 1), self._mp_on())
                    if dp else "legacy")
        if memo_key not in self._bp_cache:
            if dp:
                self._bp_cache[memo_key] = compile_cache.get_or_build(
                    ("multilayer_backprop_dp", self._conf_signature(),
                     mesh_signature(mesh)),
                    lambda: self._build_dp_machinery(mesh))
            else:
                self._bp_cache[memo_key] = compile_cache.get_or_build(
                    ("multilayer_backprop", self._conf_signature()),
                    self._build_backprop_machinery)
        return self._bp_cache[memo_key]

    def _build_backprop_machinery(self):
        # Close over a DETACHED replica rebuilt from the conf JSON
        # (params=None), never over ``self``: the engine entry outlives
        # this network, and a closure over ``self`` would pin the first
        # network's whole object graph — trained params included — for
        # process lifetime.
        net = MultiLayerNetwork(
            MultiLayerConfiguration.from_json(self._conf_signature()))
        updaters = [dl4j_updater(
            lr=c.lr, momentum=c.momentum, momentum_schedule=c.momentum_after,
            use_adagrad=c.use_adagrad, l2=c.l2,
            use_regularization=c.use_regularization,
            constrain_unit_norm=c.constrain_gradient_to_unit_norm,
        ) for c in net.conf.confs]
        bn_layers = [i for i, c in enumerate(net.conf.confs)
                     if c.kind is LayerKind.BATCH_NORM]

        def step_body(params, ustate, x, y, key, iteration):
            # derive this step's key on-device from the run key: no
            # host-side split (whose [n_steps]-shaped output recompiles
            # whenever the step count changes)
            key = jax.random.fold_in(key, iteration)

            def obj(p):
                # Single forward: reuse the loss-side activations to
                # harvest the batch statistics BN's running-stat EMA needs
                # (previously a second full feed_forward per step — ~2x
                # forward cost on any BN net).
                n = len(net.layers)
                acts = net.feed_forward(p, x, key, train=True, upto=n - 1)
                h = acts[-1]
                last = n - 1
                if last in net._in_pre:
                    h = net._in_pre[last](h, key)
                loss = net.output_layer.loss(p[-1], h, y)
                stats = {}
                for i in bn_layers:
                    h_in = acts[i]
                    ax = tuple(range(h_in.ndim - 1))
                    stats[i] = (jnp.mean(h_in, axis=ax),
                                jnp.var(h_in, axis=ax))
                return loss, stats
            (score, stats), grads = jax.value_and_grad(
                obj, has_aux=True)(params)
            new_params, new_ustate = [], []
            for i, upd in enumerate(updaters):
                u_i, s_i = upd.update(ustate[i], grads[i], params[i],
                                      iteration, 1)
                new_params.append(apply_updates(params[i], u_i))
                new_ustate.append(s_i)
            for i in bn_layers:
                # EMA-refresh batch-norm running stats (momentum 0.9) from
                # the training forward's own batch statistics.
                mean, var = stats[i]
                p = dict(new_params[i])
                p["running_mean"] = 0.9 * p["running_mean"] + 0.1 * mean
                p["running_var"] = 0.9 * p["running_var"] + 0.1 * var
                new_params[i] = p
            # in-step anomaly guard: a non-finite loss or gradient drops
            # the whole update (params AND updater state — a poisoned
            # AdaGrad accumulator would corrupt every later step) and
            # raises the skip flag.  Pure jnp.where select: same XLA
            # program on the healthy path, no extra compiles.
            new_params, new_ustate, skipped = resilience.guard_update(
                params, ustate, new_params, new_ustate, (score, grads))
            return new_params, new_ustate, score, skipped

        # donate params + updater state: the update writes back into the
        # same HBM instead of doubling traffic/peak memory per step.  The
        # fit entry points copy caller arrays once, so only loop-internal
        # buffers are ever consumed.
        train_step = compile_cache.cached_jit(
            step_body, label="multilayer.train_step", donate_argnums=(0, 1))

        def _epoch_scan(carry, xs, ys, key):
            """lax.scan the step over device-stacked batches [NB, B, ...]."""
            def body(c, inp):
                p, u, it = c
                x, y = inp
                p, u, score, skipped = step_body(p, u, x, y, key, it)
                return (p, u, it + 1), (score, skipped)

            return lax.scan(body, carry, (xs, ys))

        def train_epochs(params, ustate, xs, ys, key, it0, num_epochs):
            """ONE dispatch for the whole fit: scan over epochs of the
            scanned step.  A python per-step loop costs one host->device
            round-trip per step, and even a per-epoch loop pays one per
            epoch — dispatch latency that small-model compute cannot
            hide.  Returns per-step scores AND guard skip flags,
            each [num_epochs, NB], so listeners replay exactly and the
            host books skipped steps with one sync at the end."""
            def epoch_body(carry, _):
                return _epoch_scan(carry, xs, ys, key)

            (params, ustate, _), (scores, skips) = lax.scan(
                epoch_body, (params, ustate, it0), None, length=num_epochs)
            return params, ustate, scores, skips

        train_epochs = compile_cache.cached_jit(
            train_epochs, label="multilayer.train_epochs",
            static_argnums=(6,), donate_argnums=(0, 1))

        return (train_step, train_epochs, updaters)

    def _build_dp_machinery(self, mesh):
        """Data-parallel engine bundle: the scanned-epoch step under a
        device mesh (batch sharded over ``data``, grads psum'd in-graph,
        params/updater state replicated) and/or microbatch gradient
        accumulation (``conf.grad_accum`` inner scan, fp32 sum
        accumulators, ONE update per step).

        The loss is computed in masked-SUM form — per-example losses
        times a validity mask, summed, then psum'd with the real row
        count and divided ONCE — so (a) zero-padded trailing-batch rows
        contribute nothing to loss or gradient, and (b) shard/microbatch
        combination is a single global reduction whose math equals the
        full-batch mean exactly.  The in-step guard then sees the
        COLLECTIVE (score, grads): one shard's non-finite gradient
        poisons the psum, so every replica skips the same step and the
        replicated params cannot diverge.

        ``conf.mixed_precision == "bf16"`` additionally runs the
        forward/backward in bfloat16 against fp32 MASTER params (the
        cast lives inside the objective, so grads and every updater
        accumulator stay fp32) with DYNAMIC loss scaling: the loss is
        multiplied by the scale before the backward, grads unscaled in
        the same global divide as the mean, and an overflowed step rides
        the existing guard — the collective skip verdict both drops the
        update and halves the scale on every replica identically
        (``parallel/sharded_fit.next_loss_scale``).  The scale state
        threads through the scanned epochs alongside the updater state;
        the bundle's ``init_ustate`` builds the combined structure."""
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.nn.layers.extras import bn_collective
        from deeplearning4j_tpu.parallel import sharded_fit
        from deeplearning4j_tpu.parallel.mesh import DATA_AXIS

        net = MultiLayerNetwork(
            MultiLayerConfiguration.from_json(self._conf_signature()))
        updaters = [dl4j_updater(
            lr=c.lr, momentum=c.momentum, momentum_schedule=c.momentum_after,
            use_adagrad=c.use_adagrad, l2=c.l2,
            use_regularization=c.use_regularization,
            constrain_unit_norm=c.constrain_gradient_to_unit_norm,
        ) for c in net.conf.confs]
        bn_layers = [i for i, c in enumerate(net.conf.confs)
                     if c.kind is LayerKind.BATCH_NORM]
        accum = max(net.conf.grad_accum, 1)
        axis = DATA_AXIS if mesh is not None else None
        mp_on = net._mp_on()

        def micro_fn(params, x, y, mask, key):
            """Masked SUM loss + masked BN-stat sums for one microbatch
            (the unit both the accumulation scan and the shard psum
            combine linearly).  Under mixed precision the fp32 masters
            are cast to bf16 HERE — inside the differentiated function —
            so the backward re-casts gradients to fp32.

            The forward traces under ``bn_collective``: every BatchNorm
            layer normalizes with masked GLOBAL moments (psum over the
            data axis under a mesh) instead of per-shard/pad-
            contaminated batch statistics — cross-replica BN, the
            second half of ROADMAP item 5."""
            n = len(net.layers)
            if mp_on:
                params = sharded_fit.mp_cast(params)
                if jnp.issubdtype(x.dtype, jnp.floating):
                    x = x.astype(jnp.bfloat16)
            with bn_collective(axis, mask):
                acts = net.feed_forward(params, x, key, train=True,
                                        upto=n - 1)
            h = acts[-1]
            last = n - 1
            if last in net._in_pre:
                h = net._in_pre[last](h, key)
            per = net.output_layer.per_example_loss(params[-1], h, y)
            loss_sum = jnp.sum(per * mask)
            stats = {}
            for i in bn_layers:
                h_in = acts[i]
                m = mask.reshape(mask.shape + (1,) * (h_in.ndim - 1))
                red = tuple(range(h_in.ndim - 1))
                # pre-divide by the static spatial extent (conv BN
                # reduces H*W too) so the step-level combine is just
                # Σ/row_count: mean = Σ(h)/(rows*spatial)
                spatial = float(np.prod(h_in.shape[1:-1])) \
                    if h_in.ndim > 2 else 1.0
                stats[i] = (jnp.sum(h_in * m, axis=red) / spatial,
                            jnp.sum(jnp.square(h_in) * m, axis=red)
                            / spatial)
            return loss_sum, stats

        def dp_step(params, ustate, batch, key, iteration):
            if mp_on:
                # the dynamic loss-scale state rides NEXT TO the per-
                # layer updater states so it threads through the scanned
                # epochs (and checkpoints) with zero builder changes
                layer_ustate, ls = ustate
                scale = ls["scale"]
            else:
                layer_ustate, ls, scale = ustate, None, None
            x, y, n_valid = batch
            key = jax.random.fold_in(key, iteration)
            local = x.shape[0]
            if axis is not None:
                # distinct per-shard noise stream (dropout/sampling);
                # masks are computed against GLOBAL row indices so only
                # the zero-padded tail is excluded
                key = jax.random.fold_in(key, lax.axis_index(axis))
                offset = lax.axis_index(axis) * local
            else:
                offset = 0
            mask = ((offset + jnp.arange(local)) < n_valid) \
                .astype(jnp.float32)
            # the GLOBAL valid count is n_valid by construction (padding
            # only ever extends the tail), so no psum is needed for it
            count = n_valid.astype(jnp.float32)

            def scaled_obj(p, xi, yi, mi, ki):
                """The differentiated objective: loss-scaled sum (what
                the backward sees) with the unscaled sum riding as aux
                for the score."""
                loss_sum, stats = micro_fn(p, xi, yi, mi, ki)
                scaled = loss_sum * scale if mp_on else loss_sum
                return scaled, (loss_sum, stats)

            if accum == 1:
                (_, (loss_sum, stats)), grads = jax.value_and_grad(
                    scaled_obj, has_aux=True)(params, x, y, mask, key)
            else:
                micro = local // accum
                xm = x.reshape((accum, micro) + x.shape[1:])
                ym = y.reshape((accum, micro) + y.shape[1:])
                mm = mask.reshape(accum, micro)

                def micro_body(carry, inp):
                    g_acc, s_acc = carry
                    xi, yi, mi, i = inp
                    (_, (s, st)), g = jax.value_and_grad(
                        scaled_obj, has_aux=True)(
                            params, xi, yi, mi,
                            jax.random.fold_in(key, i))
                    # fp32 sum accumulators: constant-HBM effective
                    # batch growth regardless of param/compute dtype
                    g_acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(jnp.float32), g_acc, g)
                    return (g_acc, s_acc + s), st

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (grads, loss_sum), stats_seq = lax.scan(
                    micro_body, (g0, jnp.float32(0.0)),
                    (xm, ym, mm, jnp.arange(accum)))
                grads = jax.tree.map(
                    lambda g, p: g.astype(p.dtype), grads, params)
                stats = jax.tree.map(lambda s: jnp.sum(s, axis=0),
                                     stats_seq)

            if axis is not None:
                loss_sum = lax.psum(loss_sum, axis)
                grads = jax.tree.map(lambda g: lax.psum(g, axis), grads)
                stats = jax.tree.map(lambda s: lax.psum(s, axis), stats)
            denom = jnp.maximum(count, 1.0)
            score = loss_sum / denom
            # one global divide finishes mean AND loss-scale unscaling;
            # an overflowed backward leaves inf/NaN in the grads here,
            # which the collective guard below turns into a skip
            gdenom = denom * scale if mp_on else denom
            grads = jax.tree.map(lambda g: g / gdenom, grads)

            new_params, new_ustate = [], []
            for i, upd in enumerate(updaters):
                u_i, s_i = upd.update(layer_ustate[i], grads[i], params[i],
                                      iteration, 1)
                new_params.append(apply_updates(params[i], u_i))
                new_ustate.append(s_i)
            for i in bn_layers:
                # masked moments over the GLOBAL batch (rows were mask-
                # weighted, spatial extent pre-divided in micro_fn) —
                # the sharded EMA refresh sees full-batch statistics,
                # not one shard's
                sum_h, sum_h2 = stats[i]
                mean = sum_h / denom
                var = sum_h2 / denom - jnp.square(mean)
                p = dict(new_params[i])
                p["running_mean"] = 0.9 * p["running_mean"] + 0.1 * mean
                p["running_var"] = 0.9 * p["running_var"] + 0.1 * var
                new_params[i] = p
            new_params, new_ustate, skipped = resilience.guard_update(
                params, layer_ustate, new_params, new_ustate,
                (score, grads))
            if mp_on:
                # the scale transition deliberately BYPASSES the guard:
                # a skipped (overflowed) step must still halve the scale
                # — that is the recovery.  ``skipped`` is collective, so
                # every replica takes the same transition.
                return (new_params, (new_ustate,
                                     sharded_fit.next_loss_scale(
                                         ls, skipped)), score, skipped)
            return new_params, new_ustate, score, skipped

        batch_specs = (P(DATA_AXIS), P(DATA_AXIS), P()) \
            if mesh is not None else None
        train_step = sharded_fit.build_sharded_step(
            dp_step, mesh, batch_specs=batch_specs,
            label="multilayer.train_step")
        train_epochs = sharded_fit.build_scanned_epochs(
            dp_step, mesh, batch_specs=batch_specs,
            label="multilayer.train_epochs")

        def init_ustate(params):
            layer_u = [u.init(p) for u, p in zip(updaters, params)]
            if mp_on:
                return (layer_u, sharded_fit.init_loss_scale())
            return layer_u

        for fn in (train_step, train_epochs):
            fn.takes_n_valid = True
            fn.init_ustate = init_ustate
            fn.mixed_precision = mp_on
        return (train_step, train_epochs, updaters)

    def _resolve_fit_mesh(self, mesh, min_batch: int):
        """The sharded-by-default policy.  ``mesh="auto"`` (the fit
        default) picks the all-device ``data`` mesh when it can shard
        SAFELY: >1 device and every batch holds at least one row per
        shard.  Dropout/DropConnect confs auto-shard (ROADMAP item 5,
        first half): the DP step folds the shard index into the
        per-step RNG key, so each data replica draws an INDEPENDENT
        mask over its own rows — the sampled-mask distribution over the
        global batch is unchanged, but the concrete masks differ from a
        single-device run of the same seed (MIGRATION.md documents the
        semantics change).  BatchNorm confs auto-shard too (item 5,
        second half): the DP forward normalizes with masked GLOBAL
        moments psum'd in-graph (``nn/layers/extras.bn_collective``),
        so sharding does not turn batch statistics into per-shard
        ghost-batch statistics and padded rows are exactly excluded —
        the old BN gate (and ``_check_bn_padding``'s refusal) became
        unnecessary, and the vision zoo (lenet, resnet) now takes the
        default sharded path."""
        from deeplearning4j_tpu.parallel.mesh import (DATA_AXIS,
                                                      auto_data_mesh)

        if mesh is None or mesh is False:
            return None
        if mesh != "auto":                  # explicit Mesh: caller's call
            if min_batch < mesh.shape[DATA_AXIS]:
                raise ValueError(
                    f"batch of {min_batch} cannot shard over "
                    f"data-parallel degree {mesh.shape[DATA_AXIS]}: every "
                    f"device needs at least one example — use a bigger "
                    f"batch, a smaller mesh, or mesh=None")
            return mesh
        m = auto_data_mesh()
        if m is None or min_batch < m.shape[DATA_AXIS]:
            return None
        return m

    @staticmethod
    def _pad_chunk(mesh, accum: int) -> int:
        """Row-count multiple every dispatched batch is padded to."""
        from deeplearning4j_tpu.parallel.mesh import DATA_AXIS
        ndp = mesh.shape[DATA_AXIS] if mesh is not None else 1
        return ndp * max(accum, 1)

    @staticmethod
    def _pad_rows(arr: Array, target: int) -> Array:
        from deeplearning4j_tpu.parallel.mesh import pad_rows
        return pad_rows(arr, target)

    def fit_backprop(self, data: Union[DataSet, Sequence[DataSet]],
                     num_epochs: int = 1, seed: int = 2,
                     mesh="auto") -> None:
        """Full-network supervised minibatch training with ONE fused,
        jit-compiled train step (value+grad+GradientAdjustment+update),
        compiled once per CONFIG — shared across fit calls AND across
        identically-configured networks via the runtime compile engine —
        with params/updater state donated back into the same HBM.

        Uniform-shape batch lists run as a scanned EPOCH — a single
        device dispatch per epoch, with listeners replayed from the
        scanned per-step scores afterwards.  Ragged batch lists (or a
        lone DataSet) use the per-step path.

        When a mesh with a ``data`` axis of size > 1 is available
        (auto-detected; ``mesh=`` overrides per call) the SAME scanned
        program runs sharded: batch axis over ``data``, grads psum'd
        in-graph, params/updater state replicated, guard skips decided
        collectively — still ONE dispatch per fit.  ``conf.grad_accum``
        adds the microbatch accumulation scan inside the step.  Batches
        that don't divide by the shard count are zero-padded and the
        padded rows masked out of loss and gradient (exact, not
        approximate).

        Each layer gets its OWN updater from its conf, so per-layer
        lr/momentum/l2 overrides (ConfOverride parity) take effect."""
        batches = [data] if isinstance(data, DataSet) else list(data)
        if not batches:
            return
        self._notify_fit_start()
        min_batch = min(b.features.shape[0] for b in batches)
        rmesh = self._resolve_fit_mesh(mesh, min_batch)
        dp = (rmesh is not None or self.conf.grad_accum > 1
              or self._mp_on())
        with telemetry.span("multilayer.fit", path="dp" if dp else "single",
                            epochs=num_epochs, batches=len(batches)):
            if dp:
                self._fit_backprop_dp(batches, num_epochs, seed, rmesh)
            else:
                self._fit_backprop_single(batches, num_epochs, seed)

    def _fit_backprop_single(self, batches, num_epochs: int,
                             seed: int) -> None:
        """The single-device fit body (no mesh, no grad accumulation)."""
        # donation guard: the engine steps donate params/ustate buffers;
        # one copy at the API boundary keeps caller-held references to
        # the pre-fit params valid (only loop-internal buffers, which no
        # caller ever saw, get consumed in place)
        params = jax.tree.map(jnp.copy, self._require_params())
        train_step, train_epochs, updaters = self._backprop_machinery()
        ustate = self._init_ustate(train_step, updaters, params)
        run_key = jax.random.key(seed)
        # the scanned path stacks every batch on device: only take it when
        # the whole dataset comfortably fits in HBM, else stream per-step.
        # Sized from shape/dtype — np.asarray here would D2H-copy every
        # device-resident batch just to count bytes
        def _nbytes(a):
            return math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
        total_bytes = sum(_nbytes(b.features) + _nbytes(b.labels)
                          for b in batches)
        uniform = (len(batches) > 1
                   and total_bytes <= self.SCAN_MAX_DATASET_BYTES
                   and len({(b.features.shape, b.labels.shape)
                            for b in batches}) == 1)
        it = 0
        if uniform:
            with telemetry.span("multilayer.stage",
                                batches=len(batches)) as sp:
                xs = jnp.stack([jnp.asarray(b.features) for b in batches])
                ys = jnp.stack([jnp.asarray(b.labels) for b in batches])
                sp.set(bytes=_nbytes(xs) + _nbytes(ys))
            # the dispatch span closes after _note_skips — the one
            # device sync that makes the scanned program's wall time
            # honest (the dispatch itself returns immediately)
            with telemetry.span("multilayer.dispatch", scanned=True,
                                steps=num_epochs * len(batches)):
                params, ustate, scores, skips = train_epochs(
                    params, ustate, xs, ys, run_key, it, num_epochs)
                self._note_skips(skips)
            if self.listeners:
                for j, s in enumerate(np.asarray(scores).ravel()):
                    for ls in self.listeners:
                        ls.iteration_done(self, it + j, float(s))
            it += num_epochs * len(batches)
        else:
            skips = []
            stop = False
            for epoch in range(num_epochs):
                if stop:
                    break
                with telemetry.span("multilayer.epoch", epoch=epoch):
                    for batch in batches:
                        if self._preempt_stop("fit_backprop"):
                            stop = True
                            break
                        params, ustate, it = self._step_and_notify(
                            train_step, params, ustate, batch, run_key, it,
                            skips)
            self._note_skips(skips)
        self.params = params

    def _fit_backprop_dp(self, batches, num_epochs: int, seed: int,
                         rmesh) -> None:
        """The data-parallel/microbatched fit body: same structure as the
        legacy path (scanned single dispatch when uniform, per-step
        stream otherwise) but through the DP machinery — batches padded
        to the shard x accum multiple with their real row count carried
        alongside, stacked tensors staged onto the mesh with the batch
        axis pre-sharded (the H2D transfer lands each shard's slice on
        its device, no gather-then-scatter)."""
        from deeplearning4j_tpu.parallel import sharded_fit
        from deeplearning4j_tpu.parallel.mesh import DATA_AXIS
        from deeplearning4j_tpu.runtime.metrics import dp_metrics

        params = jax.tree.map(jnp.copy, self._require_params())
        train_step, train_epochs, updaters = self._backprop_machinery(rmesh)
        ustate = self._init_ustate(train_step, updaters, params)
        run_key = jax.random.key(seed)
        accum = max(self.conf.grad_accum, 1)
        ndp = rmesh.shape[DATA_AXIS] if rmesh is not None else 1
        chunk = self._pad_chunk(rmesh, accum)
        sizes = [b.features.shape[0] for b in batches]
        pad_to = [-(-s // chunk) * chunk for s in sizes]

        def _nbytes(a):
            return math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
        total_bytes = sum(_nbytes(b.features) + _nbytes(b.labels)
                          for b in batches)
        # uniform-enough for ONE scanned dispatch: same non-batch dims
        # everywhere and equal batch rows except a smaller TRAILING
        # remainder (which pads up to the common size and masks out —
        # the classic last-batch raggedness); anything more ragged
        # streams per-step
        uniform = (len(batches) > 1
                   and total_bytes <= self.SCAN_MAX_DATASET_BYTES
                   and len({(b.features.shape[1:], b.labels.shape[1:])
                            for b in batches}) == 1
                   and len(set(sizes[:-1])) == 1
                   and sizes[-1] <= sizes[0])
        it = 0
        if uniform:
            target = max(pad_to)
            xs = jnp.stack([self._pad_rows(b.features, target)
                            for b in batches])
            ys = jnp.stack([self._pad_rows(b.labels, target)
                            for b in batches])
            nvs = jnp.asarray([b.features.shape[0] for b in batches],
                              jnp.int32)
            if rmesh is not None:
                # pre-shard the stacked epoch on its way into HBM: the
                # transfer itself is the scatter, and the one fit
                # dispatch below finds every shard already resident
                with telemetry.span("multilayer.stage", sharded=True,
                                    batches=len(batches)) as sp:
                    t0 = time.perf_counter()
                    sharding = sharded_fit.stacked_sharding(rmesh)
                    xs = jax.device_put(xs, sharding)
                    ys = jax.device_put(ys, sharding)
                    dp_metrics.note_staged(
                        _nbytes(xs) + _nbytes(ys),
                        (time.perf_counter() - t0) * 1e3)
                    sp.set(bytes=_nbytes(xs) + _nbytes(ys))
            # span closes after the skip booking's device sync so the
            # scanned dispatch's measured duration is honest wall time
            with telemetry.span("multilayer.dispatch", scanned=True,
                                data_degree=ndp, accum=accum,
                                steps=num_epochs * len(batches)):
                params, ustate, scores, skips = train_epochs(
                    params, ustate, (xs, ys, nvs), run_key, it, num_epochs)
                dp_metrics.note_dispatch(
                    steps=num_epochs * len(batches), accum=accum,
                    data_degree=ndp)
                self._note_skips(skips)
            if self.listeners:
                for j, s in enumerate(np.asarray(scores).ravel()):
                    for ls in self.listeners:
                        ls.iteration_done(self, it + j, float(s))
            it += num_epochs * len(batches)
        else:
            skips = []
            stop = False
            for epoch in range(num_epochs):
                if stop:
                    break
                with telemetry.span("multilayer.epoch", epoch=epoch,
                                    data_degree=ndp):
                    for b, target in zip(batches, pad_to):
                        if self._preempt_stop("fit_backprop_dp"):
                            stop = True
                            break
                        dp_batch = (self._pad_rows(b.features, target),
                                    self._pad_rows(b.labels, target),
                                    jnp.int32(b.features.shape[0]))
                        params, ustate, score, skipped = train_step(
                            params, ustate, dp_batch, run_key, it)
                        skips.append(skipped)
                        if self.listeners:
                            for ls in self.listeners:
                                ls.iteration_done(self, it, float(score))
                        it += 1
                        dp_metrics.note_dispatch(steps=1, accum=accum,
                                                 data_degree=ndp)
            self._note_skips(skips)
        self.params = params

    def _step_and_notify(self, train_step, params, ustate, batch,
                         run_key, step, skips=None):
        """One train_step dispatch + listener replay — shared by the
        per-step fit_backprop branch and fit_iterator so the two
        streaming paths can't drift.  The guard's skip flag lands in
        ``skips`` as a DEVICE scalar (summed once at fit end) so the hot
        path never adds a host sync."""
        params, ustate, score, skipped = train_step(
            params, ustate, batch.features, batch.labels, run_key, step)
        if skips is not None:
            skips.append(skipped)
        # float(score) synchronizes host<->device; only pay for it when
        # someone is listening
        if self.listeners:
            for ls in self.listeners:
                ls.iteration_done(self, step, float(score))
        return params, ustate, step + 1

    def _note_skips(self, skips) -> None:
        """Book guard-skipped steps — ONE device sync per fit (skips is
        either the scanned [E, NB] flag array or a list of per-step
        device scalars); shared impl in runtime/resilience.py.  The
        count also accumulates into ``self.guard_skips`` so listeners
        can log the model's fault history alongside its scores."""
        self.guard_skips += resilience.note_skips(skips, where="multilayer")

    def _notify_fit_start(self) -> None:
        """Fit-entry listener hook: lets stateful listeners reset
        per-fit state (MetricsListener's step timer) before step 0.
        getattr-guarded — duck-typed listeners that only implement
        iteration_done keep working."""
        for ls in self.listeners:
            hook = getattr(ls, "on_fit_start", None)
            if callable(hook):
                hook(self)

    @staticmethod
    def _preempt_stop(where: str) -> bool:
        """Step-boundary preemption check for the STREAMING fit loops:
        True when an installed ``resilience.PreemptionGuard`` has seen a
        preemption signal — the loop finishes cleanly with the params
        trained so far (checkpoint policy belongs to ``ResilientFit``,
        which owns the final-snapshot half of the drill).  One global
        read when no guard is installed; the single-dispatch scanned
        paths have no step boundary to stop at and run to completion."""
        if resilience.preemption_requested():
            telemetry.event("multilayer.preempt_stop", where=where)
            return True
        return False

    def fit_iterator(self, it, num_epochs: int = 1, seed: int = 2,
                     mesh="auto", prefetch_depth: int = 2) -> None:
        """STREAMING supervised backprop straight from a
        ``DataSetIterator`` — the backprop stage of the reference's
        ``fit(DataSetIterator)`` (nn/multilayer/MultiLayerNetwork.java:918)
        for data that does NOT live on device up front.  Confs wanting
        the pretrain path must use ``fit`` (greedy layer-wise pretrain
        needs per-layer passes over materialized activations and has no
        streaming form); this raises rather than silently diverging.

        Each pulled batch is dispatched asynchronously: while the device
        runs step ``k``, the iterator (e.g. the native producer thread
        behind ``NativeBatchIterator``, or a prefetching
        ``StoreDataSetIterator``) assembles batch ``k+1`` on host — so
        ingestion overlaps compute instead of serializing with it.
        Updater state persists across the whole call (unlike repeated
        single-batch ``fit_backprop`` calls, which would reset
        momentum).

        Under a ``data`` mesh (auto-detected; ``mesh=`` overrides) the
        stream additionally runs through a depth-``prefetch_depth``
        double-buffered SHARDED staging stage: a producer thread
        ``device_put``s each batch with the batch axis pre-sharded over
        the mesh, so every device's host->HBM slice transfer overlaps
        the previous step's compute, and the sharded train step finds
        its shard already resident."""
        if self.conf.pretrain or not self.conf.backprop:
            raise ValueError(
                "fit_iterator is the streaming backprop trainer; this "
                "conf wants pretrain/finetune (pretrain="
                f"{self.conf.pretrain}, backprop={self.conf.backprop}) — "
                "use fit() with materialized batches")
        self._notify_fit_start()
        batch_hint = getattr(it, "batch", 0) or 0
        if mesh == "auto" and batch_hint <= 0:
            rmesh = None        # unknown batch size: don't auto-shard blind
        else:
            # explicit mesh with an unknown batch size: trust the caller
            # (ragged batches are padded per step anyway)
            rmesh = self._resolve_fit_mesh(
                mesh, batch_hint if batch_hint > 0 else (1 << 30))
        # donation guard — see fit_backprop
        params = jax.tree.map(jnp.copy, self._require_params())
        train_step, _, updaters = self._backprop_machinery(rmesh)
        ustate = self._init_ustate(train_step, updaters, params)
        run_key = jax.random.key(seed)
        dp_mode = getattr(train_step, "takes_n_valid", False)
        accum = max(self.conf.grad_accum, 1)
        chunk = self._pad_chunk(rmesh, accum)
        src = it
        if rmesh is not None:
            from deeplearning4j_tpu.datasets.iterator import \
                PrefetchIterator
            from deeplearning4j_tpu.parallel import sharded_fit
            # wrap unless the caller's iterator ALREADY stages sharded —
            # a device-pinned PrefetchIterator still needs the sharded
            # stage on top (its gather-to-one-device would otherwise be
            # re-scattered inside every dispatch)
            if not (isinstance(it, PrefetchIterator)
                    and it.sharding is not None):
                src = PrefetchIterator(
                    it, depth=prefetch_depth,
                    sharding=sharded_fit.batch_sharding(rmesh),
                    pad_rows_to=chunk)
        step = 0
        skips = []
        stop = False
        with telemetry.span("multilayer.fit", path="iterator",
                            epochs=num_epochs, sharded=rmesh is not None):
            for epoch in range(num_epochs):
                if stop:
                    break
                with telemetry.span("multilayer.epoch", epoch=epoch):
                    src.reset()
                    while src.has_next():
                        if self._preempt_stop("fit_iterator"):
                            stop = True
                            break
                        batch = src.next()
                        if dp_mode:
                            n_valid = getattr(batch, "n_valid", None)
                            if n_valid is None:
                                n_valid = batch.features.shape[0]
                            target = -(-int(n_valid) // chunk) * chunk
                            dp_batch = (
                                self._pad_rows(batch.features, target),
                                self._pad_rows(batch.labels, target),
                                jnp.int32(n_valid))
                            params, ustate, score, skipped = train_step(
                                params, ustate, dp_batch, run_key, step)
                            skips.append(skipped)
                            if self.listeners:
                                for ls in self.listeners:
                                    ls.iteration_done(self, step,
                                                      float(score))
                            step += 1
                        else:
                            params, ustate, step = self._step_and_notify(
                                train_step, params, ustate, batch, run_key,
                                step, skips)
            self._note_skips(skips)
        self.params = params

    # -- fit (fit:918 parity: pretrain -> finetune -> optional backprop) ---
    def fit(self, data: Union[DataSet, Sequence[DataSet]],
            num_epochs: int = 1) -> None:
        batches = [data] if isinstance(data, DataSet) else list(data)
        if self.conf.pretrain:
            self.pretrain(batches)
        merged = DataSet.merge(batches) if len(batches) > 1 else batches[0]
        self.finetune(merged)
        if self.conf.backprop:
            self.fit_backprop(batches, num_epochs=num_epochs)

    def prepare_resilient_fit(self, data: Union[DataSet, Sequence[DataSet]]
                              ) -> tuple:
        """``fit()``'s front half for EXTERNAL training drivers
        (``cli train --checkpoint-dir`` -> ``runtime.resilience
        .ResilientFit``): the same finetune pass on the merged batches
        and the same gated ``mesh="auto"`` policy ``fit_backprop``
        applies, returned as ``(batch_list, mesh)`` for the driver's
        constructor.  One source of truth — a driver-run fit must never
        train something different from ``net.fit`` just because
        checkpointing was turned on.  Pretrain confs are the caller's
        problem to refuse (the driver only replays the backprop step)."""
        batches = [data] if isinstance(data, DataSet) else list(data)
        merged = DataSet.merge(batches) if len(batches) > 1 else batches[0]
        self.finetune(merged)
        mesh = self._resolve_fit_mesh(
            "auto", min(b.features.shape[0] for b in batches))
        return batches, mesh

    # -- evaluation helper -------------------------------------------------
    def evaluate(self, data: DataSet):
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        with telemetry.span("multilayer.eval",
                            rows=int(data.features.shape[0])):
            ev = Evaluation(num_classes=data.num_outcomes())
            ev.eval(data.labels, self.output(data.features))
            return ev

    # -- params plumbing (pack:773 / unPack:817 / merge:1321 / setParams) --
    def params_flat(self) -> Array:
        return pack_params(self._require_params())

    def set_params_flat(self, flat: Array) -> None:
        self.params = unpack_params(flat, self._require_params())

    def merge(self, others: Sequence["MultiLayerNetwork"]) -> None:
        """Parameter averaging with peers (distributed merge:1321)."""
        all_params = [self._require_params()] + \
            [o._require_params() for o in others]
        n = float(len(all_params))
        self.params = jax.tree.map(lambda *ps: sum(ps) / n, *all_params)

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(MultiLayerConfiguration.from_json(
            self.conf.to_json()))
        if self.params is not None:
            net.params = jax.tree.map(jnp.copy, self.params)
        return net

    # -- serialization (conf JSON + flat params :93-97) --------------------
    def to_bytes(self) -> bytes:
        buf = io.BytesIO()
        np.savez(buf, conf=self.conf.to_json(),
                 params=np.asarray(self.params_flat()))
        return buf.getvalue()

    @staticmethod
    def from_bytes(blob: bytes) -> "MultiLayerNetwork":
        with np.load(io.BytesIO(blob), allow_pickle=False) as z:
            conf = MultiLayerConfiguration.from_json(str(z["conf"]))
            net = MultiLayerNetwork(conf).init()
            net.set_params_flat(jnp.asarray(z["params"]))
        return net

    def set_listeners(self, listeners: Sequence[IterationListener]) -> None:
        self.listeners = list(listeners)

    def num_params(self) -> int:
        return int(self.params_flat().shape[0])
