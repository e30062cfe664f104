"""Compile/donation engine tests (runtime/compile_cache.py).

Covers the engine's three contracts:
- cross-network sharing: two identically-configured networks compile the
  fused train step EXACTLY once (the acceptance criterion);
- donation safety: caller-held references to pre-fit params stay valid
  (the API boundary copies before the donating steps consume buffers);
- per-step RNG: consecutive streaming steps fold the run key with the
  step index, so dropout masks differ step to step.

Plus the tier-1 run of tools/check_no_stray_jit.py — hot-path code in
nn/ and optimize/ must compile through the engine.
"""

import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import (
    LayerKind, NeuralNetConfiguration, OptimizationAlgorithm,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import CollectScoresListener
from deeplearning4j_tpu.optimize.solver import Objective, Solver
from deeplearning4j_tpu.runtime import compile_cache
from deeplearning4j_tpu.runtime.metrics import compile_metrics

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _fresh_engine():
    compile_cache.clear()
    compile_metrics.reset()


def _mlp_conf(dropout=0.0, lr=0.1, momentum=0.5):
    return (NeuralNetConfiguration.builder()
            .n_in(4).lr(lr).momentum(momentum).use_adagrad(False)
            .dropout(dropout).num_iterations(5)
            .activation("tanh")
            .list(3)
            .hidden_layer_sizes(8, 6)
            .override(2, kind=LayerKind.OUTPUT, n_out=3,
                      activation="softmax", loss_function="mcxent",
                      dropout=0.0)
            .pretrain(False).backward(True)
            .build())


def _toy_data(n=32, seed=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, 4).astype(np.float32))
    y = jnp.asarray(np.eye(3, dtype=np.float32)[rng.randint(0, 3, n)])
    return DataSet(x, y)


# -- cross-network compile cache -------------------------------------------

def test_two_identical_networks_compile_train_step_once():
    """The acceptance criterion: constructing and fitting two
    identically-configured networks traces/compiles the fused train step
    exactly once — the second network is a pure engine hit."""
    _fresh_engine()
    data = _toy_data()
    net1 = MultiLayerNetwork(_mlp_conf()).init(seed=1)
    net2 = MultiLayerNetwork(_mlp_conf()).init(seed=2)
    net1.fit_backprop(data, num_epochs=3)
    net2.fit_backprop(data, num_epochs=3)

    snap = compile_metrics.snapshot()
    assert snap["traces"].get("multilayer.train_step") == 1, snap
    assert snap["compile_count"] == 1, snap
    assert snap["engine_builds"] == 1, snap
    assert snap["engine_hits"] >= 1, snap
    assert snap["compile_ms"] > 0.0, snap
    # both fits actually dispatched steps beyond the compiling call
    assert snap["cached_dispatches"] >= 4, snap
    # the memoized machinery bundle is literally the same object
    assert net1._backprop_machinery() is net2._backprop_machinery()
    # and both networks trained (params moved off their inits)
    for net in (net1, net2):
        assert np.isfinite(np.asarray(net.params_flat())).all()


def test_different_confs_do_not_share_engines():
    _fresh_engine()
    data = _toy_data()
    MultiLayerNetwork(_mlp_conf(lr=0.1)).init().fit_backprop(data)
    MultiLayerNetwork(_mlp_conf(lr=0.2)).init().fit_backprop(data)
    snap = compile_metrics.snapshot()
    # different lr -> different canonical signature -> two engine builds
    assert snap["engine_builds"] == 2, snap
    assert snap["traces"].get("multilayer.train_step") == 2, snap


def test_scanned_epoch_path_shares_compile_too():
    """The uniform-batch scan path (train_epochs) is engine-cached the
    same way: second identical network re-uses the single compile."""
    _fresh_engine()
    batches = [_toy_data(16, seed=s) for s in range(4)]
    MultiLayerNetwork(_mlp_conf()).init(seed=1).fit_backprop(
        batches, num_epochs=2)
    MultiLayerNetwork(_mlp_conf()).init(seed=2).fit_backprop(
        batches, num_epochs=2)
    snap = compile_metrics.snapshot()
    assert snap["traces"].get("multilayer.train_epochs") == 1, snap


# -- donation safety --------------------------------------------------------

def test_caller_held_params_survive_fit_backprop():
    """fit_backprop's steps donate params/updater-state buffers, but the
    API boundary copies on entry — references a caller held BEFORE the
    fit must stay readable afterwards (no use-after-donate)."""
    _fresh_engine()
    net = MultiLayerNetwork(_mlp_conf()).init(seed=3)
    held = net.params                      # caller-held pre-fit reference
    before = np.asarray(net.params_flat()).copy()

    net.fit_backprop(_toy_data(), num_epochs=4)

    # every held leaf is still materializable (donated buffers raise) and
    # untouched: the held reference IS the pre-fit state, not an alias of
    # the trained one
    held_flat = np.concatenate([np.asarray(l).ravel()
                                for l in jax.tree.leaves(held)])
    np.testing.assert_allclose(held_flat, before, rtol=1e-6)
    # and training really moved the live params
    after = np.asarray(net.params_flat())
    assert not np.allclose(before, after)


def test_repeated_fits_and_streaming_survive_donation():
    """Back-to-back fits re-init updater state and re-donate the previous
    fit's output params; both must stay safe, including the scanned-epoch
    path and caller-held snapshots between fits."""
    _fresh_engine()
    net = MultiLayerNetwork(_mlp_conf()).init(seed=4)
    batches = [_toy_data(16, seed=s) for s in range(3)]
    net.fit_backprop(batches, num_epochs=2)      # scanned path
    mid = net.params
    net.fit_backprop(_toy_data(), num_epochs=2)  # per-step path
    for leaf in jax.tree.leaves(mid):
        np.asarray(leaf)                          # raises if donated
    assert np.isfinite(np.asarray(net.params_flat())).all()


def test_solver_optimizers_do_not_invalidate_caller_params():
    """Every Solver algorithm donates its loop-threaded state; caller
    params passed to optimize() must remain valid afterwards."""
    for algo in (OptimizationAlgorithm.GRADIENT_DESCENT,
                 OptimizationAlgorithm.CONJUGATE_GRADIENT,
                 OptimizationAlgorithm.LBFGS):
        conf = (NeuralNetConfiguration.builder()
                .lr(0.1).momentum(0.0).use_adagrad(False)
                .num_iterations(4)
                .optimization_algo(OptimizationAlgorithm(algo)).build())
        params = {"w": jnp.ones((6,)) * 3.0}
        obj = Objective(
            value_and_grad=lambda p, k: (jnp.sum(p["w"] ** 2),
                                         {"w": 2.0 * p["w"]}),
            value=lambda p, k: jnp.sum(p["w"] ** 2))
        out = Solver(conf, obj).optimize(params, jax.random.key(0))
        got = np.asarray(params["w"])             # raises if donated
        np.testing.assert_allclose(got, 3.0)
        assert float(jnp.sum(out["w"] ** 2)) < 6 * 9.0, algo


def test_pretrain_keeps_caller_params_valid():
    conf = (NeuralNetConfiguration.builder()
            .n_in(4).lr(0.05).num_iterations(5).use_adagrad(False)
            .activation("sigmoid")
            .list(3)
            .hidden_layer_sizes(6, 5)
            .override(0, kind=LayerKind.AUTOENCODER, corruption_level=0.1)
            .override(1, kind=LayerKind.AUTOENCODER, corruption_level=0.1)
            .override(2, kind=LayerKind.OUTPUT, n_out=3,
                      activation="softmax", loss_function="mcxent")
            .pretrain(True).backward(False)
            .build())
    net = MultiLayerNetwork(conf).init(seed=5)
    held = net.params
    net.pretrain(_toy_data())
    for leaf in jax.tree.leaves(held):
        assert np.isfinite(np.asarray(leaf)).all()
    # the pretrain engine entries follow the detached-replica rule too:
    # dropping the network must actually free it
    import gc
    import weakref
    ref = weakref.ref(net)
    del net, held
    gc.collect()
    assert ref() is None, "pretrain engine entry kept the network alive"


# -- per-step RNG (satellite: streaming paths fold run_key with step) -------

def test_streaming_steps_use_distinct_dropout_masks():
    """step_body folds the run key with the step index, so two
    consecutive steps through _step_and_notify (the fit_backprop per-step
    branch and fit_iterator both route here) see DIFFERENT dropout
    masks.  Regression guard: with lr=0 the params never move, so the
    per-step scores differ if and only if the masks differ."""
    _fresh_engine()
    data = _toy_data(64, seed=9)

    def run():
        net = MultiLayerNetwork(
            _mlp_conf(dropout=0.5, lr=0.0, momentum=0.0)).init(seed=6)
        listener = CollectScoresListener()
        net.set_listeners([listener])
        net.fit_backprop(data, num_epochs=3, seed=2)   # 3 steps, 1 batch
        return [s for _, s in listener.scores]

    scores = run()
    assert len(scores) == 3
    # same-key-every-step would make these identical
    assert len(set(scores)) == 3, scores
    # deterministic: the whole sequence replays exactly from the seed
    assert run() == scores


def test_engine_entry_does_not_pin_network():
    """The cached machinery must close over a detached conf-rebuilt
    replica, NOT the first network — otherwise the engine would pin that
    network's whole object graph (trained params included) for process
    lifetime."""
    import gc
    import weakref

    _fresh_engine()
    net = MultiLayerNetwork(_mlp_conf()).init(seed=8)
    net.fit_backprop(_toy_data(), num_epochs=2)
    ref = weakref.ref(net)
    del net
    gc.collect()
    assert ref() is None, "engine entry kept the fitted network alive"
    # the entry itself is still live and reusable by a successor network
    net2 = MultiLayerNetwork(_mlp_conf()).init(seed=9)
    net2.fit_backprop(_toy_data(), num_epochs=1)
    snap = compile_metrics.snapshot()
    assert snap["traces"].get("multilayer.train_step") == 1, snap


# -- lint: hot paths must go through the engine -----------------------------

def test_no_stray_jit_in_hot_paths():
    spec = importlib.util.spec_from_file_location(
        "check_no_stray_jit", REPO_ROOT / "tools" / "check_no_stray_jit.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.find_stray_jits(REPO_ROOT) == []
