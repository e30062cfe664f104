"""Model-parallel sharded fit + serving tests (the data×model tentpole).

Covers the contracts ISSUE 12 promises end-to-end:
- data×model GSPMD fit (``parallel/sharded_fit`` GSPMD mode through
  ``models/lm_fit.CausalLM``): numerically equivalent to the
  single-device run at equal effective batch, params/updater state laid
  out with ``NamedSharding`` (per-chip bytes ~1/model_degree), one
  donated dispatch per fit;
- ``mesh_signature`` keying: same devices, different model degrees are
  DIFFERENT engine entries;
- guard-skip + loss-scale verdicts replica-consistent across both axes;
- ``elastic_remesh`` shrinking only the data axis of a data×model mesh,
  with the refusal error naming survivor count and required divisor;
- bit-exact ``ResilientFit`` resume on a data×model mesh;
- model-sharded ``DecodeEngine`` (KV cache over heads) token-parity
  with the replicated engine, and ``Router.replicate`` device groups;
- sharded dropout (ROADMAP item 5 first half): dropout confs auto-shard
  with per-replica masks, deterministically;
- per-family shard specs (bert/gpt/moe) matching their param trees.
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models import bert, gpt, moe
from deeplearning4j_tpu.models import transformer as tfm
from deeplearning4j_tpu.models.lm_fit import CausalLM
from deeplearning4j_tpu.parallel.mesh import (MODEL_AXIS, MeshSpec,
                                              elastic_remesh, make_mesh,
                                              mesh_signature, model_degree,
                                              per_device_bytes)


def _cfg(**kw):
    base = dict(hidden=32, n_layers=2, n_heads=4, ffn_dim=64,
                compute_dtype="float32")
    base.update(kw)
    return dataclasses.replace(gpt.gpt_tiny(vocab_size=64, max_len=16),
                               **base)


CFG = _cfg()


def _mesh(data, model, offset=0):
    return make_mesh(MeshSpec(data=data, model=model),
                     devices=jax.devices()[offset:offset + data * model])


def _lm_batches(n=3, rows=8, seed=0):
    rng = np.random.RandomState(seed)
    return [DataSet(jnp.asarray(rng.randint(0, 64, (rows, 16)), jnp.int32),
                    jnp.asarray(rng.randint(0, 64, (rows, 16)), jnp.int32))
            for _ in range(n)]


def _fit_lm(mesh, seed=1, lr=0.05, num_epochs=2, **lm_kw):
    lm = CausalLM(CFG, lr=lr, **lm_kw).init(seed=seed)
    lm.fit_backprop(_lm_batches(), num_epochs=num_epochs, seed=3, mesh=mesh)
    return lm


# -- engine keying -----------------------------------------------------------

def test_mesh_signature_distinguishes_model_degree(devices):
    """Two meshes over the SAME eight devices with different model
    degrees must never share a compile-cache entry: different param
    layouts, different collectives, different executables."""
    m24 = _mesh(2, 4)
    m81 = _mesh(8, 1)
    assert mesh_signature(m24) != mesh_signature(m81)
    assert model_degree(m24) == 4 and model_degree(m81) == 1
    lm = CausalLM(CFG)
    b24 = lm._backprop_machinery(m24)
    b81 = lm._backprop_machinery(m81)
    assert b24 is not b81
    # same mesh on a second instance -> the SAME engine bundle
    assert CausalLM(CFG)._backprop_machinery(_mesh(2, 4)) is b24


# -- zoo shard specs ---------------------------------------------------------

def test_zoo_shard_specs_match_param_trees(devices):
    """Each family's data×model specs must mirror its param tree
    structure, put attention heads / MLP hidden (and MoE expert tables)
    over `model`, and shard embeddings over vocab when divisible."""
    deg = 4
    cases = [
        (gpt.shard_specs(CFG, deg),
         jax.eval_shape(lambda: gpt.init_params(jax.random.key(0), CFG))),
        (bert.shard_specs(bert.bert_tiny(), deg),
         jax.eval_shape(lambda: bert.init_params(jax.random.key(0),
                                                 bert.bert_tiny()))),
        (moe.shard_specs(moe.MoETransformerConfig(), deg),
         jax.eval_shape(lambda: moe.init_params(
             jax.random.key(0), moe.MoETransformerConfig()))),
    ]
    for specs, shapes in cases:
        assert (jax.tree.structure(specs,
                                   is_leaf=lambda x: isinstance(x, P))
                == jax.tree.structure(shapes))
    g = gpt.shard_specs(CFG, deg)
    assert MODEL_AXIS in g["blocks"]["wq"]       # heads over model
    assert MODEL_AXIS in g["blocks"]["w1"]       # MLP hidden over model
    assert g["embed"]["tok"] == P(MODEL_AXIS, None)   # 64 % 4 == 0
    m = moe.shard_specs(moe.MoETransformerConfig(), deg)
    assert MODEL_AXIS in m["blocks"]["wi"]       # experts over model
    # indivisible degrees fail at build time with the real constraint
    with pytest.raises(ValueError, match="n_heads"):
        gpt.shard_specs(CFG, 3)
    with pytest.raises(ValueError, match="n_experts"):
        moe.shard_specs(moe.MoETransformerConfig(n_experts=6), 4)
    assert tfm.shard_specs(_cfg(), 2)["embed"]["tok"] == P(MODEL_AXIS, None)
    with pytest.raises(ValueError, match="ffn_dim"):
        # heads divide (6 % 6) but the 64-wide MLP hidden does not
        tfm.shard_specs(_cfg(n_heads=6, hidden=36), 6)


# -- data×model fit ----------------------------------------------------------

def test_data_model_fit_matches_single_device(devices):
    """THE acceptance criterion (training half): the 2×4 data×model
    GSPMD fit equals the single-device fit at equal effective batch —
    same masked-sum/divide-once math, XLA owns the reduction order."""
    sharded = _fit_lm(_mesh(2, 4)).params_flat()
    single = _fit_lm(None).params_flat()
    np.testing.assert_allclose(sharded, single, rtol=1e-4, atol=1e-5)


def test_params_and_ustate_laid_out_over_model(devices):
    """After a data×model fit the trained params live SHARDED: every
    chip holds ~1/model_degree of the weights (plus the replicated
    norms/biases), not a full replica — the HBM win that lets a model
    bigger than one chip train."""
    lm = _fit_lm(_mesh(2, 4))
    pdb = per_device_bytes(lm.params)
    total = lm.num_param_bytes()
    assert len(pdb) == 8                         # resident on all 8 chips
    # replicated layout would charge each chip `total`; the sharded one
    # must come in well under half (1/4 sharded + small replicated tail)
    assert max(pdb.values()) < 0.45 * total, (pdb, total)
    # and the dominant leaves really carry a model-axis sharding
    wq = lm.params["blocks"]["wq"]
    assert MODEL_AXIS in wq.sharding.spec
    tok = lm.params["embed"]["tok"]
    assert tok.sharding.spec == P(MODEL_AXIS, None)


def test_loss_scale_and_guard_ride_the_data_model_step(devices):
    """Mixed precision on the 2×4 mesh: the PR 11 dynamic loss scale
    threads the scanned epochs as GLOBAL state (one logical verdict
    across both axes), and a healthy step advances good_steps without
    touching the scale."""
    from deeplearning4j_tpu.parallel.sharded_fit import LOSS_SCALE_INIT

    mesh = _mesh(2, 4)
    lm = CausalLM(CFG, lr=0.05, mixed_precision="bf16").init(seed=1)
    train_step, _, _ = lm._backprop_machinery(mesh)
    params = jax.tree.map(jnp.copy, lm.params)
    ustate = train_step.init_ustate(params)
    ids = _lm_batches(1)[0].features
    new_p, (mom, ls), score, skipped = train_step(
        params, ustate, (ids, ids, jnp.int32(8)), jax.random.key(0), 0)
    assert int(skipped) == 0
    assert float(ls["scale"]) == LOSS_SCALE_INIT
    assert int(ls["good_steps"]) == 1
    assert np.isfinite(float(score))
    # and the full mp fit stays finite with fp32 masters
    lm2 = _fit_lm(_mesh(2, 4), mixed_precision="bf16", num_epochs=1)
    flat = lm2.params_flat()
    assert np.isfinite(flat).all()
    assert lm2.params["blocks"]["wq"].dtype == jnp.float32


def test_multilayer_fit_on_data_model_mesh(devices):
    """The MultiLayerNetwork DP machinery accepts a data×model mesh
    (weights replicated over `model` — the dense zoo has no TP specs
    yet): results match single-device and one poisoned shard still
    skips EVERY replica on both axes."""
    from deeplearning4j_tpu.nn.conf import (LayerKind,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.runtime.metrics import resilience_metrics

    def conf():
        return (NeuralNetConfiguration.builder()
                .n_in(4).lr(0.1).momentum(0.5).use_adagrad(False)
                .num_iterations(1).activation("tanh")
                .list(3).hidden_layer_sizes(8, 6)
                .override(2, kind=LayerKind.OUTPUT, n_out=3,
                          activation="softmax", loss_function="mcxent")
                .pretrain(False).backward(True).build())

    def batches(poison=()):
        rng = np.random.RandomState(0)
        out = []
        for b in range(4):
            x = rng.randn(32, 4).astype(np.float32)
            if b in poison:
                x[0, 0] = np.nan
            y = np.eye(3, dtype=np.float32)[rng.randint(0, 3, 32)]
            out.append(DataSet(jnp.asarray(x), jnp.asarray(y)))
        return out

    mesh = _mesh(2, 4)
    net = MultiLayerNetwork(conf()).init(seed=1)
    net.fit_backprop(batches(), num_epochs=2, mesh=mesh)
    single = MultiLayerNetwork(conf()).init(seed=1)
    single.fit_backprop(batches(), num_epochs=2, mesh=None)
    np.testing.assert_allclose(np.asarray(net.params_flat()),
                               np.asarray(single.params_flat()),
                               rtol=1e-3, atol=1e-3)
    resilience_metrics.reset()
    poisoned = MultiLayerNetwork(conf()).init(seed=1)
    poisoned.fit_backprop(batches(poison={2}), num_epochs=2, mesh=mesh)
    assert np.isfinite(np.asarray(poisoned.params_flat())).all()
    assert resilience_metrics.count("steps_skipped") == 2


# -- sharded dropout (ROADMAP item 5, first half) ----------------------------

def test_dropout_confs_auto_shard_with_per_replica_masks(devices):
    """Dropout no longer drops the fit to single-device: the auto mesh
    engages, each data shard folds its shard index into the step key
    (independent masks), and the run replays deterministically from the
    seed.  BatchNorm auto-shards too since the cross-replica-moments
    half of ROADMAP item 5 landed (tests/test_dp_fit.py covers its
    numerics)."""
    from deeplearning4j_tpu.nn.conf import (LayerKind,
                                            NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    def conf(dropout=0.5, bn=False):
        b = (NeuralNetConfiguration.builder()
             .n_in(4).lr(0.1).momentum(0.0).use_adagrad(False)
             .dropout(dropout).num_iterations(1).activation("tanh")
             .list(4 if bn else 3).hidden_layer_sizes(*((8, 8, 6) if bn
                                                        else (8, 6))))
        if bn:
            b = b.override(1, kind=LayerKind.BATCH_NORM)
        return (b.override(3 if bn else 2, kind=LayerKind.OUTPUT, n_out=3,
                           activation="softmax", loss_function="mcxent",
                           dropout=0.0)
                .pretrain(False).backward(True).build())

    net = MultiLayerNetwork(conf()).init(seed=1)
    mesh = net._resolve_fit_mesh("auto", 32)
    assert mesh is not None and mesh.shape["data"] == 8
    # BN confs auto-shard now: cross-replica masked global moments
    # (nn/layers/extras.bn_collective) replaced the per-shard gate
    bn_mesh = MultiLayerNetwork(conf(bn=True)).init(
        seed=1)._resolve_fit_mesh("auto", 32)
    assert bn_mesh is not None and bn_mesh.shape["data"] == 8

    rng = np.random.RandomState(3)
    data = [DataSet(jnp.asarray(rng.randn(32, 4).astype(np.float32)),
                    jnp.asarray(np.eye(3, dtype=np.float32)[
                        rng.randint(0, 3, 32)]))
            for _ in range(2)]

    def run():
        n = MultiLayerNetwork(conf()).init(seed=2)
        n.fit_backprop(data, num_epochs=2, seed=5)
        return np.asarray(n.params_flat())

    a, b = run(), run()
    assert np.isfinite(a).all()
    assert np.array_equal(a, b)                  # deterministic replay


# -- elastic re-mesh ---------------------------------------------------------

def test_elastic_remesh_shrinks_data_axis_only(devices):
    """Losing a device from a data×model mesh drops a DATA replica and
    keeps whole model groups (accum scaled to preserve the effective
    batch); too few survivors for one group raises naming the survivor
    count and the required divisor."""
    m22 = _mesh(2, 2)
    new_mesh, new_accum = elastic_remesh(m22, lost_ids=[3], grad_accum=1)
    assert new_mesh.shape["data"] == 1 and new_mesh.shape["model"] == 2
    assert new_accum == 2
    assert model_degree(new_mesh) == 2
    # 2x2 loses two devices of different groups -> still one group
    new_mesh, new_accum = elastic_remesh(m22, lost_ids=[1, 3],
                                         grad_accum=2)
    assert new_mesh.shape["data"] == 1 and new_accum == 4
    # fewer survivors than one model group: refusal names the numbers
    m14 = _mesh(1, 4)
    with pytest.raises(ValueError, match=r"3 surviving device\(s\)"):
        elastic_remesh(m14, lost_ids=[0])
    with pytest.raises(ValueError, match="required divisor 4"):
        elastic_remesh(m14, lost_ids=[0])
    # non-data axes survive a shrink intact: a data×seq mesh drops the
    # data replica and keeps the whole seq group (PR 18 generalized the
    # model-group logic to model×pipe×seq×expert)
    mseq = make_mesh(MeshSpec(data=2, seq=2), devices=jax.devices()[:4])
    new_mesh, new_accum = elastic_remesh(mseq, lost_ids=[0], grad_accum=1)
    assert new_mesh.shape["data"] == 1 and new_mesh.shape["seq"] == 2
    assert new_accum == 2


def test_resilient_fit_data_model_resume_bit_exact(devices, tmp_path):
    """Kill-and-resume on the 2×2 data×model mesh == the uninterrupted
    run, bit-for-bit — snapshots gather the sharded state, restores
    re-shard through the engine step's pinned layouts."""
    from deeplearning4j_tpu.runtime.resilience import (ResilienceConfig,
                                                       ResilientFit)
    mesh = _mesh(2, 2)
    batches = _lm_batches(4)

    lmA = CausalLM(CFG, lr=0.05).init(seed=2)
    ResilientFit(lmA, ResilienceConfig(
        checkpoint_dir=str(tmp_path / "a"), checkpoint_every=3),
        mesh=mesh).fit(batches, num_epochs=2, seed=4)

    lmB = CausalLM(CFG, lr=0.05).init(seed=2)
    ResilientFit(lmB, ResilienceConfig(
        checkpoint_dir=str(tmp_path / "b"), checkpoint_every=3,
        max_steps=5), mesh=mesh).fit(batches, num_epochs=2, seed=4)
    ResilientFit(lmB, ResilienceConfig(
        checkpoint_dir=str(tmp_path / "b"), checkpoint_every=3,
        resume=True), mesh=mesh).fit(batches, num_epochs=2, seed=4)

    assert np.array_equal(lmA.params_flat(), lmB.params_flat())


def test_device_loss_on_data_model_mesh_resumes(devices, tmp_path):
    """Mid-fit device loss on a 2×2 data×model mesh re-meshes to 1×2
    (model groups intact, accum doubled) and finishes equal to the
    uninterrupted run — numerically: the re-laid-out GSPMD program may
    reassociate reductions."""
    from deeplearning4j_tpu.runtime.resilience import (DeviceLossError,
                                                       ResilienceConfig,
                                                       ResilientFit)
    mesh = _mesh(2, 2)
    batches = _lm_batches(4)

    lmA = CausalLM(CFG, lr=0.05).init(seed=2)
    ResilientFit(lmA, ResilienceConfig(
        checkpoint_dir=str(tmp_path / "a"), checkpoint_every=2),
        mesh=mesh).fit(batches, num_epochs=2, seed=4)

    fired = []

    def hook(step):
        if step == 5 and not fired:
            fired.append(step)
            raise DeviceLossError([3])

    lmC = CausalLM(CFG, lr=0.05).init(seed=2)
    drv = ResilientFit(lmC, ResilienceConfig(
        checkpoint_dir=str(tmp_path / "c"), checkpoint_every=2),
        mesh=mesh, fault_hook=hook)
    drv.fit(batches, num_epochs=2, seed=4)
    assert drv.remeshes == 1
    assert drv.mesh.shape["data"] == 1 and drv.mesh.shape["model"] == 2
    assert drv.elastic_accum == 2
    np.testing.assert_allclose(lmA.params_flat(), lmC.params_flat(),
                               rtol=1e-5, atol=1e-6)


# -- model-sharded serving ---------------------------------------------------

def _greedy(eng, prompt, n):
    slot, first = eng.start(prompt, max_tokens=n, temperature=0.0, seed=7)
    toks = [first]
    while len(toks) < n:
        toks.append(int(eng.advance()[slot]))
    eng.release(slot)
    return toks


def test_decode_engine_model_sharded_parity(devices):
    """A DecodeEngine over a model=4 group (params per shard_specs, KV
    cache sharded over heads) greedy-decodes the SAME tokens as the
    replicated engine, with per-chip param bytes ~1/4."""
    from deeplearning4j_tpu.serving.decode import DecodeEngine

    cfg = dataclasses.replace(gpt.gpt_tiny(vocab_size=64, max_len=32),
                              compute_dtype="float32")
    params = gpt.init_params(jax.random.key(0), cfg)
    eng_r = DecodeEngine(cfg, params, n_slots=2, buckets=(16,),
                         prefill_chunk=4)
    mesh = _mesh(1, 4)
    from jax.sharding import NamedSharding
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       gpt.shard_specs(cfg, 4),
                       is_leaf=lambda x: isinstance(x, P))
    sharded_params = jax.device_put(params, psh)
    eng_s = DecodeEngine(cfg, sharded_params, n_slots=2, buckets=(16,),
                         prefill_chunk=4, mesh=mesh)
    prompt = np.array([5, 9, 2, 7, 11], np.int32)
    assert _greedy(eng_r, prompt, 8) == _greedy(eng_s, prompt, 8)
    total = sum(int(np.prod(l.shape)) * l.dtype.itemsize
                for l in jax.tree.leaves(params))
    pdb = per_device_bytes(sharded_params)
    assert len(pdb) == 4
    assert max(pdb.values()) < 0.45 * total
    # the page pool itself is head-sharded, and every page came back
    assert MODEL_AXIS in eng_s._pool.k.sharding.spec
    eng_s.drop_residents()              # the prompt's first page
    assert eng_s._alloc.in_use() + eng_s.pages_unaccounted() == 0


def test_router_replicate_device_groups(devices):
    """``Router.replicate(model_degree=4)`` on eight devices builds two
    disjoint 4-chip groups (round-robin), each serving model-sharded;
    requests route and complete through both."""
    from deeplearning4j_tpu.serving.router import Router

    cfg = dataclasses.replace(gpt.gpt_tiny(vocab_size=64, max_len=32),
                              compute_dtype="float32")
    params = gpt.init_params(jax.random.key(0), cfg)
    router = Router.replicate(cfg, params, n_replicas=2, model_degree=4,
                              n_slots=2, buckets=(16,), prefill_chunk=4,
                              default_max_tokens=4, warmup=False)
    try:
        devs = [sorted(per_device_bytes(
            b.engine.current_params())) for b in router.batchers]
        assert devs[0] == [0, 1, 2, 3] and devs[1] == [4, 5, 6, 7]
        prompt = np.array([5, 9, 2], np.int32)
        h1 = router.submit(prompt, max_tokens=4)
        h2 = router.submit(prompt, max_tokens=4)
        t1, t2 = h1.result(120).tolist(), h2.result(120).tolist()
        assert t1 == t2                  # same model, same greedy tokens
        assert len(t1) == 4
    finally:
        router.close()
    # a group bigger than the fleet refuses loudly
    with pytest.raises(ValueError, match="model_degree"):
        Router.replicate(cfg, params, 1, model_degree=16, warmup=False)


def test_data_model_fit_zero_steady_state_compiles(devices):
    """The warmed 2×4 scanned fit is ONE donated dispatch and compiles
    nothing new — the engine entry (keyed on conf + mesh signature)
    serves every refit."""
    from deeplearning4j_tpu.runtime.metrics import compile_metrics, dp_metrics

    _fit_lm(_mesh(2, 4))                         # warm (or already warm)
    before = compile_metrics.snapshot()["compile_count"]
    dp_metrics.reset()
    _fit_lm(_mesh(2, 4))
    assert compile_metrics.snapshot()["compile_count"] == before
    snap = dp_metrics.snapshot()
    assert snap["dispatches"] == 1               # whole fit, one dispatch
