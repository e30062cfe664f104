"""Serving tier 2: int8 quantized weights + int8 KV cache, prefix
reuse, and the telemetry-driven autoscaling router.

The load-bearing properties:

- per-channel int8 round-trip error is bounded by scale/2 per element;
- the quantized ENGINE is bit-identical to the dequantized-weights
  reference run through the fp32 pipeline (dequant fusion changes
  nothing), and its top-1 agreement vs fp32 passes the ``Evaluation``
  accuracy-delta assertion helper;
- int8-KV decode stays within a drift bound of fp32-KV (and agrees on
  greedy tokens over short horizons);
- a prefix-cache HIT is BIT-exact vs cold prefill (full and partial
  prefixes) and books hits/misses/tokens-saved;
- the autoscale policy is hysteretic (no flapping on an oscillating
  synthetic load trace), and the autoscaling router scales up under
  pressure with ZERO new compiles, drains on scale-down, and sheds
  (``shed_by_policy``) only at its replica ceiling;
- every new path preserves the zero-steady-state-compile invariant.
"""

import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.eval.evaluation import Evaluation
from deeplearning4j_tpu.models import gpt
from deeplearning4j_tpu.models.transformer import TransformerConfig
from deeplearning4j_tpu.runtime import quantize as qz
from deeplearning4j_tpu.runtime.metrics import compile_metrics, decode_metrics
from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,
                                               DecodeEngine, PrefixCache)
from deeplearning4j_tpu.serving.engine import InferenceEngine
from deeplearning4j_tpu.serving.router import (AutoscalePolicy,
                                               AutoscalingRouter,
                                               OverloadedError)

CFG = TransformerConfig(vocab_size=64, max_len=64, hidden=32, n_layers=2,
                        n_heads=2, ffn_dim=64, dropout=0.0,
                        compute_dtype="float32", causal=True,
                        type_vocab_size=1)


@pytest.fixture(scope="module")
def params():
    return gpt.init_params(jax.random.key(7), CFG)


def _solo(params, prompt, n_tokens, p=None, cfg=CFG):
    out = gpt.generate(cfg, p if p is not None else params,
                       np.asarray(prompt, np.int32)[None, :],
                       n_tokens, jax.random.key(0), temperature=0.0)
    return list(np.asarray(out)[0])


def _engine_tokens(eng, prompt, n):
    slot, first = eng.start(np.asarray(prompt, np.int32), max_tokens=n)
    toks = [first] + [int(eng.advance()[slot]) for _ in range(n - 1)]
    eng.release(slot)
    return toks


# -- quantization numerics --------------------------------------------------

def test_int8_roundtrip_error_bound():
    """Per-channel symmetric int8: |w - dq(q(w))| <= scale/2 per
    element, channel-wise scales, int8 payload at the original shape."""
    rng = np.random.RandomState(0)
    w2 = (rng.randn(64, 16) * rng.gamma(2.0, 2.0, size=16)).astype(np.float32)
    qt = qz.quantize_leaf(w2)
    assert qt.q.dtype == jnp.int8 and qt.q.shape == w2.shape
    assert qt.scale.shape == (16,)
    err = np.abs(np.asarray(qz.dequantize_leaf(qt)) - w2)
    assert (err <= np.asarray(qt.scale)[None, :] / 2 + 1e-7).all()

    # stacked >=3-D leaves keep per-(stack, channel) scales — layers
    # never share a range
    w3 = (rng.randn(3, 32, 8) * np.asarray([1, 10, 100])[:, None, None]
          ).astype(np.float32)
    qt3 = qz.quantize_leaf(w3)
    assert qt3.scale.shape == (3, 8)
    err3 = np.abs(np.asarray(qz.dequantize_leaf(qt3)) - w3)
    assert (err3 <= np.asarray(qt3.scale)[:, None, :] / 2 + 1e-5).all()

    # all-zero channels survive (scale floored, values exactly zero)
    wz = np.zeros((8, 4), np.float32)
    assert (np.asarray(qz.dequantize_leaf(qz.quantize_leaf(wz))) == 0).all()


def test_int8_skips_stacked_norm_and_bias_leaves():
    """Per-layer vectors ride the blocks tree STACKED as 2-D [L, H]
    leaves; a shape-only rule would share one scale across layers and
    zero a layer whose gains are small relative to another's.  The
    name-aware exemption keeps bias/norm leaves fp32."""
    ln = jnp.concatenate([jnp.full((1, 4), 0.01),
                          jnp.full((1, 4), 100.0)])
    tree = {"blocks": {"ln1_g": ln, "bq": jnp.ones((2, 2, 4)),
                       "wq": jnp.ones((2, 4, 2, 2))}}
    qp = qz.quantize_tree(tree, "int8")
    assert not isinstance(qp["blocks"]["ln1_g"], qz.QTensor)
    assert not isinstance(qp["blocks"]["bq"], qz.QTensor)
    assert isinstance(qp["blocks"]["wq"], qz.QTensor)
    np.testing.assert_allclose(np.asarray(qp["blocks"]["ln1_g"])[0], 0.01)
    # the hazard the exemption prevents: raw shape-only quantization of
    # the stacked gains rounds the small layer to exactly zero
    dq = qz.dequantize_leaf(qz.quantize_leaf(ln))
    assert float(np.abs(np.asarray(dq)[0]).max()) == 0.0
    # quant_specs mirrors the exemption (structure must keep matching)
    from jax.sharding import PartitionSpec as P
    specs = {"blocks": {"ln1_g": P(), "bq": P(), "wq": P()}}
    qs = qz.quant_specs(specs, tree, "int8")
    assert not isinstance(qs["blocks"]["ln1_g"], qz.QTensor)
    assert isinstance(qs["blocks"]["wq"], qz.QTensor)


def test_quantize_tree_modes(params):
    qp = qz.quantize_tree(params, "int8")
    leaves = jax.tree.leaves(qp, is_leaf=lambda x: isinstance(x, qz.QTensor))
    assert any(isinstance(x, qz.QTensor) for x in leaves)
    # 1-D leaves (layer-norm gains/biases) pass through untouched
    assert qp["embed"]["ln_g"].dtype == jnp.float32
    assert qp["embed"]["ln_g"].ndim == 1
    # byte economics: int8 tree well under half the fp32 tree
    assert qz.tree_bytes(qp) < 0.5 * qz.tree_bytes(params)
    bp = qz.quantize_tree(params, "bf16")
    assert bp["embed"]["tok"].dtype == jnp.bfloat16
    # dequant restores structure + fp32 leaves
    dq = qz.dequantize_tree(qp)
    assert jax.tree.structure(dq) == jax.tree.structure(params)
    assert dq["embed"]["tok"].dtype == jnp.float32
    assert qz.quantize_tree(params, None) is params
    with pytest.raises(ValueError, match="quantize mode"):
        qz.quantize_tree(params, "fp4")


def test_quant_specs_match_quantized_structure(params):
    """The spec tree quant_specs produces must mirror
    quantize_tree's structure (int8 payload keeps the leaf's layout,
    scales take the entries of the axes they index) — the invariant
    model-sharded int8 serving rests on."""
    specs = gpt.shard_specs(CFG, model_degree=2)
    qspecs = qz.quant_specs(specs, params, "int8")
    qp = qz.quantize_tree(params, "int8")
    assert jax.tree.structure(
        jax.tree.map(lambda _: 0, qspecs,
                     is_leaf=lambda x: not isinstance(
                         x, (dict, qz.QTensor)))) == jax.tree.structure(
        jax.tree.map(lambda _: 0, qp,
                     is_leaf=lambda x: not isinstance(
                         x, (dict, qz.QTensor))))
    wq_spec = qspecs["blocks"]["wq"]
    assert isinstance(wq_spec, qz.QTensor)
    assert tuple(wq_spec.q) == tuple(specs["blocks"]["wq"])
    # bf16 and None modes leave the spec tree alone
    assert qz.quant_specs(specs, params, "bf16") is specs


def test_quantized_engine_bit_matches_dequant_reference(params):
    """DecodeEngine(quantize='int8') greedy tokens == generate() with
    the dequantized quantized weights through the fp32 pipeline: the
    dequant fused into the jitted programs changes NOTHING numerically
    vs materializing the dequantized tree."""
    rng = np.random.RandomState(1)
    prompt = rng.randint(1, CFG.vocab_size, size=11).astype(np.int32)
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8, quantize="int8",
                       label="t2-int8-parity")
    eng.warmup()
    got = _engine_tokens(eng, prompt, 8)
    dq = qz.dequantize_tree(qz.quantize_tree(params, "int8"))
    assert got == _solo(params, prompt, 8, p=dq)


def test_evaluation_accuracy_delta_helper(params):
    """fp32-vs-int8 top-1 agreement through the Evaluation helper: the
    quantized forward must keep argmax agreement (accuracy delta vs
    the fp32 predictions-as-labels) within tolerance — and the helper
    raises with the numbers spelled out when it does not."""
    rng = np.random.RandomState(2)
    probe = rng.randint(1, CFG.vocab_size, size=(32, 12)).astype(np.int32)
    ref_logits = np.asarray(gpt.forward_logits(CFG, params, probe)[:, -1])
    dq = qz.dequantize_tree(qz.quantize_tree(params, "int8"))
    q_logits = np.asarray(gpt.forward_logits(CFG, dq, probe)[:, -1])
    labels = np.argmax(ref_logits, -1)
    e_ref, e_q = Evaluation(), Evaluation()
    e_ref.eval(labels, ref_logits)
    e_q.eval(labels, q_logits)
    assert e_ref.accuracy() == 1.0
    delta = e_ref.assert_accuracy_within(e_q, tol=0.1, label="int8")
    assert 0.0 <= delta <= 0.1

    # the failure mode names its numbers
    e_bad = Evaluation()
    e_bad.eval(labels, -ref_logits)
    with pytest.raises(AssertionError, match="accuracy delta"):
        e_ref.assert_accuracy_within(e_bad, tol=0.01)


def test_int8_kv_drift_bound(params):
    """int8 KV vs fp32 KV (same fp32 weights): prefill logits stay
    within a quantization-commensurate bound and short-horizon greedy
    tokens agree."""
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, CFG.vocab_size, size=(1, 12)).astype(np.int32)
    ref_cache = gpt.init_cache(CFG, 1, 32)
    from deeplearning4j_tpu.models.gpt import QKVCache, _prefill_chunk
    q_cache = QKVCache(jnp.zeros((2, 1, 32, 2, 16), jnp.int8),
                       jnp.zeros((2, 1, 32, 2, 16), jnp.int8),
                       jnp.zeros((2, 1, 32), jnp.float32),
                       jnp.zeros((2, 1, 32), jnp.float32))
    _, ref_logits = _prefill_chunk(CFG, params, ref_cache,
                                   jnp.asarray(prompt), jnp.int32(0))
    _, q_logits = _prefill_chunk(CFG, params, q_cache,
                                 jnp.asarray(prompt), jnp.int32(0))
    ref_l, q_l = np.asarray(ref_logits), np.asarray(q_logits)
    scale = max(np.abs(ref_l).max(), 1.0)
    assert np.abs(q_l - ref_l).max() <= 0.05 * scale
    np.testing.assert_array_equal(np.argmax(ref_l[0, -1]),
                                  np.argmax(q_l[0, -1]))

    # greedy token agreement over a short horizon through the engine
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8, kv_dtype="int8",
                       label="t2-kv8")
    eng.warmup()
    got = _engine_tokens(eng, prompt[0], 8)
    assert got == _solo(params, prompt[0], 8)
    # capacity: the int8 cache's bytes/slot beat fp32 by >= 1.8x
    fp = gpt.slots_bytes_per_slot(CFG, 32)
    assert fp / eng.kv_bytes_per_slot >= 1.8


@pytest.mark.parametrize("kv_dtype", [None, "int8"],
                         ids=["kv_compute_dtype", "kv_int8"])
def test_kv_bytes_per_slot_accounting(params, kv_dtype):
    """The gauges match the real device arrays' bytes, scale rows
    included: a slot of a 32-token bucket is four live 8-token pages."""
    pool = gpt.init_pages(CFG, 4, 8, kv_dtype=kv_dtype)
    per_slot = sum(np.asarray(x).nbytes for x in jax.tree.leaves(pool))
    assert gpt.pages_bytes(CFG, 4, 8, kv_dtype) == per_slot
    assert gpt.slots_bytes_per_slot(CFG, 32, kv_dtype) == per_slot
    eng = DecodeEngine(CFG, params, n_slots=4, buckets=(32,),
                       prefill_chunk=8, kv_dtype=kv_dtype,
                       label="t2-kvbytes")
    assert eng.kv_bytes_per_slot == per_slot
    assert decode_metrics.snapshot()["kv_bytes_per_slot"] == per_slot
    # the default pool: n_slots slots of the largest rung + the trash page
    assert eng.n_kv_pages == 4 * 4 + 1
    assert eng.pool_bytes == sum(
        np.asarray(x).nbytes for x in jax.tree.leaves(eng._pool_state()))


# -- prefix cache -----------------------------------------------------------

def test_prefix_cache_store_semantics():
    """Host-side store semantics: longest chunk-aligned STRICT prefix
    wins, alias keys serve shorter prefixes of longer entries, LRU
    eviction under max_bytes, clear() empties."""
    C = 8
    store = PrefixCache(max_bytes=5_000)   # fits ONE ~3.2KB entry
    toks = np.arange(100, 124, dtype=np.int32)        # 3 chunks
    pages = (np.ones((2, 24, 2, 4), np.float32),
             np.full((2, 24, 2, 4), 2.0, np.float32))
    assert store.insert(toks, pages, C)
    assert not store.insert(toks, pages, C)           # dup refused
    with pytest.raises(ValueError, match="multiple"):
        store.insert(toks[:5], pages, C)

    # full prompt = stored prefix + tail -> full 24-token hit
    hit = store.lookup(np.concatenate([toks, [9, 9, 9]]), C)
    assert hit is not None and hit[0] == 24
    assert hit[1][0].shape == (2, 24, 2, 4)
    # prompt sharing only the first chunk -> 8-token alias hit
    hit = store.lookup(np.concatenate([toks[:8], [1, 2, 3, 4]]), C)
    assert hit is not None and hit[0] == 8
    # a stored prefix is only reused STRICTLY below the prompt length
    # (the final chunk always prefills: it produces the first token)
    hit = store.lookup(toks, C)
    assert hit is not None and hit[0] == 16
    # diverging tokens -> miss
    assert store.lookup(np.asarray([1, 2, 3, 4, 5, 6, 7, 8, 9], np.int32),
                        C) is None

    # eviction: a second entry pushing past max_bytes evicts the LRU
    toks2 = np.arange(200, 224, dtype=np.int32)
    assert store.insert(toks2, pages, C)
    assert store.stats()["entries"] == 1              # first evicted
    assert store.lookup(np.concatenate([toks, [9]]), C) is None
    assert store.lookup(np.concatenate([toks2, [9]]), C) is not None
    store.clear()
    assert store.stats() == {"entries": 0, "bytes": 0}

    # shared-boundary aliases survive the eviction of an OLDER entry
    # they also covered: E1 stores AB, E2 stores ABCD (same first two
    # chunks, re-pointing the shared aliases); evicting E1 must not
    # kill the AB boundary E2 still serves
    small = PrefixCache(max_bytes=2 * (np.prod(pages[0].shape) * 4 * 2
                                       + 200))
    assert small.insert(toks[:16], tuple(p[:, :16] for p in pages), C)
    assert small.insert(toks, pages, C)          # covers AB too
    # evict E1 (LRU) by inserting a third, unrelated entry
    assert small.insert(np.arange(300, 324, dtype=np.int32), pages, C)
    hit = small.lookup(np.concatenate([toks[:16], [7, 7, 7]]), C)
    assert hit is not None and hit[0] == 16

    # stored pages OWN their memory: a slice view of a big base must
    # not retain the base in the accounting
    base = np.zeros((2, 1024, 2, 4), np.float32)
    owned = PrefixCache()
    owned.insert(np.arange(8, dtype=np.int32),
                 (base[:, :8], base[:, :8]), C)
    assert owned.stats()["bytes"] < base.nbytes


def test_prefix_hit_bit_exact_vs_cold(params):
    """The acceptance property: a warm same-prompt request (and a
    partial-prefix request) decode BIT-identically to cold prefill,
    with hits/misses/tokens-saved booked and zero compiles."""
    store = PrefixCache()
    # a config of its own: engines of one config and geometry share
    # their jitted programs (tests/test_serving_tier3.py builds this
    # geometry on CFG), and this test counts its own traces
    cfg = dataclasses.replace(CFG, layer_norm_eps=4e-5)
    eng = DecodeEngine(cfg, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8, prefix_cache=store,
                       label="t2-prefix")
    warm = eng.warmup()
    assert warm["compiles"] == 4          # prefill+step+page read/write
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, CFG.vocab_size, size=21).astype(np.int32)
    base = decode_metrics.snapshot()
    cold = _engine_tokens(eng, prompt, 8)
    eng.flush_harvests()            # async harvest: read-your-writes
    s1 = decode_metrics.snapshot()
    assert s1["prefix_misses"] == base["prefix_misses"] + 1
    assert store.stats()["entries"] == 1

    decode_metrics.mark_compiles()
    hot = _engine_tokens(eng, prompt, 8)
    s2 = decode_metrics.snapshot()
    assert hot == cold == _solo(params, prompt, 8, cfg=cfg)
    assert s2["prefix_hits"] == base["prefix_hits"] + 1
    # 21 tokens -> 16 chunk-aligned prefix tokens skipped
    assert s2["prefill_tokens_saved"] >= \
        base["prefill_tokens_saved"] + 16
    assert s2["compile_delta_since_mark"] == 0

    # partial hit: shares 2 chunks then diverges — still bit-exact
    tail = rng.randint(1, CFG.vocab_size, size=6).astype(np.int32)
    p2 = np.concatenate([prompt[:16], tail])
    assert _engine_tokens(eng, p2, 8) == _solo(params, p2, 8, cfg=cfg)
    assert decode_metrics.snapshot()["prefix_hits"] == \
        base["prefix_hits"] + 2


def test_prefix_hit_int8_kv_bit_exact(params):
    """Prefix pages of a QUANTIZED cache copy payload + scales
    bit-for-bit: warm == cold under kv_dtype='int8' too."""
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                       prefill_chunk=8, kv_dtype="int8",
                       prefix_cache=True, label="t2-prefix8")
    eng.warmup()
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, CFG.vocab_size, size=19).astype(np.int32)
    cold = _engine_tokens(eng, prompt, 6)
    eng.flush_harvests()
    decode_metrics.mark_compiles()
    assert _engine_tokens(eng, prompt, 6) == cold
    assert decode_metrics.snapshot()["compile_delta_since_mark"] == 0
    assert decode_metrics.snapshot()["prefix_hits"] >= 1


def test_prefix_through_batcher_and_shared_store(params):
    """Batcher-routed requests hit the store, and a SECOND engine
    sharing the same store is warmed by the first's traffic."""
    store = PrefixCache()
    eng1 = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                        prefill_chunk=8, prefix_cache=store,
                        label="t2-share1")
    eng1.warmup()
    rng = np.random.RandomState(6)
    prompt = rng.randint(1, CFG.vocab_size, size=17).astype(np.int32)
    with ContinuousBatcher(eng1, default_max_tokens=6) as cb:
        cold = list(cb.submit(prompt, max_tokens=6).result(60))
        eng1.flush_harvests()
        warm = list(cb.submit(prompt, max_tokens=6).result(60))
    assert warm == cold
    assert decode_metrics.snapshot()["prefix_hits"] >= 1

    eng2 = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                        prefill_chunk=8, prefix_cache=store,
                        label="t2-share2")
    eng2.warmup()
    hits0 = decode_metrics.snapshot()["prefix_hits"]
    assert _engine_tokens(eng2, prompt, 6) == cold
    assert decode_metrics.snapshot()["prefix_hits"] == hits0 + 1

    # an engine in a DIFFERENT KV space sharing the same store must
    # MISS the fp32 entries (int8 pages are not interchangeable with
    # fp32 pages) and still decode correctly from its own cold prefill
    eng8 = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                        prefill_chunk=8, kv_dtype="int8",
                        prefix_cache=store, label="t2-share8")
    eng8.warmup()
    hits1 = decode_metrics.snapshot()["prefix_hits"]
    assert _engine_tokens(eng8, prompt, 6) == cold
    assert decode_metrics.snapshot()["prefix_hits"] == hits1


def test_prefix_harvest_extends_on_partial_hit(params):
    """The conversation workload: a prompt that PARTIALLY hits a
    shorter stored prefix must harvest its own longer prefix, so a
    growing history hits at full depth next turn instead of
    re-prefilling the extension forever."""
    eng = DecodeEngine(CFG, params, n_slots=2, buckets=(64,),
                       prefill_chunk=8, prefix_cache=True,
                       label="t2-extend")
    eng.warmup()
    rng = np.random.RandomState(12)
    p1 = rng.randint(1, CFG.vocab_size, size=20).astype(np.int32)
    _engine_tokens(eng, p1, 4)                    # miss, stores 16
    eng.flush_harvests()
    p2 = np.concatenate(
        [p1, rng.randint(1, CFG.vocab_size, size=17).astype(np.int32)])
    s0 = decode_metrics.snapshot()
    assert _engine_tokens(eng, p2, 4) == _solo(params, p2, 4)
    eng.flush_harvests()
    s1 = decode_metrics.snapshot()
    assert s1["prefill_tokens_saved"] - s0["prefill_tokens_saved"] == 16
    # ... and the partial hit harvested p2's 32-token prefix
    p3 = np.concatenate(
        [p2, rng.randint(1, CFG.vocab_size, size=8).astype(np.int32)])
    assert _engine_tokens(eng, p3, 4) == _solo(params, p3, 4)
    s2 = decode_metrics.snapshot()
    assert s2["prefill_tokens_saved"] - s1["prefill_tokens_saved"] == 32


# -- autoscaling ------------------------------------------------------------

def test_autoscale_policy_hysteresis():
    """Synthetic load trace: oscillation never scales, sustained heat
    scales up exactly once per cooldown window, sustained cold scales
    down, and the replica bounds clamp both directions."""
    pol = AutoscalePolicy(1, 3, high_depth=4.0, low_depth=1.0,
                          up_after=2, down_after=3, cooldown_s=10.0,
                          interval_s=0.0)
    t = [0.0]

    def obs(depth, n):
        t[0] += 1.0
        return pol.observe(depth, None, n, now=t[0])

    # oscillating around the threshold: streaks reset, no action ever
    assert [obs(d, 1) for d in (5, 0, 5, 0, 5, 0)] == ["hold"] * 6
    # sustained heat: up after exactly up_after consecutive
    assert obs(6, 1) == "hold"
    assert obs(6, 1) == "up"
    # cooldown blocks an immediate second action even under heat
    assert obs(9, 2) == "hold"
    t[0] += 20.0
    # sustained cold: down after down_after consecutive
    assert [obs(0, 2) for _ in range(2)] == ["hold", "hold"]
    assert obs(0, 2) == "down"
    # bounds clamp: at max replicas heat holds; at min cold holds
    t[0] += 20.0
    assert [obs(9, 3) for _ in range(4)] == ["hold"] * 4
    t[0] += 20.0
    assert [obs(0, 1) for _ in range(5)] == ["hold"] * 5
    # TTFT SLO is an independent heat signal — but ONLY under live
    # load: the p99 reservoir is cumulative, so a stale spike over an
    # idle fleet must read cold and allow scale-down (regression for
    # the latched-at-max failure mode)
    pol2 = AutoscalePolicy(1, 2, high_depth=100.0, low_depth=1.0,
                           ttft_p99_slo_ms=50.0, up_after=1,
                           down_after=1, cooldown_s=0.0, interval_s=0.0)
    assert pol2.observe(1.5, 80.0, 1, now=1.0) == "up"
    assert pol2.observe(0.0, 80.0, 2, now=2.0) == "down"
    with pytest.raises(ValueError, match="min_replicas"):
        AutoscalePolicy(3, 2)
    with pytest.raises(ValueError, match="low_depth"):
        AutoscalePolicy(1, 2, high_depth=1.0, low_depth=2.0)
    # low_depth = 0 would make scale-down unreachable
    with pytest.raises(ValueError, match="low_depth"):
        AutoscalePolicy(1, 2, high_depth=8.0, low_depth=0.0)
    # the fixed-fleet builder doesn't apply to a factory-built router
    with pytest.raises(TypeError, match="factory"):
        AutoscalingRouter.replicate(CFG, {}, 2)


def test_autoscaling_router_scales_up_and_drains(params):
    """Pressure scales the fleet up with ZERO new compiles (factory
    clones share the compile cache), idle ticks scale it back down,
    and every request completes."""
    decode_metrics.reset()

    def factory():
        eng = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                           prefill_chunk=8, label="t2-auto")
        eng.warmup()
        return ContinuousBatcher(eng, default_max_tokens=8)

    pol = AutoscalePolicy(1, 2, high_depth=2.0, low_depth=1.0,
                          up_after=1, down_after=2, cooldown_s=0.0,
                          interval_s=0.0)
    router = AutoscalingRouter(factory, pol, max_queue_depth=64)
    before = compile_metrics.snapshot()["compile_count"]
    rng = np.random.RandomState(7)
    with router:
        handles = [router.submit(rng.randint(1, CFG.vocab_size, size=5),
                                 max_tokens=8) for _ in range(12)]
        for h in handles:
            assert h.result(120).shape == (8,)
        # policy scale-up spawns OFF the lock: wait for it to land
        for _ in range(200):
            if decode_metrics.snapshot()["replicas_added"] >= 1:
                break
            time.sleep(0.05)
        for i in range(5):                  # idle ticks after the burst
            router.tick(now=1e9 + i)
        snap = decode_metrics.snapshot()
        assert snap["replicas_added"] >= 1
        assert snap["replicas_removed"] >= 1
        assert router.n_replicas() == 1
    assert compile_metrics.snapshot()["compile_count"] == before


def test_autoscaling_router_sheds_only_at_ceiling(params):
    """Below max_replicas an over-bound submit becomes an emergency
    scale-up; AT the ceiling it sheds with the typed error and books
    shed_by_policy."""
    def factory():
        eng = DecodeEngine(CFG, params, n_slots=2, buckets=(64,),
                           prefill_chunk=8, label="t2-shed")
        eng.warmup()
        return ContinuousBatcher(eng, default_max_tokens=8)

    pol = AutoscalePolicy(1, 2, high_depth=50.0, low_depth=0.5,
                          up_after=10 ** 6, down_after=10 ** 6,
                          cooldown_s=10 ** 6, interval_s=0.0)
    router = AutoscalingRouter(factory, pol, max_queue_depth=1)
    rng = np.random.RandomState(8)
    base = decode_metrics.snapshot()["shed_by_policy"]
    with router:
        # 56-token budgets keep replicas busy across submits; six
        # back-to-back long requests against bound 1 x 2 replicas must
        # shed at least once once the fleet is at its ceiling (the
        # fleet cannot complete a 56-token decode between every pair
        # of consecutive submits)
        handles, shed = [], 0
        for _ in range(6):
            try:
                handles.append(
                    router.submit(rng.randint(1, CFG.vocab_size, size=4),
                                  max_tokens=56))
            except OverloadedError as e:
                assert e.replicas == 2           # only sheds at ceiling
                shed += 1
        assert router.n_replicas() == 2          # emergency scale-up
        assert shed >= 1
        for h in handles:
            assert h.result(120).shape == (56,)
    assert decode_metrics.snapshot()["shed_by_policy"] == base + shed


def test_int8_model_sharded_decode_parity(params):
    """The mesh-compose requirement: an int8-weight + int8-KV engine on
    a model=2 mesh (int8 leaves laid out per quant_specs — same layout
    as their fp32 originals — KV cache head-sharded, scales replicated)
    greedy-decodes the SAME tokens as the replicated int8 engine."""
    from deeplearning4j_tpu.parallel.mesh import (MODEL_AXIS, MeshSpec,
                                                  make_mesh)

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = make_mesh(MeshSpec(data=1, model=2), devices=jax.devices()[:2])
    eng_r = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                         prefill_chunk=8, quantize="int8",
                         kv_dtype="int8", label="t2-mp-repl")
    eng_s = DecodeEngine(CFG, params, n_slots=2, buckets=(32,),
                         prefill_chunk=8, quantize="int8",
                         kv_dtype="int8", mesh=mesh, label="t2-mp-shard")
    eng_r.warmup()
    eng_s.warmup()
    rng = np.random.RandomState(11)
    prompt = rng.randint(1, CFG.vocab_size, size=13).astype(np.int32)
    assert _engine_tokens(eng_s, prompt, 8) == \
        _engine_tokens(eng_r, prompt, 8)
    # int8 payloads really carry the model layout; the cache is
    # head-sharded int8 with replicated scales
    qp = eng_s.current_params()
    wq = qp["blocks"]["wq"]
    assert isinstance(wq, qz.QTensor) and wq.q.dtype == jnp.int8
    assert MODEL_AXIS in wq.q.sharding.spec
    pool = eng_s._pool
    assert pool.k.dtype == jnp.int8
    assert MODEL_AXIS in pool.k.sharding.spec
    assert pool.k_scale.dtype == jnp.float32


# -- one-shot engine quantization + steady state ----------------------------

def test_inference_engine_int8(params):
    """InferenceEngine(quantize='int8') serves the dequant-fused
    forward — numerically the dequantized tree's forward (rounding-
    level jit-vs-eager fusion differences only, per the engine's
    documented jitting contract) — keyed apart from the fp32 engine
    sharing the same cache_key."""
    apply_fn, key = gpt.make_serving_apply(CFG)
    rng = np.random.RandomState(9)
    x = rng.randint(1, CFG.vocab_size, size=(4, 12)).astype(np.int32)
    fp = InferenceEngine(apply_fn, params, buckets=(4,), cache_key=key,
                         label="t2-fp32fwd")
    q = InferenceEngine(apply_fn, params, buckets=(4,), cache_key=key,
                        label="t2-int8fwd", quantize="int8")
    ref = np.asarray(apply_fn(
        qz.dequantize_tree(qz.quantize_tree(params, "int8")), x))
    got = np.asarray(q.infer(x))
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-5)
    fp_ref = np.asarray(apply_fn(params, x))
    # the fp32 engine is untouched by the quantized key ...
    np.testing.assert_allclose(np.asarray(fp.infer(x)), fp_ref,
                               rtol=1e-5, atol=1e-5)
    # ... and the quantized output is genuinely the QUANTIZED model's
    # (far from fp32 at rounding scale)
    assert np.abs(got - fp_ref).max() > 1e-3
    with pytest.raises(ValueError, match="raw apply_fn"):
        InferenceEngine(fp._forward, params, quantize="int8")


def test_int8_prefix_zero_steady_state_compiles(params):
    """The tier-2 composite: int8 weights + int8 KV + prefix store —
    after warmup, a mixed stream of misses, hits, joins and recycling
    dispatches only cached programs."""
    eng = DecodeEngine(CFG, params, n_slots=3, buckets=(32, 64),
                       prefill_chunk=8, quantize="int8",
                       kv_dtype="int8", prefix_cache=True,
                       label="t2-composite")
    warm = eng.warmup()
    assert warm["compiles"] == 8          # (prefill+step+read+write) x 2
    decode_metrics.mark_compiles()
    rng = np.random.RandomState(10)
    shared = rng.randint(1, CFG.vocab_size, size=16).astype(np.int32)
    with ContinuousBatcher(eng, default_max_tokens=5) as cb:
        # seed the shared prefix, then flush so the mixed stream below
        # deterministically exercises the HIT path (flush is a queue
        # join — no dispatches, no compiles)
        cb.submit(np.concatenate([shared, shared[:3]]),
                  max_tokens=3).result(120)
        eng.flush_harvests()
        handles = []
        for i in range(8):
            tail = rng.randint(1, CFG.vocab_size,
                               size=rng.randint(1, 9)).astype(np.int32)
            prompt = np.concatenate([shared, tail]) if i % 2 \
                else rng.randint(1, CFG.vocab_size,
                                 size=rng.randint(2, 40)).astype(np.int32)
            handles.append(cb.submit(prompt, max_tokens=3 + i % 5))
        for h in handles:
            h.result(120)
    snap = decode_metrics.snapshot()
    assert snap["compile_delta_since_mark"] == 0
    assert snap["prefix_hits"] >= 1
