"""The tree a ``DecodeEngine`` holds for its executables
(``serving.decode.hold_in_compute_dtype``): the leaves a family names
in ``COMPUTE_DTYPE_LEAVES`` cast to the compute type ONCE per params
tree, everything else the given arrays.

The load-bearing properties:

- with float32 masters under a bfloat16 compute type, every engine path
  (bfloat16 and int8 pools, prefill and decode, greedy and sampled,
  verify with a draft) emits EXACTLY the tokens, and leaves exactly the KV state,
  that the family's functions give on the raw tree: the cast is the one
  the steps made themselves, made once and kept;
- only the named leaves change type; every other leaf, and every leaf
  of a tree that is already in the compute type, is the SAME object (no
  copy of an 8.97 GB tree on a 16 GB chip);
- no ``convert`` of a float32 weight is left inside the decode step;
- the cast is paid once per tree: a live-params callable that returns
  the same tree twice casts once, a new tree or ``rebind_params`` casts
  again, and ``decode_metrics.params_held_casts`` counts each.
"""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deeplearning4j_tpu.models import gpt  # noqa: E402
from deeplearning4j_tpu.models.transformer import TransformerConfig  # noqa: E402
from deeplearning4j_tpu.runtime.metrics import decode_metrics  # noqa: E402
from deeplearning4j_tpu.serving.decode import (DecodeEngine,  # noqa: E402
                                               hold_in_compute_dtype)

# no two leaves of one shape: the vocabulary is not the ffn width
CFG = TransformerConfig(vocab_size=80, max_len=64, hidden=32, n_layers=2,
                        n_heads=2, ffn_dim=64, dropout=0.0,
                        compute_dtype="bfloat16", causal=True,
                        type_vocab_size=1)
DCFG = dataclasses.replace(CFG, hidden=16, n_layers=1, ffn_dim=32)
ENGINE = dict(n_slots=2, buckets=(32, 64), prefill_chunk=8)
MATRICES = ("wq", "wk", "wv", "wo", "w1", "w2")


def make_params(cfg, seed):
    """Float32 masters; the block matrices scaled up so that a greedy
    stream does not repeat one token (at 0.02 the layers are too weak to
    move the embedding's best token)."""
    p = gpt.init_params(jax.random.key(seed), cfg)
    for name in MATRICES:
        p["blocks"][name] = p["blocks"][name] * 25.0
    return p


@pytest.fixture(scope="module")
def params():
    return make_params(CFG, 7)


@pytest.fixture(scope="module")
def dparams():
    return make_params(DCFG, 3)


def casts():
    snap = decode_metrics.snapshot()
    return snap["params_held_casts"], snap["params_held_bytes"]


def matrices_bytes(cfg):
    """The six block matrices of every layer, in bfloat16."""
    H, F, L = cfg.hidden, cfg.ffn_dim, cfg.n_layers
    return L * (4 * H * H + 2 * H * F) * 2


def on_raw_tree(eng, params, dparams=None):
    """Make ``eng`` hand its executables the tree as given: the
    family's functions on the raw tree, through the same plumbing."""
    eng.current_params = lambda: params
    if dparams is not None:
        eng._draft_params = dparams
    return eng


def engine_tokens(eng, prompt, n, temperature, seed):
    slot, first = eng.start(np.asarray(prompt, np.int32), max_tokens=n,
                            temperature=temperature, seed=seed)
    out = [first]
    while len(out) < n:
        if eng.draft is not None:
            toks, n_c = eng.advance_spec()
            out.extend(int(t) for t in toks[slot, :int(n_c[slot])])
        else:
            toks = eng.advance()
            if eng.last_ran()[slot]:
                out.append(int(toks[slot]))
    state = [np.asarray(x) for x in jax.tree.leaves(eng._pool)]
    eng.release(slot)
    return out[:n], state


@pytest.mark.parametrize("temperature,seed", [(0.0, 0), (0.9, 5)],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("draft", [False, True], ids=["plain", "draft"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"],
                         ids=["kv_compute_dtype", "kv_int8"])
def test_tokens_and_kv_are_the_raw_trees(params, dparams, kv_dtype, draft,
                                         temperature, seed):
    kw = dict(ENGINE, kv_dtype=kv_dtype,
              draft=(DCFG, dparams) if draft else None)
    held = DecodeEngine(CFG, params, **kw)
    raw = on_raw_tree(DecodeEngine(CFG, params, **kw), params,
                      dparams if draft else None)
    # 19 prompt tokens: two whole prefill chunks and a ragged third
    prompt = (np.arange(1, 20, dtype=np.int32) * 7) % CFG.vocab_size
    got, got_kv = engine_tokens(held, prompt, 24, temperature, seed)
    want, want_kv = engine_tokens(raw, prompt, 24, temperature, seed)
    assert len(set(got)) > 4            # a stream that can tell trees apart
    assert got == want
    assert got_kv[0].dtype == (jnp.int8 if kv_dtype else jnp.bfloat16)
    for a, b in zip(got_kv, want_kv):
        np.testing.assert_array_equal(a, b)
    hp = held.current_params()
    assert hp["blocks"]["wq"].dtype == jnp.bfloat16
    assert raw.current_params()["blocks"]["wq"].dtype == jnp.float32
    if draft:
        assert held._draft_params["blocks"]["w1"].dtype == jnp.bfloat16
        assert held._draft_params["embed"]["tok"] is dparams["embed"]["tok"]


def _weight_converts(jaxpr, shapes):
    """``convert_element_type`` equations from float32 on an operand
    shaped like a weight (a stack or one layer's slice of it), through
    every sub-jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            aval = eqn.invars[0].aval
            if aval.dtype == jnp.float32 and tuple(aval.shape) in shapes:
                found.append(tuple(aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _weight_converts(sub, shapes)
    return found


def test_only_the_named_leaves_change_and_no_convert_is_left(params):
    before = casts()
    eng = DecodeEngine(CFG, params, **ENGINE)
    assert casts() == before            # made on first use, not before
    hp = eng.current_params()
    assert casts() == (before[0] + 1, before[1] + matrices_bytes(CFG))
    assert eng.current_params() is hp
    assert casts()[0] == before[0] + 1
    assert gpt.COMPUTE_DTYPE_LEAVES == tuple(("blocks", n) for n in MATRICES)
    for name, leaf in hp["blocks"].items():
        if name in MATRICES:
            assert leaf.dtype == jnp.bfloat16
            assert leaf.shape == params["blocks"][name].shape
            np.testing.assert_array_equal(
                np.asarray(leaf),
                np.asarray(params["blocks"][name].astype(jnp.bfloat16)))
        else:
            assert leaf is params["blocks"][name]
    for name, leaf in hp["embed"].items():
        assert leaf is params["embed"][name]
    assert hp["embed"]["tok"].dtype == jnp.float32

    # the step itself: with the raw tree it converts each layer's six
    # matrices (what XLA hoists onto the [n_layers, ...] stacks on the
    # chip), with the held tree nothing weight-shaped
    shapes = set()
    for name in MATRICES:
        shape = tuple(params["blocks"][name].shape)
        shapes |= {shape, shape[1:]}

    def step_jaxpr(tree):
        return jax.make_jaxpr(eng._decode.jitted)(
            tree, eng._pool_state(), *eng._idle_step_args(64)).jaxpr

    assert len(_weight_converts(step_jaxpr(params), shapes)) == \
        len(MATRICES) * CFG.n_layers
    assert _weight_converts(step_jaxpr(hp), shapes) == []
    stack = re.compile(r"bf16\[%d,[0-9,]+\]\S* convert\(f32\[%d,"
                       % (CFG.n_layers, CFG.n_layers))
    hlo = eng.decode_hlo(64)
    assert "convert" in hlo             # the text is HLO with its ops named
    assert not stack.search(hlo)


def _gpt_in_compute_type(params):
    cast = jax.tree.map(lambda x: x, params)
    for name in MATRICES:
        cast["blocks"][name] = params["blocks"][name].astype(jnp.bfloat16)
    return CFG, cast, dict(ENGINE)


def _gpt_float32_compute(params):
    cfg = dataclasses.replace(CFG, compute_dtype="float32")
    return cfg, params, dict(ENGINE)


def _deepseek_rehearsal(params):
    """The DeepSeek-V2 cell's rehearsal configuration, weights as the
    benchmark makes them: bfloat16, a family that names no leaf."""
    from benchmark.families import deepseek_v2 as family
    from benchmark.lib import spec

    cell = spec.load_cell("serve_dsv2_ep8_closed16_decode", rehearse=True)
    # as drivers/closed_loop_family.py reads the rehearsal's sizes
    config = {**cell.config, **cell.traffic["config"]}
    assert config["hidden_size"] == 64
    # tests/test_deepseek_v2.py serves the family; its steps take a
    # minute to compile here
    return (family.program_config(config), family.make_params(config, 3),
            dict(n_slots=2, buckets=(32,), serve=False))


@pytest.mark.parametrize("case", [_gpt_in_compute_type, _gpt_float32_compute,
                                  _deepseek_rehearsal],
                         ids=["gpt_cast_beforehand", "gpt_float32_compute",
                              "deepseek_v2_rehearsal"])
def test_a_tree_with_nothing_to_cast_is_held_as_given(params, case):
    cfg, tree, kw = case(params)
    serve = kw.pop("serve", True)
    before = casts()
    eng = DecodeEngine(cfg, tree, **kw)
    held = eng.current_params()
    assert held is tree
    for a, b in zip(jax.tree.leaves(held), jax.tree.leaves(tree)):
        assert a is b
    assert casts() == before
    if not serve:
        return
    eng.warmup()
    prompt = np.arange(1, 12, dtype=np.int32)
    slot, _ = eng.start(prompt, max_tokens=4)
    eng.advance()
    eng.release(slot)
    assert casts() == before


def test_live_params_cast_once_per_tree(params):
    box = [params]
    eng = DecodeEngine(CFG, lambda: box[0], **ENGINE)
    before = casts()[0]
    first = eng.current_params()
    eng.warmup()
    prompt = np.arange(1, 12, dtype=np.int32)
    engine_tokens(eng, prompt, 6, 0.0, 0)
    assert eng.current_params() is first
    assert casts()[0] == before + 1     # warm-up and 6 dispatches: one cast
    box[0] = make_params(CFG, 21)
    second = eng.current_params()
    assert second is not first
    assert eng.current_params() is second
    assert casts()[0] == before + 2
    want = on_raw_tree(DecodeEngine(CFG, box[0], **ENGINE),
                       box[0])
    # another prompt: a swap without rebind_params leaves the first
    # tree's resident prefix pages mounted (the serving contract)
    prompt = np.arange(30, 41, dtype=np.int32)
    assert engine_tokens(eng, prompt, 10, 0.0, 0)[0] == \
        engine_tokens(want, prompt, 10, 0.0, 0)[0]


def test_rebind_drops_the_held_tree_and_casts_the_new_one(params):
    before = casts()[0]
    p_new = make_params(CFG, 11)
    eng = DecodeEngine(CFG, params, **ENGINE)
    eng.warmup()
    prompt = np.arange(1, 12, dtype=np.int32)
    old = engine_tokens(eng, prompt, 10, 0.0, 0)[0]
    assert casts()[0] == before + 1
    eng.rebind_params(p_new)
    assert casts()[0] == before + 1     # lazily, on the next use
    new = engine_tokens(eng, prompt, 10, 0.0, 0)[0]
    assert casts()[0] == before + 2
    # same shapes and dtypes: the cast's executable and the steps' are
    # the ones the first tree compiled
    assert decode_metrics.snapshot()["compile_delta_since_mark"] == 0
    want = on_raw_tree(DecodeEngine(CFG, p_new, **ENGINE),
                       p_new)
    assert new == engine_tokens(want, prompt, 10, 0.0, 0)[0]
    assert new != old


def test_held_leaves_take_the_mesh_layout(params):
    from deeplearning4j_tpu.parallel.mesh import (MODEL_AXIS, MeshSpec,
                                                  make_mesh)

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = make_mesh(MeshSpec(data=1, model=2), devices=jax.devices()[:2])
    kw = dict(ENGINE)
    sharded = DecodeEngine(CFG, params, mesh=mesh, label="held-shard", **kw)
    hp = sharded.current_params()
    for name in MATRICES:
        leaf = hp["blocks"][name]
        assert leaf.dtype == jnp.bfloat16
        assert leaf.sharding == sharded._param_shardings["blocks"][name]
        assert MODEL_AXIS in leaf.sharding.spec
    assert hp["blocks"]["bq"] is params["blocks"]["bq"]
    prompt = np.arange(1, 12, dtype=np.int32)
    one = DecodeEngine(CFG, params, label="held-repl", **kw)
    assert engine_tokens(sharded, prompt, 8, 0.0, 0)[0] == \
        engine_tokens(one, prompt, 8, 0.0, 0)[0]


def test_quantize_takes_the_steps_place(params):
    """``quantize`` is a user's choice that changes numerics; where it
    is set the hold does not run beside it."""
    before = casts()
    eng = DecodeEngine(CFG, params, quantize="bf16", **ENGINE)
    qp = eng.current_params()
    assert qp["embed"]["tok"].dtype == jnp.bfloat16     # quantize's own rule
    assert casts() == before
    assert hold_in_compute_dtype(CFG, qp) is qp
