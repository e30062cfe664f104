"""Ling-3.0-flash (models/ling_hybrid.py) against the plain reference
(benchmark/reference/ling_hybrid.py) at small widths on the CPU, and
through the serving spine: KDA layers whose state is a SLOT STATE the
engine holds beside the latent page pool, an MLA layer over the pool, a
leading dense layer, and expert layers routed by grouped sigmoid scores
under per-layer SwiGLU limits.

Tolerance.  With float32 weights and ``compute_dtype="float32"`` the
program and the reference do the same arithmetic in another order (the
chunked WY form against the token recurrence; folded against expanded
MLA), and the CPU backend's float32 products are exact to rounding:
logits of magnitude ~5 agree to 2e-4 of their largest, as in
``tests/test_exaone_moe.py``.
"""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import ling_hybrid as ref  # noqa: E402
from deeplearning4j_tpu.models import (deepseek_v2 as ds,  # noqa: E402
                                       exaone_moe as ex, gpt,
                                       ling_hybrid as lh, mellum as ml)
from deeplearning4j_tpu.parallel import expert  # noqa: E402
from deeplearning4j_tpu.runtime import telemetry  # noqa: E402
from deeplearning4j_tpu.runtime.metrics import decode_metrics  # noqa: E402
from deeplearning4j_tpu.serving.decode import (ContinuousBatcher,  # noqa: E402
                                               DecodeEngine, model_family)

F32_TOL = 2e-4
C = 8                # page of the engines here; tiny_config's chunk is 4
#: a limit on layer 2 (routed) and on layers 1 and 3 (shared), small
#: enough at these widths to bind
LIMITS = dict(expert_swiglu_limits=(0.0, 0.0, 0.3, 0.5),
              shared_swiglu_limits=(0.0, 0.4, 0.0, 0.2))


def published_keys(cfg):
    """The reference reads a dict with the published key names; the
    limit lists are indexed by published layer."""
    n = max(cfg.layer_ids) + 1
    routed, shared = [0.0] * n, [0.0] * n
    for l, i in enumerate(cfg.layer_ids):
        routed[i], shared[i] = cfg.limits(l)
    return {"hidden_size": cfg.hidden, "layers": list(cfg.layer_ids),
            "layer_group_size": cfg.layer_group_size,
            "num_attention_heads": cfg.n_heads, "head_dim": cfg.head_dim,
            "short_conv_kernel_size": cfg.short_conv_kernel_size,
            "kda_lower_bound": cfg.kda_lower_bound,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.num_experts_per_tok,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "expert_swiglu_limit_list": routed,
            "share_expert_swiglu_limit_list": shared,
            "rms_norm_eps": cfg.rms_norm_eps}


def model(seed=0, **over):
    cfg = lh.tiny_config(compute_dtype="float32", **{**LIMITS, **over})
    return cfg, lh.init_params(jax.random.key(seed), cfg, std=0.3)


def reference_logits(cfg, params, ids):
    """Rows padded to whole blocks of 16 (causal: never attended)."""
    ids = np.atleast_2d(ids)
    n = ids.shape[1]
    padded = np.pad(ids, ((0, 0), (0, -n % 16)))
    return np.asarray(ref.logits(
        params, jnp.asarray(padded), config=published_keys(cfg),
        held=cfg.held_experts, q_block=16))[:, :n]


def some_ids(cfg, shape, seed=1):
    return np.asarray(jax.random.randint(jax.random.key(seed), shape, 0,
                                         cfg.vocab_size), np.int32)


def close(got, want):
    return np.abs(got - want).max() <= F32_TOL * np.abs(want).max()


# ---------------------------------------------------------------------------
# The mathematics, no cache
# ---------------------------------------------------------------------------

def kda_inputs(B, T, NH=2, D=8, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda k: (lambda x: x / jnp.linalg.norm(x, axis=-1,  # noqa: E731
                                                    keepdims=True))(
        jax.random.normal(k, (B, T, NH, D)))
    q, k = unit(ks[0]) * D ** -0.5, unit(ks[1])
    v = jax.random.normal(ks[2], (B, T, NH, D))
    # log-decays over the whole published range [-5, 0]: a chunk's
    # running sum reaches -80 and more, which an unmasked exp overflows
    g = -5.0 * jax.random.uniform(ks[3], (B, T, NH, D))
    beta = jax.random.uniform(ks[4], (B, T, NH))
    return q, k, v, g, beta


def recurrence(q, k, v, g, beta, S):
    out = []
    for t in range(q.shape[1]):
        o, S = lh.kda_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], S)
        out.append(o)
    return jnp.stack(out, axis=1), S


@pytest.mark.parametrize("T,chunk,cuts", [
    (16, 4, ()),                  # four chunks, one dispatch
    (24, 8, (8,)),                # a dispatch edge on a chunk edge
    (32, 16, (16,)),              # chunks of 16: decay sums past -80
    (24, 4, (4, 12)),             # three dispatches of 4, 8 and 12 rows
    (128, 64, (64,)),             # the published chunk: 4 sub-chunks of 16
], ids=["one-dispatch", "two-dispatches", "long-chunks", "three-dispatches",
        "sub-chunks"])
def test_chunked_kda_is_the_token_recurrence(T, chunk, cuts):
    """The WY form over chunks, the state carried from chunk to chunk and
    from dispatch to dispatch, gives the one-row recurrence's outputs and
    final state; a start state that is not zero is carried in too.  A
    chunk past ``SUB_CHUNK`` rows takes its blocks below the diagonal as
    products (running decays past -300 over a chunk of 64)."""
    q, k, v, g, beta = kda_inputs(2, T)
    S0 = 0.3 * jax.random.normal(jax.random.key(9), (2, 2, 8, 8))
    want_o, want_S = recurrence(q, k, v, g, beta, S0)
    S, outs = S0, []
    for lo, hi in zip((0,) + cuts, cuts + (T,)):
        o, S = lh.kda_chunked(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                              g[:, lo:hi], beta[:, lo:hi], S,
                              min(chunk, hi - lo))
        outs.append(o)
    got = jnp.concatenate(outs, axis=1)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(S, want_S, rtol=1e-4, atol=1e-5)
    assert np.abs(np.asarray(want_S)).max() > 0.1


def test_padding_rows_fold_nothing_into_the_state():
    """Rows with ``beta = 0`` and ``g = 0`` (a dispatch's padding) leave
    the state as the valid rows left it."""
    q, k, v, g, beta = kda_inputs(1, 16)
    S0 = jnp.zeros((1, 2, 8, 8))
    _, want = recurrence(q[:, :11], k[:, :11], v[:, :11], g[:, :11],
                         beta[:, :11], S0)
    pad = (jnp.arange(16) >= 11)[None, :, None]
    _, got = lh.kda_chunked(q, jnp.where(pad[..., None], 0.0, k), v,
                            jnp.where(pad[..., None], 0.0, g),
                            jnp.where(pad, 0.0, beta), S0, 4)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("folded,chunk", [(False, 4), (True, 4), (True, 16)],
                         ids=["expanded", "folded", "sub-chunks"])
def test_full_forward_matches_reference(folded, chunk, monkeypatch):
    """The chunked KDA from a zero state in chunks of ``chunk`` rows (the
    last case in four sub-chunks of 4) against the token recurrence."""
    monkeypatch.setattr(lh, "CHUNK", chunk)
    monkeypatch.setattr(lh, "SUB_CHUNK", 4)
    cfg, params = model()
    ids = some_ids(cfg, (2, 48))
    got = np.asarray(lh.forward_logits(cfg, params, jnp.asarray(ids),
                                       folded=folded))
    want = reference_logits(cfg, params, ids)
    assert np.abs(want).max() > 1.0
    assert close(got, want)


def test_the_published_config_and_its_cut():
    cfg = lh.LingHybridConfig()
    kinds = [cfg.kind_of(l) for l in range(42)]
    assert kinds[:6] == [lh.KDA] * 5 + [lh.MLA]
    assert (cfg.layers_of(lh.MLA), cfg.layers_of(lh.KDA)) == (7, 35)
    cut = lh.LingHybridConfig(layer_ids=(0, 36, 37, 38, 39, 40, 41),
                              held_experts=(0, 128), vocab_size=39296,
                              max_len=16384)
    assert [cut.kind_of(l) for l in range(7)] == [lh.KDA] * 6 + [lh.MLA]
    assert [cut.dense(l) for l in range(7)] == [True] + [False] * 6
    shapes = lh.param_shapes(cut)
    assert "mlp" in shapes["layers"][0] and "moe" in shapes["layers"][1]
    assert shapes["layers"][1]["moe"]["router"] == (2560, 512)
    assert shapes["layers"][1]["moe"]["experts"]["w_gate"] == (128, 2560, 768)
    assert shapes["layers"][6]["attn"]["w_q"] == (2560, 32, 192)
    assert shapes["layers"][1]["attn"]["conv"] == (4, 3 * 4096)
    # a slot's state: S in float32 and three convolution rows in bf16, a
    # KDA layer; 13.0 MB over the cut's six
    state = lh.slot_state(cut)
    assert [s.shape for s in state["S"]] == [(32, 128, 128)] * 6
    assert state["conv"][0].shape == (3, 12288)
    assert lh.state_bytes_per_slot(cut) == 6 * (2097152 + 73728)
    # a row of 576 lanes (1,152 B) is cached 640 wide: whole 128-lane tiles
    assert (cut.cache_width, cut.row_width) == (576, 640)
    assert lh.pages_bytes(cut, 10, 128) == 10 * 128 * 1280
    with pytest.raises(ValueError, match="held_experts"):
        lh.LingHybridConfig(held_experts=(480, 64))
    with pytest.raises(ValueError, match="SwiGLU"):
        lh.LingHybridConfig(expert_swiglu_limits=(4.0,))


# ---------------------------------------------------------------------------
# The expert layer: the grouped router, the limit, a rank's share
# ---------------------------------------------------------------------------

def v3_router(s, b, n_group, topk_group, top_k, scale):
    """DeepSeek-V3's ``get_topk_indices`` and weights restated in numpy
    (transformers/models/deepseek_v3/modeling_deepseek_v3.py:115-152)."""
    N, E = s.shape
    choice = s + b[None, :]
    groups = np.sort(choice.reshape(N, n_group, E // n_group), axis=-1)
    group_scores = groups[..., -2:].sum(axis=-1)
    keep = np.argsort(-group_scores, axis=-1)[:, :topk_group]
    mask = np.zeros((N, n_group), bool)
    np.put_along_axis(mask, keep, True, axis=1)
    choice = np.where(np.repeat(mask, E // n_group, axis=1), choice, 0.0)
    taken = np.argsort(-choice, axis=-1)[:, :top_k]
    w = np.zeros((N, E), np.float32)
    picked = np.take_along_axis(s, taken, axis=1)
    np.put_along_axis(w, taken, scale * picked
                      / (picked.sum(-1, keepdims=True) + 1e-20), axis=1)
    return w


def test_the_grouped_sigmoid_router_is_deepseek_v3s():
    key_s, key_b = jax.random.split(jax.random.key(4))
    s = np.asarray(jax.nn.sigmoid(jax.random.normal(key_s, (53, 64))))
    b = np.asarray(0.3 * jax.random.normal(key_b, (64,)))
    w, chosen = expert.route_sigmoid_bias(jnp.asarray(s), jnp.asarray(b),
                                          6, 2.5, n_group=8, topk_group=3)
    w, chosen = np.asarray(w), np.asarray(chosen)
    want = v3_router(s, b, 8, 3, 6, 2.5)
    np.testing.assert_allclose(w, want, rtol=1e-6, atol=1e-7)
    assert (chosen == (want > 0)).all() and (chosen.sum(axis=1) == 6).all()
    # the taken experts lie in at most 3 groups, and the group step moved
    # some choice against the one-group router
    assert (chosen.reshape(53, 8, 8).any(-1).sum(-1) <= 3).all()
    _, flat = expert.route_sigmoid_bias(jnp.asarray(s), jnp.asarray(b),
                                        6, 2.5)
    assert (np.asarray(flat) != chosen).any()
    # the reference's routing, written another way, takes the same
    keys = {"n_group": 8, "topk_group": 3, "num_experts_per_tok": 6,
            "routed_scaling_factor": 2.5}
    np.testing.assert_allclose(ref.route(keys, jnp.asarray(s),
                                         jnp.asarray(b)), w, rtol=1e-6)


def test_the_swiglu_limit_clamps_a_scaled_input():
    ks = jax.random.split(jax.random.key(6), 4)
    x = 8.0 * jax.random.normal(ks[0], (5, 16))
    wg, wu = (jax.random.normal(k, (16, 12)) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (12, 16))
    g, u = np.asarray(x @ wg), np.asarray(x @ wu)
    silu = lambda z: z * 0.5 * (1.0 + np.tanh(z / 2))  # noqa: E731
    want = (silu(np.minimum(g, 4.0)) * np.clip(u, -4.0, 4.0)) @ np.asarray(wd)
    got = np.asarray(expert.gated_ffn(x, wg, wu, wd, limit=4.0))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
    # the clamp bound at this scale, and 0 clamps nothing
    assert (np.abs(u) > 4.0).any() and (g > 4.0).any()
    free = np.asarray(expert.gated_ffn(x, wg, wu, wd))
    np.testing.assert_allclose(free, (silu(g) * u) @ np.asarray(wd),
                               rtol=1e-4, atol=1e-3)
    assert np.abs(free - got).max() > 1.0


def test_four_ranks_parts_and_the_shared_expert_once_make_the_layer():
    """One expert layer cut over 4 ranks of 4 experts each (a router
    group a rank here): the routed parts of all ranks, and what every
    rank computes alike (the shared expert) counted once, are the uncut
    reference layer, the SwiGLU limits binding."""
    cfg, params = model()
    l = 3                                   # routed limit 0.5, shared 0.2
    layer = params["layers"][l]
    x = 1.5 * jax.random.normal(jax.random.key(5), (24, cfg.hidden))
    keys = published_keys(cfg)
    limits = cfg.limits(l)
    whole = np.asarray(ref.moe(keys, layer["moe"], x, (0, 16), limits,
                               "f32"))
    shared = np.asarray(ref.gated(x, layer["moe"]["shared"], limits[1],
                                  "f32"))
    parts = []
    for rank in range(4):
        mine, held = lh.hold_experts(cfg, params, 4 * rank, 4)
        assert mine.held_experts == (4 * rank, 4)
        p = held["layers"][l]["moe"]
        assert p["experts"]["w_gate"].shape[0] == 4
        y, counts = lh.moe_routed(mine, p, x, limits[0])
        parts.append(np.asarray(y))
        assert counts.tolist()[0] == 24 * 3 and counts[1] <= 24 * 3
        alone = np.asarray(ref.moe(keys, p, x, (4 * rank, 4), limits, "f32"))
        assert close(parts[-1] + shared, alone)
    assert np.abs(sum(parts)).max() > 0.1
    assert close(sum(parts) + shared, whole)
    assert not close(sum(parts) + 4 * shared, whole)
    # the limits took part: without them the layer is another
    free = np.asarray(ref.moe(keys, layer["moe"], x, (0, 16), (0.0, 0.0),
                              "f32"))
    assert not close(free, whole)


# ---------------------------------------------------------------------------
# The paged paths, tables made by hand
# ---------------------------------------------------------------------------

def zero_state(cfg, S):
    return jax.tree.map(lambda s: jnp.zeros((S,) + s.shape, s.dtype),
                        lh.slot_state(cfg))


def test_prefill_in_dispatches_then_decode_match_reference_logits():
    """A prompt of 21 in dispatches of 8 rows (the last padded to 8 with 5
    valid), then one row a step, the sequence in slot 1 of 3 with
    scattered pages: every position's logits are the reference's."""
    cfg, params = model()
    row = some_ids(cfg, (40,), seed=3)
    n_prompt, S, TBL = 21, 3, 40 // C
    pages = lh.init_pages(cfg, 1 + S * TBL, C)
    state = zero_state(cfg, S)
    ptab = np.zeros((S, TBL), np.int32)
    ptab[1] = 1 + np.random.default_rng(0).permutation(S * TBL)[:TBL]

    @jax.jit
    def dispatch(pages, S_, conv, tab, toks, posw, ok):
        pages, S_, conv, x, _ = lh._paged_stack(cfg, params, pages, S_, conv,
                                                tab, toks, posw, ok)
        return pages, S_, conv, lh._readout(cfg, params, x)

    got = {}
    at = np.arange(C, dtype=np.int32)
    mine = lambda leaves: [a[1:2] for a in leaves]  # noqa: E731
    S1, conv1 = mine(state["S"]), mine(state["conv"])
    for lo in range(0, n_prompt, C):
        n_valid = min(C, n_prompt - lo)
        chunk = np.zeros((C,), np.int32)
        chunk[:n_valid] = row[lo:lo + n_valid]
        pages, S1, conv1, lg = dispatch(pages, S1, conv1, ptab[1:2],
                                        chunk[None], (lo + at)[None],
                                        (at < n_valid)[None])
        for i in range(n_valid):
            got[lo + i] = np.asarray(lg[0, i])
    S_all = [a.at[1:2].set(b) for a, b in zip(state["S"], S1)]
    conv_all = [a.at[1:2].set(b) for a, b in zip(state["conv"], conv1)]
    active = np.array([False, True, False])
    for p in range(n_prompt, len(row)):
        toks = np.zeros((S, 1), np.int32)
        toks[1] = row[p]
        pos = np.full((S, 1), p, np.int32)
        pages, S_all, conv_all, lg = dispatch(pages, S_all, conv_all, ptab,
                                              toks, pos, active[:, None])
        got[p] = np.asarray(lg[1, 0])
    want = reference_logits(cfg, params, row)[0]
    assert close(np.stack([got[i] for i in range(len(row))]), want)


def test_a_decode_dispatch_leaves_an_idle_slots_state_alone():
    cfg, params = model()
    S, TBL = 3, 4
    pages = lh.init_pages(cfg, 1 + S * TBL, C)
    state = jax.tree.map(lambda a: 0.5 * jax.random.normal(
        jax.random.key(a.size), a.shape).astype(a.dtype), zero_state(cfg, S))
    ptab = (1 + np.arange(S * TBL, dtype=np.int32)).reshape(S, TBL)
    active = np.array([True, False, True])
    (pages2, after), out = jax.jit(
        lambda pool: lh.paged_decode(
            cfg, params, pool, ptab, np.array([3, 4, 5], np.int32),
            np.array([6, 2, 9], np.int32), active,
            np.zeros((S,), np.float32), np.zeros((S,), np.uint32))
    )((pages, state))
    for name in ("S", "conv"):
        for old, new in zip(state[name], after[name]):
            old, new = np.asarray(old), np.asarray(new)
            np.testing.assert_array_equal(new[1], old[1])
            assert not np.array_equal(new[0], old[0])
            assert not np.array_equal(new[2], old[2])
    # the idle slot wrote no latent row either (into the trash page only)
    np.testing.assert_array_equal(np.asarray(pages2.rows)[:, 5:9], 0.0)
    assert out.shape == (S + len(lh.DECODE_COUNTERS),)
    assert int(out[1]) == 4                 # an idle slot's token is kept


# ---------------------------------------------------------------------------
# Through DecodeEngine
# ---------------------------------------------------------------------------

def engine(cfg, params, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("buckets", (32, 64, 128))
    return DecodeEngine(cfg, params, prefill_chunk=C, **kw)


def greedy_gaps(cfg, params, prompt, tokens):
    """By how much each served token's reference logit lies under the
    reference's best, the row teacher-forced with the served tokens."""
    row = np.concatenate([prompt, np.asarray(tokens)[:-1]])
    logits = reference_logits(cfg, params, row)[0][len(prompt) - 1:]
    return logits.max(axis=-1) - logits[np.arange(len(tokens)), tokens]


def test_engine_takes_the_family_and_holds_its_slot_state():
    cfg, params = model()
    assert model_family(cfg) is lh
    eng = engine(cfg, params)
    # one unbounded kind of page (the MLA layer's latent rows), a prefill
    # dispatch of every page a rung allows, and beside the pool a slab of
    # three slots' KDA states
    assert [(k.name, k.bounded) for k in eng._kinds] == [("kv", False)]
    assert eng.prefill_rows(128) == 128 and not eng._mounts_prefixes
    per_slot = lh.state_bytes_per_slot(cfg)
    assert per_slot == 3 * (4 * 8 * 8 * 4 + 3 * 96 * 4)
    assert eng.state_bytes == 3 * per_slot
    assert eng.pool_bytes == lh.pages_bytes(cfg, eng.n_kv_pages, C) \
        + eng.state_bytes
    assert decode_metrics.snapshot()["state_bytes_held"] == eng.state_bytes
    pages, state = eng._pool_state()
    assert pages.rows.shape == (1, 3 * 16 + 1, C, cfg.row_width)
    assert state["S"][0].shape == (3, 4, 8, 8) and len(state["conv"]) == 3
    for option in ({"kv_dtype": "int8"}, {"quantize": "int8"},
                   {"prefix_cache": True}, {"draft": "self"},
                   {"draft": (cfg, params)}):
        with pytest.raises(ValueError, match="not supported"):
            engine(cfg, params, **option)


def test_prefill_over_dispatches_then_decode_through_the_engine():
    """Prompts over one, two and three dispatches of 8 rows (the last
    padded), then greedy decode: every token is the reference's best."""
    cfg, params = model()
    eng = engine(cfg, params)
    for n, seed in ((5, 11), (16, 12), (23, 13)):
        prompt = some_ids(cfg, (n,), seed=seed)
        slot, first = eng.start(prompt, max_tokens=40)
        toks = [first] + [int(eng.advance()[slot]) for _ in range(14)]
        assert greedy_gaps(cfg, params, prompt, toks).max() <= 1e-3
        eng.release(slot)
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0


def alone(cfg, params, prompt, n):
    eng = engine(cfg, params, n_slots=1, label="ling-alone")
    slot, first = eng.start(prompt, max_tokens=n)
    return [first] + [int(eng.advance()[slot]) for _ in range(n - 1)]


def test_a_join_under_a_step_in_flight_starts_from_zero_state():
    """The order the one-step-ahead worker relies on, carried by data: a
    step in flight still writes slot 0's state when its request is
    released; the successor's join into slot 0 takes the pool that step
    returns and zeroes the slot; the next step takes the join's.  The
    successor's stream is its stream alone, the neighbour's is its own."""
    cfg, params = model()
    a, b, c = (some_ids(cfg, (n,), seed=s) for n, s in ((13, 21), (9, 22),
                                                        (17, 23)))
    eng = engine(cfg, params, n_slots=2)
    sa, _ = eng.start(a, max_tokens=40)
    sb, first_b = eng.start(b, max_tokens=40)
    got_b = [first_b]
    for _ in range(5):
        got_b.append(int(eng.advance()[sb]))
    flying = eng.dispatch_step()            # runs slot 0 for ``a``
    eng.release(sa)                         # a step of ``a`` in flight
    sc, first_c = eng.start(c, max_tokens=40)
    assert sc == sa
    step = eng.dispatch_step(after=flying)
    got_b.append(int(eng.collect(flying)[sb]))
    toks = eng.collect(step)
    got_c = [first_c, int(toks[sc])]
    got_b.append(int(toks[sb]))
    for _ in range(8):
        toks = eng.advance()
        got_c.append(int(toks[sc]))
        got_b.append(int(toks[sb]))
    assert got_c == alone(cfg, params, c, len(got_c))
    assert got_b == alone(cfg, params, b, len(got_b))
    for s in (sb, sc):
        eng.release(s)
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0


def test_the_batcher_reuses_slots_one_step_ahead_and_counts_the_resets():
    """Six requests through two slots, the worker one step ahead: every
    slot is released and admitted again, each request's stream is its
    own alone, ``linear_state_resets`` equals ``admissions``, and each
    join's ``decode.prefill`` span says it reset the state."""
    cfg, params = model()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 19, 8, 30, 3, 12)]
    budgets = [12, 7, 16, 9, 20, 6]
    eng = engine(cfg, params, n_slots=2)
    eng.warmup()
    before = decode_metrics.snapshot()
    tr = telemetry.enable("ling-slots")
    try:
        with ContinuousBatcher(eng) as batcher:
            handles = [batcher.submit(p, max_tokens=m, seed=i, eos_id=None)
                       for i, (p, m) in enumerate(zip(prompts, budgets))]
            outs = [h.result(timeout=300.0) for h in handles]
    finally:
        telemetry.disable()
    after = decode_metrics.snapshot()
    for p, m, out in zip(prompts, budgets, outs):
        assert list(out) == alone(cfg, params, p, m)
    d = {k: after[k] - before[k] for k in (
        "admissions", "linear_state_resets", "decode_dispatches",
        "decode_dispatches_ahead", "moe_layer_dispatches")}
    assert d["admissions"] == d["linear_state_resets"] == len(prompts)
    assert d["decode_dispatches_ahead"] > 0
    assert d["moe_layer_dispatches"] == 3 * d["decode_dispatches"]
    assert after["compile_delta_since_mark"] == 0
    spans = [r for r in tr.records() if r["type"] == "span"
             and r["name"] == "decode.prefill"]
    assert len(spans) == len(prompts)
    assert all(r["attrs"]["state_reset"] is True for r in spans)
    assert eng._alloc.in_use() == 0 and eng.pages_unaccounted() == 0


def test_the_audit_counts_a_slot_state_no_slot_holds():
    cfg, params = model()
    eng = engine(cfg, params, n_slots=2)
    slot, _ = eng.start(some_ids(cfg, (6,)), max_tokens=4)
    assert eng.pages_unaccounted() == 0
    eng._state_held[1 - slot] = True          # a state with no owner
    assert eng.pages_unaccounted() == 1
    eng._state_held[1 - slot] = False
    eng.release(slot)
    assert eng.pages_unaccounted() == 0 and not eng._state_held.any()


def test_the_programs_carry_the_scopes():
    cfg, params = model()
    eng = engine(cfg, params, n_slots=2, buckets=(32,))
    text = eng.decode_hlo(32)
    for scope in ("kda_attention", "short_conv", "state_update",
                  "mla_attention", "moe_route", "moe_experts",
                  "shared_expert"):
        assert f"/{scope}/" in text, scope
    assert "decode_fn" in text
    prefill = eng._prefill.jitted.lower(
        eng.current_params(), eng._pool_state(), np.zeros((4,), np.int32),
        np.zeros((32,), np.int32), np.int32(0), np.int32(1), np.float32(0),
        np.uint32(0), np.int32(0)).compile().as_text()
    assert "prefill_fn" in prefill
    for scope in ("kda_attention", "state_update", "mla_attention"):
        assert f"/{scope}/" in prefill, scope


# ---------------------------------------------------------------------------
# The code shared with the other families lowers as before
# ---------------------------------------------------------------------------

def _engines(name):
    kw = dict(n_slots=3, buckets=(16, 32), prefill_chunk=8,
              label=f"dg-{name}")
    if name == "gpt":
        cfg = gpt.gpt_tiny()
        return DecodeEngine(cfg, gpt.init_params(jax.random.key(0), cfg), **kw)
    mod, tiny = {"deepseek_v2": (ds, ds.tiny_config),
                 "mellum": (ml, ml.tiny_config),
                 "exaone_moe": (ex, ex.tiny_config),
                 "exaone_moe-self": (ex, ex.tiny_config)}[name]
    cfg = tiny()
    if name.endswith("-self"):
        kw["draft"] = "self"
    return DecodeEngine(cfg, mod.init_params(jax.random.key(0), cfg), **kw)


def programs(eng):
    """sha256 of each rung's decode (or speculative round) and prefill
    program as the engine lowers them."""
    out = {}
    for t in eng.buckets:
        out[f"decode.{t}"] = hashlib.sha256(
            eng._lower_decode(t).as_text().encode()).hexdigest()
        ptab = jax.tree.map(lambda a: a[0], eng._idle_step_args(t)[0])
        rows = np.zeros((eng.prefill_rows(t),), np.int32)
        out[f"prefill.{t}"] = hashlib.sha256(eng._prefill.jitted.lower(
            eng.current_params(), eng._pool_state(), ptab, rows,
            *((rows,) if eng._self_draft else ()), np.int32(0), np.int32(1),
            np.float32(0), np.uint32(0)).as_text().encode()).hexdigest()
    return out


@pytest.mark.parametrize("name", ["gpt", "deepseek_v2", "mellum",
                                  "exaone_moe", "exaone_moe-self"])
def test_every_other_family_lowers_the_parents_programs(name):
    """``tests/data/family_programs_pr38.json``: the parent commit's
    programs, lowered on this CPU.  The group step and the SwiGLU limit
    of ``parallel/expert.py`` and the engine's slot state add nothing to
    a family that asks for neither."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "family_programs_pr38.json")) as f:
        parent = json.load(f)
    if jax.__version__ != parent["jax"]:
        pytest.skip(f"the parent's programs were lowered by jax "
                    f"{parent['jax']}")
    assert programs(_engines(name)) == parent["programs"][name]


def test_one_group_and_no_limit_lower_todays_expert_programs():
    """``route_sigmoid_bias`` with one group and ``gated_ffn`` with no
    limit are, op for op, the functions as they stood before the group
    step and the limit."""
    def route_before(scores, bias, top_k, scale):
        N, E = scores.shape
        _, top = jax.lax.top_k(scores + bias[None, :], top_k)
        chosen = jnp.zeros((N, E), jnp.bool_).at[
            jnp.arange(N)[:, None], top].set(True)
        taken = jnp.where(chosen, scores, 0.0)
        return taken * (scale / (taken.sum(axis=-1, keepdims=True) + 1e-20)
                        ), chosen

    def ffn_before(x, w_gate, w_up, w_down):
        g = jnp.einsum("nh,hf->nf", x, w_gate,
                       preferred_element_type=jnp.float32)
        u = jnp.einsum("nh,hf->nf", x, w_up,
                       preferred_element_type=jnp.float32)
        return jnp.einsum("nf,fh->nh", (jax.nn.silu(g) * u).astype(x.dtype),
                          w_down, preferred_element_type=jnp.float32)

    s = jax.ShapeDtypeStruct((16, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128,), jnp.float32)
    def text(f, *a):        # the program, its module's name left out
        return jax.jit(f).lower(*a).as_text().split("\n", 1)[1]

    assert text(lambda s, b: expert.route_sigmoid_bias(s, b, 8, 2.5), s, b) \
        == text(lambda s, b: route_before(s, b, 8, 2.5), s, b)
    x = jax.ShapeDtypeStruct((16, 64), jnp.bfloat16)
    w1 = jax.ShapeDtypeStruct((64, 96), jnp.bfloat16)
    w2 = jax.ShapeDtypeStruct((96, 64), jnp.bfloat16)
    assert text(expert.gated_ffn, x, w1, w1, w2) \
        == text(ffn_before, x, w1, w1, w2)
    assert text(lambda *a: expert.gated_ffn(*a, limit=4.0), x, w1, w1, w2) \
        != text(ffn_before, x, w1, w1, w2)
