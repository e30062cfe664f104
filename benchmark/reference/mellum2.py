"""Plain reference for Mellum 2 (JetBrains ``Mellum2-12B-A2.5B-Instruct``,
``model_type`` ``mellum``), written from the published ``config.json``'s
keys and the equations below, not from the program.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no pages, nothing imported
from the program.  It reads the tree the benchmark's own
``families/mellum.py`` makes (every leaf bfloat16, upcast here a layer
at a time), ``L`` the layers kept, ``E`` the experts a layer:

    embed [V, H]   final_norm [H]   head [H, V]
    layers (every leaf stacked over the layers):
      attn_norm, ffn_norm [L, H]   q_norm, k_norm [L, D]
      w_q [L, H, NH D]  w_k, w_v [L, H, KV D]  w_o [L, NH D, H]
      router [L, H, E]
    experts (layer l's at l E .. l E + E - 1):
      w_gate, w_up [L E, H, F]   w_down [L E, F, H]

Equations, ``x`` the residual stream, every norm RMSNorm (eps from the
config), pre-norm, no bias; layer ``l`` is of kind ``layer_types[l]``:

    n = RMSNorm(x; attn_norm)
    q = n W_q -> [NH, D];  k = n W_k, v = n W_v -> [KV, D]
    q = RoPE(RMSNorm_D(q; q_norm));  k = RoPE(RMSNorm_D(k; k_norm))
    query head h reads K/V head h // (NH / KV)
    score = q . k / sqrt(D), softmax over keys j <= i; on a
            sliding_attention layer also j > i - sliding_window
    x = x + concat_heads(softmax(score) v) W_o
    n = RMSNorm(x; ffn_norm);  p = softmax(n W_r) over the E experts
    the num_experts_per_tok largest p are taken, w = p / sum of the taken
    x = x + sum_taken w_e W_down,e (silu(n W_gate,e) * (n W_up,e))
    logits = RMSNorm(x; final_norm) W_head

RoPE rotates all D lanes as two halves (rotate-half), ``inv_freq_i =
theta^(-2i/D)``.  On ``sliding_attention`` layers that is all
(``rope_parameters.sliding_attention``: ``default``).  On
``full_attention`` layers it is YaRN as published
(``rope_parameters.full_attention``): every frequency kept or divided by
``factor``, by a linear ramp between the dimensions that turn
``beta_fast`` and ``beta_slow`` times over
``original_max_position_embeddings``; cos and sin multiplied by
``attention_factor``.

Taken on trust, each also in the configuration's ``assumed``: the
per-head RMSNorm of q and k (``config.json`` has no key for it; the
Qwen3-MoE lineage whose key set this config carries has it), the
rotate-half lane order, no multi-token-prediction module.

Two things are done for size, and change no arithmetic: an expert runs
over the rows that took it (the assignments sorted by expert and cut
into blocks of ``expert_block`` rows, each block one expert's; 64
experts over every row would be eight times the work), and the scores
are taken ``q_block`` query rows at a time.  ``at`` asks for the logits
of some positions only.

``precision="fp8"`` is the CONTROL, as in ``gpt2_postln.py``: both
operands of every matrix product rounded to float8_e4m3fn under one
scale per tensor (amax / 448), accumulated in float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PRECISIONS = ("f32", "fp8")
FP8_MAX = 448.0


def _q(x, precision):
    if precision == "f32":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, precision):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                         + eps) * g


def rope_tables(c, kind, n):
    """cos, sin [n, head_dim] for positions 0..n-1 on a layer of ``kind``."""
    r = c["rope_parameters"][kind]
    d, base = c["head_dim"], float(r["rope_theta"])
    inv_freq = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    factor = 1.0
    if r.get("rope_type", "default") == "yarn":
        orig = r["original_max_position_embeddings"]

        def dim_of(rotations):
            return d * math.log(orig / (rotations * 2 * math.pi)) / (
                2 * math.log(base))

        low = max(math.floor(dim_of(r["beta_fast"])), 0)
        high = min(math.ceil(dim_of(r["beta_slow"])), d - 1)
        interpolated = np.clip((np.arange(d // 2) - low)
                               / max(high - low, 1e-3), 0, 1)
        inv_freq = (inv_freq / float(r["factor"]) * interpolated
                    + inv_freq * (1.0 - interpolated))
        factor = float(r["attention_factor"])
    ang = np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang) * factor, jnp.float32),
            jnp.asarray(np.sin(ang) * factor, jnp.float32))


def rope(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]],
                                     axis=-1) * sin


def attention(c, a, h, kind, precision, q_block=256):
    """``h`` [T, H] normed rows of one sequence -> [T, H]; ``a`` the
    layer's leaves.  ``q_block`` query rows at a time ([NH, q_block, T]
    float32 fits where [NH, T, T] does not)."""
    T = h.shape[0]
    NH, KV, D = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    cos, sin = rope_tables(c, kind, T)
    q = _mm("th,hf->tf", h, a["w_q"], precision).reshape(T, NH, D)
    k = _mm("th,hf->tf", h, a["w_k"], precision).reshape(T, KV, D)
    v = _mm("th,hf->tf", h, a["w_v"], precision).reshape(T, KV, D)
    q = rope(rms_norm(q, a["q_norm"], eps), cos[:, None], sin[:, None])
    k = rope(rms_norm(k, a["k_norm"], eps), cos[:, None], sin[:, None])
    # every query head beside the K/V head it reads
    k, v = (jnp.repeat(t, NH // KV, axis=1) for t in (k, v))
    q_block = min(q_block, T)
    if T % q_block:
        raise ValueError(f"{T} rows are not whole blocks of {q_block}")

    def rows(lo):
        i = (lo + jnp.arange(q_block))[:, None]
        j = jnp.arange(T)[None, :]
        seen = j <= i
        if kind == "sliding_attention":
            seen = seen & (j > i - c["sliding_window"])
        s = _mm("qnd,knd->nqk", lax.dynamic_slice_in_dim(q, lo, q_block), k,
                precision) / math.sqrt(D)
        s = jnp.where(seen[None], s, -1e9)
        return _mm("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v, precision)

    o = lax.map(rows, jnp.arange(0, T, q_block))
    return _mm("tf,fh->th", o.reshape(T, NH * D), a["w_o"], precision)


def gated(x, w_gate, w_up, w_down, precision):
    g = _mm("th,hf->tf", x, w_gate, precision)
    u = _mm("th,hf->tf", x, w_up, precision)
    return _mm("tf,fh->th", jax.nn.silu(g) * u, w_down, precision)


def route(c, p):
    """[T, E] probabilities -> (weights [T, K], experts [T, K]): the K
    largest a row, each over their sum."""
    taken, experts = lax.top_k(p, c["num_experts_per_tok"])
    return taken / taken.sum(axis=-1, keepdims=True), experts


def moe(c, router, e, h, precision, expert_block=256):
    """``h`` [T, H] normed rows -> [T, H]; ``e`` the layer's experts
    ([E, H, F] / [E, F, H]).  The T x K assignments are sorted by expert
    and laid into blocks of ``expert_block`` rows, each expert's padded
    to whole blocks with a row of zeros at weight 0; a block is one
    expert over its rows."""
    T, H = h.shape
    E, K = router.shape[1], c["num_experts_per_tok"]
    w, experts = route(c, jax.nn.softmax(
        _mm("th,he->te", h, router, "f32"), axis=-1))
    order = jnp.argsort(experts.reshape(-1), stable=True)
    experts = experts.reshape(-1)[order]
    rows = jnp.repeat(jnp.arange(T), K)[order]
    w = w.reshape(-1)[order]
    count = jnp.bincount(experts, length=E)
    padded = -(-count // expert_block) * expert_block
    slot = (jnp.cumsum(padded) - padded)[experts] + jnp.arange(T * K) - (
        jnp.cumsum(count) - count)[experts]
    n_blocks = -(-(T * K) // expert_block) + E
    row_of = jnp.full((n_blocks, expert_block), T).at[
        slot // expert_block, slot % expert_block].set(rows)
    w_of = jnp.zeros((n_blocks, expert_block), jnp.float32).at[
        slot // expert_block, slot % expert_block].set(w)
    expert_of = jnp.minimum(jnp.searchsorted(
        jnp.cumsum(padded), jnp.arange(n_blocks) * expert_block,
        side="right"), E - 1)
    h0 = jnp.concatenate([h, jnp.zeros((1, H), h.dtype)])

    def block(b):
        mine = (lax.dynamic_index_in_dim(e[k], expert_of[b], keepdims=False)
                for k in ("w_gate", "w_up", "w_down"))
        return gated(h0[row_of[b]], *mine, precision) * w_of[b][:, None]

    y = lax.map(block, jnp.arange(n_blocks))
    return jnp.zeros((T + 1, H), jnp.float32).at[row_of.reshape(-1)].add(
        y.reshape(-1, H))[:T]


@functools.partial(jax.jit, static_argnames=("c", "kind", "precision",
                                             "q_block", "expert_block"))
def _layer(x, layer, e, *, c, kind, precision, q_block, expert_block):
    c = _unstatic(c)
    layer, e = jax.tree.map(lambda w: w.astype(jnp.float32), (layer, e))
    eps = c["rms_norm_eps"]

    def one(x):
        x = x + attention(c, layer, rms_norm(x, layer["attn_norm"], eps),
                          kind, precision, q_block)
        return x + moe(c, layer["router"], e,
                       rms_norm(x, layer["ffn_norm"], eps), precision,
                       expert_block)

    return lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, norm, head, *, eps, precision):
    h = rms_norm(x, norm.astype(jnp.float32), eps)
    return _mm("bth,hv->btv", h, head.astype(jnp.float32), precision)


def _static(c):
    """The config's numbers as a hashable, for the jit's static key."""
    keep = {k: v for k, v in c.items() if isinstance(v, (int, float))}
    keep["rope_parameters"] = tuple(sorted(
        (kind, tuple(sorted(r.items())))
        for kind, r in c["rope_parameters"].items()))
    return tuple(sorted(keep.items()))


def _unstatic(c):
    c = dict(c)
    c["rope_parameters"] = {kind: dict(r) for kind, r in c["rope_parameters"]}
    return c


def logits(params, ids, *, config, precision="f32", at=None, q_block=256,
           expert_block=256):
    """``ids`` [B, T] int32 -> logits float32: [B, T, V], or [B, n, V] at
    the positions ``at`` [B, n] of each row.  The layers run one after
    the other over all B rows (a sequence at a time inside), each
    layer's bfloat16 leaves upcast for its own call only, so that beside
    the tree there is one float32 layer and one sequence's activations."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    c = _static(config)
    E = config["num_experts"]
    x = params["embed"][ids].astype(jnp.float32)
    for l in range(config["layers"]):
        x = _layer(x, jax.tree.map(lambda a: a[l], params["layers"]),
                   jax.tree.map(lambda a: a[l * E:(l + 1) * E],
                                params["experts"]),
                   c=c, kind=config["layer_types"][l], precision=precision,
                   q_block=q_block, expert_block=expert_block)
    if at is not None:
        x = jnp.take_along_axis(x, jnp.asarray(at)[:, :, None], axis=1)
    return _readout(x, params["final_norm"], params["head"],
                    eps=float(config["rms_norm_eps"]), precision=precision)
