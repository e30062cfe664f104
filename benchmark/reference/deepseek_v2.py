"""Plain reference for DeepSeek-V2 (arXiv 2405.04434; the published
``modeling_deepseek.py``) as ONE expert-parallel rank serves it.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no folded products, nothing
imported from the program.  It reads the tree the benchmark's own
``families/deepseek_v2.py`` makes (every leaf bfloat16, upcast here a
layer at a time):

    embed [V, H]   final_norm [H]   head [H, V]
    layers[i]: attn_norm, ffn_norm [H]
      attn: w_dq [H, q_lora]  q_norm [q_lora]  w_uq [q_lora, NH, nope + rope]
            w_dkv [H, kv_lora + rope]  kv_norm [kv_lora]
            w_ukv [kv_lora, NH, nope + v]  w_o [NH, v, H]
      mlp (the leading dense layers): w_gate, w_up [H, I]  w_down [I, H]
      moe (the others): router [H, E_all]
            experts: w_gate, w_up [E_held, H, F]  w_down [E_held, F, H]
            shared:  w_gate, w_up [H, n_shared F]  w_down [n_shared F, H]

Equations, ``x`` the residual stream, every norm RMSNorm (eps from the
config), pre-norm, no bias:

    cQ = RMSNorm(h W_DQ);  [q_nope | q_rope] = cQ W_UQ  (per head)
    [cKV | k_r] = h W_DKV;  cKV = RMSNorm(cKV)
    q_rope = RoPE(q_rope);  k_rope = RoPE(k_r)  (one for all heads)
    [k_nope | v] = cKV W_UKV  (per head)
    score = (q_nope . k_nope + q_rope . k_rope) (nope + rope)^-0.5 m^2,
            m = 0.1 mscale_all_dim ln(factor) + 1
    x = x + concat_heads(softmax_causal(score) v) W_O
    layer 0:   x = x + (silu(h W_gate) * (h W_up)) W_down
    others:    s = softmax(h W_r) over all E_all experts, E_all / n_group a group;
               a group's score its largest s; the topk_group best groups stay;
               of their experts the num_experts_per_tok best are taken;
               x = x + sum_{i taken AND held here} routed_scaling_factor s_i E_i(h)
                     + Shared(h)
    logits = RMSNorm(x) W_head

RoPE is YaRN as published: every frequency ``theta^(-2i/d)`` either kept
or divided by ``factor``, by a linear ramp between the dimensions that
turn ``beta_fast`` and ``beta_slow`` times over the original context;
cos and sin scaled by ``mscale / mscale_all_dim`` (1).

Departures, each also in the configuration's ``assumed``/``reduced``:

* the rotary lanes are rotated as halves (rotate-half) with no
  de-interleaving first: the published code permutes ``q_rope``/``k_r``
  from interleaved pairs before the same rotation, which is a fixed
  permutation of W_UQ's and W_DKV's columns and nothing to random
  weights;
* this rank's share: the sum over taken experts leaves out those held on
  other ranks (``held`` = first id and count), and the head is the
  vocabulary's slice.  Nothing stands in for what is left out.

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


def yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1.0 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_tables(c, n):
    """cos, sin [n, qk_rope_head_dim] for positions 0..n-1."""
    r = c["rope_scaling"]
    d, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    orig, factor = r["original_max_position_embeddings"], float(r["factor"])
    kept = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    interpolated = kept / factor

    def dim_of(rotations):
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(r["beta_fast"])), 0)
    high = min(math.ceil(dim_of(r["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv_freq = interpolated * ramp + kept * (1.0 - ramp)
    ang = np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    m = yarn_mscale(factor, r["mscale"]) / yarn_mscale(factor,
                                                       r["mscale_all_dim"])
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32))


def rope(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]],
                                     axis=-1) * sin


def attention(c, a, h, precision, q_block=256):
    """``h`` [T, H] normed rows of one sequence -> [T, H].  The scores
    are taken ``q_block`` query rows at a time ([NH, q_block, T] float32
    fits where [NH, T, T] does not); the arithmetic is the whole
    matrix's."""
    T = h.shape[0]
    dn, R = c["qk_nope_head_dim"], c["kv_lora_rank"]
    r = c["rope_scaling"]
    m = yarn_mscale(float(r["factor"]), r["mscale_all_dim"])
    scale = (dn + c["qk_rope_head_dim"]) ** -0.5 * m * m
    cos, sin = yarn_tables(c, T)
    cq = rms_norm(_mm("th,hr->tr", h, a["w_dq"], precision), a["q_norm"],
                  c["rms_norm_eps"])
    q = _mm("tr,rnd->tnd", cq, a["w_uq"], precision)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], cos[:, None], sin[:, None])
    ckv = _mm("th,hc->tc", h, a["w_dkv"], precision)
    k_rope = rope(ckv[:, R:], cos, sin)
    kv = _mm("tr,rnd->tnd", rms_norm(ckv[:, :R], a["kv_norm"],
                                     c["rms_norm_eps"]), a["w_ukv"],
             precision)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_block = min(q_block, T)
    if T % q_block:
        raise ValueError(f"{T} rows are not whole blocks of {q_block}")

    def rows(lo):
        qn = lax.dynamic_slice_in_dim(q_nope, lo, q_block, axis=0)
        qr = lax.dynamic_slice_in_dim(q_rope, lo, q_block, axis=0)
        s = (_mm("qnd,knd->nqk", qn, k_nope, precision)
             + _mm("qnd,kd->nqk", qr, k_rope, precision)) * scale
        causal = (lo + jnp.arange(q_block))[:, None] >= jnp.arange(T)[None]
        s = jnp.where(causal[None], s, -1e9)
        return _mm("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v, precision)

    o = lax.map(rows, jnp.arange(0, T, q_block))
    return _mm("tnd,ndh->th", o.reshape(T, *o.shape[2:]), a["w_o"],
               precision)


def gated(h, p, precision):
    g = _mm("th,hf->tf", h, p["w_gate"], precision)
    u = _mm("th,hf->tf", h, p["w_up"], precision)
    return _mm("tf,fh->th", jax.nn.silu(g) * u, p["w_down"], precision)


def route(c, scores):
    """[T, E_all] probabilities -> [T, E_all] weights, zero off the
    experts taken."""
    T, E = scores.shape
    G = c["n_group"]
    g = scores.reshape(T, G, E // G)
    best_groups = jnp.argsort(-g.max(axis=-1), axis=-1)[:, :c["topk_group"]]
    keep = jnp.any(best_groups[:, :, None] == jnp.arange(G)[None, None, :],
                   axis=1)                                    # [T, G]
    kept = jnp.where(keep[:, :, None], g, 0.0).reshape(T, E)
    taken = jnp.argsort(-kept, axis=-1)[:, :c["num_experts_per_tok"]]
    mask = jnp.any(taken[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    return jnp.where(mask, scores * c["routed_scaling_factor"], 0.0)


def moe(c, p, h, held, precision):
    """The expert layer's share: every held expert over every row,
    weighted by what the router gave it (0 where it was not taken)."""
    first, count = held
    scores = jax.nn.softmax(_mm("th,he->te", h, p["router"], "f32"), axis=-1)
    w = route(c, scores)[:, first:first + count]              # [T, count]
    e = p["experts"]
    g = _mm("th,ehf->etf", h, e["w_gate"], precision)
    u = _mm("th,ehf->etf", h, e["w_up"], precision)
    y = _mm("etf,efh->eth", jax.nn.silu(g) * u, e["w_down"], precision)
    return jnp.einsum("te,eth->th", w, y,
                      precision=lax.Precision.HIGHEST) + gated(
                          h, p["shared"], precision)


@functools.partial(jax.jit, static_argnames=("c", "held", "precision"))
def _layer(x, layer, *, c, held, precision):
    c = dict(c)
    c["rope_scaling"] = dict(c["rope_scaling"])
    layer = jax.tree.map(lambda w: w.astype(jnp.float32), layer)
    eps = c["rms_norm_eps"]

    def one(x):
        x = x + attention(c, layer["attn"],
                          rms_norm(x, layer["attn_norm"], eps), precision)
        h = rms_norm(x, layer["ffn_norm"], eps)
        if "mlp" in layer:
            return x + gated(h, layer["mlp"], precision)
        return x + moe(c, layer["moe"], h, held, precision)

    return lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, norm, head, *, eps, precision):
    h = rms_norm(x, norm.astype(jnp.float32), eps)
    return _mm("bth,hv->btv", h, head.astype(jnp.float32), precision)


def _static(c):
    """The config's numbers as a hashable, for the jit's static key."""
    keep = {k: v for k, v in c.items() if isinstance(v, (int, float))}
    keep["rope_scaling"] = tuple(sorted(
        (k, v) for k, v in c["rope_scaling"].items()
        if isinstance(v, (int, float))))
    return tuple(sorted(keep.items()))


def logits(params, ids, *, config, held, precision="f32"):
    """``ids`` [B, T] int32 -> logits [B, T, V] float32.  The layers run
    one after the other over all B rows (a sequence at a time inside),
    each layer's bfloat16 leaves upcast for its own call only, so that
    beside the tree there is one float32 layer and one sequence's
    activations."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    c = _static(config)
    x = params["embed"][ids].astype(jnp.float32)
    for layer in params["layers"]:
        x = _layer(x, layer, c=c, held=tuple(held), precision=precision)
    return _readout(x, params["final_norm"], params["head"],
                    eps=float(config["rms_norm_eps"]), precision=precision)
