"""Plain reference for K-EXAONE (LG AI Research ``K-EXAONE-236B-A23B``,
``model_type`` ``exaone_moe``) as ONE expert-parallel rank serves it,
written from the published ``config.json``'s keys and the equations
below, not from the program.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no pages, no draft, nothing
imported from the program.  It reads the tree the benchmark's own
``families/exaone_moe.py`` makes (every leaf bfloat16, upcast here a
layer at a time), ``E_all`` the router's width, ``E`` the experts held:

    embed [V, H]   final_norm [H]   head [H, V]
    layers[i]: attn_out_norm, ffn_out_norm [H]
      attn: w_q [H, NH D]  w_k, w_v [H, KV D]  w_o [NH D, H]
            q_norm, k_norm [D]
      mlp (the leading dense layers): w_gate, w_up [H, I]  w_down [I, H]
      moe (the others): router [H, E_all]   bias [E_all]
            experts: w_gate, w_up [E, H, F]  w_down [E, F, H]
            shared:  w_gate, w_up [H, n_shared F]  w_down [n_shared F, H]
    mtp: h_norm, e_norm, out_norm [H]  w_eh [2 H, H]  block: a moe layer

Equations, ``x`` the residual stream, ``RMS_g(v) = g v / sqrt(mean(v^2)
+ eps)``, ``F(v; Wg, Wu, Wd) = (silu(v Wg) * (v Wu)) Wd``, no bias;
layer ``l`` is of kind ``layer_types[l]``:

    q = x W_q -> [NH, D];  k = x W_k, v = x W_v -> [KV, D]
    q = RMS_D(q; q_norm);  k = RMS_D(k; k_norm)
    on a sliding_attention layer only: q, k = RoPE(q), RoPE(k)
        (rope_type default at rope_theta, rotate-half, all D lanes);
        a full_attention layer has no positions
    query head h reads K/V head h // (NH / KV)
    score = q . k / sqrt(D), softmax over keys j <= i; on a
            sliding_attention layer also j > i - sliding_window
    x = x + RMS(concat_heads(softmax(score) v) W_o; attn_out_norm)
    layer 0:   x = x + RMS(F(x; mlp); ffn_out_norm)
    others:    s = sigmoid(x W_r) over the E_all experts
               the num_experts_per_tok largest of s + bias are taken
               w_e = routed_scaling_factor s_e / sum of the taken s
               x = x + RMS(sum_{e taken AND held here} w_e F_e(x)
                           + F_shared(x); ffn_out_norm)
    logits = RMS(x; final_norm) W_head

The MTP block (:func:`draft_logits`; DeepSeek-V3, arXiv 2412.19437,
section 2.2), whose output no served token depends on: at position i,
``u_i = [RMS(h_i; h_norm) ; RMS(Emb(t_{i+1}); e_norm)] W_eh`` with ``h``
the last main layer's output before ``final_norm``; one block as above
of kind ``mtp_layer_types[0]`` with a sparse MLP; ``RMS(.; out_norm)
W_head`` are the logits over the token at i + 2.

Taken on trust, each also in the configuration's ``assumed``: the
per-head RMSNorm of q and k, no rotation on full layers and the norms on
the sublayers' outputs (the EXAONE-4 lineage's modelling code); the
selection bias and the order of the router's operations (DeepSeek-V3's
router, whose key set this config carries); the MTP block's make-up.

This rank's share: the sum over taken experts leaves out those held on
other ranks (``held`` = first id and count), and the head is the
vocabulary's slice.  Nothing stands in for what is left out.

Two things are done for size, and change no arithmetic: the scores are
taken ``q_block`` query rows at a time, and ``at`` asks for the logits
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


def rope_tables(c, n):
    """cos, sin [n, head_dim] for positions 0..n-1 (sliding layers)."""
    d, base = c["head_dim"], float(c["rope_theta"])
    inv_freq = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(n, dtype=np.float64)[:, None] * inv_freq[None, :]
    ang = np.concatenate([ang, ang], axis=-1)
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(np.sin(ang), jnp.float32))


def rope(x, cos, sin):
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]],
                                     axis=-1) * sin


def attention(c, a, x, kind, precision, q_block=256):
    """``x`` [T, H] rows of one sequence -> [T, H] (before the output's
    norm); ``a`` the layer's attention leaves.  ``q_block`` query rows at
    a time ([NH, q_block, T] float32 fits where [NH, T, T] does not)."""
    T = x.shape[0]
    NH, KV, D = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    q = _mm("th,hf->tf", x, a["w_q"], precision).reshape(T, NH, D)
    k = _mm("th,hf->tf", x, a["w_k"], precision).reshape(T, KV, D)
    v = _mm("th,hf->tf", x, a["w_v"], precision).reshape(T, KV, D)
    q, k = rms_norm(q, a["q_norm"], eps), rms_norm(k, a["k_norm"], eps)
    if kind == "sliding_attention":
        cos, sin = rope_tables(c, T)
        q, k = rope(q, cos[:, None], sin[:, None]), rope(k, cos[:, None],
                                                         sin[:, None])
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


def gated(x, p, precision):
    g = _mm("th,hf->tf", x, p["w_gate"], precision)
    u = _mm("th,hf->tf", x, p["w_up"], precision)
    return _mm("tf,fh->th", jax.nn.silu(g) * u, p["w_down"], precision)


def route(c, scores, bias):
    """[T, E_all] sigmoid scores -> [T, E_all] weights, zero off the
    experts taken: chosen by ``scores + bias``, weighed by ``scores``."""
    E = scores.shape[1]
    taken = jnp.argsort(-(scores + bias[None, :]), axis=-1, stable=True
                        )[:, :c["num_experts_per_tok"]]
    mask = jnp.any(taken[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    mine = jnp.where(mask, scores, 0.0)
    return mine * c["routed_scaling_factor"] / (
        mine.sum(axis=-1, keepdims=True) + 1e-20)


def moe(c, p, x, held, precision):
    """The expert layer's share: every held expert over every row,
    weighted by what the router gave it (0 where it was not taken), and
    the shared expert.  The experts are taken one after the other (each
    upcast for its own turn), their weighted results summed."""
    first, count = held
    scores = jax.nn.sigmoid(_mm("th,he->te", x, p["router"], "f32"))
    w = route(c, scores, p["bias"])[:, first:first + count]    # [T, count]

    def one(acc, ew):
        expert, mine = ew
        expert = jax.tree.map(lambda a: a.astype(jnp.float32), expert)
        return acc + mine[:, None] * gated(x, expert, precision), None

    routed, _ = lax.scan(one, jnp.zeros_like(x), (p["experts"], w.T))
    return routed + gated(x, p["shared"], precision)


def block(c, layer, x, kind, held, precision, q_block):
    """One block on the rows ``x`` [T, H] of one sequence."""
    eps = c["rms_norm_eps"]
    x = x + rms_norm(attention(c, layer["attn"], x, kind, precision,
                               q_block), layer["attn_out_norm"], eps)
    f = (gated(x, layer["mlp"], precision) if "mlp" in layer
         else moe(c, layer["moe"], x, held, precision))
    return x + rms_norm(f, layer["ffn_out_norm"], eps)


def _f32_but_experts(tree):
    """Every leaf upcast but the routed experts' (:func:`moe` upcasts
    each for its own turn: sixteen at once would be 2.4 GB)."""
    if isinstance(tree, dict):
        return {k: v if k == "experts" else _f32_but_experts(v)
                for k, v in tree.items()}
    return tree.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("c", "kind", "held",
                                             "precision", "q_block"))
def _layer(x, layer, *, c, kind, held, precision, q_block):
    c = dict(c)
    layer = _f32_but_experts(layer)
    return lax.map(lambda row: block(c, layer, row, kind, held, precision,
                                     q_block), x)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, norm, head, *, eps, precision):
    h = rms_norm(x, norm.astype(jnp.float32), eps)
    return _mm("bth,hv->btv", h, head.astype(jnp.float32), precision)


def _static(c):
    """The config's numbers as a hashable, for the jit's static key."""
    keep = {k: v for k, v in c.items() if isinstance(v, (int, float))}
    keep["rope_theta"] = float(c["rope_parameters"]["rope_theta"])
    return tuple(sorted(keep.items()))


def hidden(params, ids, *, config, held, precision="f32", q_block=256):
    """``ids`` [B, T] int32 -> the last main layer's output [B, T, H]
    float32, before ``final_norm``.  The layers run one after the other
    over all B rows (a sequence at a time inside), each layer's bfloat16
    leaves upcast for its own call only."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    if config["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError(config["rope_parameters"])
    c = _static(config)
    x = params["embed"][ids].astype(jnp.float32)
    for l, layer in enumerate(params["layers"]):
        x = _layer(x, layer, c=c, kind=config["layer_types"][l],
                   held=tuple(held), precision=precision, q_block=q_block)
    return x


def logits(params, ids, *, config, held, precision="f32", at=None,
           q_block=256):
    """``ids`` [B, T] int32 -> logits float32: [B, T, V], or [B, n, V] at
    the positions ``at`` [B, n] of each row."""
    x = hidden(params, ids, config=config, held=held, precision=precision,
               q_block=q_block)
    if at is not None:
        x = jnp.take_along_axis(x, jnp.asarray(at)[:, :, None], axis=1)
    return _readout(x, params["final_norm"], params["head"],
                    eps=float(config["rms_norm_eps"]), precision=precision)


def draft_logits(params, ids, *, config, held, precision="f32", q_block=256):
    """``ids`` [B, T] -> the MTP block's logits [B, T - 1, V]: entry i
    over the token at i + 2, from ``h_i`` and the token at i + 1.  (The
    block runs over all T rows, the last beside a token 0 that nothing
    before it attends, and that row is dropped.)"""
    m = jax.tree.map(lambda w: w.astype(jnp.float32), params["mtp"])
    eps = float(config["rms_norm_eps"])
    h = hidden(params, ids, config=config, held=held, precision=precision,
               q_block=q_block)
    after = jnp.concatenate([ids[:, 1:], jnp.zeros_like(ids[:, :1])], axis=1)
    e = params["embed"][after].astype(jnp.float32)
    u = _mm("btf,fh->bth", jnp.concatenate(
        [rms_norm(h, m["h_norm"], eps), rms_norm(e, m["e_norm"], eps)],
        axis=-1), m["w_eh"], precision)
    u = _layer(u, params["mtp"]["block"], c=_static(config),
               kind=config["mtp_layer_types"][0], held=tuple(held),
               precision=precision, q_block=q_block)
    return _readout(u[:, :-1], params["mtp"]["out_norm"], params["head"],
                    eps=eps, precision=precision)
