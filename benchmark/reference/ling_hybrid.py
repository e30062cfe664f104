"""Plain reference for Ling-3.0-flash (inclusionAI, ``model_type``
``bailing_hybrid``) as ONE expert-parallel rank serves it, written from
the published ``config.json``'s keys and the equations below, not from
the program.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no pages, no chunked form,
nothing imported from the program.  It reads the tree the benchmark's
own ``families/ling_hybrid.py`` makes (every leaf bfloat16, upcast here
a layer at a time), ``E_all`` the router's width, ``E`` the experts held,
``HD = num_attention_heads x head_dim``:

    embed [V, H]   final_norm [H]   head [H, V]
    layers[l]: attn_norm, ffn_norm [H]
      attn of a KDA layer: w_q, w_k, w_v, w_f, w_g [H, HD]  w_b [H, NH]
            conv [K, 3 HD]  A_log [NH]  dt_bias [HD]  o_norm [D]
            w_o [HD, H]
      attn of an MLA layer: w_q [H, NH, nope + rope]  w_dkv [H, R + rope]
            kv_norm [R]  w_ukv [R, NH, nope + v]  w_ga [H, NH]
            w_o [NH, v, H]
      mlp (dense layers): w_gate, w_up [H, I]  w_down [I, H]
      moe (the others): router [H, E_all]  bias [E_all]
            experts: w_gate, w_up [E, H, F]  w_down [E, F, H]
            shared:  w_gate, w_up [H, Fs]  w_down [Fs, H]

Equations, ``x`` the residual stream, ``RMS_g(v) = g v / sqrt(mean(v^2)
+ eps)``, no bias; kept layer ``l`` is the published layer ``i =
layers[l]``, MLA where ``(i + 1) % layer_group_size == 0``, KDA otherwise,
dense where ``i < first_k_dense_replace``:

    h = RMS(x; attn_norm)
    KDA (Kimi Linear, arXiv 2510.26692), a head of D lanes:
        [q; k; v] = SiLU(conv_t),  conv_t = sum_{j<K} conv[j] u_{t-K+1+j},
            u_t = h_t [W_q W_k W_v] (u before the sequence = 0)
        q = q / |q| / sqrt(D),  k = k / |k|   (|.| over a head, eps 1e-6)
        g = kda_lower_bound sigmoid(exp(A_log) (h W_f + dt_bias))
        beta = sigmoid(h W_b)
        S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T, S_0 = 0
        o_t = S_t^T q_t;  y = (RMS(o; o_norm) sigmoid(h W_g)) W_o
    MLA (no q low-rank step):
        q = h W_q -> [nope | rope] a head;  c = h W_dkv
        c_kv = RMS(c[:R]; kv_norm);  k_rope = RoPE(c[R:]);  q_rope = RoPE(q_rope)
        [k_nope | v] = c_kv W_ukv a head
        score = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope),
            softmax over keys j <= i
        y = (softmax(score) v * sigmoid(h W_ga)[head]) W_o
        RoPE: lanes (2j, 2j + 1) turned by position x rope_theta^(-2j/rope)
    x = x + y;  h = RMS(x; ffn_norm)
    dense: x = x + F(h; mlp, 0)
    others: s = sigmoid(h W_r) over E_all experts; c = s + bias; a group
        of E_all / n_group scores sum(top 2 of c); the topk_group best
        groups stay, c of the others is 0; the num_experts_per_tok
        largest c are taken; w_e = routed_scaling_factor s_e / (sum of
        the taken s + 1e-20)
        x = x + sum_{e taken AND held} w_e F(h; e, L_e) + F(h; shared, L_s)
    F(h; W, L) = (silu(min(h Wg, L)) * clip(h Wu, -L, L)) Wd, L = 0: no clamp
    logits = RMS(x; final_norm) W_head

Taken on trust, each also in the configuration's ``assumed``: the form of
the KDA gate's lower bound and of the SwiGLU clamp, the layer-pattern
rule, the head-wise gate's place, where the qk norm applies, the
convolution's weight layout.

This rank's share: the sum over taken experts leaves out those held on
other ranks (``held`` = first id and count), and the head is the
vocabulary's slice.  Nothing stands in for what is left out.

Two things are done for size, and change no arithmetic: the MLA scores
are taken ``q_block`` query rows at a time, and ``at`` asks for the
logits of some positions only.

``precision="fp8"`` is the CONTROL, as in ``gpt2_postln.py``: both
operands of every matrix product with a weight rounded to float8_e4m3fn
under one scale per tensor (amax / 448), accumulated in float32.
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


def kda(c, a, h, precision):
    """``h`` [T, H] normed rows of one sequence -> [T, H]: the token
    recurrence from a zero state."""
    T = h.shape[0]
    NH, D, K = (c["num_attention_heads"], c["head_dim"],
                c["short_conv_kernel_size"])
    u = jnp.concatenate([_mm("th,hf->tf", h, a[n], precision)
                         for n in ("w_q", "w_k", "w_v")], axis=-1)
    u = jnp.concatenate([jnp.zeros((K - 1, u.shape[1])), u], axis=0)
    conv = sum(a["conv"][j] * u[j:j + T] for j in range(K))
    q, k, v = (t.reshape(T, NH, D) for t in jnp.split(jax.nn.silu(conv), 3,
                                                       axis=-1))
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(D)
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = c["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(a["A_log"])[:, None]
        * (_mm("th,hf->tf", h, a["w_f"], precision).reshape(T, NH, D)
           + a["dt_bias"].reshape(NH, D)))
    beta = jax.nn.sigmoid(_mm("th,hn->tn", h, a["w_b"], precision))

    def step(S, row):
        q_t, k_t, v_t, g_t, b_t = row
        S = jnp.exp(g_t)[:, :, None] * S                     # Diag(exp g) S
        S = S - b_t[:, None, None] * k_t[:, :, None] * jnp.einsum(
            "nd,nde->ne", k_t, S, precision=lax.Precision.HIGHEST)[:, None, :]
        S = S + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("nde,nd->ne", S, q_t,
                             precision=lax.Precision.HIGHEST)

    _, o = lax.scan(step, jnp.zeros((NH, D, D)), (q, k, v, g, beta))
    o = rms_norm(o, a["o_norm"], c["rms_norm_eps"]) * jax.nn.sigmoid(
        _mm("th,hf->tf", h, a["w_g"], precision)).reshape(T, NH, D)
    return _mm("tf,fh->th", o.reshape(T, NH * D), a["w_o"], precision)


def rope(x, n_pos, theta):
    """Interleaved pairs of the last axis turned by position."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(n_pos, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def mla(c, a, h, precision, q_block):
    """``h`` [T, H] normed rows of one sequence -> [T, H]; ``q_block``
    query rows at a time ([NH, q_block, T] float32 fits where [NH, T, T]
    does not)."""
    T = h.shape[0]
    NH, R = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    theta = float(c["rope_theta"])
    q = _mm("th,hnd->tnd", h, a["w_q"], precision)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], T, theta)
    ckv = _mm("th,hf->tf", h, a["w_dkv"], precision)
    c_kv = rms_norm(ckv[:, :R], a["kv_norm"], c["rms_norm_eps"])
    k_rope = rope(ckv[:, R:], T, theta)
    kv = _mm("tr,rnd->tnd", c_kv, a["w_ukv"], precision)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_block = min(q_block, T)
    if T % q_block:
        raise ValueError(f"{T} rows are not whole blocks of {q_block}")

    def rows(lo):
        i = (lo + jnp.arange(q_block))[:, None]
        seen = jnp.arange(T)[None, :] <= i
        qn = lax.dynamic_slice_in_dim(q_nope, lo, q_block)
        qr = lax.dynamic_slice_in_dim(q_rope, lo, q_block)
        s = (_mm("qnd,knd->nqk", qn, k_nope, precision)
             + _mm("qnd,kd->nqk", qr, k_rope, precision)) / math.sqrt(dn + dr)
        s = jnp.where(seen[None], s, -1e9)
        return _mm("nqk,knd->qnd", jax.nn.softmax(s, axis=-1), v, precision)

    o = lax.map(rows, jnp.arange(0, T, q_block)).reshape(T, NH, -1)
    o = o * jax.nn.sigmoid(_mm("th,hn->tn", h, a["w_ga"], precision))[..., None]
    return _mm("tnd,ndh->th", o, a["w_o"], precision)


def gated(x, p, limit, precision):
    g = _mm("th,hf->tf", x, p["w_gate"], precision)
    u = _mm("th,hf->tf", x, p["w_up"], precision)
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return _mm("tf,fh->th", jax.nn.silu(g) * u, p["w_down"], precision)


def route(c, scores, bias):
    """[T, E_all] sigmoid scores -> [T, E_all] weights, zero off the
    experts taken: groups and experts chosen by ``scores + bias``,
    weighed by ``scores`` (DeepSeek-V3's ``noaux_tc``)."""
    T, E = scores.shape
    n_group, k = c["n_group"], c["num_experts_per_tok"]
    choice = scores + bias[None, :]
    groups = choice.reshape(T, n_group, E // n_group)
    two = -jnp.sort(-groups, axis=-1)[..., :2].sum(axis=-1)      # [T, n_group]
    best = jnp.argsort(-two, axis=-1, stable=True)[:, :c["topk_group"]]
    kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)
    choice = jnp.where(kept[:, :, None], groups, 0.0).reshape(T, E)
    taken = jnp.argsort(-choice, axis=-1, stable=True)[:, :k]
    mask = jnp.any(taken[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    mine = jnp.where(mask, scores, 0.0)
    return mine * c["routed_scaling_factor"] / (
        mine.sum(axis=-1, keepdims=True) + 1e-20)


def moe(c, p, x, held, limits, precision):
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
        return acc + mine[:, None] * gated(x, expert, limits[0],
                                           precision), None

    routed, _ = lax.scan(one, jnp.zeros_like(x), (p["experts"], w.T))
    return routed + gated(x, p["shared"], limits[1], precision)


def kind_of(c, i):
    """What published layer ``i`` is: (MLA?, its routed and shared SwiGLU
    limits)."""
    return ((i + 1) % c["layer_group_size"] == 0,
            (float(c["expert_swiglu_limit_list"][i]),
             float(c["share_expert_swiglu_limit_list"][i])))


def block(c, layer, x, is_mla, limits, held, precision, q_block):
    """One layer on the rows ``x`` [T, H] of one sequence."""
    eps = c["rms_norm_eps"]
    h = rms_norm(x, layer["attn_norm"], eps)
    if is_mla:
        x = x + mla(c, layer["attn"], h, precision, q_block)
    else:
        x = x + kda(c, layer["attn"], h, precision)
    h = rms_norm(x, layer["ffn_norm"], eps)
    if "mlp" in layer:
        return x + gated(h, layer["mlp"], 0.0, precision)
    return x + moe(c, layer["moe"], h, held, limits, precision)


def _f32_but_experts(tree):
    """Every leaf upcast but the routed experts' (:func:`moe` upcasts
    each for its own turn)."""
    if isinstance(tree, dict):
        return {k: v if k == "experts" else _f32_but_experts(v)
                for k, v in tree.items()}
    return tree.astype(jnp.float32)


class _Frozen(tuple):
    """The config as a hashable, for the jit's static key."""

    def get(self):
        return {k: list(v) if isinstance(v, tuple) else v for k, v in self}


def _static(c):
    keep = {k: tuple(v) if isinstance(v, list) else v for k, v in c.items()
            if isinstance(v, (int, float))
            or (isinstance(v, list) and all(isinstance(e, (int, float))
                                            for e in v))}
    return _Frozen(sorted(keep.items()))


@functools.partial(jax.jit, static_argnames=("c", "is_mla", "limits", "held",
                                             "precision", "q_block"))
def _layer(x, layer, *, c, is_mla, limits, held, precision, q_block):
    """Keyed by the layer's kind and limits, not its index: the layers
    of one kind share a compile."""
    c = c.get()
    layer = _f32_but_experts(layer)
    return lax.map(lambda row: block(c, layer, row, is_mla, limits, held,
                                     precision, q_block), x)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, norm, head, *, eps, precision):
    h = rms_norm(x, norm.astype(jnp.float32), eps)
    return _mm("bth,hv->btv", h, head.astype(jnp.float32), precision)


def logits(params, ids, *, config, held, precision="f32", at=None,
           q_block=256):
    """``ids`` [B, T] int32 -> logits float32: [B, T, V], or [B, n, V] at
    the positions ``at`` [B, n] of each row.  The layers run one after
    the other over all B rows (a sequence at a time inside), each layer's
    bfloat16 leaves upcast for its own call only."""
    if precision not in PRECISIONS:
        raise ValueError(precision)
    c = _static(config)
    x = params["embed"][ids].astype(jnp.float32)
    for i, layer in zip(config["layers"], params["layers"]):
        is_mla, limits = kind_of(config, i)
        x = _layer(x, layer, c=c, is_mla=is_mla, limits=limits,
                   held=tuple(held), precision=precision, q_block=q_block)
    if at is not None:
        x = jnp.take_along_axis(x, jnp.asarray(at)[:, :, None], axis=1)
    return _readout(x, params["final_norm"], params["head"],
                    eps=float(config["rms_norm_eps"]), precision=precision)
