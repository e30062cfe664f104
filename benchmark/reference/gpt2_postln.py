"""Plain reference for the GPT-2 widths on the repo's post-LN block.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no batching tricks, and
nothing imported from the program.  It reads the parameter tree by the
names the benchmark's own ``lib/weights.py`` makes it under:

    embed:  tok [V, H]  pos [T, H]  ln_g, ln_b [H]     (type [1, H]: unread)
    blocks: wq, wk, wv [L, H, NH, D]  bq, bk, bv [L, NH, D]
            wo [L, NH, D, H]  bo [L, H]  ln1_g, ln1_b [L, H]
            w1 [L, H, F]  b1 [L, F]  w2 [L, F, H]  b2 [L, H]  ln2_g, ln2_b

Equations (departures from GPT-2 as published are the configuration
file's ``assumed``):

    x0 = LN(tok[ids] + pos[0..T))
    a  = softmax(causal(q k^T / sqrt(D))) v,  q/k/v = x W + b
    x  = LN(x + a Wo + bo);   x = LN(x + gelu_tanh(x W1 + b1) W2 + b2)
    logits = x tok^T                         (tied readout, no final LN)
    loss = mean over rows and positions t < T-1 of -log softmax(logits_t)[ids_{t+1}]

``precision="fp8"`` is the CONTROL, not a second reference: the same
equations in the usual float8 recipe (Micikevicius et al. 2022), the
step below the bfloat16 the configuration states.  Both operands of
every matrix product are rounded to float8_e4m3fn under one scale per
tensor (amax / 448), and the cotangent that comes back into every
product is rounded to float8_e5m2 the same way (amax / 57344).  The
products themselves still accumulate in float32, and the backward pass
sees the rounded forward values through a straight-through estimator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ("f32", "fp8")
FP8_MAX = 448.0          # float8_e4m3fn, forward operands
FP8_GRAD_MAX = 57344.0   # float8_e5m2, cotangents


def _q(x, precision):
    """Operand of a matrix product at the stated precision."""
    if precision == "f32":
        return x
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    # straight through: the rounding is in the forward values only.  A
    # cotangent cast to float8 with no scale of its own would flush to
    # zero and read as a leaf that never moved, which no float8 path
    # worth the name would do: the control has to be the tempting one
    return x + lax.stop_gradient(q - x)


@jax.custom_vjp
def _round_cotangent(y):
    return y


def _round_cotangent_bwd(_, g):
    scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30) / FP8_GRAD_MAX
    return ((g / scale).astype(jnp.float8_e5m2).astype(jnp.float32)
            * scale,)


_round_cotangent.defvjp(lambda y: (y, None), _round_cotangent_bwd)


def _mm(spec, a, b, precision):
    y = jnp.einsum(spec, _q(a, precision), _q(b, precision),
                   precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    return y if precision == "f32" else _round_cotangent(y)


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def block(x, p, eps, precision):
    """One post-LN block: x [B, T, H] float32, p one layer's leaves."""
    T, D = x.shape[1], p["wq"].shape[-1]
    q = _mm("bth,hnd->btnd", x, p["wq"], precision) + p["bq"]
    k = _mm("bth,hnd->btnd", x, p["wk"], precision) + p["bk"]
    v = _mm("bth,hnd->btnd", x, p["wv"], precision) + p["bv"]
    s = _mm("bqnd,bknd->bnqk", q, k, precision) / jnp.sqrt(jnp.float32(D))
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -1e9)
    a = _mm("bnqk,bknd->bqnd", jax.nn.softmax(s, axis=-1), v, precision)
    a = _mm("btnd,ndh->bth", a, p["wo"], precision) + p["bo"]
    x = layer_norm(x + a, p["ln1_g"], p["ln1_b"], eps)
    f = gelu_tanh(_mm("bth,hf->btf", x, p["w1"], precision) + p["b1"])
    f = _mm("btf,fh->bth", f, p["w2"], precision) + p["b2"]
    return layer_norm(x + f, p["ln2_g"], p["ln2_b"], eps)


def forward_logits(params, ids, *, eps, precision="f32", remat=False):
    """ids [B, T] int32 -> logits [B, T, V] float32."""
    e = params["embed"]
    x = e["tok"][ids] + e["pos"][: ids.shape[1]]
    x = layer_norm(x, e["ln_g"], e["ln_b"], eps)

    def body(x, p):
        return block(x, p, eps, precision), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = lax.scan(body, x, params["blocks"])
    return _mm("bth,vh->btv", x, e["tok"], precision)


def nll_sum(params, ids, *, eps, precision="f32"):
    """Summed next-token negative log-likelihood of ids [B, T]."""
    logits = forward_logits(params, ids[:, :-1], eps=eps,
                            precision=precision, remat=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, ids[:, 1:, None], axis=-1))


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _block_grad(params, ids, *, eps, precision):
    return jax.value_and_grad(nll_sum)(params, ids, eps=eps,
                                       precision=precision)


@jax.jit
def _tree_add(a, b):
    return jax.tree.map(jnp.add, a, b)


@functools.partial(jax.jit, donate_argnums=(0,))
def _sgd(params, grads, scale, lr):
    return jax.tree.map(lambda p, g: p - lr * (g * scale), params, grads)


def loss_and_grad(params, ids, *, eps, precision="f32", rows_per_block=2):
    """Mean loss and its gradient over a batch ids [B, T], taken
    ``rows_per_block`` rows at a time so that float32 activations and
    logits fit beside the trees."""
    B, T = ids.shape
    total, grads = 0.0, None
    for lo in range(0, B, rows_per_block):
        s, g = _block_grad(params, ids[lo:lo + rows_per_block], eps=eps,
                           precision=precision)
        total = total + s
        grads = g if grads is None else _tree_add(grads, g)
    denom = jnp.float32(B * (T - 1))
    return total / denom, jax.tree.map(lambda g: g / denom, grads)


def sgd_steps(params, batches, *, lr, eps, precision="f32",
              rows_per_block=2, on_step=None):
    """Plain SGD (no momentum) over ``batches``: p <- p - lr * grad.
    ``on_step(i, loss, grads, params_after)`` sees every step.  Consumes
    ``params``; returns the parameters after the last step."""
    for i, ids in enumerate(batches):
        loss, grads = loss_and_grad(params, ids, eps=eps,
                                    precision=precision,
                                    rows_per_block=rows_per_block)
        params = _sgd(params, grads, jnp.float32(1.0), jnp.float32(lr))
        if on_step is not None:
            on_step(i, loss, grads, params)
    return params


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _logits(params, ids, *, eps, precision):
    return forward_logits(params, ids, eps=eps, precision=precision)


def logits(params, ids, *, eps, precision="f32"):
    return _logits(params, ids, eps=eps, precision=precision)
