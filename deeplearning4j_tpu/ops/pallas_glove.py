"""Fused GloVe chunk update as a Pallas TPU kernel (small-vocab path).

Reference parity: ``GloveWeightLookupTable.iterateSample`` (the
f(X) = (X/xMax)^0.75-weighted WLS update with per-row AdaGrad).  The XLA
path (``nlp/glove._glove_update``) batches it as gathers + einsums +
count-normalized AdaGrad scatter-adds; like word2vec, those row
gathers/scatters dominate chunk time on TPU.

Same redesign as ``ops/pallas_word2vec``: for vocabularies whose tables
fit in VMEM, rows move exclusively through one-hot matmuls on the MXU.
The bias terms fold into EXTENDED tables so the whole pair score is one
row-dot:

    wext[i]  = (w[i]  | b[i] | 1)          [V, D+2]
    wtext[j] = (wt[j] | 1 | bt[j])         [V, D+2]
    score(i, j) = wext[i] . wtext[j] = w[i].wt[j] + b[i] + bt[j]

Per side the kernel emits dense accumulators
``(sum g*p | sum (g*p)^2 | hit count)`` over the D+1 update columns
(weights + own bias; ``p`` = the partner's matching columns), from which
the XLA AdaGrad semantics reconstruct outside the kernel:
per-occurrence grads are ``g*p/k`` (k = row hits in the chunk), so
``gsq += sum_sq / k^2`` and ``step = alpha * (sum/k) / sqrt(gsq + eps)``
— exact ALGEBRA vs ``_glove_update.adagrad_scatter``, but the grad-square
lanes accumulate through bf16 matmuls, so numeric parity holds at bf16
precision only (tests/test_nlp_glove_pv.py asserts rtol 3e-2 in
interpreter mode).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

try:                                     # TPU-only compiler knobs
    from jax.experimental.pallas import tpu as pltpu
except ImportError:                      # pragma: no cover
    pltpu = None

Array = jax.Array

VMEM_BUDGET_BYTES = 14 * 2 ** 20


def _pad(x: int, m: int) -> int:
    return -(-x // m) * m


def _padded(vocab: int, dim: int):
    """(Vp, Ep): table extents as the kernel sees them — vocab and the
    extended width D+2 padded to whole 128-lane tiles, with at least one
    spare column: the last one carries the per-row hit count."""
    return _pad(vocab, 128), _pad(dim + 3, 128)


def choose_block(vocab: int, dim: int, batch: int,
                 interpret: bool = False) -> int:
    """Largest grid block for which the VMEM model fits, else 0."""
    vp, ep = _padded(vocab, dim)
    # 2 bf16 tables (double-buffered by the pipeline) + 4 resident fp32
    # [Vp, Ep] accumulators; per step two bf16 [Vp, BLK] one-hots.  At
    # the bench shape (vocab 2000, dim 100) this admits 1024 and not
    # 2048, which is where Mosaic's own VMEM accounting draws the line.
    fixed = 2 * 2 * vp * ep * 2 + 4 * vp * ep * 4
    for blk in (2048, 1024, 512, 256):
        if batch % blk:
            continue
        if fixed + 2 * vp * blk * 2 <= VMEM_BUDGET_BYTES:
            return blk
    if interpret and batch <= 1024:
        return batch
    return 0


def _kernel(rows_ref, cols_ref, x_ref, mask_ref, wext_ref, wtext_ref,
            sw_ref, qw_ref, swt_ref, qwt_ref, loss_ref,
            *, x_max: float, power: float):
    """Vocab-major throughout: one-hots are [Vp, BLK], the gathered
    rows are [Ep, BLK], and every per-pair quantity is a [1, BLK] ROW
    vector (a 1-D block is a layout Mosaic no longer takes, and a
    column vector would need a lane-to-sublane move per use).  The
    tables arrive bf16, padded to whole tiles."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for ref in (sw_ref, qw_ref, swt_ref, qwt_ref):
            ref[...] = jnp.zeros_like(ref)
        loss_ref[0, 0] = 0.0
        loss_ref[0, 1] = 0.0

    bf = jnp.bfloat16
    BLK = rows_ref.shape[1]
    Vp, Ep = wext_ref.shape

    iota = lax.broadcasted_iota(jnp.int32, (Vp, BLK), 0)
    ohr = (iota == rows_ref[...]).astype(bf)                   # [Vp, BLK]
    ohc = (iota == cols_ref[...]).astype(bf)
    rows0 = (((0,), (0,)), ((), ()))            # table^T . one-hot
    wi = lax.dot_general(wext_ref[...], ohr, rows0,
                         preferred_element_type=jnp.float32)   # [Ep, BLK]
    wj = lax.dot_general(wtext_ref[...], ohc, rows0,
                         preferred_element_type=jnp.float32)
    x = x_ref[...]                                             # [1, BLK]
    mask = mask_ref[...]
    diff = jnp.sum(wi * wj, axis=0, keepdims=True) \
        - jnp.log(jnp.maximum(x, 1e-12))
    fx = jnp.minimum((x / x_max) ** power, 1.0)
    g = fx * diff * mask                                       # [1, BLK]
    loss_ref[0, 0] += 0.5 * jnp.sum(fx * diff * diff * mask)
    loss_ref[0, 1] += jnp.sum(mask)

    # the spare last column of each accumulator counts row hits: its
    # payload row is the pair mask instead of a gradient
    count_row = lax.broadcasted_iota(jnp.int32, (Ep, BLK), 0) == Ep - 1

    def accumulate(s_ref, q_ref, oh, partner):
        grad = g * partner                                     # [Ep, BLK]
        # payloads are transposed in fp32 (the width Mosaic transposes
        # natively) so the scatters are plain [Vp, BLK] x [BLK, Ep]
        s_ref[...] += jnp.dot(
            oh, jnp.where(count_row, mask, grad).T.astype(bf),
            preferred_element_type=jnp.float32)                # [Vp, Ep]
        q_ref[...] += jnp.dot(
            oh, (grad * grad).T.astype(bf),
            preferred_element_type=jnp.float32)

    # row side updates (w | b): partner = (wt_j | 1 | .); col side
    # updates (wt | . | bt): partner = (w_i | . | 1).  The dotted
    # columns hold the other side's bias and are dropped outside.
    accumulate(sw_ref, qw_ref, ohr, wj)
    accumulate(swt_ref, qwt_ref, ohc, wi)


@functools.partial(
    jax.jit, static_argnames=("x_max", "power", "block", "interpret"))
def fused_glove_chunk(wext: Array, wtext: Array, rows: Array, cols: Array,
                      x: Array, mask: Array,
                      *, x_max: float, power: float, block: int = 1024,
                      interpret: bool = False):
    """One chunk's dense gradient accumulators via the VMEM kernel.

    Returns (accw, accwt, loss_sums): acc* [V, 2D+3] =
    (grad sums [D+1] | grad-square sums [D+1] | hit count);
    loss_sums [1, 2] = (weighted sq-err sum, mask sum).
    """
    B = rows.shape[0]
    BLK = min(block, B)
    NB = B // BLK
    assert NB * BLK == B, f"B={B} not a multiple of block={BLK}"
    V, E = wext.shape
    D = E - 2
    Vp, Ep = _padded(V, D)

    def table(t):
        return jnp.pad(t, ((0, Vp - V), (0, Ep - E))).astype(jnp.bfloat16)

    vec = pl.BlockSpec((1, BLK), lambda i: (0, i))
    full = pl.BlockSpec((Vp, Ep), lambda i: (0, 0))   # tables, accumulators
    smem = None if pltpu is None else pltpu.SMEM
    sw, qw, swt, qwt, loss = pl.pallas_call(
        functools.partial(_kernel, x_max=x_max, power=power),
        grid=(NB,),
        in_specs=[vec] * 4 + [full] * 2,
        out_specs=[full] * 4 + [pl.BlockSpec(memory_space=smem)],
        out_shape=[jax.ShapeDtypeStruct((Vp, Ep), jnp.float32)] * 4
        + [jax.ShapeDtypeStruct((1, 2), jnp.float32)],
        interpret=interpret,
        compiler_params=None if (interpret or pltpu is None) else
        pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(rows[None, :], cols[None, :], x.astype(jnp.float32)[None, :],
      mask.astype(jnp.float32)[None, :], table(wext), table(wtext))

    cnt = slice(Ep - 1, Ep)
    accw = jnp.concatenate(
        [sw[:V, :D + 1], qw[:V, :D + 1], sw[:V, cnt]], axis=1)

    def col_side(a):                    # (wt | bt): skip the row bias
        return jnp.concatenate([a[:V, :D], a[:V, D + 1:D + 2]], axis=1)

    accwt = jnp.concatenate(
        [col_side(swt), col_side(qwt), swt[:V, cnt]], axis=1)
    return accw, accwt, loss


def apply_chunk(table_b: Array, gsq_b: Array, acc: Array, alpha):
    """Apply one side's accumulators to (weights|bias) [V, D+1] and
    their AdaGrad state [V, D+1] — the same ALGEBRA as the scatter path
    (gsq += sum_sq / k^2 ; step = alpha * (sum/k) / sqrt(gsq + eps)),
    at bf16 precision: the accumulators arrive from bf16 kernel matmuls,
    so parity with the fp32 XLA path is approximate (rtol ~3e-2), not
    bitwise."""
    d1 = table_b.shape[1]
    cnt = jnp.maximum(acc[:, 2 * d1:2 * d1 + 1], 1.0)
    grad = acc[:, :d1] / cnt
    gsq_b = gsq_b + acc[:, d1:2 * d1] / (cnt * cnt)
    return table_b - alpha * grad / jnp.sqrt(gsq_b + 1e-8), gsq_b


_PROBE_CACHE: dict = {}


def probe_compile(block: int, vocab_size: int = 128, dim: int = 8,
                  timeout_s: float = 240.0) -> Optional[str]:
    """One real compile of the kernel at the given block size AND the
    caller's actual (vocab, dim): None when Mosaic took it, else its
    message (``kernel_select.probe_mosaic``).  ``auto`` selection on
    hardware goes through here (``kernel_select.choose_kernel``) so a
    refusal degrades to the XLA path, and says so, instead of crashing
    fit().  VMEM fit depends on the table shapes, so the probe runs at
    the production shapes; cached per the full key."""
    from deeplearning4j_tpu.ops.kernel_select import probe_mosaic

    key = (block, vocab_size, dim)
    if key not in _PROBE_CACHE:
        def compile_once():
            wext = jnp.zeros((vocab_size, dim + 2), jnp.float32)
            rows = jnp.zeros((block,), jnp.int32)
            x = jnp.ones((block,), jnp.float32)
            accw, _, _ = fused_glove_chunk(
                wext, wext, rows, rows, x, x, x_max=100.0, power=0.75,
                block=block, interpret=False)
            float(accw[0, 0])

        _PROBE_CACHE[key] = probe_mosaic(compile_once, "glove", timeout_s)
    return _PROBE_CACHE[key]
