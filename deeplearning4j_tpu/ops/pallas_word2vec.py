"""Fused word2vec chunk update as a Pallas TPU kernel (small-vocab path).

Reference parity: the inner training kernel
``InMemoryLookupTable.iterateSample:195-303`` (HS tree walk + negative
sampling, BLAS-1 axpy per word).  The XLA redesign in ``nlp/word2vec.py``
batches those axpys into gathers + einsums + scatter-adds; on TPU those
gathers/scatters of ~400-byte rows run far from HBM peak (measured ~6 ms
per 16k-pair chunk for HS alone) because XLA lowers row scatter-adds to a
serial per-row loop and row gathers to narrow copies.

This kernel removes gathers and scatters ENTIRELY for vocabularies whose
tables fit in VMEM (the classic word2vec regime of 1e2..1e4 vocab, the
reference's own test scale), via a DENSE-SCORES formulation:

- syn0 / syn1 / syn1neg stay resident in VMEM (bf16) for the whole chunk;
- ALL pair-vs-row dot products are computed at once:
  ``scores = syn · l1ᵀ`` — ONE [V, BLK] matmul per objective, amortized
  over every HS level / negative partner, instead of one gather-matmul
  per level (the round-3 kernel's cost was ~4·V·D MXU flops per level
  per pair; this is ~6·V·D per OBJECTIVE per pair — ~4.7x fewer at
  Huffman depth ~14);
- the per-level work drops to VPU-only: extract ``f = scores[pts, b]``
  by iota-compare, fold the resulting signed lr coefficient ``g`` into a
  coefficient plane ``G[v, b]`` (and its hit-mask twin ``M``);
- the level loop's matmuls then collapse to two per objective:
  ``neu1e = synᵀ · G`` (the input-side update) and ``acc += G · l1ᵀ``
  (the output-side scatter), with per-row hit counts as row sums of
  ``M``;
- every plane is VOCAB-major ``[V, BLK]`` and every per-pair quantity a
  ``[1, BLK]`` row vector, so the level loop never moves data between
  lanes and sublanes.

The update math is IDENTICAL to ``nlp/word2vec._hs_update`` /
``_neg_update`` (bf16 matmuls, fp32 accumulation): per chunk, both
objectives read the chunk-start table values, per-row update sums are
normalized by hit counts, and ``syn0 += hs_part/cnt_hs + neg_part/cnt_neg``.
``interpret=True`` runs the kernel through the Pallas interpreter for the
CPU test harness (tests/test_nlp.py compares it against the XLA path).
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

#: VMEM budget for the resident tables + [BLK, V] score/coefficient
#: planes + accumulators (~14 MB of the ~16 MB/core VMEM)
VMEM_BUDGET_BYTES = 14 * 2 ** 20


def _pad(x: int, m: int) -> int:
    return -(-x // m) * m


def choose_block(vocab: int, dim: int, negative: int, batch: int,
                 interpret: bool = False) -> int:
    """Largest grid block for which the VMEM model fits, or 0 when the
    vocabulary is too large for the resident kernel (callers then use the
    XLA gather/scatter path)."""
    n_tables = 3 if negative > 0 else 2
    n_obj = 1 + (1 if negative > 0 else 0)
    vp = _pad(vocab, 128)
    # pad(dim+1), not pad(dim): the spare last column of every
    # accumulator carries the hit count, so at dim%128==0 the +1 costs a
    # whole extra 128-lane tile per table (ADVICE r4)
    dp = _pad(dim + 1, 128)
    # bf16 tables (double-buffered by the pipeline) + resident fp32
    # accumulators: two [V, Dp] for syn0 and one per output table
    fixed = 2 * n_tables * vp * dp * 2 + (2 + n_obj) * vp * dp * 4
    for blk in (512, 256, 128):
        if batch % blk:
            continue
        # per-step planes [V, BLK]: the bf16 input one-hot, and fp32
        # scores + bf16 G + bf16 M of ONE objective (they run one after
        # the other).  At the bench shape (vocab 2000, dim 100, HS +
        # negative) this admits 256 and not 512, which is where Mosaic's
        # own VMEM accounting draws the line.
        planes = blk * vp * (2 + 4 + 2 + 2)
        if fixed + planes <= VMEM_BUDGET_BYTES:
            return blk
    if interpret and batch <= 1024:
        return batch
    return 0


def _kernel(alpha_ref, inputs_ref, targets_ref, pmask_ref,
            codes_ref, points_ref, mask_ref, negs_ref,
            syn0_ref, syn1_ref, syn1neg_ref,
            acc0h_ref, acc0n_ref, acc1_ref, accn_ref,
            *, L: int, K: int, use_hs: bool):
    """Vocab-major throughout: one-hots, scores and coefficient planes
    are [V, BLK], gathered rows are [Dp, BLK], and every per-pair
    quantity is a [1, BLK] ROW vector (a 1-D block is a layout Mosaic no
    longer takes, and a column vector would need a lane-to-sublane move
    per level).  Tables arrive bf16, padded to whole tiles; the spare
    last column of each accumulator carries its hit count."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        for ref in (acc0h_ref, acc0n_ref, acc1_ref, accn_ref):
            ref[...] = jnp.zeros_like(ref)

    bf = jnp.bfloat16
    alpha = alpha_ref[...]                                  # [1, 1]
    BLK = inputs_ref.shape[1]
    V0, Dp = syn0_ref.shape
    rows0 = (((0,), (0,)), ((), ()))            # table^T . plane

    def with_count(payload, count, axis):
        """``payload`` with its spare last row/column (``axis``)
        replaced by ``count`` (broadcast along the other axis)."""
        idx = lax.broadcasted_iota(jnp.int32, payload.shape, axis)
        return jnp.where(idx == Dp - 1, count, payload)

    oh0 = (lax.broadcasted_iota(jnp.int32, (V0, BLK), 0)
           == inputs_ref[...]).astype(bf)                   # [V0, BLK]
    l1 = lax.dot_general(syn0_ref[...], oh0, rows0,
                         preferred_element_type=jnp.float32)  # [Dp, BLK]
    l1bf = l1.astype(bf)
    # transposed in fp32 (the width Mosaic transposes natively)
    l1t = l1.T.astype(bf)                                   # [BLK, Dp]

    def objective(syn_ref, coeff_levels, n_levels):
        """Shared dense-scores core: all pair-row dots in one matmul,
        VPU level loop folds lr coefficients into G (and hit-masks into
        M), then two matmuls recover the input-side update and the
        output-side accumulator payload.

        ``coeff_levels(l) -> (rows, g_fn, hit)``: the level's partner
        rows [1, BLK], the map from the extracted dot products f
        [1, BLK] to the signed lr coefficient g, and the hit mask
        [1, BLK]."""
        v = syn_ref.shape[0]
        scores = jnp.dot(syn_ref[...], l1bf,
                         preferred_element_type=jnp.float32)   # [v, BLK]
        iota = lax.broadcasted_iota(jnp.int32, (v, BLK), 0)

        def level(l, carry):
            G, M = carry
            rows, g_fn, hit = coeff_levels(l)
            eq = iota == rows                               # [v, BLK]
            f = jnp.sum(jnp.where(eq, scores, 0.0), axis=0, keepdims=True)
            g = g_fn(f)                                     # [1, BLK] fp32
            G = G + jnp.where(eq, g, 0.0).astype(bf)
            M = M + jnp.where(eq, hit, 0.0).astype(bf)
            return G, M

        zero = jnp.zeros((v, BLK), bf)
        G, M = lax.fori_loop(0, n_levels, level, (zero, zero))
        neu1e = lax.dot_general(
            syn_ref[...], G, rows0,
            preferred_element_type=jnp.float32)             # [Dp, BLK]
        # output-side accumulator: [v, Dp] grad sums, hit counts in the
        # spare column
        dacc = jnp.dot(G, l1t, preferred_element_type=jnp.float32)
        cnt = jnp.sum(M.astype(jnp.float32), axis=1, keepdims=True)
        return neu1e, with_count(dacc, cnt, 1)

    def scatter0(acc_ref, neu1e, row_hit):
        """syn0 side: per-input-row update sums, each objective divided
        by its OWN count outside (matching the XLA path exactly)."""
        acc_ref[...] += jnp.dot(
            oh0, with_count(neu1e, row_hit, 0).T.astype(bf),
            preferred_element_type=jnp.float32)             # [V0, Dp]

    if use_hs:
        def hs_levels(l):
            level = pl.dslice(l, 1)
            code = codes_ref[level, :]                      # [1, BLK]
            m = mask_ref[level, :]
            return points_ref[level, :], (
                lambda f: (1.0 - code - jax.nn.sigmoid(f)) * alpha * m), m

        neu1e_hs, dacc1 = objective(syn1_ref, hs_levels, L)
        acc1_ref[...] += dacc1
        row_hs = (jnp.sum(mask_ref[...], axis=0, keepdims=True)
                  > 0).astype(jnp.float32)
        scatter0(acc0h_ref, neu1e_hs, row_hs)

    if K > 0:
        tgt = targets_ref[...]
        pmask = pmask_ref[...]

        def ng_levels(k):
            first = k == 0                      # level 0 is the target
            rows = jnp.where(
                first, tgt, negs_ref[pl.dslice(jnp.maximum(k - 1, 0), 1), :])
            label = jnp.where(first, 1.0, 0.0)
            valid = jnp.where(first | (rows != tgt), 1.0, 0.0) * pmask
            return rows, (lambda f: (label - jax.nn.sigmoid(f))
                          * alpha * valid), valid

        neu1e_ng, daccn = objective(syn1neg_ref, ng_levels, K + 1)
        accn_ref[...] += daccn
        scatter0(acc0n_ref, neu1e_ng, pmask)


@functools.partial(
    jax.jit, static_argnames=("use_hs", "negative", "block", "interpret"))
def fused_chunk_update(syn0: Array, syn1: Array, syn1neg: Array,
                       inputs: Array, targets: Array, codes: Array,
                       points: Array, mask: Array, negs: Array,
                       pmask: Array, alpha: Array,
                       *, use_hs: bool, negative: int,
                       block: int = 512, interpret: bool = False):
    """One training chunk through the VMEM-resident kernel.

    inputs/targets [B]; codes/points/mask [B, L]; negs [B, K] (already
    mapped through the unigram table); pmask [B] combined pad+window mask.
    Returns updated (syn0, syn1, syn1neg).
    """
    B = inputs.shape[0]
    L = codes.shape[1]
    K = negative
    BLK = min(block, B)
    NB = B // BLK
    assert NB * BLK == B, f"B={B} must be a multiple of block={BLK}"
    V0, D = syn0.shape
    Dp = _pad(D + 1, 128)

    def table(t):
        """bf16 and padded to whole tiles: the kernel reads bf16 (halves
        the VMEM footprint, no per-grid-step cast); the fp32 masters
        stay out here where the accumulator updates are applied."""
        return jnp.pad(t, ((0, _pad(t.shape[0], 128) - t.shape[0]),
                           (0, Dp - D))).astype(jnp.bfloat16)

    tables = [table(syn0), table(syn1), table(syn1neg)]
    codes = codes.astype(jnp.float32)
    mask = mask.astype(jnp.float32) * pmask[:, None]

    def full(shape):
        return pl.BlockSpec(shape, lambda i: (0, 0))

    def per_pair(n_rows):
        return pl.BlockSpec((n_rows, BLK), lambda i: (0, i))

    acc_shapes = [tables[0].shape, tables[0].shape,
                  tables[1].shape, tables[2].shape]
    acc0h, acc0n, acc1, accn = pl.pallas_call(
        functools.partial(_kernel, L=L, K=K, use_hs=use_hs),
        grid=(NB,),
        in_specs=[
            full((1, 1)),                                    # alpha
            per_pair(1), per_pair(1), per_pair(1),   # inputs targets pmask
            per_pair(L), per_pair(L), per_pair(L),   # codes^T points^T mask^T
            per_pair(max(K, 1)),                             # negs^T
        ] + [full(t.shape) for t in tables],
        out_specs=[full(sh) for sh in acc_shapes],
        out_shape=[jax.ShapeDtypeStruct(sh, jnp.float32)
                   for sh in acc_shapes],
        interpret=interpret,
        compiler_params=None if (interpret or pltpu is None) else
        pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(jnp.reshape(alpha, (1, 1)).astype(jnp.float32),
      inputs[None, :], targets[None, :], pmask[None, :],
      codes.T, points.T, mask.T,
      (negs.T if K > 0 else jnp.zeros((1, B), jnp.int32)),
      *tables)

    def mean_update(acc, n_rows):
        return acc[:n_rows, :D] / jnp.maximum(acc[:n_rows, Dp - 1:], 1.0)

    if use_hs:
        syn1 = syn1 + mean_update(acc1, syn1.shape[0])
    if K > 0:
        syn1neg = syn1neg + mean_update(accn, syn1neg.shape[0])
    return (syn0 + mean_update(acc0h, V0) + mean_update(acc0n, V0),
            syn1, syn1neg)


_PROBE_CACHE: dict = {}


def probe_compile(block: int, use_hs: bool, negative: int,
                  vocab_size: int = 128, dim: int = 8,
                  hs_depth: int = 4, timeout_s: float = 240.0
                  ) -> Optional[str]:
    """One real compile at the given statics AND the caller's actual
    table shapes: None when Mosaic took it, else its message
    (``kernel_select.probe_mosaic``).  ``auto`` selection on hardware
    goes through here (``kernel_select.choose_kernel``) so a refusal
    degrades to the XLA path, and says so, instead of crashing fit()
    (explicit kernel='pallas' still surfaces the error).  Mosaic
    acceptance and VMEM fit depend on (vocab, dim, Huffman depth), not
    just the block statics, so the probe runs at the production shapes
    and is cached per the full key."""
    from deeplearning4j_tpu.ops.kernel_select import probe_mosaic

    key = (block, use_hs, negative, vocab_size, dim, hs_depth)
    if key not in _PROBE_CACHE:
        def compile_once():
            V, D, L = vocab_size, dim, max(hs_depth, 1)
            z = jnp.zeros
            out = fused_chunk_update(
                z((V, D)), z((V, D)) if use_hs else z((1, D)),
                z((V, D)) if negative else z((1, D)),
                z((block,), jnp.int32), z((block,), jnp.int32),
                z((block, L)), z((block, L), jnp.int32), z((block, L)),
                z((block, max(negative, 1)), jnp.int32),
                jnp.ones((block,)), jnp.float32(0.01), use_hs=use_hs,
                negative=negative, block=block, interpret=False)
            float(out[0][0, 0])

        _PROBE_CACHE[key] = probe_mosaic(compile_once, "word2vec",
                                         timeout_s)
    return _PROBE_CACHE[key]
