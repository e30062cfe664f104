"""Flash-attention block-size tuner — run on real TPU hardware.

The kernels default to (block_q, block_k) = (128, 128); the best tiling
depends on the chip generation (VMEM size / MXU shape) and sequence
length.  This sweeps the grid at the bench shapes and prints one JSON
line per (T, bq, bk) plus the winner per T, so the defaults can be
retuned from data rather than guesswork.

Usage:  python tools/tune_flash.py [T ...]     (default: 8192 16384 32768)
"""
import itertools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BLOCKS = (128, 256, 512, 1024)
HEADS, HEAD_DIM, BATCH = 12, 64, 1
STEPS, WARMUP = 8, 2


def time_config(T: int, bq: int, bk: int) -> float | None:
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops import pallas_attention as pa

    ks = jax.random.split(jax.random.key(0), 3)
    shape = (BATCH, T, HEADS, HEAD_DIM)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks)

    def fwd_bwd(q, k, v):
        def loss(q, k, v):
            return jnp.sum(pa.flash_attention(
                q, k, v, None, True, block_q=bq, block_k=bk,
                interpret=False).astype(jnp.float32))
        l, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        return l, grads

    try:
        f = jax.jit(fwd_bwd)
        (l, _) = f(q, k, v)
        float(l)                                  # compile + warm
        for _ in range(WARMUP):
            l, _ = f(q, k, v)
        float(l)
        t0 = time.perf_counter()
        for _ in range(STEPS):
            l, g = f(q, k, v)
        float(l)
        float(jnp.ravel(g[0])[0])                 # true device sync
        return (time.perf_counter() - t0) / STEPS
    except Exception as e:                        # Mosaic reject / OOM
        print(json.dumps({"T": T, "bq": bq, "bk": bk,
                          "error": repr(e)[:160]}))
        return None


def main() -> None:
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("tune_flash: no accelerator — the tuner times kernels "
                 "and is chip-only")
    seqs = [int(a) for a in sys.argv[1:]] or [8192, 16384, 32768]
    for T in seqs:
        best = None
        for bq, bk in itertools.product(BLOCKS, BLOCKS):
            # flash_attention clamps to the largest divisor of T
            # (_pick_block); only run configs whose tiling is what the
            # label says, or the winner records a tiling never executed
            if T % bq != 0 or T % bk != 0:
                continue
            dt = time_config(T, bq, bk)
            if dt is None:
                continue
            toks = BATCH * T / dt
            print(json.dumps({"T": T, "bq": bq, "bk": bk,
                              "step_ms": round(dt * 1e3, 2),
                              "tokens_per_sec": round(toks, 0)}))
            if best is None or dt < best[0]:
                best = (dt, bq, bk)
        if best:
            print(json.dumps({"T": T, "best_bq": best[1],
                              "best_bk": best[2],
                              "best_step_ms": round(best[0] * 1e3, 2)}))


if __name__ == "__main__":
    main()
