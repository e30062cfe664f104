"""Weights from ``--seed``: one jitted call on the device, in the type
the program holds them in (float32 masters; both spines cast to the
compute type themselves).  The tree is laid out as both spines and the
plain reference read it (``reference/gpt2_postln.py`` lists the leaves).

Biases and LayerNorm offsets are drawn too, not left at the zeros a
fresh model starts from: a path that dropped a bias would otherwise
pass every comparison.
"""

from __future__ import annotations

from typing import Any, Dict

import numpy as np


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole number (the driver's seeds pass 2**31),
    ``stream`` separating the uses of one seed."""
    import jax

    words = np.random.SeedSequence([int(seed), int(stream)]
                                   ).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def make_params(config: Dict[str, Any], seed: int) -> Any:
    import jax
    import jax.numpy as jnp

    V, T = config["vocab_size"], config["n_positions"]
    H, L, NH = config["n_embd"], config["n_layer"], config["n_head"]
    F, D = config["n_inner"], config["n_embd"] // config["n_head"]
    shapes = {
        "embed": {"tok": (V, H), "pos": (T, H), "type": (1, H),
                  "ln_g": (H,), "ln_b": (H,)},
        "blocks": {"wq": (L, H, NH, D), "wk": (L, H, NH, D),
                   "wv": (L, H, NH, D), "wo": (L, NH, D, H),
                   "bq": (L, NH, D), "bk": (L, NH, D), "bv": (L, NH, D),
                   "bo": (L, H), "ln1_g": (L, H), "ln1_b": (L, H),
                   "w1": (L, H, F), "b1": (L, F), "w2": (L, F, H),
                   "b2": (L, H), "ln2_g": (L, H), "ln2_b": (L, H)},
    }
    flat = [(g, n) for g in shapes for n in shapes[g]]
    # every leaf N(0, 0.02), GPT-2's own init, but the blocks' matrices
    # where the configuration says otherwise (the rehearsal preset does:
    # at toy widths 0.02 leaves the layers so weak that every greedy
    # token copies the last one, and no comparison could tell)
    matrix_std = float(config.get("init_matrix_std", 0.02))

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(flat))
        out: Dict[str, Dict[str, Any]] = {g: {} for g in shapes}
        for k, (g, n) in zip(keys, flat):
            std = matrix_std if g == "blocks" and n[0] == "w" else 0.02
            w = std * jax.random.normal(k, shapes[g][n], jnp.float32)
            out[g][n] = 1.0 + w if n.endswith("_g") else w
        return out

    return build(seed_key(seed, stream=0))


def program_config(config: Dict[str, Any]):
    """The program's own config object for these sizes: the one place
    the benchmark names the program's model family."""
    import dataclasses

    from deeplearning4j_tpu.models import gpt

    cfg = gpt.gpt_config(vocab_size=config["vocab_size"],
                         max_len=config["n_positions"],
                         hidden=config["n_embd"],
                         n_layers=config["n_layer"],
                         n_heads=config["n_head"])
    if cfg.ffn_dim != config["n_inner"]:
        raise ValueError(f"n_inner {config['n_inner']} is not the program's "
                         f"ffn width {cfg.ffn_dim} for these sizes")
    return dataclasses.replace(
        cfg, dropout=float(config["dropout"]),
        layer_norm_eps=float(config["layer_norm_epsilon"]),
        compute_dtype=config["compute_dtype"])
