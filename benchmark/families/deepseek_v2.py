"""Everything the ``closed_loop_family`` driver needs to know of the
DeepSeek-V2 family, for a configuration file that names it
(``"family": "deepseek_v2"``): weights from the seed, the program's
config object, the engine's arguments, what the algorithm NEEDS in
operations and bytes, and the call of the plain reference.

The configuration file holds the published ``config.json`` keys; the
cut (``layers``, the experts held, the vocabulary's slice, the longest
context) is in the keys its ``reduced`` lists.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.lib import spec
from benchmark.lib.weights import seed_key

EXPERT_MATRICES = 3          # gate, up, down
DTYPE_BYTES = 2              # bfloat16: weights and cached rows


def sizes(c: Dict[str, Any]) -> Dict[str, int]:
    return {"H": c["hidden_size"], "L": c["layers"],
            "NH": c["num_attention_heads"], "Rq": c["q_lora_rank"],
            "R": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
            "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
            "I": c["intermediate_size"], "F": c["moe_intermediate_size"],
            "E": c["n_routed_experts"], "E_all": c["router_width"],
            "Fs": c["moe_intermediate_size"] * c["n_shared_experts"],
            "K": c["num_experts_per_tok"], "V": c["vocab_size"],
            "dense": c["first_k_dense_replace"]}


def held(c: Dict[str, Any]) -> tuple:
    return (int(c["held_experts_first"]), int(c["n_routed_experts"]))


# -- weights ---------------------------------------------------------------

def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    """The tree the program and the reference read (the reference's
    docstring lists it)."""
    s = sizes(c)
    H, NH = s["H"], s["NH"]

    def ffn(width):
        return {"w_gate": (H, width), "w_up": (H, width),
                "w_down": (width, H)}

    layers = []
    for i in range(s["L"]):
        layer: Dict[str, Any] = {
            "attn_norm": (H,), "ffn_norm": (H,),
            "attn": {"w_dq": (H, s["Rq"]), "q_norm": (s["Rq"],),
                     "w_uq": (s["Rq"], NH, s["dn"] + s["dr"]),
                     "w_dkv": (H, s["R"] + s["dr"]), "kv_norm": (s["R"],),
                     "w_ukv": (s["R"], NH, s["dn"] + s["dv"]),
                     "w_o": (NH, s["dv"], H)}}
        if i < s["dense"]:
            layer["mlp"] = ffn(s["I"])
        else:
            layer["moe"] = {
                "router": (H, s["E_all"]),
                "experts": {"w_gate": (s["E"], H, s["F"]),
                            "w_up": (s["E"], H, s["F"]),
                            "w_down": (s["E"], s["F"], H)},
                "shared": ffn(s["Fs"])}
        layers.append(layer)
    return {"embed": (s["V"], H), "layers": layers, "final_norm": (H,),
            "head": (H, s["V"])}


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_params(c: Dict[str, Any], seed: int) -> Any:
    """Every leaf from ``--seed`` on the device, a jitted call a leaf
    (so that no more than one leaf's float32 draw is ever live beside
    the tree): N(0, ``init_std``), norm gains 1 + that, rounded to
    bfloat16."""
    import jax
    import jax.numpy as jnp

    std = float(c.get("init_std", 0.02))

    def draw(key, shape):
        w = std * jax.random.normal(key, shape, jnp.float32)
        return (1.0 + w if len(shape) == 1 else w).astype(jnp.bfloat16)

    draw = jax.jit(draw, static_argnums=(1,))
    shapes, treedef = jax.tree.flatten(param_shapes(c), is_leaf=_is_shape)
    keys = jax.random.split(seed_key(seed, stream=0), len(shapes))
    return jax.tree.unflatten(treedef, [draw(k, s)
                                        for k, s in zip(keys, shapes)])


def program_config(c: Dict[str, Any]):
    """The one place the benchmark names the program's model family."""
    from deeplearning4j_tpu.models import deepseek_v2 as ds

    r = c["rope_scaling"]
    return ds.DeepSeekV2Config(
        vocab_size=c["vocab_size"], max_len=c["max_position_embeddings"],
        hidden=c["hidden_size"], n_layers=c["layers"],
        n_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        intermediate_size=c["intermediate_size"],
        moe_intermediate_size=c["moe_intermediate_size"],
        first_k_dense_replace=c["first_k_dense_replace"],
        n_routed_experts=c["router_width"],
        n_shared_experts=c["n_shared_experts"],
        num_experts_per_tok=c["num_experts_per_tok"], n_group=c["n_group"],
        topk_group=c["topk_group"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        rms_norm_eps=float(c["rms_norm_eps"]),
        rope_theta=float(c["rope_theta"]), rope_factor=float(r["factor"]),
        rope_original_max_len=r["original_max_position_embeddings"],
        rope_beta_fast=float(r["beta_fast"]),
        rope_beta_slow=float(r["beta_slow"]), rope_mscale=r["mscale"],
        rope_mscale_all_dim=r["mscale_all_dim"],
        compute_dtype=c["compute_dtype"], held_experts=held(c))


def engine_kwargs(c: Dict[str, Any], tr: Dict[str, Any]) -> Dict[str, Any]:
    """``DecodeEngine``'s arguments beside config, weights and slots."""
    out: Dict[str, Any] = {"paged": True}
    if tr.get("buckets"):
        out["buckets"] = [int(b) for b in tr["buckets"]]
    if tr.get("n_pages"):
        out["n_pages"] = int(tr["n_pages"])
    return out


def vocab(c: Dict[str, Any]) -> int:
    return int(c["vocab_size"])


# -- what the algorithm needs ----------------------------------------------

def attention_params(c: Dict[str, Any]) -> int:
    s = sizes(c)
    return (s["H"] * s["Rq"] + s["Rq"] * s["NH"] * (s["dn"] + s["dr"])
            + s["H"] * (s["R"] + s["dr"])
            + s["R"] * s["NH"] * (s["dn"] + s["dv"])
            + s["NH"] * s["dv"] * s["H"])


def expert_params(c: Dict[str, Any]) -> int:
    s = sizes(c)
    return EXPERT_MATRICES * s["H"] * s["F"]


def nonrouted_params(c: Dict[str, Any]) -> int:
    """Parameters in a matrix product for EVERY token: attention of every
    layer, the dense layers' feed-forward, each expert layer's router
    and shared experts, and the head's slice.  The embedding is a
    look-up; norms are not products."""
    s = sizes(c)
    moe_layers = s["L"] - s["dense"]
    return (s["L"] * attention_params(c)
            + s["dense"] * EXPERT_MATRICES * s["H"] * s["I"]
            + moe_layers * (s["H"] * s["E_all"]
                            + EXPERT_MATRICES * s["H"] * s["Fs"])
            + s["H"] * s["V"])


def total_params(c: Dict[str, Any]) -> int:
    """Every element of the tree."""
    s = sizes(c)
    moe_layers = s["L"] - s["dense"]
    norms = s["L"] * (2 * s["H"] + s["Rq"] + s["R"]) + s["H"]
    return (nonrouted_params(c) + s["V"] * s["H"] + norms
            + moe_layers * s["E"] * expert_params(c))


def attention_flops_per_position(c: Dict[str, Any], folded: bool) -> float:
    """FLOPs one query token spends on ONE attended position, all
    layers.  Expanded: q.k over nope + rope and p.v over v, a head.
    Folded (what a decode step needs: expanding every cached row again
    for one query would cost kv_lora x NH x (nope + v) a position): the
    score over the cached row, latent + rope wide, and the weighted sum
    over the latent, a head."""
    s = sizes(c)
    per_head = (2.0 * (s["R"] + s["dr"]) + 2.0 * s["R"] if folded
                else 2.0 * (s["dn"] + s["dr"]) + 2.0 * s["dv"])
    return s["L"] * s["NH"] * per_head


def forward_flops_token(c: Dict[str, Any], context: float,
                        held_per_token_layer: float, folded: bool) -> float:
    """Forward FLOPs of one token attending ``context`` positions: 2 a
    parameter in a product — the non-routed ones and, an expert layer,
    ``held_per_token_layer`` experts held here (of the token's 6 the
    share that fell on this rank; the others are other ranks' work)."""
    s = sizes(c)
    moe_layers = s["L"] - s["dense"]
    return (2.0 * nonrouted_params(c)
            + 2.0 * moe_layers * held_per_token_layer * expert_params(c)
            + attention_flops_per_position(c, folded) * context)


def sequence_forward_flops(c: Dict[str, Any], n: int,
                           held_per_token_layer: float) -> float:
    """A prompt of ``n`` tokens at positions 0 .. n-1, expanded form (a
    prefill makes k and v once for its own rows: that product is in the
    2 a parameter)."""
    ctx_sum = n * (n + 1) / 2.0
    return (n * forward_flops_token(c, 0.0, held_per_token_layer, False)
            + attention_flops_per_position(c, False) * ctx_sum)


def cache_bytes_row(c: Dict[str, Any]) -> int:
    """Bytes of one cached position, every layer: 1,152 B a layer."""
    s = sizes(c)
    return s["L"] * (s["R"] + s["dr"]) * DTYPE_BYTES


def expert_bytes(c: Dict[str, Any]) -> int:
    """One expert's three matrices: 47.19 MB."""
    return expert_params(c) * DTYPE_BYTES


def decode_needed(c: Dict[str, Any], contexts_sum: float, n_tokens: int,
                  dispatches: float, expert_hits: float,
                  assignments_held: float) -> Dict[str, float]:
    """What decoding ``n_tokens`` tokens in ``dispatches`` dispatches
    needs.  FLOPs: the tokens' own (folded attention).  Bytes: one pass
    over the non-routed weights a dispatch, each distinct held expert a
    dispatch touched once (``expert_hits``, the program's count summed
    over layers and dispatches), and each token's live cached rows."""
    s = sizes(c)
    flops = (2.0 * nonrouted_params(c) * n_tokens
             + 2.0 * assignments_held * expert_params(c)
             + attention_flops_per_position(c, True) * contexts_sum)
    nbytes = (dispatches * nonrouted_params(c) * DTYPE_BYTES
              + expert_hits * expert_bytes(c)
              + contexts_sum * cache_bytes_row(c))
    return {"flops": flops, "bytes": nbytes,
            "expert_bytes": expert_hits * expert_bytes(c)
            + 2.0 * assignments_held * s["H"] * 4.0}


# -- the reference -----------------------------------------------------------

def reference_logits(c: Dict[str, Any], params: Any, rows: List[np.ndarray],
                     tr: Dict[str, Any], precision: str = "f32"
                     ) -> List[np.ndarray]:
    """Reference logits [len(row), V] for each row, the rows padded to
    one length (a multiple of ``reference_pad``; causal: the padding is
    never attended) and taken ``reference_rows_per_block`` at a time."""
    import jax.numpy as jnp

    ref = spec.reference(c["reference"])
    pad = int(tr.get("reference_pad", 256))
    step = int(tr["reference_rows_per_block"])
    T = -(-max(len(r) for r in rows) // pad) * pad
    out: List[np.ndarray] = []
    for lo in range(0, len(rows), step):
        block = rows[lo:lo + step]
        ids = np.zeros((len(block), T), np.int32)
        for i, r in enumerate(block):
            ids[i, :len(r)] = r
        logits = np.asarray(ref.logits(params, jnp.asarray(ids), config=c,
                                       held=held(c), precision=precision))
        out.extend(logits[i, :len(r)] for i, r in enumerate(block))
    return out
