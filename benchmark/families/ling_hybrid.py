"""Everything the ``closed_loop_family`` driver needs to know of the
Ling-3.0-flash family, for a configuration file that names it
(``"family": "ling_hybrid"``): weights from the seed, the program's
config object, the engine's arguments, what the algorithm NEEDS in
operations and bytes, and the call of the plain reference.

The configuration file holds the published ``config.json`` keys; the
rank's cut (``layers``: the published indices kept, ``num_experts``
held, the vocabulary's slice, the longest context, no MTP block) is in
the keys its ``reduced`` lists.

What is NEEDED of a decode step: one pass over the non-routed weights a
dispatch, each held expert a dispatch touched once (the program's
count), the live latent rows of the MLA layer, and every running slot's
KDA state read and written once (``kda_step_bytes``): the state does not
grow with the context, the latent rows do.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.families.mellum import Tail
from benchmark.lib import spec
from benchmark.lib.weights import seed_key

EXPERT_MATRICES = 3          # gate, up, down
DTYPE_BYTES = 2              # bfloat16: weights, latent rows, conv rows
STATE_BYTES = 4              # float32: the KDA state


def sizes(c: Dict[str, Any]) -> Dict[str, int]:
    ids = list(c["layers"])
    mla = [(i + 1) % c["layer_group_size"] == 0 for i in ids]
    dense = sum(i < c["first_k_dense_replace"] for i in ids)
    return {"H": c["hidden_size"], "L": len(ids), "ids": ids,
            "NH": c["num_attention_heads"], "D": c["head_dim"],
            "K": c["short_conv_kernel_size"], "R": c["kv_lora_rank"],
            "dn": c["qk_nope_head_dim"], "dr": c["qk_rope_head_dim"],
            "dv": c["v_head_dim"], "I": c["intermediate_size"],
            "F": c["moe_intermediate_size"],
            "Fs": c["moe_shared_expert_intermediate_size"],
            "E": c["num_experts"], "E_all": c["router_width"],
            "k": c["num_experts_per_tok"], "V": c["vocab_size"],
            "dense": dense, "sparse": len(ids) - dense,
            "L_mla": sum(mla), "L_kda": len(ids) - sum(mla)}


def held(c: Dict[str, Any]) -> tuple:
    return (int(c["held_experts_first"]), int(c["num_experts"]))


def is_mla(c: Dict[str, Any], i: int) -> bool:
    return (i + 1) % c["layer_group_size"] == 0


# -- weights ---------------------------------------------------------------

def param_shapes(c: Dict[str, Any]) -> Dict[str, Any]:
    """The tree the program and the reference read (the reference's
    docstring lists it)."""
    s = sizes(c)
    H, NH, D = s["H"], s["NH"], s["D"]
    HD = NH * D

    def ffn(width):
        return {"w_gate": (H, width), "w_up": (H, width),
                "w_down": (width, H)}

    layers = []
    for i in s["ids"]:
        if is_mla(c, i):
            attn = {"w_q": (H, NH, s["dn"] + s["dr"]),
                    "w_dkv": (H, s["R"] + s["dr"]), "kv_norm": (s["R"],),
                    "w_ukv": (s["R"], NH, s["dn"] + s["dv"]),
                    "w_ga": (H, NH), "w_o": (NH, s["dv"], H)}
        else:
            attn = {"w_q": (H, HD), "w_k": (H, HD), "w_v": (H, HD),
                    "conv": (s["K"], 3 * HD), "w_f": (H, HD),
                    "A_log": (NH,), "dt_bias": (HD,), "w_b": (H, NH),
                    "w_g": (H, HD), "o_norm": (D,), "w_o": (HD, H)}
        layer: Dict[str, Any] = {"attn_norm": (H,), "ffn_norm": (H,),
                                 "attn": attn}
        if i < c["first_k_dense_replace"]:
            layer["mlp"] = ffn(s["I"])
        else:
            layer["moe"] = {
                "router": (H, s["E_all"]), "bias": (s["E_all"],),
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
    the tree): N(0, ``init_std``), the selection bias, ``A_log`` and
    ``dt_bias`` too, norm gains 1 + that, rounded to bfloat16."""
    import jax
    import jax.numpy as jnp

    std = float(c.get("init_std", 0.02))

    def draw(key, shape, gain):
        w = std * jax.random.normal(key, shape, jnp.float32)
        return (1.0 + w if gain else w).astype(jnp.bfloat16)

    draw = jax.jit(draw, static_argnums=(1, 2))
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(c), is_leaf=_is_shape)
    keys = jax.random.split(seed_key(seed, stream=0), len(paths))
    return jax.tree.unflatten(treedef, [
        draw(k, shape, str(path[-1].key).endswith("norm"))
        for k, (path, shape) in zip(keys, paths)])


def program_config(c: Dict[str, Any]):
    """The one place the benchmark names the program's model family."""
    from deeplearning4j_tpu.models import ling_hybrid as lh

    s = sizes(c)
    if (c["scoring_func"] != "sigmoid" or not c["norm_topk_prob"]
            or c["topk_method"] != "noaux_tc" or c["q_lora_rank"] is not None
            or not c["rope_interleave"] or c["rope_scaling"] is not None
            or c["num_nextn_predict_layers"]
            or c["gated_attention_proj_granularity_type"] != "head_wise"):
        raise ValueError("a router, a query path, a rotary table, a gate "
                         "or an MTP block the program has no code for")
    return lh.LingHybridConfig(
        vocab_size=s["V"], max_len=c["max_position_embeddings"],
        hidden=s["H"], layer_ids=tuple(s["ids"]),
        layer_group_size=c["layer_group_size"], n_heads=s["NH"],
        head_dim=s["D"], short_conv_kernel_size=s["K"],
        kda_lower_bound=float(c["kda_lower_bound"]),
        kv_lora_rank=s["R"], qk_nope_head_dim=s["dn"],
        qk_rope_head_dim=s["dr"], v_head_dim=s["dv"],
        rope_theta=float(c["rope_theta"]), intermediate_size=s["I"],
        moe_intermediate_size=s["F"],
        moe_shared_expert_intermediate_size=s["Fs"],
        first_k_dense_replace=c["first_k_dense_replace"],
        num_experts=s["E_all"], num_experts_per_tok=s["k"],
        n_group=c["n_group"], topk_group=c["topk_group"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        expert_swiglu_limits=tuple(float(c["expert_swiglu_limit_list"][i])
                                   for i in s["ids"]),
        shared_swiglu_limits=tuple(
            float(c["share_expert_swiglu_limit_list"][i]) for i in s["ids"]),
        rms_norm_eps=float(c["rms_norm_eps"]), held_experts=held(c),
        compute_dtype=c["compute_dtype"])


def engine_kwargs(c: Dict[str, Any], tr: Dict[str, Any]) -> Dict[str, Any]:
    """``DecodeEngine``'s arguments beside config, weights and slots."""
    out: Dict[str, Any] = {"prefill_chunk": int(tr["prefill_chunk"])}
    if tr.get("buckets"):
        out["buckets"] = [int(b) for b in tr["buckets"]]
    if tr.get("n_pages"):
        out["n_pages"] = int(tr["n_pages"])
    return out


def vocab(c: Dict[str, Any]) -> int:
    return int(c["vocab_size"])


# -- what the algorithm needs ----------------------------------------------

def kda_attention_params(c: Dict[str, Any]) -> int:
    """Every element of a KDA layer's attention: 63,049,888."""
    s = sizes(c)
    HD = s["NH"] * s["D"]
    return (6 * s["H"] * HD + s["H"] * s["NH"] + s["K"] * 3 * HD
            + s["NH"] + HD + s["D"])


def mla_attention_params(c: Dict[str, Any]) -> int:
    """Every element of the MLA layer's attention: 31,965,696."""
    s = sizes(c)
    return (s["H"] * s["NH"] * (s["dn"] + s["dr"])
            + s["H"] * (s["R"] + s["dr"]) + s["R"]
            + s["R"] * s["NH"] * (s["dn"] + s["dv"]) + s["H"] * s["NH"]
            + s["NH"] * s["dv"] * s["H"])


def expert_params(c: Dict[str, Any]) -> int:
    s = sizes(c)
    return EXPERT_MATRICES * s["H"] * s["F"]


def layer_params(c: Dict[str, Any], i: int) -> int:
    """Every element of published layer ``i`` as this rank holds it."""
    s = sizes(c)
    attn = mla_attention_params(c) if is_mla(c, i) else kda_attention_params(c)
    if i < c["first_k_dense_replace"]:
        ffn = EXPERT_MATRICES * s["H"] * s["I"]
    else:
        ffn = (s["H"] * s["E_all"] + s["E_all"]
               + EXPERT_MATRICES * s["H"] * s["Fs"]
               + s["E"] * expert_params(c))
    return attn + ffn + 2 * s["H"]


def total_params(c: Dict[str, Any]) -> int:
    """Every element of the tree: 5,231.9 M at the cell's cut."""
    s = sizes(c)
    return (sum(layer_params(c, i) for i in s["ids"])
            + 2 * s["V"] * s["H"] + s["H"])


def nonrouted_params(c: Dict[str, Any]) -> int:
    """Parameters in a matrix product for EVERY token: attention of every
    layer, the dense layer's feed-forward, each expert layer's router
    and shared expert, and the head's slice.  The embedding is a
    look-up; norms, gains and the short convolution are not products."""
    s = sizes(c)
    HD = s["NH"] * s["D"]
    kda = 6 * s["H"] * HD + s["H"] * s["NH"]
    return (s["L_kda"] * kda + s["L_mla"] * mla_attention_params(c)
            + s["dense"] * EXPERT_MATRICES * s["H"] * s["I"]
            + s["sparse"] * (s["H"] * s["E_all"]
                             + EXPERT_MATRICES * s["H"] * s["Fs"])
            + s["H"] * s["V"])


def kda_flops_token(c: Dict[str, Any]) -> float:
    """FLOPs of the state products of one token, every KDA layer: S^T
    (exp(g) k), S^T q and the rank-one update, 2 D^2 each a head."""
    s = sizes(c)
    return 6.0 * s["L_kda"] * s["NH"] * s["D"] * s["D"]


def attention_flops_per_position(c: Dict[str, Any], folded: bool) -> float:
    """FLOPs one query token spends on ONE attended position of the MLA
    layers (as ``families/deepseek_v2.py``): folded, the score over the
    cached row and the weighted sum over the latent; expanded, q . k over
    nope + rope and p . v, a head."""
    s = sizes(c)
    per_head = (2.0 * (s["R"] + s["dr"]) + 2.0 * s["R"] if folded
                else 2.0 * (s["dn"] + s["dr"]) + 2.0 * s["dv"])
    return s["L_mla"] * s["NH"] * per_head


def forward_flops_token(c: Dict[str, Any], context: float,
                        held_per_token_layer: float, folded: bool) -> float:
    """Forward FLOPs of one token attending ``context`` positions in the
    MLA layer: 2 a parameter in a product (the non-routed ones and, an
    expert layer, ``held_per_token_layer`` experts held here), the KDA
    state's products, the MLA scores."""
    s = sizes(c)
    return (2.0 * nonrouted_params(c)
            + 2.0 * s["sparse"] * held_per_token_layer * expert_params(c)
            + kda_flops_token(c)
            + attention_flops_per_position(c, folded) * context)


def sequence_forward_flops(c: Dict[str, Any], n: int,
                           held_per_token_layer: float) -> float:
    """A prompt of ``n`` tokens at positions 0 .. n-1, expanded MLA."""
    return (n * forward_flops_token(c, 0.0, held_per_token_layer, False)
            + attention_flops_per_position(c, False) * n * (n + 1) / 2.0)


def cache_bytes_row(c: Dict[str, Any]) -> int:
    """Bytes of one cached position, every MLA layer: 1,152 B a layer."""
    s = sizes(c)
    return s["L_mla"] * (s["R"] + s["dr"]) * DTYPE_BYTES


def expert_bytes(c: Dict[str, Any]) -> int:
    """One expert's three matrices: 11.80 MB."""
    return expert_params(c) * DTYPE_BYTES


def kda_state_bytes(c: Dict[str, Any]) -> int:
    """What ONE slot holds over the KDA layers: S [NH, D, D] float32 and
    the convolution's last K - 1 input rows [K - 1, 3 NH D] bfloat16 a
    layer: 6 x (2,097,152 + 73,728) = 13,025,280 B."""
    s = sizes(c)
    return s["L_kda"] * (s["NH"] * s["D"] * s["D"] * STATE_BYTES
                         + (s["K"] - 1) * 3 * s["NH"] * s["D"] * DTYPE_BYTES)


def kda_step_bytes(c: Dict[str, Any]) -> int:
    """A slot's KDA state read and written by one decode step."""
    return 2 * kda_state_bytes(c)


def kda_matrix_step_bytes(c: Dict[str, Any]) -> int:
    """The part of it the ``state_update`` scope moves: S [NH, D, D]
    float32 read and written, every KDA layer: 25,165,824 B (what
    ``kda_roofline`` counts a slot step)."""
    s = sizes(c)
    return 2 * s["L_kda"] * s["NH"] * s["D"] * s["D"] * STATE_BYTES


def decode_needed(c: Dict[str, Any], contexts_sum: float, n_tokens: int,
                  dispatches: float, expert_hits: float,
                  assignments_held: float) -> Dict[str, float]:
    """What decoding ``n_tokens`` tokens in ``dispatches`` dispatches
    needs.  FLOPs: the tokens' own (folded MLA).  Bytes: one pass over
    the non-routed weights a dispatch, each distinct held expert a
    dispatch touched once (``expert_hits``, the program's count summed
    over layers and dispatches), each token's live latent rows, and each
    token's slot state read and written."""
    s = sizes(c)
    flops = (2.0 * nonrouted_params(c) * n_tokens
             + 2.0 * assignments_held * expert_params(c)
             + kda_flops_token(c) * n_tokens
             + attention_flops_per_position(c, True) * contexts_sum)
    nbytes = (dispatches * nonrouted_params(c) * DTYPE_BYTES
              + expert_hits * expert_bytes(c)
              + contexts_sum * cache_bytes_row(c)
              + n_tokens * kda_step_bytes(c))
    return {"flops": flops, "bytes": nbytes,
            "expert_bytes": expert_hits * expert_bytes(c)
            + 2.0 * assignments_held * s["H"] * 4.0}


# -- the reference -----------------------------------------------------------

def reference_logits(c: Dict[str, Any], params: Any, rows: List[np.ndarray],
                     tr: Dict[str, Any], precision: str = "f32"
                     ) -> List[Tail]:
    """Reference logits at the last ``output_len.max`` positions of each
    row (a row is a prompt and its served tokens but the last), the rows
    padded to one length (a multiple of ``reference_pad``; causal: the
    padding is never attended) and taken ``reference_rows_per_block`` at
    a time."""
    import jax.numpy as jnp

    ref = spec.reference(c["reference"])
    pad = int(tr.get("reference_pad", 256))
    step = int(tr["reference_rows_per_block"])
    last = int(tr["output_len"]["max"])
    out: List[Tail] = []
    for lo in range(0, len(rows), step):
        block = rows[lo:lo + step]
        T = -(-max(len(r) for r in block) // pad) * pad
        ids = np.zeros((len(block), T), np.int32)
        for i, r in enumerate(block):
            ids[i, :len(r)] = r
        at = np.stack([np.arange(len(r) - last, len(r)) for r in block])
        logits = np.asarray(ref.logits(
            params, jnp.asarray(ids), config=c, held=held(c),
            precision=precision, at=np.maximum(at, 0),
            q_block=int(tr.get("reference_q_block", 256))))
        out.extend(Tail(len(r) - last, logits[i])
                   for i, r in enumerate(block))
    return out
