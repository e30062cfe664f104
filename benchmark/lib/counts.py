"""Operations and bytes the GPT block NEEDS, from shapes alone.  The
yardstick of every ``*_mfu`` and ``*_roofline``: what the algorithm
requires, not what an implementation happens to execute (recomputed
forward passes, gathered page views, float32 masters are not counted).

All counts are for a config dict with the published keys
(``n_embd`` H, ``n_layer`` L, ``n_inner`` F, ``vocab_size`` V).
"""

from __future__ import annotations

from typing import Any, Dict


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters that sit in a matrix product for every token: the four
    attention projections and the two MLP matrices of each layer, and
    the tied readout.  Embedding look-ups, biases and LayerNorms are not
    matrix products."""
    H, L, F, V = c["n_embd"], c["n_layer"], c["n_inner"], c["vocab_size"]
    return L * (4 * H * H + 2 * H * F) + V * H


def total_params(c: Dict[str, Any]) -> int:
    """Every element of the parameter tree (the unread [1, H] segment
    embedding included)."""
    H, L, F, V, T = (c["n_embd"], c["n_layer"], c["n_inner"],
                     c["vocab_size"], c["n_positions"])
    embed = V * H + T * H + H + 2 * H
    block = 4 * H * H + 4 * H + 2 * H * F + F + H + 4 * H
    return embed + L * block


def forward_flops_token(c: Dict[str, Any], context: float) -> float:
    """Forward FLOPs of one token that attends ``context`` positions
    (itself included): 2 per parameter in a product, and 2 (q k^T) + 2
    (p v) per attended position, head width and layer."""
    return 2.0 * matmul_params(c) + 4.0 * c["n_layer"] * c["n_embd"] * context


def train_flops_token(c: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward FLOPs per trained token of a causal sequence of
    ``seq_len``: three times the forward, the mean causal context being
    (seq_len + 1) / 2.  Recomputation is not counted."""
    return 3.0 * forward_flops_token(c, (seq_len + 1) / 2.0)


def sequence_forward_flops(c: Dict[str, Any], start: int, n: int) -> float:
    """Forward FLOPs of ``n`` consecutive tokens at positions
    ``start .. start + n - 1`` (contexts ``start + 1 .. start + n``)."""
    ctx_sum = n * start + n * (n + 1) / 2.0
    return (2.0 * matmul_params(c) * n
            + 4.0 * c["n_layer"] * c["n_embd"] * ctx_sum)


def kv_bytes_row(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Bytes of one cached position: K and V, every layer."""
    return 2 * c["n_layer"] * c["n_embd"] * dtype_bytes


def weight_bytes(c: Dict[str, Any], dtype_bytes: int = 2) -> int:
    """Bytes of the weights a decode step has to stream at the compute
    type: the matrices of every layer and the readout."""
    return matmul_params(c) * dtype_bytes


def decode_needed(c: Dict[str, Any], contexts_sum: float, n_tokens: int,
                  n_slots: int) -> Dict[str, float]:
    """What decoding ``n_tokens`` output tokens needs when ``n_slots``
    sequences share each pass over the weights: FLOPs, and bytes = one
    pass over the weights per ``n_slots`` tokens plus each token's own
    live KV rows (``contexts_sum`` = the sum over tokens of the rows its
    request held when it was decoded)."""
    flops = (2.0 * matmul_params(c) * n_tokens
             + 4.0 * c["n_layer"] * c["n_embd"] * contexts_sum)
    nbytes = (n_tokens / float(n_slots)) * weight_bytes(c) \
        + contexts_sum * kv_bytes_row(c)
    return {"flops": flops, "bytes": nbytes}
