"""Operations and bytes that a call needs, computed from its shapes.

These are what the algorithm requires, not what a kernel happens to do:
causal attention counts the keys at or before each query, decode attention
the tokens each sequence holds. A kernel that does more (padding, masked
blocks, block-diagonal queries) spends that time against these counts, so a
roofline share or a model-FLOP utilisation from them cannot pass 100%.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple


def dense_sizes(cfg: dict) -> Dict[str, int]:
    """The sizes of a dense GQA decoder from its configuration file."""
    return {
        "L": int(cfg["num_hidden_layers"]),
        "d": int(cfg["hidden_size"]),
        "H": int(cfg["num_attention_heads"]),
        "K": int(cfg["num_key_value_heads"]),
        "D": int(cfg["head_dim"]),
        "F": int(cfg["intermediate_size"]),
        "V": int(cfg["vocab_size"]),
    }


def matmul_params_per_layer(s: Dict[str, int]) -> int:
    """Weights a token multiplies through in one layer: q, k, v, o and the
    three projections of the gated MLP."""
    d, H, K, D, F = s["d"], s["H"], s["K"], s["D"], s["F"]
    return d * H * D + 2 * d * K * D + H * D * d + 3 * d * F


def causal_pairs(S: int) -> int:
    """(query, key) pairs of causal attention over ``S`` tokens."""
    return S * (S + 1) // 2


def flash_cost(S: int, s: Dict[str, int], itemsize: int = 2) -> Tuple[float, float]:
    """One causal flash-attention call over ``S`` tokens (one layer, one
    sequence): ``(flops, bytes)``. Bytes are q, k, v read once and o written."""
    H, K, D = s["H"], s["K"], s["D"]
    flops = 4.0 * H * D * causal_pairs(S)  # q·k and p·v, 2 flops a MAC
    nbytes = float(itemsize * S * D * (2 * H + 2 * K))
    return flops, nbytes


def paged_cost(lengths: Sequence[int], s: Dict[str, int], itemsize: int = 2) -> Tuple[float, float]:
    """One paged decode-attention call (one layer) for rows holding
    ``lengths`` tokens each: ``(flops, bytes)``. Bytes are the K and V of
    every held token read once, plus each row's query and output."""
    H, K, D = s["H"], s["K"], s["D"]
    tokens = sum(lengths)
    flops = 4.0 * H * D * tokens
    nbytes = float(itemsize * (2 * K * D * tokens + 2 * H * D * len(lengths)))
    return flops, nbytes


def prefill_flops(S: int, s: Dict[str, int]) -> float:
    """Model FLOPs of prefilling one ``S``-token prompt: every layer's
    matmuls and causal attention for all tokens, and the head for the last
    token (the only logits prefill returns)."""
    L, d, V = s["L"], s["d"], s["V"]
    matmul = 2.0 * S * L * matmul_params_per_layer(s)
    attn = L * flash_cost(S, s)[0]
    return matmul + attn + 2.0 * d * V


def decode_flops(lengths: Sequence[int], s: Dict[str, int]) -> float:
    """Model FLOPs of one decode step for the live rows, row ``i`` attending
    over ``lengths[i]`` tokens (its new one included)."""
    L, d, V = s["L"], s["d"], s["V"]
    rows = len(lengths)
    matmul = 2.0 * rows * (L * matmul_params_per_layer(s) + d * V)
    attn = L * paged_cost(lengths, s)[0]
    return matmul + attn


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at peak."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])
