"""Operations and bytes from shapes: the benchmark's own arithmetic.

Nothing here asks XLA (``cost_analysis`` counts recomputation and sees
nothing inside a Pallas call).  Every function takes the configuration
file's dict (``n_layer``, ``n_embd``, ``n_head``, ``n_inner``,
``vocab_size``) and sizes of the call.
"""
from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that sit in a matrix multiplication for every token:
    the four attention projections and the two MLP matrices of every
    layer, and the tied head.  The embedding LOOKUP and the position
    table are gathers, not multiplications; LayerNorm scales are not
    matrices."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    per_layer = 4 * d * d + 2 * d * f
    return cfg["n_layer"] * per_layer + cfg["vocab_size"] * d


def total_params(cfg: dict) -> int:
    d = cfg["n_embd"]
    return (matmul_params(cfg) + cfg["n_positions"] * d
            + cfg["n_layer"] * 2 * d + d)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, no recomputation: 6 FLOPs per matmul
    parameter, plus causal attention.  Per token and layer the scores
    and the weighted sum are 2 * 2 * seq * d multiply-adds over the FULL
    square; a causal model needs half of it; backward costs twice the
    forward: 3 * (4 * seq * d) / 2 = 6 * seq * d."""
    attn = cfg["n_layer"] * 6 * seq_len * cfg["n_embd"]
    return 6.0 * matmul_params(cfg) + attn


def flash_call(batch: int, heads: int, seq: int, head_dim: int,
               in_bytes: int, *, backward: bool) -> tuple:
    """(FLOPs, bytes) one causal flash-attention pass over [B, H, T, D]
    has to do.  Forward: QK^T and PV over the causal half, 2 * 2 * T^2 *
    D / 2 per head; reads q, k, v and writes o once.  Backward (dq, dk,
    dv): five such products over the causal half (recomputing the scores
    is part of the algorithm, not a waste: S, dP, dV, dQ, dK); reads q,
    k, v, o, do and writes dq, dk, dv."""
    half_square = batch * heads * seq * seq * head_dim      # = 2*T^2*D/2
    if not backward:
        return 2.0 * half_square, 4.0 * batch * heads * seq * head_dim \
            * in_bytes
    return 5.0 * half_square, 8.0 * batch * heads * seq * head_dim \
        * in_bytes


def decode_tick_bytes(cfg: dict, *, layer_weight_bytes: int,
                      table_bytes: int, kv_bytes: int,
                      live_tokens: int) -> float:
    """Bytes ONE decode tick must read: every layer matrix once, the tied
    embedding table once (the head multiplies against all of it) and the
    keys and values of every live token (2 * n_layer * n_embd each)."""
    d, f = cfg["n_embd"], cfg["n_inner"]
    layers = cfg["n_layer"] * (4 * d * d + 2 * d * f) * layer_weight_bytes
    head = cfg["vocab_size"] * d * table_bytes
    kv = live_tokens * 2 * cfg["n_layer"] * d * kv_bytes
    return float(layers + head + kv)


def kv_bytes_per_token(cfg: dict, kv_bytes: int) -> int:
    return 2 * cfg["n_layer"] * cfg["n_embd"] * kv_bytes
