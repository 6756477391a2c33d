"""Operations and bytes from shapes for a SmallThinker language model
(window layers among global layers, grouped-query heads, a router before
attention, ReLU-gated experts) and for one chip's share of it: the
benchmark's own arithmetic, beside ``flops_dsa_moe.py``.

Every function takes the configuration file's dict (the source's keys:
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``sliding_window_size``, ``sliding_window_layout``,
``moe_ffn_hidden_size``, ``moe_num_active_primary_experts``,
``num_hidden_layers``, ``vocab_size``, and ``deployment`` for the share)
and sizes of the call.  What is counted is what the MODEL asks for,
whatever computes it: attention over the ATTENDED pairs (a window layer's
``sum_t min(t + 1, window)``, a global layer's causal triangle).
"""
from __future__ import annotations

# the same keys mean the same here: the four projections of grouped-query
# heads, the causal triangle, the share of the experts held
from benchmark.flops_dsa_moe import (  # noqa: F401  (readers use them)
    attention_params,
    causal_pairs,
    held_share,
)


def expert_params(cfg: dict) -> int:
    """One expert: three matrices of the ReGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def window_pairs(seq_len: int, window: int) -> int:
    """Pairs of query and key one head of a window layer attends to over
    one sequence: ``sum_t min(t + 1, window)``."""
    full = min(seq_len, window)
    return causal_pairs(full) + (seq_len - full) * window


def window_layers(cfg: dict) -> int:
    return sum(1 for kind in cfg["sliding_window_layout"] if kind)


def attended_pairs(cfg: dict, seq_len: int) -> int:
    """Pairs one head attends to over one sequence, summed over the
    layers held here."""
    windowed = window_layers(cfg)
    return (windowed * window_pairs(seq_len, cfg["sliding_window_size"])
            + (cfg["num_hidden_layers"] - windowed) * causal_pairs(seq_len))


def total_params(cfg: dict) -> int:
    """Every parameter held here (what the optimizer steps)."""
    d = cfg["hidden_size"]
    per_layer = (attention_params(cfg)
                 + d * cfg["deployment"]["num_experts_published"] + 2 * d
                 + cfg["deployment"]["experts_held"][1] * expert_params(cfg))
    return (cfg["num_hidden_layers"] * per_layer
            + 2 * cfg["vocab_size"] * d + d)


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token meets in a TRAINED matrix multiplication here:
    the heads' projections, the router over ALL experts, the EXPECTED part
    of its ``moe_num_active_primary_experts`` picks that this chip holds,
    and the untied head over the vocabulary held."""
    d = cfg["hidden_size"]
    per_layer = (attention_params(cfg)
                 + d * cfg["deployment"]["num_experts_published"]
                 + cfg["moe_num_active_primary_experts"] * held_share(cfg)
                 * expert_params(cfg))
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Attention over the ATTENDED pairs, forward and backward: per pair
    and head QK^T and PV are ``2 Dh`` each, and the backward costs twice
    the forward."""
    return (3 * 4 * cfg["head_dim"] * cfg["num_attention_heads"]
            * attended_pairs(cfg, seq_len) / seq_len)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, no recomputation: 6 FLOPs per trained matmul
    parameter and attention over the attended pairs."""
    return (6.0 * matmul_params_per_token(cfg)
            + attention_flops_per_token(cfg, seq_len))


def routed_flops_per_token(cfg: dict) -> float:
    """The part of :func:`train_flops_per_token` in the experts held
    here, at the expected load."""
    return (6.0 * cfg["num_hidden_layers"]
            * cfg["moe_num_active_primary_experts"] * held_share(cfg)
            * expert_params(cfg))


def _attention_call(pairs_a_head: int, batch: int, cfg: dict, seq_len: int,
                    in_bytes: int, backward: bool) -> tuple:
    """(FLOPs, bytes) of one layer's attention over ``pairs_a_head`` pairs
    a head and sequence.  Forward: QK^T and PV, ``4 Dh`` a pair and head;
    reads q, writes o (``H`` heads), reads k, v (``G`` heads).  Backward:
    S, dP, dV, dK, dQ, ``10 Dh`` a pair; reads q, o, do and writes dq,
    reads k, v and writes dk, dv."""
    h, g, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    pairs = batch * h * pairs_a_head
    rows = batch * seq_len * dh * in_bytes
    if not backward:
        return float(4 * dh * pairs), float(rows * (2 * h + 2 * g))
    return float(10 * dh * pairs), float(rows * (4 * h + 4 * g))


def window_attention_call(batch: int, cfg: dict, seq_len: int,
                          in_bytes: int, *, backward: bool) -> tuple:
    """(FLOPs, bytes) one WINDOW layer's attention over the window's
    pairs has to do, whatever does it."""
    return _attention_call(
        window_pairs(seq_len, cfg["sliding_window_size"]), batch, cfg,
        seq_len, in_bytes, backward)


def global_attention_call(batch: int, cfg: dict, seq_len: int,
                          in_bytes: int, *, backward: bool) -> tuple:
    """(FLOPs, bytes) one GLOBAL layer's attention over the causal
    triangle has to do, whatever does it."""
    return _attention_call(causal_pairs(seq_len), batch, cfg, seq_len,
                           in_bytes, backward)
