"""Operations and bytes from shapes for a ``KeyeVL2`` language model
(grouped-query heads over the keys an indexer selects, softmax-routed
experts) and for one chip's share of it: the benchmark's own arithmetic,
beside ``flops_mla_moe.py``.

Every function takes the configuration file's dict (the source's keys:
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``sa_config``, ``moe_intermediate_size``,
``num_experts_per_tok``, ``num_hidden_layers``, ``vocab_size``, and
``deployment`` for the share) and sizes of the call.  What is counted is
what the MODEL asks for, whatever computes it: attention over the
SELECTED pairs, index scores for the rows that select (a row before
``topk`` takes every earlier key and needs none), forward only.
"""
from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """The four projections of one layer's main heads: W_q, W_k, W_v,
    W_o."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * dh * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def indexer_params(cfg: dict) -> int:
    """The indexer's three projections: W_qI, W_kI, W_w."""
    sa = cfg["sa_config"]
    heads = sa["indexer_num_heads"]
    return cfg["hidden_size"] * (heads * sa["indexer_head_dim"]
                                 + sa["indexer_num_kv_heads"]
                                 * sa["indexer_head_dim"] + heads)


def expert_params(cfg: dict) -> int:
    """One expert: three matrices of the SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_share(cfg: dict) -> float:
    """The share of the experts this chip holds: of an even router's
    picks, that share lands here."""
    dep = cfg["deployment"]
    return dep["experts_held"][1] / dep["num_experts_published"]


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len: int, topk: int) -> int:
    """Pairs of query and key one head attends to over one sequence:
    ``sum_t min(t + 1, topk)``."""
    full = min(seq_len, topk)
    return causal_pairs(full) + (seq_len - full) * topk


def scored_pairs(seq_len: int, topk: int) -> int:
    """Pairs whose index score the selection needs: the causal pairs of
    the rows past ``topk``."""
    return causal_pairs(seq_len) - causal_pairs(min(seq_len, topk))


def total_params(cfg: dict) -> int:
    """Every parameter held here (what the optimizer steps)."""
    d, sa = cfg["hidden_size"], cfg["sa_config"]
    per_layer = (attention_params(cfg) + 2 * cfg["head_dim"]
                 + indexer_params(cfg) + 2 * sa["indexer_head_dim"]
                 + d * cfg["deployment"]["num_experts_published"] + 2 * d
                 + cfg["deployment"]["experts_held"][1] * expert_params(cfg))
    return (cfg["num_hidden_layers"] * per_layer
            + 2 * cfg["vocab_size"] * d + d)


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token meets in a TRAINED matrix multiplication here:
    the main heads' projections, the router over ALL experts, the
    EXPECTED part of its ``num_experts_per_tok`` picks that this chip
    holds, and the untied head over the vocabulary held."""
    d = cfg["hidden_size"]
    per_layer = (attention_params(cfg)
                 + d * cfg["deployment"]["num_experts_published"]
                 + cfg["num_experts_per_tok"] * held_share(cfg)
                 * expert_params(cfg))
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def index_flops_per_token(cfg: dict, seq_len: int) -> float:
    """The indexer, forward only (nothing trains it): 2 FLOPs a parameter
    of its projections, and for every scored pair ``J`` products ``Di``
    wide and the weighted sum over ``J``."""
    sa = cfg["sa_config"]
    per_pair = 2 * sa["indexer_num_heads"] * (sa["indexer_head_dim"] + 1)
    return cfg["num_hidden_layers"] * (
        2.0 * indexer_params(cfg)
        + per_pair * scored_pairs(seq_len, sa["topk"]) / seq_len)


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Main attention over the SELECTED pairs, forward and backward: per
    pair and head QK^T and PV are ``2 Dh`` each, and the backward costs
    twice the forward."""
    pairs = selected_pairs(seq_len, cfg["sa_config"]["topk"]) / seq_len
    return (cfg["num_hidden_layers"] * 3 * 4 * cfg["head_dim"]
            * cfg["num_attention_heads"] * pairs)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, no recomputation: 6 FLOPs per trained matmul
    parameter, attention over the selected pairs, the indexer forward."""
    return (6.0 * matmul_params_per_token(cfg)
            + attention_flops_per_token(cfg, seq_len)
            + index_flops_per_token(cfg, seq_len))


def routed_flops_per_token(cfg: dict) -> float:
    """The part of :func:`train_flops_per_token` in the experts held
    here, at the expected load."""
    return (6.0 * cfg["num_hidden_layers"] * cfg["num_experts_per_tok"]
            * held_share(cfg) * expert_params(cfg))


def sparse_attention_call(batch: int, cfg: dict, seq_len: int,
                          in_bytes: int, *, backward: bool) -> tuple:
    """(FLOPs, bytes) one layer's attention over the selected pairs has
    to do, whatever does it.  Forward: QK^T and PV, ``4 Dh`` a pair and
    head; reads q, writes o (``H`` heads), reads k, v (``G`` heads) and
    one bit a causal pair.  Backward: S, dP, dV, dK, dQ, ``10 Dh`` a
    pair; reads q, o, do and writes dq, reads k, v and writes dk, dv,
    reads the bits."""
    h, g, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    pairs = batch * h * selected_pairs(seq_len, cfg["sa_config"]["topk"])
    rows = batch * seq_len * dh * in_bytes
    bits = batch * causal_pairs(seq_len) / 8
    if not backward:
        return float(4 * dh * pairs), float(rows * (2 * h + 2 * g) + bits)
    return float(10 * dh * pairs), float(rows * (4 * h + 4 * g) + bits)
