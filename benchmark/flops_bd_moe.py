"""Operations and bytes from shapes for an ``sdar_moe`` language model
trained by diffusion over blocks (grouped-query heads, softmax-routed
experts, the two-copy pass under the block mask) and for one chip's share
of it: the benchmark's own arithmetic, beside ``flops_dsa_moe.py``.

Every function takes the configuration file's dict (the source's keys:
``hidden_size``, ``num_attention_heads``, ``num_key_value_heads``,
``head_dim``, ``moe_intermediate_size``, ``num_experts_per_tok``,
``num_hidden_layers``, ``vocab_size``; ``block_diffusion.block_length``
and ``deployment`` for the share) and sizes of the call.  ``seq_len`` is
``L``, the DATA length (``run.counters["seq_len"]``), and a token is a
data token: what is counted is what the stated algorithm asks for a data
token, whatever computes it: ``2 L`` rows through the layers, ``L``
through the head, attention over the mask's ``L (L + B)`` pairs a head and
sequence, no recomputation.
"""
from __future__ import annotations


def attention_params(cfg: dict) -> int:
    """The four projections of one layer's heads: W_q, W_k, W_v, W_o."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * dh * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def expert_params(cfg: dict) -> int:
    """One expert: three matrices of the SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_share(cfg: dict) -> float:
    """The share of the experts this chip holds: of an even router's
    picks, that share lands here."""
    dep = cfg["deployment"]
    return dep["experts_held"][1] / dep["num_experts_published"]


def attended_pairs(cfg: dict, seq_len: int) -> int:
    """Pairs of query and key one head attends to over one sequence and
    its noised copy: ``L (L + B) / 2`` in each half (a clean row sees its
    block and those before it; a noised row the clean blocks before its
    twin's and its own noised block)."""
    return seq_len * (seq_len + cfg["block_diffusion"]["block_length"])


def total_params(cfg: dict) -> int:
    """Every parameter held here (what the optimizer steps)."""
    d = cfg["hidden_size"]
    per_layer = (attention_params(cfg) + 2 * cfg["head_dim"]
                 + d * cfg["deployment"]["num_experts_published"] + 2 * d
                 + cfg["deployment"]["experts_held"][1] * expert_params(cfg))
    return (cfg["num_hidden_layers"] * per_layer
            + 2 * cfg["vocab_size"] * d + d)


def layer_matmul_params(cfg: dict) -> float:
    """Parameters a ROW meets in a trained matrix multiplication of one
    layer: the heads' projections, the router over ALL experts, and the
    EXPECTED part of its ``num_experts_per_tok`` picks that this chip
    holds."""
    return (attention_params(cfg)
            + cfg["hidden_size"] * cfg["deployment"]["num_experts_published"]
            + cfg["num_experts_per_tok"] * held_share(cfg)
            * expert_params(cfg))


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Attention over the ATTENDED pairs of both halves, forward and
    backward, a data token: per pair and head QK^T and PV are ``2 Dh``
    each, and the backward costs twice the forward."""
    return (cfg["num_hidden_layers"] * 3 * 4 * cfg["head_dim"]
            * cfg["num_attention_heads"]
            * attended_pairs(cfg, seq_len) / seq_len)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward a DATA token, no recomputation: 6 FLOPs per
    trained matmul parameter of the layers for each of its TWO rows, 6 per
    parameter of the untied head for its one noised row, attention over the
    attended pairs."""
    return (6.0 * (2 * cfg["num_hidden_layers"] * layer_matmul_params(cfg)
                   + cfg["vocab_size"] * cfg["hidden_size"])
            + attention_flops_per_token(cfg, seq_len))


def routed_flops_per_token(cfg: dict) -> float:
    """The part of :func:`train_flops_per_token` in the experts held
    here, at the expected load, both rows of a data token."""
    return (6.0 * 2 * cfg["num_hidden_layers"] * cfg["num_experts_per_tok"]
            * held_share(cfg) * expert_params(cfg))


def block_attention_call(batch: int, cfg: dict, seq_len: int,
                         in_bytes: int, *, backward: bool) -> tuple:
    """(FLOPs, bytes) one layer's attention under the block mask has to
    do over ``batch`` sequences of ``seq_len`` tokens and their noised
    copies, whatever does it.  Forward: QK^T and PV, ``4 Dh`` a pair and
    head; reads q, writes o (``H`` heads, ``2 L`` rows), reads k, v (``G``
    heads).  Backward: S, dP, dV, dK, dQ, ``10 Dh`` a pair; reads q, o,
    do and writes dq, reads k, v and writes dk, dv."""
    h, g, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    pairs = batch * h * attended_pairs(cfg, seq_len)
    rows = batch * 2 * seq_len * dh * in_bytes
    if not backward:
        return float(4 * dh * pairs), float(rows * (2 * h + 2 * g))
    return float(10 * dh * pairs), float(rows * (4 * h + 4 * g))
