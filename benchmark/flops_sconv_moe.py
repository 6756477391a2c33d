"""Operations and bytes from shapes for an LFM2-MoE language model (gated
short-convolution layers to one grouped-query attention layer by a list,
leading dense layers, sigmoid-routed experts, none shared) and for one
chip's share of it: the benchmark's own arithmetic, beside
``flops_gdn_moe.py``.

Every function takes the configuration file's dict (the source's keys:
``hidden_size``, ``conv_L_cache``, ``layer_types``, ``num_dense_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
``moe_intermediate_size``, ``num_experts_per_tok``, ``vocab_size``, and
``deployment`` for the share, ``tie_embedding`` for the head) and sizes of
the call.  What is counted is what the MODEL asks for, whatever computes
it: the convolution as ``conv_L_cache`` multiply-adds a channel and token,
attention over the causal triangle.
"""
from __future__ import annotations

from benchmark.flops_dsa_moe import causal_pairs, held_share  # noqa: F401
from benchmark.flops_gdn_moe import least_seconds  # noqa: F401


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def conv_layers(cfg: dict) -> int:
    return cfg["layer_types"].count("conv")


def attention_layers(cfg: dict) -> int:
    return cfg["layer_types"].count("full_attention")


def expert_layers(cfg: dict) -> int:
    return len(cfg["layer_types"]) - cfg["num_dense_layers"]


def conv_mixer_params(cfg: dict) -> int:
    """W_in (to the three chunks), the taps, W_out."""
    d = cfg["hidden_size"]
    return d * 3 * d + d * cfg["conv_L_cache"] + d * d


def attention_mixer_params(cfg: dict) -> int:
    """W_q, W_k, W_v, the two norms over a head, W_o."""
    d, dh = cfg["hidden_size"], head_dim(cfg)
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * dh + 2 * d * g * dh + 2 * dh + h * dh * d


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices of the SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    """The router over ALL experts and its selection bias."""
    total = cfg["deployment"]["num_experts_published"]
    return cfg["hidden_size"] * total + total


def table_params(cfg: dict) -> int:
    """The embedding, and the head where it is a table of its own."""
    tables = 1 if cfg.get("tie_embedding", True) else 2
    return tables * cfg["vocab_size"] * cfg["hidden_size"]


def total_params(cfg: dict) -> int:
    """Every parameter held here (what the optimizer steps)."""
    d = cfg["hidden_size"]
    held = cfg["deployment"]["experts_held"][1]
    return (conv_layers(cfg) * conv_mixer_params(cfg)
            + attention_layers(cfg) * attention_mixer_params(cfg)
            + cfg["num_dense_layers"] * dense_ffn_params(cfg)
            + expert_layers(cfg) * (router_params(cfg)
                                    + held * expert_params(cfg))
            + len(cfg["layer_types"]) * 2 * d
            + table_params(cfg) + d)


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token meets in a TRAINED product here, each once: the
    mixers' projections (the taps among them: one multiply-add a tap a
    channel), the dense FFN, the router over ALL experts, the EXPECTED
    part of its ``num_experts_per_tok`` picks that this chip holds, and
    the head over the vocabulary held (tied or not, one product)."""
    d = cfg["hidden_size"]
    total = cfg["deployment"]["num_experts_published"]
    return (conv_layers(cfg) * conv_mixer_params(cfg)
            + attention_layers(cfg) * (attention_mixer_params(cfg)
                                       - 2 * head_dim(cfg))
            + cfg["num_dense_layers"] * dense_ffn_params(cfg)
            + expert_layers(cfg) * (d * total + routed_params_per_token(cfg))
            + cfg["vocab_size"] * d)


def routed_params_per_token(cfg: dict) -> float:
    """One expert layer's routed parameters a token is expected to meet
    HERE."""
    return (cfg["num_experts_per_tok"] * held_share(cfg)
            * expert_params(cfg))


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """The attention layers' scores and sums over the causal triangle,
    forward and backward: per pair and head QK^T and PV are ``2 Dh``
    each, and the backward costs twice the forward."""
    return (attention_layers(cfg) * 3 * 4 * head_dim(cfg)
            * cfg["num_attention_heads"] * causal_pairs(seq_len) / seq_len)


def gate_flops_per_token(cfg: dict) -> float:
    """One conv layer's two elementwise gates, forward: ``B * X`` and
    ``C * c``, one multiplication a channel each."""
    return 2.0 * cfg["hidden_size"]


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, no recomputation: 6 FLOPs per trained matmul
    parameter, the gates three times over and attention over the causal
    pairs."""
    return (6.0 * matmul_params_per_token(cfg)
            + 3.0 * conv_layers(cfg) * gate_flops_per_token(cfg)
            + attention_flops_per_token(cfg, seq_len))


def routed_flops_per_token(cfg: dict) -> float:
    """The part of :func:`train_flops_per_token` in the routed experts
    held here, at the expected load."""
    return 6.0 * expert_layers(cfg) * routed_params_per_token(cfg)


def short_conv_mixer_call(batch: int, cfg: dict, seq_len: int,
                          in_bytes: int, *, backward: bool) -> tuple:
    """(FLOPs, bytes) ONE conv layer's mixer as written has to do,
    whatever does it: ``W_in`` (D -> 3D), ``z = B * X``, ``conv_L_cache``
    taps a channel, ``C * c``, ``W_out`` (D -> D).  Forward: reads the
    normed input ``[tokens, D]`` and the weights, writes the output
    ``[tokens, D]``; what lies between need never leave the chip.
    Backward: twice the operations; reads the input and the output's
    cotangent and the weights, writes the input's cotangent and the
    weights' gradients."""
    d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    tokens = batch * seq_len
    flops = tokens * (2.0 * (3 * d * d + d * d) + 2.0 * taps * d
                      + gate_flops_per_token(cfg))
    rows = tokens * d * in_bytes
    weights = (4 * d * d + d * taps) * in_bytes
    if not backward:
        return float(flops), float(2 * rows + weights)
    return float(2 * flops), float(3 * rows + 2 * weights)


def gqa_attention_call(batch: int, cfg: dict, seq_len: int, in_bytes: int,
                       *, backward: bool) -> tuple:
    """(FLOPs, bytes) ONE attention layer's attention over the causal
    triangle has to do, whatever does it.  Forward: QK^T and PV, ``4 Dh``
    a pair and head; reads q, writes o (``H`` heads), reads k, v (``G``
    heads).  Backward: S, dP, dV, dK, dQ, ``10 Dh`` a pair; reads q, o, do
    and writes dq, reads k, v and writes dk, dv."""
    h, g, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                head_dim(cfg))
    pairs = batch * h * causal_pairs(seq_len)
    rows = batch * seq_len * dh * in_bytes
    if not backward:
        return float(4 * dh * pairs), float(rows * (2 * h + 2 * g))
    return float(10 * dh * pairs), float(rows * (4 * h + 4 * g))
