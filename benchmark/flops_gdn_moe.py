"""Operations and bytes from shapes for a Qwen3-Next language model (Gated
DeltaNet layers to one gated full-attention layer, softmax-routed experts
beside a gated shared expert) and for one chip's share of it: the
benchmark's own arithmetic, beside ``flops_swa_moe.py``.

Every function takes the configuration file's dict (the source's keys:
``hidden_size``, ``linear_num_key_heads``, ``linear_num_value_heads``,
``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``head_dim``, ``full_attention_interval``,
``moe_intermediate_size``, ``shared_expert_intermediate_size``,
``num_experts_per_tok``, ``num_hidden_layers``, ``vocab_size``, and
``deployment`` for the share) and sizes of the call.  What is counted is
what the MODEL asks for, whatever computes it: the recurrence AS WRITTEN
(``exp(g) S``, ``S^T k``, ``k d^T`` added, ``S^T q``: 7 operations a state
element a token), not any chunked form of it; attention over the causal
triangle.
"""
from __future__ import annotations

from benchmark.flops_dsa_moe import causal_pairs, held_share  # noqa: F401


def full_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] // cfg["full_attention_interval"]


def linear_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - full_layers(cfg)


def linear_mixer_params(cfg: dict) -> int:
    """W_qkvz, W_ba, the convolution's taps, A_log, dt_bias, the gated
    norm's weight, W_out."""
    d = cfg["hidden_size"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    heads = cfg["linear_num_value_heads"]
    values = heads * cfg["linear_value_head_dim"]
    return (d * (2 * keys + 2 * values) + d * 2 * heads
            + (2 * keys + values) * cfg["linear_conv_kernel_dim"]
            + 2 * heads + cfg["linear_value_head_dim"] + values * d)


def full_mixer_params(cfg: dict) -> int:
    """W_q (queries and gates), W_k, W_v, the two norms over a head, W_o."""
    d, dh = cfg["hidden_size"], cfg["head_dim"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * h * 2 * dh + 2 * d * g * dh + 2 * dh + h * dh * d


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices of the SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    """The shared expert and its gate's column."""
    d = cfg["hidden_size"]
    return 3 * d * cfg["shared_expert_intermediate_size"] + d


def total_params(cfg: dict) -> int:
    """Every parameter held here (what the optimizer steps)."""
    d = cfg["hidden_size"]
    dep = cfg["deployment"]
    every_layer = (d * dep["num_experts_published"] + shared_params(cfg)
                   + dep["experts_held"][1] * expert_params(cfg) + 2 * d)
    return (linear_layers(cfg) * linear_mixer_params(cfg)
            + full_layers(cfg) * full_mixer_params(cfg)
            + cfg["num_hidden_layers"] * every_layer
            + 2 * cfg["vocab_size"] * d + d)


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token meets in a TRAINED product here, each once: the
    mixers' projections (the convolution's taps among them: one
    multiply-add a tap a channel), the router over ALL experts, the shared
    expert, the EXPECTED part of its ``num_experts_per_tok`` picks that
    this chip holds, and the untied head over the vocabulary held."""
    d = cfg["hidden_size"]
    heads, dh = cfg["linear_num_value_heads"], cfg["head_dim"]
    every_layer = (d * cfg["deployment"]["num_experts_published"]
                   + shared_params(cfg)
                   + cfg["num_experts_per_tok"] * held_share(cfg)
                   * expert_params(cfg))
    return (linear_layers(cfg) * (linear_mixer_params(cfg) - 2 * heads
                                  - cfg["linear_value_head_dim"])
            + full_layers(cfg) * (full_mixer_params(cfg) - 2 * dh)
            + cfg["num_hidden_layers"] * every_layer
            + cfg["vocab_size"] * d)


def recurrence_flops_per_token(cfg: dict) -> float:
    """One layer's recurrence as written, forward: 7 a state element a
    value head."""
    return 7.0 * (cfg["linear_num_value_heads"] * cfg["linear_key_head_dim"]
                  * cfg["linear_value_head_dim"])


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """The full layers' attention over the causal triangle, forward and
    backward: per pair and head QK^T and PV are ``2 Dh`` each, and the
    backward costs twice the forward."""
    return (full_layers(cfg) * 3 * 4 * cfg["head_dim"]
            * cfg["num_attention_heads"] * causal_pairs(seq_len) / seq_len)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, no recomputation: 6 FLOPs per trained matmul
    parameter, the recurrence as written three times over (the backward
    twice the forward) and attention over the causal pairs."""
    return (6.0 * matmul_params_per_token(cfg)
            + 3.0 * linear_layers(cfg) * recurrence_flops_per_token(cfg)
            + attention_flops_per_token(cfg, seq_len))


def routed_flops_per_token(cfg: dict) -> float:
    """The part of :func:`train_flops_per_token` in the routed experts
    held here, at the expected load."""
    return (6.0 * cfg["num_hidden_layers"] * cfg["num_experts_per_tok"]
            * held_share(cfg) * expert_params(cfg))


def gdn_scan_call(batch: int, cfg: dict, seq_len: int, in_bytes: int, *,
                  backward: bool) -> tuple:
    """(FLOPs, bytes) one LINEAR layer's recurrence has to do, whatever
    does it.  Forward: the recurrence as written; reads q, k (``Hk``
    heads), v, g, beta and writes o (``Hv`` heads).  Backward: twice the
    operations; reads all of those and o's cotangent, writes the five
    inputs' cotangents."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    tokens = batch * seq_len
    inputs = tokens * (2 * hk * dk + hv * dv + 2 * hv) * in_bytes
    o = tokens * hv * dv * in_bytes
    flops = tokens * recurrence_flops_per_token(cfg)
    if not backward:
        return float(flops), float(inputs + o)
    return float(2 * flops), float(2 * inputs + o)


def gated_attention_call(batch: int, cfg: dict, seq_len: int, in_bytes: int,
                         *, backward: bool) -> tuple:
    """(FLOPs, bytes) one FULL layer's attention over the causal triangle
    has to do, whatever does it (the gate's multiplication is not the
    call's).  Forward: QK^T and PV, ``4 Dh`` a pair and head; reads q,
    writes o (``H`` heads), reads k, v (``G`` heads).  Backward: S, dP,
    dV, dK, dQ, ``10 Dh`` a pair; reads q, o, do and writes dq, reads k, v
    and writes dk, dv."""
    h, g, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    pairs = batch * h * causal_pairs(seq_len)
    rows = batch * seq_len * dh * in_bytes
    if not backward:
        return float(4 * dh * pairs), float(rows * (2 * h + 2 * g))
    return float(10 * dh * pairs), float(rows * (4 * h + 4 * g))


def least_seconds(call, peaks: dict, *sizes) -> tuple:
    """The least time the chip could take for ``call(*sizes, backward=)``
    forward and backward, each the larger of FLOPs / peak FLOP/s and bytes
    / peak bytes/s, and which of the two binds each (``peaks``:
    ``benchmark/peaks.json``'s row of the chip)."""
    least, bound = 0.0, []
    for backward in (False, True):
        f, b = call(*sizes, backward=backward)
        t_f = f / peaks["flops_per_s_bf16"]
        t_b = b / peaks["hbm_bytes_per_s"]
        least += max(t_f, t_b)
        bound.append("flops" if t_f >= t_b else "bytes")
    return least, bound
