"""Operations and bytes from shapes for a ``deepseek_v3`` configuration
(latent attention, routed experts) and for one chip's share of it: the
benchmark's own arithmetic, beside ``flops.py`` (which counts GPT-2's
keys).

Every function takes the configuration file's dict (the source's keys:
``hidden_size``, ``num_attention_heads``, ``qk_nope_head_dim``,
``qk_rope_head_dim``, ``v_head_dim``, ``kv_lora_rank``,
``intermediate_size``, ``moe_intermediate_size``, ``n_shared_experts``,
``num_experts_per_tok``, ``first_k_dense_replace``, ``num_hidden_layers``,
``vocab_size``, and ``deployment`` for the share) and sizes of the call.
"""
from __future__ import annotations


def qk_width(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def attention_params(cfg: dict) -> int:
    """The four projections of one latent-attention layer: W_q, W_kva,
    W_kvb, W_o (no query low-rank)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    c = cfg["kv_lora_rank"]
    return (d * h * qk_width(cfg) + d * (c + cfg["qk_rope_head_dim"])
            + c * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])
            + h * cfg["v_head_dim"] * d)


def expert_params(cfg: dict) -> int:
    """One routed expert: three matrices of the SwiGLU."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def held_share(cfg: dict) -> float:
    """The share of the routed experts this chip holds: of an even
    router's picks, that share lands here."""
    dep = cfg["deployment"]
    return dep["experts_held"][1] / dep["n_routed_experts_published"]


def matmul_params_per_token(cfg: dict) -> float:
    """Parameters a token meets in a matrix multiplication HERE: attention
    and the dense FFN of the leading layers; in an expert layer the shared
    experts, the router over ALL experts, and the EXPECTED part of its
    ``num_experts_per_tok`` picks that this chip holds; the untied head
    over the vocabulary held.  The embedding lookup is a gather."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    routed_total = cfg["deployment"]["n_routed_experts_published"]
    per_expert_layer = (cfg["n_shared_experts"] * expert_params(cfg)
                        + d * routed_total
                        + cfg["num_experts_per_tok"] * held_share(cfg)
                        * expert_params(cfg))
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + sparse * per_expert_layer + cfg["vocab_size"] * d)


def total_params(cfg: dict) -> int:
    """Every parameter held here (what the optimizer steps)."""
    d = cfg["hidden_size"]
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    routed_total = cfg["deployment"]["n_routed_experts_published"]
    norms = cfg["num_hidden_layers"] * (2 * d + cfg["kv_lora_rank"]) + d
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense * 3 * d * cfg["intermediate_size"]
            + sparse * (cfg["n_shared_experts"] * expert_params(cfg)
                        + d * routed_total + routed_total
                        + cfg["deployment"]["experts_held"][1]
                        * expert_params(cfg))
            + 2 * cfg["vocab_size"] * d + norms)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, no recomputation: 6 FLOPs per matmul
    parameter, plus causal attention: per token, layer and head the
    scores are 2 * seq * Dk multiply-adds over the full square and the
    weighted sum 2 * seq * Dv; a causal model needs half; backward costs
    twice the forward: 3 * seq * heads * (Dk + Dv)."""
    attn = (cfg["num_hidden_layers"] * 3 * seq_len
            * cfg["num_attention_heads"]
            * (qk_width(cfg) + cfg["v_head_dim"]))
    return 6.0 * matmul_params_per_token(cfg) + attn


def routed_flops_per_token(cfg: dict) -> float:
    """The part of :func:`train_flops_per_token` in the routed experts
    held here, at the expected load."""
    sparse = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return (6.0 * sparse * cfg["num_experts_per_tok"] * held_share(cfg)
            * expert_params(cfg))


def flash_call(batch: int, heads: int, seq: int, dk: int, dv: int,
               in_bytes: int, *, backward: bool) -> tuple:
    """(FLOPs, bytes) one causal flash-attention pass has to do with
    queries and keys ``dk`` wide and values ``dv`` wide.  Forward: QK^T
    (``dk``) and PV (``dv``) over the causal half, 2 * T^2 * D / 2 per
    head each; reads q, k, v and writes o once.  Backward: S, dK, dQ
    (``dk``) and dP, dV (``dv``); reads q, k, v, o, do and writes dq, dk,
    dv."""
    half_square = batch * heads * seq * seq
    rows = batch * heads * seq * in_bytes
    if not backward:
        return (float(half_square * (dk + dv)),
                float(rows * (2 * dk + 2 * dv)))
    return (float(half_square * (3 * dk + 2 * dv)),
            float(rows * (4 * dk + 4 * dv)))
