"""Model operations of the `joyai_llm_flash` configuration IN TRAINING
(latent attention expanded, gated experts, the next-n module), counted from
its `model` dict (`drivers/train_joyai.model_of`).  A multiply-add is two
operations; forward and backward together are three times the forward
(6 N); recomputed operations (activation checkpointing) are never counted.
What depends on the data is taken from what the program counted: the
token-expert pairs computed here (the routed experts' work), not a nominal
top-k."""
from __future__ import annotations

MTP_PATTERN = "LE"          # the next-n module's one layer


def latent_params_per_token(model: dict) -> int:
    """A latent layer's matrices that every token multiplies in the EXPANDED
    form: q_a, q_b, kv_a, W^K and W^V on the cached latent, W^O."""
    D, H = model["hidden_size"], model["num_attention_heads"]
    Q, C = model["q_lora_rank"], model["kv_lora_rank"]
    N, R, V = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
               model["v_head_dim"])
    return D * Q + Q * H * (N + R) + D * (C + R) + H * N * C + H * C * V + \
        H * V * D


def patterns(model: dict) -> str:
    """Every mixer a token passes: the main model's, then the module's."""
    return model["mixer_pattern"] + \
        MTP_PATTERN * model["num_nextn_predict_layers"]


def dense_params_per_token(model: dict) -> int:
    """Matrices every token multiplies whatever it is routed to: attention,
    the dense FFN, each expert layer's router and shared expert, the head
    (once for the main loss, once for the module's) and the module's
    eh_proj.  The embedding is a lookup."""
    D = model["hidden_size"]
    p = patterns(model)
    experts = D * model["router_experts"] + \
        3 * D * model["moe_intermediate_size"] * model["n_shared_experts"]
    mtp = model["num_nextn_predict_layers"]
    return p.count("L") * latent_params_per_token(model) + \
        p.count("F") * 3 * D * model["intermediate_size"] + \
        p.count("E") * experts + \
        (1 + mtp) * D * model["vocab_size"] + mtp * 2 * D * D


def expert_params_per_pair(model: dict) -> int:
    """Gate, up and down matrix of one gated expert."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def attention_flops_per_token(model: dict, seq: int) -> float:
    """Forward causal attention of one token in one latent layer, averaged
    over a sequence: (seq + 1) / 2 keys, a score nope + rope wide and values
    v_head_dim wide, every head."""
    width = model["qk_nope_head_dim"] + model["qk_rope_head_dim"] + \
        model["v_head_dim"]
    return 2.0 * model["num_attention_heads"] * width * (seq + 1) / 2.0


def train_flops(model: dict, tokens: float, seq: int,
                pairs_here: float) -> float:
    matmul = dense_params_per_token(model) * tokens + \
        expert_params_per_pair(model) * pairs_here
    attn = patterns(model).count("L") * \
        attention_flops_per_token(model, seq) * tokens
    return 6.0 * matmul + 3.0 * attn


def train_slice(model: dict, facts: dict) -> float:
    """Model operations of the train steps that began and ended inside the
    slice (the `step_mfu` reducer's adaptor)."""
    return train_flops(model, facts["slice_tokens"], facts["seq"],
                       facts["slice_moe_pairs_here"])
