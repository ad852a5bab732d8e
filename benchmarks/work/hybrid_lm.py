"""Model operations of the hybrid configuration, counted from its `model`
dict (`drivers/serve_hybrid.model_of`).  A multiply-add is two operations.
What depends on the data is taken from what the program counted: the
token-expert pairs computed here (the routed experts' work) and the keys
attention read."""
from __future__ import annotations

from . import moe, ssm


def dense_params_per_token(model: dict) -> int:
    """Weights every token multiplies, whatever it is routed to: the Mamba
    projections, attention's, the router and the shared expert, summed over
    the layers of each kind."""
    D = model["hidden_size"]
    pattern = model["hybrid_override_pattern"]
    H, P, N, G = (model["mamba_num_heads"], model["mamba_head_dim"],
                  model["ssm_state_size"], model["n_groups"])
    d_inner = H * P
    mamba = D * (2 * d_inner + 2 * G * N + H) + d_inner * D
    AH, KVH, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                   model["head_dim"])
    attn = D * (AH + 2 * KVH) * hd + AH * hd * D
    experts = D * model["router_experts"] + \
        2 * D * model["moe_shared_expert_intermediate_size"]
    return pattern.count("M") * mamba + pattern.count("*") * attn + \
        pattern.count("E") * experts


def forward_flops(model: dict, tokens: float, head_tokens: float,
                  context_sum: float, pairs_here: float) -> float:
    """A forward pass over `tokens` positions, `head_tokens` of which go
    through the head (every decoded token; of a prompt only the last), whose
    attention reads `context_sum` keys in all and whose routed experts
    computed `pairs_here` token-expert pairs on this chip."""
    pattern = model["hybrid_override_pattern"]
    scan = pattern.count("M") * ssm.scan_flops_per_token(
        model["mamba_num_heads"], model["mamba_head_dim"],
        model["ssm_state_size"])
    attn = 4.0 * pattern.count("*") * model["num_attention_heads"] * \
        model["head_dim"] * context_sum
    routed = moe.expert_flops_per_pair(
        model["hidden_size"], model["moe_intermediate_size"]) * pairs_here
    head = 2.0 * model["hidden_size"] * model["vocab_size"] * head_tokens
    return (2.0 * dense_params_per_token(model) + scan) * tokens + attn + \
        routed + head


def serve_slice(model: dict, facts: dict) -> float:
    """Model operations of the tokens the engine decoded and prefilled in
    the traced slice (the `step_mfu` reducer's adaptor)."""
    return forward_flops(model, facts["slice_tokens"],
                         facts["slice_decode_tokens"] +
                         facts["slice_prefills"],
                         facts["slice_context_sum"],
                         facts["slice_moe_pairs_here"])
