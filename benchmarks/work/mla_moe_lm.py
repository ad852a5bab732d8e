"""Model operations of the `xing4_0` configuration (latent attention, gated
experts, hyper-connections), counted from its `model` dict
(`drivers/serve_xing4.model_of`).  A multiply-add is two operations.  What
depends on the data is taken from what the program counted: the token-expert
pairs computed here (the routed experts' work) and the latent rows attention
read."""
from __future__ import annotations


def latent_params_per_token(model: dict) -> int:
    """A latent layer's weights that every token multiplies in the ABSORBED
    form: q_a, q_b, kv_a, the per-head W^K on the query side, W^V on the
    output side, and W^O."""
    D, H = model["hidden_size"], model["num_attention_heads"]
    Q, C = model["q_lora_rank"], model["kv_lora_rank"]
    N, R, V = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
               model["v_head_dim"])
    return D * Q + Q * H * (N + R) + D * (C + R) + H * N * C + H * C * V + \
        H * V * D


def mix_params_per_token(model: dict) -> int:
    """One hyper-connection's weights and mixes a token: phi, then reading
    h = pre X, writing res X and post^T y over n streams of D."""
    n, D = model["hc_mult"], model["hidden_size"]
    return n * D * (2 * n + n * n) + n * D + n * n * D + n * D


def dense_params_per_token(model: dict) -> int:
    """Weights every token multiplies whatever it is routed to, summed over
    the mixers of each kind."""
    D = model["hidden_size"]
    pattern = model["mixer_pattern"]
    experts = D * model["router_experts"] + \
        3 * D * model["moe_intermediate_size"] * model["n_shared_experts"]
    ffn = 3 * D * model["intermediate_size"]
    return pattern.count("L") * latent_params_per_token(model) + \
        pattern.count("E") * experts + pattern.count("F") * ffn + \
        len(pattern) * mix_params_per_token(model)


def expert_flops_per_pair(D, F) -> int:
    """Gate, up and down projection of one token through one gated expert."""
    return 3 * 2 * D * F


def forward_flops(model: dict, tokens: float, head_tokens: float,
                  latent_tokens: float, pairs_here: float) -> float:
    """A forward pass over `tokens` positions, `head_tokens` of which go
    through the head, whose latent layers' attention read `latent_tokens`
    cached rows in all per layer (summed over the programs' active slots;
    one query token a slot in decode) and whose routed experts computed
    `pairs_here` token-expert pairs on this chip.  Attention in the absorbed
    form: 2 x ((latent + rope) + latent) operations a row and query head."""
    H, C, R = (model["num_attention_heads"], model["kv_lora_rank"],
               model["qk_rope_head_dim"])
    attn = model["mixer_pattern"].count("L") * 2.0 * H * (2 * C + R) * \
        latent_tokens
    routed = expert_flops_per_pair(
        model["hidden_size"], model["moe_intermediate_size"]) * pairs_here
    head = 2.0 * model["hidden_size"] * model["vocab_size"] * head_tokens
    return 2.0 * dense_params_per_token(model) * tokens + attn + routed + head


def serve_slice(model: dict, facts: dict) -> float:
    """Model operations of the tokens the engine decoded and prefilled in
    the traced slice (the `step_mfu` reducer's adaptor).  A prefill counts
    its length once in `latent_tokens_written` (its rows are read once a
    query tile, not once a query), so a prompt's causal pairs are taken
    from the finished requests' own lengths, as the dense adaptor takes
    them: half its prompt a prefilled token."""
    decode_rows = facts["slice_latent_tokens"] - \
        facts["slice_prefilled_tokens"]
    prompts = [r["n_prompt"] for r in facts["requests"]]
    prefill_pairs = facts["slice_prefilled_tokens"] * \
        sum(n * n / 2.0 for n in prompts) / max(1, sum(prompts))
    return forward_flops(model, facts["slice_tokens"],
                         facts["slice_decode_tokens"] +
                         facts["slice_prefills"],
                         decode_rows + prefill_pairs,
                         facts["slice_moe_pairs_here"])
