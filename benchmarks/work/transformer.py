"""Model operations of the dense transformer, counted from the configuration's
`model` group.  A multiply-add is two operations.  Recomputed operations
(activation checkpointing) are never counted."""
from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Weights that every token multiplies: the blocks' matrices and the
    head.  The embedding table is a lookup and counts only as the head (when
    tied it is the head)."""
    D, L, F, V = (model["hidden_size"], model["num_hidden_layers"],
                  model["intermediate_size"], model["vocab_size"])
    H, KVH, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    ffn = (3 if model["gated_mlp"] else 2) * D * F
    return L * (D * (H + 2 * KVH) * hd + H * hd * D + ffn) + D * V


def all_params(model: dict) -> int:
    """Every parameter the program holds (what `6 N` has for N in the
    repo's earlier records: the tied table counted once, norms and biases
    in)."""
    D, L, F, V = (model["hidden_size"], model["num_hidden_layers"],
                  model["intermediate_size"], model["vocab_size"])
    H, KVH, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    qkv = (H + 2 * KVH) * hd
    n = L * (D * qkv + D * D + (3 if model["gated_mlp"] else 2) * D * F + 4 * D)
    if model["bias"]:
        n += L * (qkv + D + (2 if model["gated_mlp"] else 1) * F + D)
    n += V * D + 2 * D
    if not model["tie_word_embeddings"]:
        n += D * V
    return n


def train_flops_per_token(model: dict, seq: int) -> float:
    """6 N + 6 L S D: forward and backward over N parameters, and causal
    attention's two matmuls (PaLM appendix B, halved for the causal mask).
    N is `all_params`, as `bench.py` had it."""
    return 6.0 * all_params(model) + \
        6.0 * model["num_hidden_layers"] * seq * model["hidden_size"]


def forward_flops(model: dict, tokens: float, context_sum: float) -> float:
    """A forward pass over `tokens` positions whose attention reads
    `context_sum` keys in total (the sum over positions of the keys each
    attends to): 2 per weight per token, 4 per key per query head per
    channel."""
    attn = 4.0 * model["num_hidden_layers"] * model["num_attention_heads"] * \
        model["head_dim"] * context_sum
    return 2.0 * matmul_params(model) * tokens + attn


# ---- adaptors for the `step_mfu` reducer: (model, facts) -> operations ----

def train_slice(model: dict, facts: dict) -> float:
    """Model operations of the train steps that ended inside the slice."""
    return train_flops_per_token(model, facts["seq"]) * facts["slice_tokens"]


def serve_slice(model: dict, facts: dict) -> float:
    """Model operations of the tokens the engine decoded and prefilled in
    the slice; attention's keys from the requests' own lengths."""
    return forward_flops(model, facts["slice_tokens"],
                         facts["slice_context_sum"])
