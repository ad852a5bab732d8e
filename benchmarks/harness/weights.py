"""Weights from `--seed`, made by the benchmark: on the device, in one jitted
call, in the type they are served or trained in.  The program is handed this
tree and so is the plain reference; neither makes weights of its own.

The tree has the layout the program's entry points take (`models/gpt.py`):
stacked blocks under "blocks", the embedding "wte", the final norm, and
"lm_head" where the head is untied.  Law: normal, std `initializer_range`;
the two residual projections std / sqrt(2 L) (GPT-2/3); norms at one, biases
at nought.  The "rbg" generator is used because it fills gigabytes on a TPU
in well under a second; the same seed gives the same bits on the same device
kind.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A key from any non-negative whole number (seeds may pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, seed >> 31)


def leaf_shapes(model: dict) -> dict:
    """{path: (shape, std or None for a norm weight, 0.0 for a bias)}"""
    D, L, F, V = (model["hidden_size"], model["num_hidden_layers"],
                  model["intermediate_size"], model["vocab_size"])
    H, KVH, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    if H * hd != D:
        raise ValueError("the program's block takes heads x head_dim == hidden")
    std = model["initializer_range"]
    proj = std / math.sqrt(2 * L)
    qkv = (H + 2 * KVH) * hd
    out = {("blocks", "ln1_w"): ((L, D), None), ("blocks", "ln2_w"): ((L, D), None),
           ("blocks", "ln1_b"): ((L, D), 0.0), ("blocks", "ln2_b"): ((L, D), 0.0),
           ("blocks", "qkv_w"): ((L, D, qkv), std),
           ("blocks", "proj_w"): ((L, D, D), proj),
           ("blocks", "fc1_w"): ((L, D, F), std),
           ("blocks", "fc2_w"): ((L, F, D), proj),
           ("wte",): ((V, D), std), ("lnf_w",): ((D,), None),
           ("lnf_b",): ((D,), 0.0)}
    if model["gated_mlp"]:
        out[("blocks", "fcg_w")] = ((L, D, F), std)
    if model["bias"]:
        out.update({("blocks", "qkv_b"): ((L, qkv), 0.0),
                    ("blocks", "proj_b"): ((L, D), 0.0),
                    ("blocks", "fc1_b"): ((L, F), 0.0),
                    ("blocks", "fc2_b"): ((L, D), 0.0)})
        if model["gated_mlp"]:
            out[("blocks", "fcg_b")] = ((L, F), 0.0)
    if not model["tie_word_embeddings"]:
        out[("lm_head",)] = ((D, V), std)
    return out


def make_params(model: dict, key):
    """The whole tree (trace this under `jax.jit`)."""
    dtype = jnp.dtype(model["dtype"])
    shapes = leaf_shapes(model)
    tree = {"blocks": {}}
    for i, path in enumerate(sorted(shapes)):
        shape, std = shapes[path]
        if std is None:
            leaf = jnp.ones(shape, dtype)
        elif std == 0.0:
            leaf = jnp.zeros(shape, dtype)
        else:
            leaf = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                      jnp.bfloat16) * std).astype(dtype)
        node = tree
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = leaf
    return tree


def params_on_device(model: dict, seed: int, shardings=None):
    fn = jax.jit(lambda k: make_params(model, k), out_shardings=shardings)
    return fn(seed_key(seed))
