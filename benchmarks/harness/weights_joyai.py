"""Weights of the `joyai_llm_flash` configuration from `--seed`, made by the
benchmark: on the device, in one jitted call, in the types they are trained
in.  The program is handed this tree and so is the plain reference
(`reference/joyai_flash.py` has the layout); neither makes weights of its
own.

Laws (each listed under `assumed` in the configuration file): matrices normal
with std `initializer_range`; the residual out-projections (`o_w`, `down_w`,
`shared_down_w`, the next-n module's too) std / sqrt(2 L), L the number of
the main model's mixers; every norm gain one; the router's correction bias
fitted to even loads, as `weights_hybrid.py` and `weights_xing4.py` do for
the served configurations and for their reasons (`fit_router_bias`: the same
step through THIS configuration's reference, the module's router included).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..reference import joyai_flash as ref
from ..reference.joyai_flash import MTP_PATTERN
from .weights import seed_key
from .weights_hybrid import _draw, _fit_bias

F32 = "float32"


def _mixer_leaves(letter: str, model: dict) -> list:
    """[(name, shape, law, dtype or None)] of one mixer."""
    D = model["hidden_size"]
    std = model["initializer_range"]
    proj = std / math.sqrt(2 * len(model["mixer_pattern"]))
    H, Q, C = (model["num_attention_heads"], model["q_lora_rank"],
               model["kv_lora_rank"])
    N, R, Vh = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                model["v_head_dim"])
    E_all, E = model["router_experts"], model["n_routed_experts"]
    F, Fd = model["moe_intermediate_size"], model["intermediate_size"]
    Fs = F * model["n_shared_experts"]
    out = [("norm_w", (D,), ("ones",), None)]
    if letter == "L":
        out += [("q_a_w", (D, Q), ("normal", std), None),
                ("q_norm_w", (Q,), ("ones",), None),
                ("q_b_w", (Q, H * (N + R)), ("normal", std), None),
                ("kv_a_w", (D, C + R), ("normal", std), None),
                ("kv_norm_w", (C,), ("ones",), None),
                ("kv_b_k_w", (H, N, C), ("normal", std), None),
                ("kv_b_v_w", (H, C, Vh), ("normal", std), None),
                ("o_w", (H * Vh, D), ("normal", proj), None)]
    elif letter == "F":
        out += [("gate_w", (D, Fd), ("normal", std), None),
                ("up_w", (D, Fd), ("normal", std), None),
                ("down_w", (Fd, D), ("normal", proj), None)]
    elif letter == "E":
        out += [("router_w", (D, E_all), ("normal", std), F32),
                ("router_bias", (E_all,), ("zeros",), F32),
                ("gate_w", (E, F, D), ("normal", std), None),    # transposed
                ("up_w", (E, F, D), ("normal", std), None),      # transposed
                ("down_w", (E, F, D), ("normal", proj), None),
                ("shared_gate_w", (D, Fs), ("normal", std), None),
                ("shared_up_w", (D, Fs), ("normal", std), None),
                ("shared_down_w", (Fs, D), ("normal", proj), None)]
    else:
        raise ValueError(f"unknown mixer letter {letter!r}")
    return out


def leaf_specs(model: dict) -> list:
    """[(path, shape, law, dtype or None for the model's)] in a fixed order;
    a path is ("wte",), ("layers", l, name), ("mtp", name) or
    ("mtp", "layers", l, name)."""
    D, V = model["hidden_size"], model["vocab_size"]
    std = model["initializer_range"]
    out = [(("wte",), (V, D), ("normal", std), None),
           (("lnf_w",), (D,), ("ones",), None),
           (("lm_head",), (D, V), ("normal", std), None)]
    for l, letter in enumerate(model["mixer_pattern"]):
        out += [(("layers", l, name), shape, law, dtype)
                for name, shape, law, dtype in _mixer_leaves(letter, model)]
    if model["num_nextn_predict_layers"]:
        out += [(("mtp", "hnorm_w"), (D,), ("ones",), None),
                (("mtp", "enorm_w"), (D,), ("ones",), None),
                (("mtp", "eh_proj"), (2 * D, D), ("normal", std), None),
                (("mtp", "norm_w"), (D,), ("ones",), None)]
        for l, letter in enumerate(MTP_PATTERN):
            out += [(("mtp", "layers", l, name), shape, law, dtype)
                    for name, shape, law, dtype in
                    _mixer_leaves(letter, model)]
    return out


def make_params(model: dict, key):
    """The whole tree (trace this under `jax.jit`); every router bias
    nought."""
    tree = {"layers": [{} for _ in model["mixer_pattern"]]}
    if model["num_nextn_predict_layers"]:
        tree["mtp"] = {"layers": [{} for _ in MTP_PATTERN]}
    for i, (path, shape, law, dtype) in enumerate(leaf_specs(model)):
        leaf = _draw(jax.random.fold_in(key, i), shape, law,
                     jnp.dtype(dtype or model["dtype"]), model)
        node = tree
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = leaf
    return tree


def router_biases(params) -> list:
    """Every expert layer's `router_bias`, the main model's in order, then
    the module's."""
    layers = params["layers"] + params.get("mtp", {}).get("layers", [])
    return [lp["router_bias"] for lp in layers if "router_bias" in lp]


def params_on_device(model: dict, seed: int):
    params = jax.jit(lambda k: make_params(model, k))(seed_key(seed))
    if model.get("router_bias_fit"):
        params = fit_router_bias(params, model, seed)
    return params


def fit_router_bias(params, model: dict, seed: int):
    """The tree with every expert layer's `router_bias` fitted to even loads
    on a seeded sample of uniform ids, layer by layer through the plain
    reference (each expert layer sees the hidden states the fitted layers
    before it produce; the module's sees its own input).  See
    `weights_hybrid.py` for why."""
    import numpy as np
    fit = model["router_bias_fit"]
    rows, width = fit["sequences"], fit["length"]
    rng = np.random.default_rng([int(seed), 5])
    ids = rng.integers(0, model["vocab_size"], (rows, width + 1),
                       dtype=np.int32)
    frozen = ref._frozen(model)
    k = model["num_experts_per_tok"]
    f32 = jnp.float32
    scores = jax.jit(lambda lp, x: jax.nn.sigmoid(jnp.matmul(
        ref.rms_norm(x, lp["norm_w"].astype(f32), model["rms_norm_eps"]),
        lp["router_w"], precision=jax.lax.Precision.HIGHEST)))
    fit_fn = jax.jit(lambda s: _fit_bias(s.reshape(-1, s.shape[-1]), k,
                                         fit["iters"], fit["step"]))

    def through(pattern, layers, x):
        layers = list(layers)
        for l, letter in enumerate(pattern):
            if letter == "E":
                small = {n: layers[l][n] for n in ("norm_w", "router_w")}
                layers[l] = dict(layers[l],
                                 router_bias=fit_fn(scores(small, x)))
            step = ref._jit_layer(letter, frozen, "f32", "")
            # a sequence at a time, as the check's reference goes
            x = jnp.concatenate([step(layers[l], x[r:r + 1])[0]
                                 for r in range(rows)])
        return layers, x

    x = jnp.take(params["wte"], ids[:, :-1], axis=0).astype(f32)
    layers, x = through(model["mixer_pattern"], params["layers"], x)
    out = dict(params, layers=layers)
    if model["num_nextn_predict_layers"]:
        mt = params["mtp"]
        nxt = jnp.take(params["wte"], ids[:, 1:], axis=0).astype(f32)
        h = jax.jit(lambda m, a, b: ref.mtp_input(m, a, b, model))(
            {n: mt[n] for n in ("hnorm_w", "enorm_w", "eh_proj")}, x, nxt)
        out["mtp"] = dict(mt, layers=through(MTP_PATTERN, mt["layers"], h)[0])
    return out


def count_params(model: dict) -> int:
    return sum(math.prod(shape) for _, shape, _, _ in leaf_specs(model))
