"""Weights of the hybrid configuration from `--seed`, made by the benchmark:
on the device, in one jitted call, in the types they are served in.  The
program is handed this tree and so is the plain reference
(`reference/nemotron_h.py` has the layout); neither makes weights of its own.

Laws (each listed under `assumed` in the configuration file): matrices normal
with std `initializer_range`; the residual out-projections (`out_w`, `proj_w`,
the experts' `down_w`, `shared_down_w`) std / sqrt(2 L); `A_log` = log U(1,
16); `dt_bias` the inverse softplus of a time step drawn log-uniformly in
[time_step_min, time_step_max] and floored at time_step_floor; `D` and every
norm weight one; the depthwise convolution's weight and bias
U(+-1/sqrt(kernel)), a Conv1d's default; the router's correction bias nought,
or fitted to even loads where the configuration asks (`router_bias_fit`, see
`fit_router_bias`); the head as drawn, or with the direction that every
position's final hidden state shares taken out of it (`head_centred`, see
`centre_head`).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import seed_key

F32 = "float32"


def leaf_specs(model: dict) -> list:
    """[(path, shape, law, dtype or None for the model's)] in a fixed order;
    a path is ("wte",) or ("layers", l, name)."""
    D, V = model["hidden_size"], model["vocab_size"]
    pattern = model["hybrid_override_pattern"]
    L = len(pattern)
    std = model["initializer_range"]
    proj = std / math.sqrt(2 * L)
    H, P, N, G, K = (model["mamba_num_heads"], model["mamba_head_dim"],
                     model["ssm_state_size"], model["n_groups"],
                     model["conv_kernel"])
    d_inner, conv_dim = H * P, H * P + 2 * G * N
    AH, KVH, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                   model["head_dim"])
    E_all, E = model["router_experts"], model["n_routed_experts"]
    F, Fs = (model["moe_intermediate_size"],
             model["moe_shared_expert_intermediate_size"])
    bound = 1.0 / math.sqrt(K)
    out = [(("wte",), (V, D), ("normal", std), None),
           (("lnf_w",), (D,), ("ones",), None),
           (("lm_head",), (D, V), ("normal", std), None)]
    for l, letter in enumerate(pattern):
        def leaf(name, shape, law, dtype=None, l=l):
            out.append((("layers", l, name), shape, law, dtype))
        leaf("norm_w", (D,), ("ones",))
        if letter == "M":
            leaf("in_w", (D, 2 * d_inner + 2 * G * N + H), ("normal", std))
            leaf("conv_w", (K, conv_dim), ("uniform", bound))
            leaf("conv_b", (conv_dim,), ("uniform", bound))
            leaf("dt_bias", (H,), ("dt_bias",), F32)
            leaf("A_log", (H,), ("A_log",), F32)
            leaf("D", (H,), ("ones",), F32)
            leaf("gnorm_w", (d_inner,), ("ones",))
            leaf("out_w", (d_inner, D), ("normal", proj))
        elif letter == "E":
            leaf("router_w", (D, E_all), ("normal", std), F32)
            leaf("router_bias", (E_all,), ("zeros",), F32)
            leaf("up_w", (E, F, D), ("normal", std))          # U transposed
            leaf("down_w", (E, F, D), ("normal", proj))
            leaf("shared_up_w", (D, Fs), ("normal", std))
            leaf("shared_down_w", (Fs, D), ("normal", proj))
        elif letter == "*":
            leaf("qkv_w", (D, (AH + 2 * KVH) * hd), ("normal", std))
            leaf("proj_w", (AH * hd, D), ("normal", proj))
        else:
            raise ValueError(f"unknown layer letter {letter!r}")
    return out


def _draw(key, shape, law, dtype, model):
    kind = law[0]
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "normal":
        return (jax.random.normal(key, shape, jnp.bfloat16) * law[1]
                ).astype(dtype)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -law[1], law[1]
                                  ).astype(dtype)
    if kind == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)
                       ).astype(dtype)
    if kind == "dt_bias":
        lo, hi = math.log(model["time_step_min"]), \
            math.log(model["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, model["time_step_floor"])
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    raise ValueError(f"unknown law {law!r}")


def make_params(model: dict, key):
    """The whole tree (trace this under `jax.jit`)."""
    layers = [{} for _ in model["hybrid_override_pattern"]]
    tree = {"layers": layers}
    for i, (path, shape, law, dtype) in enumerate(leaf_specs(model)):
        leaf = _draw(jax.random.fold_in(key, i), shape, law,
                     jnp.dtype(dtype or model["dtype"]), model)
        if path[0] == "layers":
            layers[path[1]][path[2]] = leaf
        else:
            tree[path[0]] = leaf
    return tree


def params_on_device(model: dict, seed: int):
    params = jax.jit(lambda k: make_params(model, k))(seed_key(seed))
    if model.get("router_bias_fit"):
        params, final = fit_router_bias(params, model, seed)
        if model.get("head_centred"):
            params = centre_head(params, final, model)
    elif model.get("head_centred"):
        raise SystemExit("head_centred needs the sample of router_bias_fit")
    return params


# ---------------------------------------------------------------------------
# the router's correction bias, fitted
# ---------------------------------------------------------------------------
# The published router chooses its top-k on score + a per-expert correction
# bias; in training that bias is what keeps the experts' loads even (it is
# nudged up for an underloaded expert and down for an overloaded one, with no
# auxiliary loss).  Seeded weights have had no such training, and with uniform
# token ids the hidden states of different sequences share a large common
# component at long context, so with a zero bias the top-k collapse onto a few
# experts, more with every layer (PERF.md, PR 28): the cell then streams a
# fraction of the expert bytes a deployment streams, and how small a fraction
# hangs on the seed.  So the bias is fitted here the way training fits it, on
# a seeded sample of the cell's own kind of sequence, layer by layer (each
# expert layer sees the hidden states the fitted layers before it produce),
# through the plain reference.  The program and the reference are handed the
# same fitted values; neither knows they were fitted.

def _fit_bias(scores, k: int, iters: int, step: float):
    """b [E] such that the top-k of scores + b load the experts evenly over
    the sample; scores [N, E] float32."""
    N, E = scores.shape
    target = N * k / E

    def body(i, b):
        _, idx = jax.lax.top_k(scores + b, k)
        load = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        rate = step * (1.0 - i / iters)
        return b + rate * jnp.clip((target - load) / target, -1.0, 1.0)

    return jax.lax.fori_loop(0, iters, body, jnp.zeros((E,), jnp.float32))


def fit_router_bias(params, model: dict, seed: int):
    """(the tree with every expert layer's `router_bias` fitted, see above;
    the sample's hidden states after the last layer [sequences, length, D]).
    `model["router_bias_fit"]` = {"sequences", "length", "iters", "step"}."""
    import numpy as np

    from ..reference import nemotron_h as ref
    fit = model["router_bias_fit"]
    rows, width = fit["sequences"], fit["length"]
    rng = np.random.default_rng([int(seed), 5])
    tokens = rng.integers(0, model["vocab_size"], (rows, width), dtype=np.int32)
    frozen = ref._frozen(model)
    k = model["num_experts_per_tok"]
    scores = jax.jit(lambda lp, x: jax.nn.sigmoid(jnp.matmul(
        ref.rms_norm(x, lp["norm_w"].astype(jnp.float32), model["norm_eps"]),
        lp["router_w"], precision=jax.lax.Precision.HIGHEST)))
    fit_fn = jax.jit(lambda s: _fit_bias(s.reshape(-1, s.shape[-1]), k,
                                         fit["iters"], fit["step"]))
    x = jnp.take(params["wte"], tokens, axis=0).astype(jnp.float32)
    layers = list(params["layers"])
    for l, letter in enumerate(model["hybrid_override_pattern"]):
        if letter == "E":
            small = {n: layers[l][n] for n in ("norm_w", "router_w")}
            layers[l] = dict(layers[l], router_bias=fit_fn(scores(small, x)))
        step = ref._jit_layer(letter, frozen, "f32")
        # two sequences at a time: the shapes the check's reference compiles
        x = jnp.concatenate([step(layers[l], x[r:r + 2])
                             for r in range(0, rows, 2)])
    return dict(params, layers=layers), x


# ---------------------------------------------------------------------------
# the head, centred
# ---------------------------------------------------------------------------
# Seeded weights give every position nearly the same final hidden state: the
# experts' relu(.)^2 has a mean that is not zero, so each expert layer adds
# one fixed vector to every token, and after 13 layers 78 % of the final
# state's energy lies along one direction, the same at every position and in
# every sequence (CPU probe at the real widths, PR 28).  A head drawn
# independently of that direction gives a few tokens the highest logit
# whatever the context: greedy decoding serves one token 41 % of the time at
# one seed and 9 % at another, the slots of a step then hold 20 or 43
# distinct tokens of 64, the router sees as few distinct inputs, and the
# experts touched a step, and with them tokens/s, hang on the seed (my chip
# runs, PR 28).  No trained head ignores its context, so the direction is
# taken out of the head's columns: W <- W - u (u^T W), u the mean normalised
# final state of the fit sample.  No parameter is added and the program and
# the reference are handed the same matrix.

def centre_head(params, final, model: dict):
    """The tree with the sample's common final direction projected out of
    `lm_head`; final [sequences, length, D] float32."""
    from ..reference import nemotron_h as ref

    def project(head, lnf_w, x):
        h = ref.rms_norm(x.reshape(-1, x.shape[-1]),
                         lnf_w.astype(jnp.float32), model["norm_eps"])
        u = jnp.mean(h, axis=0)
        u = u / jnp.linalg.norm(u)
        w = head.astype(jnp.float32)
        along = jnp.matmul(u, w, precision=jax.lax.Precision.HIGHEST)
        return (w - u[:, None] * along[None, :]).astype(head.dtype)

    return dict(params, lm_head=jax.jit(project)(
        params["lm_head"], params["lnf_w"], final))


def count_params(model: dict) -> int:
    return sum(math.prod(shape) for _, shape, _, _ in leaf_specs(model))
