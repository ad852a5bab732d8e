"""Weights of the `xing4_0` configuration from `--seed`, made by the
benchmark: on the device, in one jitted call, in the types they are served
in.  The program is handed this tree and so is the plain reference
(`reference/xing4.py` has the layout); neither makes weights of its own.

Laws (each listed under `assumed` in the configuration file): matrices normal
with std `initializer_range`; the residual out-projections (`o_w`, `down_w`,
`shared_down_w`) std / sqrt(2 L), L the number of mixers; every norm weight
one; the hyper-connections' `hc_phi` normal with std 1 / sqrt(hc_mult x
hidden), `hc_alpha` the configuration's three values, `hc_b` = [normal(hc_b_std)
for pre and post | hc_b_res_eye x I + normal(hc_b_res_std) for res]; the
router's correction bias fitted to even loads and the head centred, as
`weights_hybrid.py` does for the hybrid configuration and for its reasons
(`fit_router_bias`, `centre_head`: the same two steps through THIS
configuration's reference).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .weights import seed_key
from . import weights_hybrid
from .weights_hybrid import _draw, _fit_bias

F32 = "float32"


def leaf_specs(model: dict) -> list:
    """[(path, shape, law, dtype or None for the model's)] in a fixed order;
    a path is ("wte",) or ("layers", l, name)."""
    D, V = model["hidden_size"], model["vocab_size"]
    pattern = model["mixer_pattern"]
    L = len(pattern)
    std = model["initializer_range"]
    proj = std / math.sqrt(2 * L)
    n = model["hc_mult"]
    H, Q, C = (model["num_attention_heads"], model["q_lora_rank"],
               model["kv_lora_rank"])
    N, R, Vh = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                model["v_head_dim"])
    E_all, E = model["router_experts"], model["n_routed_experts"]
    F, Fd = model["moe_intermediate_size"], model["intermediate_size"]
    Fs = F * model["n_shared_experts"]
    out = [(("wte",), (V, D), ("normal", std), None),
           (("lnf_w",), (D,), ("ones",), None),
           (("lm_head",), (D, V), ("normal", std), None)]
    for l, letter in enumerate(pattern):
        def leaf(name, shape, law, dtype=None, l=l):
            out.append((("layers", l, name), shape, law, dtype))
        leaf("norm_w", (D,), ("ones",))
        leaf("hc_phi", (n * D, 2 * n + n * n),
             ("normal_f32", 1.0 / math.sqrt(n * D)), F32)
        leaf("hc_alpha", (3,), ("values", tuple(model["hc_alpha"])), F32)
        leaf("hc_b", (2 * n + n * n,),
             ("hc_b", n, model["hc_b_std"], model["hc_b_res_eye"],
              model["hc_b_res_std"]), F32)
        if letter == "L":
            leaf("q_a_w", (D, Q), ("normal", std))
            leaf("q_norm_w", (Q,), ("ones",))
            leaf("q_b_w", (Q, H * (N + R)), ("normal", std))
            leaf("kv_a_w", (D, C + R), ("normal", std))
            leaf("kv_norm_w", (C,), ("ones",))
            leaf("kv_b_k_w", (H, N, C), ("normal", std))
            leaf("kv_b_v_w", (H, C, Vh), ("normal", std))
            leaf("o_w", (H * Vh, D), ("normal", proj))
        elif letter == "F":
            leaf("gate_w", (D, Fd), ("normal", std))
            leaf("up_w", (D, Fd), ("normal", std))
            leaf("down_w", (Fd, D), ("normal", proj))
        elif letter == "E":
            leaf("router_w", (D, E_all), ("normal", std), F32)
            leaf("router_bias", (E_all,), ("zeros",), F32)
            leaf("gate_w", (E, F, D), ("normal", std))        # transposed
            leaf("up_w", (E, F, D), ("normal", std))          # transposed
            leaf("down_w", (E, F, D), ("normal", proj))
            leaf("shared_gate_w", (D, Fs), ("normal", std))
            leaf("shared_up_w", (D, Fs), ("normal", std))
            leaf("shared_down_w", (Fs, D), ("normal", proj))
        else:
            raise ValueError(f"unknown mixer letter {letter!r}")
    return out


def _draw_hc(key, shape, law, dtype, model):
    kind = law[0]
    if kind == "normal_f32":
        return jax.random.normal(key, shape, jnp.float32) * law[1]
    if kind == "values":
        return jnp.asarray(law[1], dtype)
    if kind == "hc_b":
        _, n, std, eye, res_std = law
        k1, k2 = jax.random.split(key)
        res = eye * jnp.eye(n) + res_std * jax.random.normal(k2, (n, n))
        return jnp.concatenate([std * jax.random.normal(k1, (2 * n,)),
                                res.reshape(-1)]).astype(dtype)
    return _draw(key, shape, law, dtype, model)


def make_params(model: dict, key):
    """The whole tree (trace this under `jax.jit`)."""
    layers = [{} for _ in model["mixer_pattern"]]
    tree = {"layers": layers}
    for i, (path, shape, law, dtype) in enumerate(leaf_specs(model)):
        leaf = _draw_hc(jax.random.fold_in(key, i), shape, law,
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


def fit_router_bias(params, model: dict, seed: int):
    """(the tree with every expert layer's `router_bias` fitted to even
    loads on a seeded sample of uniform ids, layer by layer through the
    plain reference; the sample's residual state after the last mixer summed
    over its streams [sequences, length, D]).  See `weights_hybrid.py` for
    why."""
    import numpy as np

    from ..reference import xing4 as ref
    fit = model["router_bias_fit"]
    rows, width = fit["sequences"], fit["length"]
    rng = np.random.default_rng([int(seed), 5])
    tokens = rng.integers(0, model["vocab_size"], (rows, width), dtype=np.int32)
    frozen = ref._frozen(model)
    k = model["num_experts_per_tok"]
    f32 = jnp.float32

    def scores(lp, X):
        lp = {n: v.astype(f32) for n, v in lp.items()}
        pre, _, _ = ref.stream_mixes(lp, X, model)
        h = jnp.einsum("bsn,bsnd->bsd", pre, X,
                       precision=jax.lax.Precision.HIGHEST)
        return jax.nn.sigmoid(jnp.matmul(
            ref.rms_norm(h, lp["norm_w"], model["rms_norm_eps"]),
            lp["router_w"], precision=jax.lax.Precision.HIGHEST))
    scores = jax.jit(scores)
    fit_fn = jax.jit(lambda s: _fit_bias(s.reshape(-1, s.shape[-1]), k,
                                         fit["iters"], fit["step"]))
    x = jnp.take(params["wte"], tokens, axis=0).astype(f32)
    X = jnp.broadcast_to(x[:, :, None, :],
                         x.shape[:2] + (model["hc_mult"], x.shape[-1]))
    layers = list(params["layers"])
    small_names = ("norm_w", "router_w", "hc_phi", "hc_alpha", "hc_b")
    for l, letter in enumerate(model["mixer_pattern"]):
        if letter == "E":
            small = {n: layers[l][n] for n in small_names}
            layers[l] = dict(layers[l],
                             router_bias=fit_fn(scores(small, X)))
        step = ref._jit_layer(letter, frozen, "f32", "")
        # a sequence at a time, as the check's reference goes
        X = jnp.concatenate([step(layers[l], X[r:r + 1])
                             for r in range(rows)])
    return dict(params, layers=layers), jnp.sum(X, axis=2)


def centre_head(params, final, model: dict):
    """`weights_hybrid.centre_head` under this configuration's key for the
    norm's epsilon (both references share one `rms_norm`)."""
    return weights_hybrid.centre_head(params, final,
                                      {"norm_eps": model["rms_norm_eps"]})


def count_params(model: dict) -> int:
    return sum(math.prod(shape) for _, shape, _, _ in leaf_specs(model))
