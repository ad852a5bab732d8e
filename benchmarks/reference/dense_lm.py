"""The plain reference: a dense decoder-only language model in straightforward
`jax.numpy`, float32, matmul precision "highest".  No kernel, no cache, no
batching tricks, nothing imported from the program.

It reads the `model` group of a `benchmarks/configs/<config>.json` file and a
parameter tree in the layout the program is handed (the benchmark makes that
tree itself from `--seed`, see `harness/weights.py`):

    wte [V, D]; lnf_w, lnf_b [D]; lm_head [D, V] (untied only);
    blocks: ln1_w ln1_b ln2_w ln2_b [L, D]; qkv_w [L, D, (H + 2 KVH) hd];
            proj_w [L, D, D]; fc1_w [L, D, F]; fc2_w [L, F, D];
            fcg_w [L, D, F] (gated only); *_b where the model has biases.

The equations (departures from the published models are listed in the
configuration files under `assumed`):

    h   = norm(x)                       LayerNorm(eps) or RMSNorm(eps)
    q|k|v = h @ qkv_w (+ b)             q: H heads, k, v: KVH heads of hd
    q, k = rope(q), rope(k)             rotate-half, theta, absolute position
    a   = softmax(q k^T / sqrt(hd) + causal) v     q head i uses kv head i // (H / KVH)
    x   = x + a @ proj_w (+ b)
    h   = norm(x)
    f   = act(h @ fc1_w)                or  silu(h @ fcg_w) * (h @ fc1_w)  when gated
    x   = x + f @ fc2_w (+ b)
    logits = norm_f(x) @ head           head = wte^T when tied

`prec` names the precision of every matmul's operands: "f32" is the reference
proper; "bf16", "fp8" and "int8" round both operands first (straight-through
in the backward pass) and are what the controls of `correct` use — the
reference put in the program's place, computed one precision lower.

Everything works layer by layer (and, for training, row block by row block),
so that a 1.3B or 7B-wide model fits beside nothing else on one chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ---------------------------------------------------------------------------
# operand rounding for the controls
# ---------------------------------------------------------------------------

def _round_operand(x, prec: str):
    if prec == "f32":
        return x
    if prec == "bf16":
        r = x.astype(jnp.bfloat16).astype(F32)
    elif prec == "fp8":
        # e4m3 with one scale per tensor, amax -> 448 (the usual recipe)
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        r = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    elif prec == "int8":
        # symmetric, one scale per row of the last axis
        s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True),
                        1e-30) / 127.0
        r = jnp.round(x / s) * s
    else:
        raise ValueError(f"unknown precision {prec!r}")
    return x + jax.lax.stop_gradient(r - x)


def _mm(a, b, prec: str):
    return jnp.matmul(_round_operand(a, prec), _round_operand(b, prec),
                      precision=HIGHEST)


# ---------------------------------------------------------------------------
# one block
# ---------------------------------------------------------------------------

def _norm(x, w, b, model):
    if model["norm"] == "rmsnorm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + model["norm_eps"]) * w
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + model["norm_eps"]) * w + b


def _rope(x, theta: float):
    """x [B, S, H, hd]; rotate-half; position = index along S."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[None, :, None, :], jnp.cos(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(x, name: str):
    if name == "gelu_tanh":
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    raise ValueError(f"unknown activation {name!r}")


def block(bp, x, model, prec: str = "f32"):
    """One transformer block on x [B, S, D] (float32); bp holds this block's
    weights (any float dtype; they are read as float32)."""
    bp = {k: v.astype(F32) for k, v in bp.items()}
    B, S, D = x.shape
    H, KVH, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    h = _norm(x, bp["ln1_w"], bp.get("ln1_b"), model)
    qkv = _mm(h, bp["qkv_w"], prec)
    if "qkv_b" in bp:
        qkv = qkv + bp["qkv_b"]
    q = qkv[..., :H * hd].reshape(B, S, H, hd)
    k = qkv[..., H * hd:(H + KVH) * hd].reshape(B, S, KVH, hd)
    v = qkv[..., (H + KVH) * hd:].reshape(B, S, KVH, hd)
    q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    G = H // KVH
    q = q.reshape(B, S, KVH, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", _round_operand(q, prec),
                   _round_operand(k, prec), precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    a = jnp.einsum("bkgqs,bskd->bqkgd", _round_operand(p, prec),
                   _round_operand(v, prec), precision=HIGHEST)
    a = _mm(a.reshape(B, S, H * hd), bp["proj_w"], prec)
    if "proj_b" in bp:
        a = a + bp["proj_b"]
    x = x + a
    h = _norm(x, bp["ln2_w"], bp.get("ln2_b"), model)
    up = _mm(h, bp["fc1_w"], prec)
    if "fc1_b" in bp:
        up = up + bp["fc1_b"]
    if model["gated_mlp"]:
        gate = _mm(h, bp["fcg_w"], prec)
        if "fcg_b" in bp:
            gate = gate + bp["fcg_b"]
        f = _act(gate, model["hidden_act"]) * up
    else:
        f = _act(up, model["hidden_act"])
    f = _mm(f, bp["fc2_w"], prec)
    if "fc2_b" in bp:
        f = f + bp["fc2_b"]
    return x + f


def _head(params, model):
    if model["tie_word_embeddings"]:
        return params["wte"].astype(F32).T
    return params["lm_head"].astype(F32)


def head_logits(top, x, model, prec: str = "f32"):
    """Final norm and vocabulary projection of hidden rows x [..., D]."""
    h = _norm(x, top["lnf_w"].astype(F32),
              top["lnf_b"].astype(F32) if "lnf_b" in top else None, model)
    return _mm(h, _head(top, model), prec)


# ---------------------------------------------------------------------------
# layer-by-layer drivers
# ---------------------------------------------------------------------------

def layer(params, l: int):
    """Block l's weights out of the stacked tree."""
    return {k: v[l] for k, v in params["blocks"].items()}


def top_of(params):
    return {k: v for k, v in params.items() if k != "blocks"}


@functools.lru_cache(maxsize=None)
def _jit_block(model_items, prec):
    model = dict(model_items)
    return jax.jit(lambda bp, x: block(bp, x, model, prec))


def _frozen(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool))))


def logits_at(params, tokens, rows, cols, model, prec: str = "f32"):
    """Reference logits [n, V] at positions (rows[i], cols[i]) of a full
    causal forward over tokens [B, S] (right-padded; padding never reaches an
    earlier position).  One layer's float32 weights live at a time."""
    fn = _jit_block(_frozen(model), prec)
    x = jnp.take(params["wte"], tokens, axis=0).astype(F32)
    for l in range(model["num_hidden_layers"]):
        x = fn(layer(params, l), x)
    picked = x[rows, cols]
    return jax.jit(lambda t, h: head_logits(t, h, model, prec))(
        top_of(params), picked)


# ---- training: loss, gradients and the AdamW step, row block by row block --
#
# Gradients are float32 and are summed in place: every jitted piece takes the
# running sum, donated, and returns it with its own part added, so that no
# second copy of a layer's gradients outlives the call that made it.

@functools.lru_cache(maxsize=None)
def _jit_block_bwd(model_items, prec):
    model = dict(model_items)

    def bwd(bp, x, dy, acc):
        # weights read as float32 before the function that is differentiated,
        # so that their gradients are float32 too
        bp = {k: v.astype(F32) for k, v in bp.items()}
        _, vjp = jax.vjp(lambda b, xx: block(b, xx, model, prec), bp, x)
        g, dx = vjp(dy)
        return jax.tree_util.tree_map(jnp.add, acc, g), dx
    return jax.jit(bwd, donate_argnums=(3,))


@functools.lru_cache(maxsize=None)
def _jit_head_loss(model_items, prec):
    model = dict(model_items)

    def loss_sum(top, x, labels):
        lp = jax.nn.log_softmax(head_logits(top, x, model, prec), axis=-1)
        return -jnp.sum(jnp.take_along_axis(lp, labels[..., None], -1))

    def fn(top, x, labels, acc):
        top = {k: v.astype(F32) for k, v in top.items()}
        ls, (g, dx) = jax.value_and_grad(loss_sum, argnums=(0, 1))(
            top, x, labels)
        return ls, jax.tree_util.tree_map(jnp.add, acc, g), dx
    return jax.jit(fn, donate_argnums=(3,))


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_grad(acc, tok, dx):
    """The embedding lookup's gradient, added to the table's (which holds
    the tied head's already)."""
    acc = dict(acc)
    acc["wte"] = acc["wte"].at[tok.reshape(-1)].add(
        dx.reshape(-1, dx.shape[-1]))
    return acc


_zeros_f32 = jax.jit(lambda t: jax.tree_util.tree_map(
    lambda x: jnp.zeros(x.shape, F32), t))


def loss_and_grads(layers, top, tokens, labels, model, prec: str = "f32",
                   rows_per_block: int = 1):
    """Mean next-token loss over all of tokens/labels [B, S] and its float32
    gradients, as (loss, [per-layer grads], top grads).  `layers` is a list of
    per-block weight dicts, `top` the rest of the tree."""
    frozen = _frozen(model)
    fwd, bwd = _jit_block(frozen, prec), _jit_block_bwd(frozen, prec)
    head = _jit_head_loss(frozen, prec)
    B, S = tokens.shape
    n_tok = float(B * S)
    g_layers = [_zeros_f32(bp) for bp in layers]
    g_top = _zeros_f32(top)
    loss = 0.0
    for r0 in range(0, B, rows_per_block):
        tok = jnp.asarray(tokens[r0:r0 + rows_per_block])
        lab = jnp.asarray(labels[r0:r0 + rows_per_block])
        xs = [jnp.take(top["wte"], tok, axis=0).astype(F32)]
        for bp in layers:
            xs.append(fwd(bp, xs[-1]))
        ls, g_top, dx = head(top, xs.pop(), lab, g_top)
        loss = loss + ls / n_tok
        for l in range(len(layers) - 1, -1, -1):
            g_layers[l], dx = bwd(layers[l], xs.pop(), dx, g_layers[l])
        g_top = _embed_grad(g_top, tok, dx)
    scale = jnp.asarray(1.0 / n_tok, F32)
    div = jax.jit(lambda t: jax.tree_util.tree_map(lambda g: g * scale, t),
                  donate_argnums=(0,))
    return loss, [div(g) for g in g_layers], div(g_top)


def sq_norm(tree):
    return sum(jnp.sum(jnp.square(g.astype(F32)))
               for g in jax.tree_util.tree_leaves(tree))


def adamw_update(p, g, m, v, step: int, clip_scale, opt):
    """One AdamW step on one group of leaves, float32 arithmetic, results
    stored in the types the configuration states (p.dtype, m.dtype)."""
    b1, b2, lr, wd, eps = (opt["beta1"], opt["beta2"], opt["learning_rate"],
                           opt["weight_decay"], opt["eps"])

    def one(p, g, m, v):
        g = g.astype(F32) * clip_scale
        m32 = b1 * m.astype(F32) + (1 - b1) * g
        v32 = b2 * v.astype(F32) + (1 - b2) * g * g
        u = (m32 / (1 - b1 ** step)) / (jnp.sqrt(v32 / (1 - b2 ** step)) + eps)
        newp = p.astype(F32) * (1 - lr * wd) - lr * u
        return newp.astype(p.dtype), m32.astype(m.dtype), v32.astype(v.dtype)

    out = jax.tree_util.tree_map(one, p, g, m, v)
    pick = lambda i: jax.tree_util.tree_map(
        lambda t: t[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2)
