"""The plain reference of the hybrid configuration (`model_type: nemotron_h`):
Mamba-2, mixture-of-experts and attention layers in straightforward
`jax.numpy`, float32, matmul precision "highest".  No kernel, no cache, no
chunked scan, no grouped product; nothing imported from the program.

It reads a `model` dict (the published keys of the configuration file, see
`drivers/serve_hybrid.model_of`) and a parameter tree in the layout the
program is handed (`harness/weights_hybrid.py` makes it from `--seed`):

    wte [V, D]; lnf_w [D]; lm_head [D, V]; layers: one dict per letter of
    `hybrid_override_pattern`, each with norm_w [D] and
      M: in_w [D, 2 d_inner + 2 G N + H]  (columns z | xBC | dt),
         conv_w [K, conv_dim], conv_b [conv_dim], dt_bias A_log D [H],
         gnorm_w [d_inner], out_w [d_inner, D]
      E: router_w [D, E_all], router_bias [E_all] (float32),
         up_w [E_held, F, D] (each expert's U transposed), down_w [E_held, F, D],
         shared_up_w [D, Fs], shared_down_w [Fs, D]
      *: qkv_w [D, (H + 2 KVH) hd], proj_w [H hd, D]

Every layer is x <- x + mixer(RMSNorm(x; norm_w, eps)); then RMSNorm(lnf_w)
and the untied head.  The mixers:

  M  [z | xBC | dt] = h in_w;  xBC <- silu(causal depthwise conv_K(xBC) + b);
     xBC -> x [H, P] | B [G, N] | C [G, N];  dt <- softplus(dt + dt_bias);
     A = -exp(A_log);  per head h (group g = h // (H / G)), position by
     position:  S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (outer) B_{g,t},
     y_t = S_t C_{g,t} + D_h x_t;  y <- RMSNorm over each of G groups of
     (y * silu(z)) times gnorm_w;  out = y out_w.
  E  s = sigmoid(h router_w) (float32); top-k of s + router_bias;
     w_i = s_i / (sum_topk s + 1e-20) * routed_scaling_factor;
     out = sum over the chosen experts HELD here of w_i relu(h U_i)^2 D_i
           + relu(h U_s)^2 D_s   (the shared expert).
     The tree holds experts [expert_offset, expert_offset + E_held) of the
     router's `router_experts`; what an absent expert would add is left out.
     Every held expert is applied to every token and weighted (0 where the
     token did not choose it).
  *  q|k|v = h qkv_w; no rotary or other position term;
     softmax(q k^T / sqrt(hd) + causal) v; q head i uses kv head i // (H/KVH).

Departures from the published model are listed in the configuration file
under `assumed`.  `prec` names the precision of the operands of every
bf16-stated matmul (projections, experts, attention, head): "f32" is the
reference proper, "bf16"/"fp8" round both operands first (the control of
`correct`).  The router's float32 product is never rounded.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def _round_operand(x, prec: str):
    if prec == "f32":
        return x
    if prec == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    if prec == "fp8":
        # e4m3 with one scale per tensor, amax -> 448 (the usual recipe)
        s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    raise ValueError(f"unknown precision {prec!r}")


def _mm(a, b, prec: str):
    return jnp.matmul(_round_operand(a, prec), _round_operand(b, prec),
                      precision=HIGHEST)


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


# ---------------------------------------------------------------------------
# the three mixers, on normed h [B, S, D] float32
# ---------------------------------------------------------------------------

def mamba_mixer(lp, h, model, prec: str = "f32"):
    B, S, _ = h.shape
    H, P, N, G, K = (model["mamba_num_heads"], model["mamba_head_dim"],
                     model["ssm_state_size"], model["n_groups"],
                     model["conv_kernel"])
    d_inner = H * P
    conv_dim = d_inner + 2 * G * N
    zxbcdt = _mm(h, lp["in_w"], prec)
    z = zxbcdt[..., :d_inner]
    xbc = zxbcdt[..., d_inner:d_inner + conv_dim]
    dt = zxbcdt[..., d_inner + conv_dim:]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = silu(sum(lp["conv_w"][j] * padded[:, j:j + S] for j in range(K))
               + lp["conv_b"])
    x = xbc[..., :d_inner].reshape(B, S, H, P)
    Bm = jnp.repeat(xbc[..., d_inner:d_inner + G * N].reshape(B, S, G, N),
                    H // G, axis=2)                              # [B, S, H, N]
    Cm = jnp.repeat(xbc[..., d_inner + G * N:].reshape(B, S, G, N),
                    H // G, axis=2)
    dt = jax.nn.softplus(dt + lp["dt_bias"])                     # [B, S, H]
    A = -jnp.exp(lp["A_log"])

    def step(state, inp):                                        # one position
        x_t, b_t, c_t, dt_t = inp
        state = state * jnp.exp(dt_t * A)[..., None, None] + \
            (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.sum(state * c_t[:, :, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((B, H, P, N), F32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (x, Bm, Cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + lp["D"][:, None] * x             # [B, S, H, P]
    y = (y.reshape(B, S, d_inner) * silu(z)).reshape(B, S, G, d_inner // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + model["norm_eps"])
    return _mm(y.reshape(B, S, d_inner) * lp["gnorm_w"], lp["out_w"], prec)


def route(lp, h, model):
    """(scores-derived weights [.., E_all] float32, zero off the top-k)."""
    s = jax.nn.sigmoid(jnp.matmul(h, lp["router_w"], precision=HIGHEST))
    _, idx = jax.lax.top_k(s + lp["router_bias"], model["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32), axis=-2)
    w = s * chosen
    if model["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * model["routed_scaling_factor"]


def shared_expert(lp, h, prec: str = "f32"):
    return _mm(relu2(_mm(h, lp["shared_up_w"], prec)), lp["shared_down_w"],
               prec)


def expert_mixer(lp, h, model, prec: str = "f32"):
    """The part of the layer that the held experts give, plus the shared
    expert.  lp["up_w"] / ["down_w"] may be any float type: one expert at a
    time is read as float32."""
    first = model.get("expert_offset", 0)
    held = lp["up_w"].shape[0]
    w = route(lp, h, model)[..., first:first + held]             # [B, S, held]

    def one(acc, inp):
        up, down, w_e = inp
        y = _mm(relu2(_mm(h, up.astype(F32).T, prec)), down.astype(F32), prec)
        return acc + w_e[..., None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (lp["up_w"], lp["down_w"],
                              jnp.moveaxis(w, -1, 0)))
    return routed + shared_expert(lp, h, prec)


def attention_mixer(lp, h, model, prec: str = "f32"):
    B, S, _ = h.shape
    H, KVH, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                  model["head_dim"])
    qkv = _mm(h, lp["qkv_w"], prec)
    q = qkv[..., :H * hd].reshape(B, S, KVH, H // KVH, hd)
    k = qkv[..., H * hd:(H + KVH) * hd].reshape(B, S, KVH, hd)
    v = qkv[..., (H + KVH) * hd:].reshape(B, S, KVH, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", _round_operand(q, prec),
                   _round_operand(k, prec), precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    a = jnp.einsum("bkgqs,bskd->bqkgd", _round_operand(p, prec),
                   _round_operand(v, prec), precision=HIGHEST)
    return _mm(a.reshape(B, S, H * hd), lp["proj_w"], prec)


MIXERS = {"M": mamba_mixer, "E": expert_mixer, "*": attention_mixer}
_BIG = ("up_w", "down_w")           # read as float32 one expert at a time


def layer(letter: str, lp, x, model, prec: str = "f32"):
    lp = {k: v if k in _BIG else v.astype(F32) for k, v in lp.items()}
    h = rms_norm(x, lp["norm_w"], model["norm_eps"])
    return x + MIXERS[letter](lp, h, model, prec)


def head_logits(top, x, model, prec: str = "f32"):
    h = rms_norm(x, top["lnf_w"].astype(F32), model["norm_eps"])
    return _mm(h, top["lm_head"].astype(F32), prec)


# ---------------------------------------------------------------------------
# layer-by-layer driver
# ---------------------------------------------------------------------------

def _frozen(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _jit_layer(letter, model_items, prec):
    model = dict(model_items)
    return jax.jit(lambda lp, x: layer(letter, lp, x, model, prec))


def hidden(params, tokens, model, prec: str = "f32"):
    """Hidden states [B, S, D] before the final norm, float32."""
    frozen = _frozen(model)
    x = jnp.take(params["wte"], tokens, axis=0).astype(F32)
    for letter, lp in zip(model["hybrid_override_pattern"], params["layers"]):
        x = _jit_layer(letter, frozen, prec)(lp, x)
    return x


def logits_at(params, tokens, rows, cols, model, prec: str = "f32",
              block_rows: int = 2):
    """Reference logits [n, V] at positions (rows[i], cols[i]) of a full
    causal forward over tokens [B, S] (right-padded; padding never reaches an
    earlier position: every mixer is causal).  `block_rows` sequences go
    through at a time, so that the attention scores and one expert's float32
    weights fit beside the weights themselves."""
    import numpy as np
    rows, cols = np.asarray(rows), np.asarray(cols)
    top = {k: v for k, v in params.items() if k != "layers"}
    head = jax.jit(lambda t, h: head_logits(t, h, model, prec))
    order, blocks = [], []
    for r0 in range(0, tokens.shape[0], block_rows):
        mine = np.nonzero((rows >= r0) & (rows < r0 + block_rows))[0]
        if not mine.size:
            continue
        x = hidden(params, jnp.asarray(tokens[r0:r0 + block_rows]), model,
                   prec)
        blocks.append(head(top, x[rows[mine] - r0, cols[mine]]))
        order.append(mine)
    return jnp.concatenate(blocks)[np.argsort(np.concatenate(order))]
