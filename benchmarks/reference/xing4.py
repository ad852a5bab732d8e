"""The plain reference of the `xing4_0` configuration (Xing4.0-29B-A4B):
DeepSeek-V2/V3 latent attention in its EXPANDED form, the V3 router over
gated experts, and manifold-constrained hyper-connections (arXiv:2512.24880,
over arXiv:2409.19606) around every mixer, in straightforward `jax.numpy`,
float32, matmul precision "highest".  No kernel, no cache, no absorbed
product, no grouped product, no batching; nothing imported from the program.

It reads a `model` dict (the published keys of the configuration file with
the share and the assumed values, see `drivers/serve_xing4.model_of`) and a
parameter tree in the layout the program is handed
(`harness/weights_xing4.py` makes it from `--seed`):

    wte [V, D]; lnf_w [D]; lm_head [D, V]; layers: one dict per letter of
    `mixer_pattern` (a published layer is two mixers: "LF" for a leading
    dense layer, "LE" after it), each with norm_w [D] and the streams' mixes
    hc_phi [n D, 2 n + n^2] (columns pre | post | res), hc_alpha [3],
    hc_b [2 n + n^2] (float32), and
      L: q_a_w [D, q_lora], q_norm_w [q_lora], q_b_w [q_lora, H (N + R)]
         (per head [nope | rope]), kv_a_w [D, C + R] (columns c_kv | k_r),
         kv_norm_w [C], kv_b_k_w [H, N, C] and kv_b_v_w [H, C, Vh] (the
         published kv_b_proj per head, W_kvb,h = [kv_b_k_w[h]^T | kv_b_v_w[h]]),
         o_w [H Vh, D]
      F: gate_w up_w [D, F], down_w [F, D]
      E: router_w [D, E_all], router_bias [E_all] (float32),
         gate_w up_w down_w [E_held, F, D] (gate and up transposed),
         shared_gate_w shared_up_w [D, Fs], shared_down_w [Fs, D]

The residual state of a token is X [n, D], n = hc_mult = 4; X_0 is the
embedding row repeated n times.  For each mixer F with its own phi, alpha, b:

    x~ = vec(X) / sqrt(mean(vec(X)^2) + hc_eps)                  (no gain)
    H~_pre  = alpha_pre  (x~ phi_pre)  + b_pre                   [n]
    H~_post = alpha_post (x~ phi_post) + b_post                  [n]
    H~_res  = alpha_res  mat(x~ phi_res) + b_res                 [n, n]
    H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
    H_res = Sinkhorn(exp(clip(H~_res, clamp_min, clamp_max))): hc_sinkhorn_iters
            rounds of row- then column-normalisation
    h = H_pre X;  y = F(RMSNorm(h; norm_w, rms_norm_eps));
    X <- H_res X + H_post^T y
After the last mixer the n streams are summed; RMSNorm(lnf_w) and the head.

  L  c_q = RMSNorm(h q_a_w; q_norm_w);  q = c_q q_b_w -> H x [nope | rope];
     [c_kv | k_r] = h kv_a_w;  c_kv <- RMSNorm(c_kv; kv_norm_w);  rotary
     (YaRN frequencies, half-split pairing) on q's rope part and on k_r, one
     k_r for all heads;  k_nope,h = c_kv kv_b_k_w[h]^T, v_h = c_kv kv_b_v_w[h];
     score = (q_nope . k_nope + q_rope . k_r) (N + R)^-1/2 mscale^2,
     mscale = 0.1 mscale_all_dim ln(factor) + 1;  causal softmax;
     out = concat_h(sum p v_h) o_w.
  F  down(silu(h gate_w) * (h up_w)).
  E  s = sigmoid(h router_w) (float32); top-k of s + router_bias;
     w_i = s_i / (sum_topk s + 1e-20) * routed_scaling_factor;
     out = sum over the chosen experts HELD here of w_i expert_i(h) +
     expert_shared(h), expert(h) = down(silu(h gate) * (h up)).  The tree
     holds experts [expert_offset, expert_offset + E_held) of the router's
     `router_experts`; what an absent expert would add is left out.

What the paper and the config leave open, and what was chosen (also under
`assumed` in the configuration file): x~'s norm has no gain; the streams are
read out by their sum; rotary pairs column i with column i + R/2 (the
published checkpoints store the interleaved order and permute it at load,
which seeded weights cannot tell apart); the multi-token-prediction module
is not part of the main model's logits and is not here.

`prec` names the precision of the operands of every bf16-stated matmul
(projections, experts, attention, head): "f32" is the reference proper,
"bf16"/"fp8" round both operands first (the control of `correct`).  The
router's and the mixes' float32 products are never rounded.  `fault` plants
a wrong program in the reference's place, for the checks: "sinkhorn_skipped"
(H_res = exp(clip(.)) as it stands, never normalised) and "k_rope_off" (k_r
cached and scored unrotated).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .nemotron_h import F32, HIGHEST, _mm, _round_operand, rms_norm, silu

HEAD_GROUP = 4          # heads whose [S, S] scores are alive together


def yarn_inv_freq(dim: int, theta: float, sc: dict) -> np.ndarray:
    """YaRN's inverse frequencies [dim / 2] as the DeepSeek-V2/V3 code
    computes them (numpy, float64 until the end)."""
    idx = np.arange(0, dim, 2, dtype=np.float64)
    extra = 1.0 / theta ** (idx / dim)
    inter = extra / sc["factor"]

    def correction_dim(rot):
        return dim * math.log(sc["original_max_position_embeddings"] /
                              (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def softmax_scale(model: dict) -> float:
    sc = model["rope_scaling"]
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
    return m * m / math.sqrt(model["qk_nope_head_dim"] +
                             model["qk_rope_head_dim"])


def rotate(x, model):
    """Rotary on the last axis of x [B, S, ..., R] at positions 0..S-1."""
    S, R = x.shape[1], x.shape[-1]
    inv = jnp.asarray(yarn_inv_freq(R, model["rope_theta"],
                                    model["rope_scaling"]))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    shape = (1, S) + (1,) * (x.ndim - 3) + (R // 2,)
    sin, cos = jnp.sin(ang).reshape(shape), jnp.cos(ang).reshape(shape)
    a, b = x[..., :R // 2], x[..., R // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


# ---------------------------------------------------------------------------
# the three mixers, on normed h [B, S, D] float32
# ---------------------------------------------------------------------------

def latent_attention(lp, h, model, prec: str = "f32", fault: str = ""):
    B, S, _ = h.shape
    H, C = model["num_attention_heads"], model["kv_lora_rank"]
    N, R = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    eps = model["rms_norm_eps"]
    cq = rms_norm(_mm(h, lp["q_a_w"], prec), lp["q_norm_w"], eps)
    q = _mm(cq, lp["q_b_w"], prec).reshape(B, S, H, N + R)
    q_nope, q_rope = q[..., :N], rotate(q[..., N:], model)
    ckv = _mm(h, lp["kv_a_w"], prec)
    c = rms_norm(ckv[..., :C], lp["kv_norm_w"], eps)
    k_r = ckv[..., C:] if fault == "k_rope_off" else rotate(ckv[..., C:],
                                                              model)
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    scale = softmax_scale(model)

    def heads(_, inp):
        wk, wv, qn, qr = inp                 # [G, N, C] [G, C, V] [G, B, S, .]
        k_nope = jnp.einsum("bsc,gnc->gbsn", _round_operand(c, prec),
                            _round_operand(wk, prec), precision=HIGHEST)
        v = jnp.einsum("bsc,gcv->gbsv", _round_operand(c, prec),
                       _round_operand(wv, prec), precision=HIGHEST)
        s = jnp.einsum("gbqn,gbsn->gbqs", _round_operand(qn, prec),
                       _round_operand(k_nope, prec), precision=HIGHEST) + \
            jnp.einsum("gbqr,bsr->gbqs", _round_operand(qr, prec),
                       _round_operand(k_r, prec), precision=HIGHEST)
        p = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), axis=-1)
        return None, jnp.einsum("gbqs,gbsv->gbqv", _round_operand(p, prec),
                                _round_operand(v, prec), precision=HIGHEST)

    G = math.gcd(H, HEAD_GROUP)

    def grouped(x):                           # [H, ...] -> [H / G, G, ...]
        return x.reshape((H // G, G) + x.shape[1:])
    _, o = jax.lax.scan(heads, None, (
        grouped(lp["kv_b_k_w"]), grouped(lp["kv_b_v_w"]),
        grouped(jnp.moveaxis(q_nope, 2, 0)), grouped(jnp.moveaxis(q_rope, 2, 0))))
    o = jnp.moveaxis(o.reshape((H,) + o.shape[2:]), 0, 2)       # [B, S, H, V]
    return _mm(o.reshape(B, S, -1), lp["o_w"], prec)


def dense_ffn(lp, h, model, prec: str = "f32", fault: str = ""):
    return _mm(silu(_mm(h, lp["gate_w"], prec)) * _mm(h, lp["up_w"], prec),
               lp["down_w"], prec)


def route(lp, h, model):
    """(scores-derived weights [.., E_all] float32, zero off the top-k)."""
    s = jax.nn.sigmoid(jnp.matmul(h, lp["router_w"], precision=HIGHEST))
    _, idx = jax.lax.top_k(s + lp["router_bias"], model["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32), axis=-2)
    w = s * chosen
    if model["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * model["routed_scaling_factor"]


def gated_experts(lp, h, model, prec: str = "f32", fault: str = ""):
    """The part of the layer that the held experts give, plus the shared
    expert once.  gate_w / up_w / down_w may be any float type: one expert
    at a time is read as float32."""
    first = model.get("expert_offset", 0)
    held = lp["up_w"].shape[0]
    w = route(lp, h, model)[..., first:first + held]             # [B, S, held]

    def one(acc, inp):
        gate, up, down, w_e = inp
        a = silu(_mm(h, gate.astype(F32).T, prec)) * \
            _mm(h, up.astype(F32).T, prec)
        return acc + w_e[..., None] * _mm(a, down.astype(F32), prec), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (lp["gate_w"], lp["up_w"], lp["down_w"],
                              jnp.moveaxis(w, -1, 0)))
    shared = _mm(silu(_mm(h, lp["shared_gate_w"], prec)) *
                 _mm(h, lp["shared_up_w"], prec), lp["shared_down_w"], prec)
    return routed + shared


MIXERS = {"L": latent_attention, "F": dense_ffn, "E": gated_experts}
_BIG = ("gate_w", "up_w", "down_w")     # an E layer's: float32 one at a time


# ---------------------------------------------------------------------------
# the residual streams
# ---------------------------------------------------------------------------

def sinkhorn(m, iters: int):
    for _ in range(iters):
        m = m / jnp.sum(m, axis=-1, keepdims=True)
        m = m / jnp.sum(m, axis=-2, keepdims=True)
    return m


def stream_mixes(lp, X, model, fault: str = ""):
    """(H_pre [B, S, n], H_post [B, S, n], H_res [B, S, n, n]) of a mixer,
    from the residual state X [B, S, n, D]."""
    B, S, n, D = X.shape
    x = X.reshape(B, S, n * D)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                          + model["hc_eps"])
    m = jnp.matmul(x, lp["hc_phi"], precision=HIGHEST)
    a, b = lp["hc_alpha"], lp["hc_b"]
    pre = jax.nn.sigmoid(a[0] * m[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * m[..., 2 * n:] + b[2 * n:]).reshape(B, S, n, n)
    res = jnp.exp(jnp.clip(res, model["mhc_h_res_clamp_min"],
                           model["mhc_h_res_clamp_max"]))
    if fault != "sinkhorn_skipped":
        res = sinkhorn(res, model["hc_sinkhorn_iters"])
    return pre, post, res


def layer(letter: str, lp, X, model, prec: str = "f32", fault: str = ""):
    """One mixer with its hyper-connection: X [B, S, n, D] -> X'."""
    big = _BIG if letter == "E" else ()
    lp = {k: v if k in big else v.astype(F32) for k, v in lp.items()}
    pre, post, res = stream_mixes(lp, X, model, fault)
    h = jnp.einsum("bsn,bsnd->bsd", pre, X, precision=HIGHEST)
    y = MIXERS[letter](lp, rms_norm(h, lp["norm_w"], model["rms_norm_eps"]),
                       model, prec, fault)
    return jnp.einsum("bsij,bsjd->bsid", res, X, precision=HIGHEST) + \
        post[..., None] * y[:, :, None, :]


def head_logits(top, x, model, prec: str = "f32"):
    h = rms_norm(x, top["lnf_w"].astype(F32), model["rms_norm_eps"])
    return _mm(h, top["lm_head"].astype(F32), prec)


# ---------------------------------------------------------------------------
# layer-by-layer driver
# ---------------------------------------------------------------------------

def _frozen(model):
    def freeze(v):
        return tuple(sorted(v.items())) if isinstance(v, dict) else v
    return tuple(sorted((k, freeze(v)) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool, dict))))


@functools.lru_cache(maxsize=None)
def _jit_layer(letter, model_items, prec, fault):
    model = {k: dict(v) if isinstance(v, tuple) else v
             for k, v in model_items}
    return jax.jit(lambda lp, X: layer(letter, lp, X, model, prec, fault))


def hidden(params, tokens, model, prec: str = "f32", fault: str = ""):
    """Hidden states [B, S, D] before the final norm (the streams summed),
    float32."""
    frozen = _frozen(model)
    x = jnp.take(params["wte"], tokens, axis=0).astype(F32)
    X = jnp.broadcast_to(x[:, :, None, :],
                         x.shape[:2] + (model["hc_mult"], x.shape[-1]))
    for letter, lp in zip(model["mixer_pattern"], params["layers"]):
        X = _jit_layer(letter, frozen, prec, fault)(lp, X)
    return jnp.sum(X, axis=2)


def logits_at(params, tokens, rows, cols, model, prec: str = "f32",
              fault: str = "", block_rows: int = 1):
    """Reference logits [n, V] at positions (rows[i], cols[i]) of a full
    causal forward over tokens [B, S] (right-padded; padding never reaches an
    earlier position: every mixer is causal).  `block_rows` sequences go
    through at a time, so that `HEAD_GROUP` heads' scores, the four streams
    and one expert's float32 weights fit beside the weights themselves."""
    rows, cols = np.asarray(rows), np.asarray(cols)
    top = {k: v for k, v in params.items() if k != "layers"}
    head = jax.jit(lambda t, h: head_logits(t, h, model, prec))
    order, blocks = [], []
    for r0 in range(0, tokens.shape[0], block_rows):
        mine = np.nonzero((rows >= r0) & (rows < r0 + block_rows))[0]
        if not mine.size:
            continue
        x = hidden(params, jnp.asarray(tokens[r0:r0 + block_rows]), model,
                   prec, fault)
        blocks.append(head(top, x[rows[mine] - r0, cols[mine]]))
        order.append(mine)
    return jnp.concatenate(blocks)[np.argsort(np.concatenate(order))]
