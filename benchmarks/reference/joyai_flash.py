"""The plain reference of the `joyai_llm_flash` configuration
(JoyAI-LLM-Flash, 48B-A2.7B) FOR TRAINING: forward, both losses, gradients,
AdamW and the router's bias rule in straightforward `jax.numpy`, float32,
matmul precision "highest".  No kernel, no cache, no grouped product, no
rematerialisation policy; nothing imported from the program.

It reads a `model` dict (the published keys of the configuration file with
the share and the assumed values, `drivers/train_joyai.model_of`) and a
parameter tree in the layout the program trains and serves
(`harness/weights_joyai.py` makes it from `--seed`):

    wte [V, D]; lnf_w [D]; lm_head [D, V]; layers: one dict per letter of
    `mixer_pattern` (a published layer is two mixers: "LF" for the leading
    dense layer, "LE" after it), each with norm_w [D] and
      L: q_a_w [D, q_lora], q_norm_w [q_lora], q_b_w [q_lora, H (N + R)]
         (per head [nope | rope]), kv_a_w [D, C + R] (columns c_kv | k_r),
         kv_norm_w [C], kv_b_k_w [H, N, C] and kv_b_v_w [H, C, Vh] (the
         published kv_b_proj per head), o_w [H Vh, D]
      F: gate_w up_w [D, F], down_w [F, D]
      E: router_w [D, E_all], router_bias [E_all] (float32),
         gate_w up_w down_w [E_held, F, D] (gate and up transposed),
         shared_gate_w shared_up_w [D, Fs], shared_down_w [Fs, D]
    mtp: hnorm_w enorm_w [D], eh_proj [2 D, D], layers [L, E], norm_w [D]

One plain residual stream: x <- x + F(RMSNorm(x; norm_w, rms_norm_eps)).

  L  c_q = RMSNorm(h q_a_w; q_norm_w);  q = c_q q_b_w -> H x [nope | rope];
     [c_kv | k_r] = h kv_a_w;  c_kv <- RMSNorm(c_kv; kv_norm_w);  rotary
     (plain frequencies theta^(-2i/R): rope_scaling is null; half-split
     pairing) on q's rope part and on k_r, one k_r for all heads;
     k_nope,h = c_kv kv_b_k_w[h]^T, v_h = c_kv kv_b_v_w[h];
     score = (q_nope . k_nope + q_rope . k_r) (N + R)^-1/2; causal softmax;
     out = concat_h(sum p v_h) o_w.
  F  down(silu(h gate_w) * (h up_w)).
  E  s = sigmoid(h router_w) (float32); top-k of s + router_bias;
     w_i = s_i / (sum_topk s + 1e-20) * routed_scaling_factor;
     out = sum over the chosen experts HELD here of w_i expert_i(h) +
     expert_shared(h), expert(h) = down(silu(h gate) * (h up)).  The tree
     holds experts [expert_offset, expert_offset + E_held) of the router's
     `router_experts`; what an absent expert would add is left out.  The
     scores take their gradient through w_i; the choice carries none, so
     router_bias takes none.

  loss_main = mean_i CE(head(RMSNorm(x_i; lnf_w)), t_{i+1})
  next-n module (DeepSeek-V3, arXiv:2412.19437 section 2.2, depth 1), on x
  BEFORE the final norm:
     h'_i = [RMSNorm(x_i; hnorm_w) ; RMSNorm(wte[t_{i+1}]; enorm_w)] eh_proj
     h'' = E(L(h'))   (one whole layer, its own weights, same positions)
     loss_mtp = mean_i CE(head(RMSNorm(h''_i; mtp.norm_w)), t_{i+2})
  over the positions whose t_{i+2} exists (labels[i] = t_{i+1} is given for
  every i, so all but the last);  loss = loss_main + mtp_loss_weight loss_mtp.

  AdamW as `dense_lm.adamw_update` (every leaf decays, the norms' gains too:
  what the trainer does) after a global-norm clip; `router_bias` is outside
  it and moves by b_e += router_bias_update_rate x sign(mean load - load_e),
  load_e the times expert e (of ALL the router's) was chosen in the step.

Departures from the published description: none in the layers; the module's
equations, the loss weight, the bias rule's step and the initialiser are the
family's, not the config's (listed under `assumed` in the configuration
file); rotary pairs column i with column i + R/2 (seeded weights cannot tell
the interleaved order apart).

`prec` names the precision of the operands of every bf16-stated matmul
(projections, experts, attention, heads): "f32" is the reference proper,
"bf16"/"fp8" round both operands first, straight-through in the backward
pass (the control of `correct`).  The router's float32 product is never
rounded.  `fault` plants a wrong program in the reference's place:
"k_rope_off" (k_r scored unrotated), "mtp_off" (the module's loss left out
of the sum), "bias_rule_off" (router_bias never moved).

`loss_terms` is the whole plain function (differentiate it with `jax.grad`);
`loss_and_grads` computes the same numbers layer by layer and a sequence at a
time, attention over groups of heads, so that the published widths at 8,192
positions fit on one chip beside the float32 gradients.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .dense_lm import F32, HIGHEST, _mm, _round_operand, adamw_update, sq_norm

HEAD_GROUP = 4          # heads whose [S, S] scores are alive together
MTP_PATTERN = "LE"


def rms_norm(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def rotate(x, model):
    """Rotary on the last axis of x [B, S, ..., R] at positions 0..S-1."""
    if model.get("rope_scaling"):
        raise ValueError("this configuration has no rotary scaling")
    S, R = x.shape[1], x.shape[-1]
    inv = 1.0 / model["rope_theta"] ** (jnp.arange(0, R, 2, dtype=F32) / R)
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
    scale = 1.0 / math.sqrt(N + R)

    def heads(carry, inp):
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
        return carry, jnp.einsum("gbqs,gbsv->gbqv", _round_operand(p, prec),
                                 _round_operand(v, prec), precision=HIGHEST)

    G = math.gcd(H, HEAD_GROUP)

    def grouped(x):                           # [H, ...] -> [H / G, G, ...]
        return x.reshape((H // G, G) + x.shape[1:])
    # a group's scores are worked out again in the backward pass, so that
    # only one group's [G, S, S] is ever alive
    _, o = jax.lax.scan(jax.checkpoint(heads), None, (
        grouped(lp["kv_b_k_w"]), grouped(lp["kv_b_v_w"]),
        grouped(jnp.moveaxis(q_nope, 2, 0)),
        grouped(jnp.moveaxis(q_rope, 2, 0))))
    o = jnp.moveaxis(o.reshape((H,) + o.shape[2:]), 0, 2)       # [B, S, H, V]
    return _mm(o.reshape(B, S, -1), lp["o_w"], prec)


def dense_ffn(lp, h, model, prec: str = "f32", fault: str = ""):
    return _mm(silu(_mm(h, lp["gate_w"], prec)) * _mm(h, lp["up_w"], prec),
               lp["down_w"], prec)


def route(lp, h, model):
    """(weights [.., E_all] float32, zero off the top-k; chosen [.., E_all]
    0/1)."""
    s = jax.nn.sigmoid(jnp.matmul(h, lp["router_w"], precision=HIGHEST))
    _, idx = jax.lax.top_k(s + lp["router_bias"], model["num_experts_per_tok"])
    chosen = jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32), axis=-2)
    w = s * chosen
    if model["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * model["routed_scaling_factor"], chosen


def gated_experts(lp, h, model, prec: str = "f32", fault: str = ""):
    """(the part of the layer that the held experts give plus the shared
    expert once, load [E_all]: how often each of the router's experts was
    chosen).  Every held expert is computed for every token and weighted by
    w (nought where it was not chosen)."""
    first = model.get("expert_offset", 0)
    held = lp["up_w"].shape[0]
    w_all, chosen = route(lp, h, model)
    w = w_all[..., first:first + held]                           # [B, S, held]

    def one(acc, inp):
        gate, up, down, w_e = inp
        a = silu(_mm(h, gate.T, prec)) * _mm(h, up.T, prec)
        return acc + w_e[..., None] * _mm(a, down, prec), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h),
                             (lp["gate_w"], lp["up_w"], lp["down_w"],
                              jnp.moveaxis(w, -1, 0)))
    shared = _mm(silu(_mm(h, lp["shared_gate_w"], prec)) *
                 _mm(h, lp["shared_up_w"], prec), lp["shared_down_w"], prec)
    load = jnp.sum(chosen.reshape(-1, chosen.shape[-1]), axis=0)
    return routed + shared, jax.lax.stop_gradient(load)


def layer(letter: str, lp, x, model, prec: str = "f32", fault: str = ""):
    """One mixer with its residual: x [B, S, D] -> (x', load [E_all] of an
    expert layer or None)."""
    lp = {k: v.astype(F32) for k, v in lp.items()}
    h = rms_norm(x, lp["norm_w"], model["rms_norm_eps"])
    if letter == "L":
        return x + latent_attention(lp, h, model, prec, fault), None
    if letter == "F":
        return x + dense_ffn(lp, h, model, prec, fault), None
    if letter == "E":
        y, load = gated_experts(lp, h, model, prec, fault)
        return x + y, load
    raise ValueError(f"unknown mixer letter {letter!r}")


def ce_sum(norm_w, head, x, labels, model, prec: str = "f32"):
    """(-sum log p[label] over labels >= 0, their count)."""
    logits = _mm(rms_norm(x, norm_w.astype(F32), model["rms_norm_eps"]),
                 head.astype(F32), prec)
    lp = jax.nn.log_softmax(logits, axis=-1)
    pick = jnp.take_along_axis(lp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    mask = (labels >= 0).astype(F32)
    return -jnp.sum(pick * mask), jnp.sum(mask)


def mtp_input(mt, x, nxt, model, prec: str = "f32"):
    """h' = [RMSNorm_h(x) ; RMSNorm_e(Emb(t_{i+1}))] eh_proj."""
    eps = model["rms_norm_eps"]
    return _mm(jnp.concatenate(
        [rms_norm(x, mt["hnorm_w"].astype(F32), eps),
         rms_norm(nxt, mt["enorm_w"].astype(F32), eps)], axis=-1),
        mt["eh_proj"].astype(F32), prec)


def mtp_labels(labels):
    """Position i's target t_{i+2} = labels[i + 1]; the last position has
    none (-100), nor has a position whose own label is ignored."""
    nxt = jnp.concatenate([labels[:, 1:], jnp.full_like(labels[:, :1], -100)],
                          axis=1)
    return jnp.where((labels >= 0) & (nxt >= 0), nxt, -100)


# ---------------------------------------------------------------------------
# the whole plain function
# ---------------------------------------------------------------------------

def loss_terms(params, tokens, labels, model, prec: str = "f32",
               fault: str = ""):
    """(loss_main, loss_mtp, loads [expert layers, E_all]: the main model's
    expert layers in order, then the module's)."""
    x = jnp.take(params["wte"], tokens, axis=0).astype(F32)
    loads = []
    for letter, lp in zip(model["mixer_pattern"], params["layers"]):
        x, load = layer(letter, lp, x, model, prec, fault)
        if load is not None:
            loads.append(load)
    ls, n = ce_sum(params["lnf_w"], params["lm_head"], x, labels, model, prec)
    loss_main, loss_mtp = ls / n, jnp.zeros((), F32)
    if model["num_nextn_predict_layers"]:
        mt = params["mtp"]
        nxt = jnp.take(params["wte"], jnp.maximum(labels, 0), axis=0
                       ).astype(F32)
        h = mtp_input(mt, x, nxt, model, prec)
        for letter, lp in zip(MTP_PATTERN, mt["layers"]):
            h, load = layer(letter, lp, h, model, prec, fault)
            if load is not None:
                loads.append(load)
        ls, n = ce_sum(mt["norm_w"], params["lm_head"], h, mtp_labels(labels),
                       model, prec)
        loss_mtp = ls / jnp.maximum(n, 1.0)
    return loss_main, loss_mtp, jnp.stack(loads)


def mtp_weight(model, fault: str = "") -> float:
    return 0.0 if fault == "mtp_off" else model["mtp_loss_weight"]


def loss(params, tokens, labels, model, prec: str = "f32", fault: str = ""):
    main, mtp, _ = loss_terms(params, tokens, labels, model, prec, fault)
    return main + mtp_weight(model, fault) * mtp


def bias_step(bias, load, model, fault: str = ""):
    """The router's bias after the rule's one step on this step's `load`."""
    if fault == "bias_rule_off":
        return bias
    return bias + model["router_bias_update_rate"] * \
        jnp.sign(jnp.mean(load) - load)


# ---------------------------------------------------------------------------
# the same numbers, layer by layer and a sequence at a time
# ---------------------------------------------------------------------------
#
# Gradients are float32 and are summed in place: every jitted piece takes the
# running sum, donated, and returns it with its own part added.

def _frozen(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, (int, float, str, bool))))


@functools.lru_cache(maxsize=None)
def _jit_layer(letter, model_items, prec, fault):
    model = dict(model_items)
    return jax.jit(lambda lp, x: layer(letter, lp, x, model, prec, fault))


@functools.lru_cache(maxsize=None)
def _jit_layer_bwd(letter, model_items, prec, fault):
    model = dict(model_items)

    def bwd(lp, x, dy, acc):
        # weights read as float32 before the function that is differentiated,
        # so that their gradients are float32 too
        lp = {k: v.astype(F32) for k, v in lp.items()}
        _, vjp = jax.vjp(lambda p, xx: layer(letter, p, xx, model, prec,
                                             fault)[0], lp, x)
        g, dx = vjp(dy)
        return jax.tree_util.tree_map(jnp.add, acc, g), dx
    return jax.jit(bwd, donate_argnums=(3,))


@functools.lru_cache(maxsize=None)
def _jit_head(model_items, prec):
    model = dict(model_items)

    def fn(norm_w, head, x, labels, weight, acc_norm, acc_head):
        """The head's loss sum, and weight x its gradients added to the
        running sums."""
        f = lambda nw, hd, xx: ce_sum(nw, hd, xx, labels, model, prec)[0]
        ls, (g_n, g_h, dx) = jax.value_and_grad(f, argnums=(0, 1, 2))(
            norm_w.astype(F32), head.astype(F32), x)
        return ls, acc_norm + weight * g_n, acc_head + weight * g_h, \
            weight * dx
    return jax.jit(fn, donate_argnums=(5, 6))


@functools.lru_cache(maxsize=None)
def _jit_mtp_input(model_items, prec):
    model = dict(model_items)
    fwd = jax.jit(lambda mt, x, nxt: mtp_input(mt, x, nxt, model, prec))

    def bwd(mt, x, nxt, dy, acc):
        mt = {k: v.astype(F32) for k, v in mt.items()}
        _, vjp = jax.vjp(lambda m, xx, nn: mtp_input(m, xx, nn, model, prec),
                         mt, x, nxt)
        g, dx, dnxt = vjp(dy)
        return jax.tree_util.tree_map(jnp.add, acc, g), dx, dnxt
    return fwd, jax.jit(bwd, donate_argnums=(4,))


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_grad(acc, tok, dx):
    return acc.at[tok.reshape(-1)].add(dx.reshape(-1, dx.shape[-1]))


_zeros_f32 = jax.jit(lambda t: jax.tree_util.tree_map(
    lambda x: jnp.zeros(x.shape, F32), t))
_MTP_TOP = ("hnorm_w", "enorm_w", "eh_proj")


def loss_and_grads(params, tokens, labels, model, prec: str = "f32",
                   fault: str = "", rows_per_block: int = 1):
    """(loss_main, loss_mtp, loads [expert layers, E_all], float32 gradients
    of loss_main + weight x loss_mtp in the tree's own layout) over all of
    tokens / labels [B, S], `rows_per_block` sequences at a time."""
    frozen = _frozen(model)
    head = _jit_head(frozen, prec)
    mtp_fwd, mtp_bwd = _jit_mtp_input(frozen, prec)
    pattern, layers = model["mixer_pattern"], params["layers"]
    with_mtp = bool(model["num_nextn_predict_layers"])
    lam = mtp_weight(model, fault)
    B, S = tokens.shape
    labels = jnp.asarray(labels)
    mlab = mtp_labels(labels)
    n_main = float(jnp.sum(labels >= 0))
    n_mtp = max(float(jnp.sum(mlab >= 0)), 1.0)
    g = {"wte": _zeros_f32(params["wte"]), "lnf_w": _zeros_f32(params["lnf_w"]),
         "lm_head": _zeros_f32(params["lm_head"]),
         "layers": [_zeros_f32(lp) for lp in layers]}
    if with_mtp:
        mt = params["mtp"]
        mt_top = {k: mt[k] for k in _MTP_TOP}
        g["mtp"] = {"layers": [_zeros_f32(lp) for lp in mt["layers"]],
                    "norm_w": _zeros_f32(mt["norm_w"])}
        g_mt_top = _zeros_f32(mt_top)
    sums = [0.0, 0.0]
    loads = None
    for r0 in range(0, B, rows_per_block):
        tok = jnp.asarray(tokens[r0:r0 + rows_per_block])
        lab = labels[r0:r0 + rows_per_block]
        xs, mine = [jnp.take(params["wte"], tok, axis=0).astype(F32)], []
        for letter, lp in zip(pattern, layers):
            x, load = _jit_layer(letter, frozen, prec, fault)(lp, xs[-1])
            xs.append(x)
            if load is not None:
                mine.append(load)
        x_last = xs.pop()
        dx = jnp.zeros_like(x_last)
        if with_mtp:
            safe = jnp.maximum(lab, 0)
            nxt = jnp.take(params["wte"], safe, axis=0).astype(F32)
            hs = [mtp_fwd(mt_top, x_last, nxt)]
            for letter, lp in zip(MTP_PATTERN, mt["layers"]):
                h, load = _jit_layer(letter, frozen, prec, fault)(lp, hs[-1])
                hs.append(h)
                if load is not None:
                    mine.append(load)
            ls, g["mtp"]["norm_w"], g["lm_head"], dh = head(
                mt["norm_w"], params["lm_head"], hs.pop(),
                mlab[r0:r0 + rows_per_block], jnp.asarray(lam / n_mtp, F32),
                g["mtp"]["norm_w"], g["lm_head"])
            sums[1] += float(ls)
            for l in range(len(MTP_PATTERN) - 1, -1, -1):
                g["mtp"]["layers"][l], dh = _jit_layer_bwd(
                    MTP_PATTERN[l], frozen, prec, fault)(
                        mt["layers"][l], hs.pop(), dh, g["mtp"]["layers"][l])
            g_mt_top, dx, dnxt = mtp_bwd(mt_top, x_last, nxt, dh, g_mt_top)
            g["wte"] = _embed_grad(g["wte"], safe, dnxt)
        ls, g["lnf_w"], g["lm_head"], dmain = head(
            params["lnf_w"], params["lm_head"], x_last, lab,
            jnp.asarray(1.0 / n_main, F32), g["lnf_w"], g["lm_head"])
        sums[0] += float(ls)
        dx = dx + dmain
        for l in range(len(pattern) - 1, -1, -1):
            g["layers"][l], dx = _jit_layer_bwd(pattern[l], frozen, prec,
                                                fault)(
                layers[l], xs.pop(), dx, g["layers"][l])
        g["wte"] = _embed_grad(g["wte"], tok, dx)
        mine = jnp.stack(mine)
        loads = mine if loads is None else loads + mine
    if with_mtp:
        g["mtp"].update(g_mt_top)
    return sums[0] / n_main, sums[1] / n_mtp, loads, g


_norms = jax.jit(lambda t: jax.tree_util.tree_map(
    lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))


@functools.lru_cache(maxsize=None)
def _jit_update(step: int, optimizer_items):
    optimizer = dict(optimizer_items)
    return jax.jit(lambda p, g, m, v, s: adamw_update(p, g, m, v, step, s,
                                                      optimizer),
                   donate_argnums=(0, 2, 3))


def train_step(params, opt, tokens, labels, step: int, model, optimizer: dict,
               prec: str = "f32", fault: str = "", rows_per_block: int = 1):
    """One step of the reference's training: gradients, global-norm clip,
    AdamW over every leaf but `router_bias`, the bias rule.  `opt` = {"m",
    "v"}: trees like `params` (any float type; `router_bias` entries are
    carried and never read).  Returns (params, opt, readings): loss_main,
    loss_mtp, loads, grad_norm (before the clip), grads: {leaf path: norm of
    the clipped gradient}."""
    main, mtp, loads, g = loss_and_grads(params, tokens, labels, model, prec,
                                         fault, rows_per_block)
    gnorm = float(jnp.sqrt(sq_norm(g)))
    clip = optimizer["grad_clip_norm"]
    scale = min(clip / max(gnorm, clip), 1.0) if clip else 1.0
    readings = {"loss_main": main, "loss_mtp": mtp, "loads": loads,
                "grad_norm": gnorm,
                "grads": {k: float(v) * scale for k, v in
                          flat(jax.device_get(_norms(g))).items()}}
    update = _jit_update(step, tuple(sorted(optimizer.items())))
    s = jnp.asarray(scale, F32)
    it = iter(loads)

    def group(p, gg, m, v):
        """One group of leaves (a layer, or the leaves outside the layers)."""
        bias = p.get("router_bias")
        rest = lambda t: {k: x for k, x in t.items() if k != "router_bias"}
        new_p, new_m, new_v = update(rest(p), rest(gg), rest(m), rest(v), s)
        if bias is not None:
            new_p["router_bias"] = bias_step(bias, next(it), model, fault)
            new_m["router_bias"], new_v["router_bias"] = \
                m["router_bias"], v["router_bias"]
        return new_p, new_m, new_v

    new = [{}, {}, {}]
    for (where, p_grp), (_, g_grp), (_, m_grp), (_, v_grp) in zip(
            groups(params), groups(g), groups(opt["m"]), groups(opt["v"])):
        for tree, leaves in zip(new, group(p_grp, g_grp, m_grp, v_grp)):
            _put(tree, where, leaves)
    # float32 gradients cannot alias the results they were donated for
    for leaf in jax.tree_util.tree_leaves(g):
        if not leaf.is_deleted():
            leaf.delete()
    return new[0], {"m": new[1], "v": new[2]}, readings


def groups(p):
    """[(where the group lives, its leaves)] of a tree in the layout above:
    each layer, each of the module's layers, the module's other leaves, the
    leaves outside the layers; the expert layers come in the order of
    `loads`."""
    out = [(("layers", l), lp) for l, lp in enumerate(p["layers"])]
    if "mtp" in p:
        out += [(("mtp", "layers", l), lp)
                for l, lp in enumerate(p["mtp"]["layers"])]
        out.append((("mtp",), {k: v for k, v in p["mtp"].items()
                               if k != "layers"}))
    out.append(((), {k: v for k, v in p.items()
                     if k not in ("layers", "mtp")}))
    return out


def _put(tree, where, leaves):
    """`leaves` into the nested tree at `where`: ("layers", l) is the l-th
    entry of a list, ("mtp",) and () take the leaves beside what is there."""
    node = tree
    for i, key in enumerate(where):
        if key == "layers":
            lst = node.setdefault("layers", [])
            assert where[i + 1] == len(lst)
            lst.append(leaves)
            return
        node = node.setdefault(key, {})
    node.update(leaves)


def flat(tree, prefix: str = "") -> dict:
    """{"layers.3.up_w": leaf, "mtp.layers.1.gate_w": leaf, "wte": leaf}."""
    out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            out.update(flat(v, f"{prefix}{k}."))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            out.update(flat(v, f"{prefix}{i}."))
    else:
        out[prefix[:-1]] = tree
    return out
