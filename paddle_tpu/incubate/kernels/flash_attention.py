"""Flash attention for TPU.

Reference parity: `phi/kernels/gpu/flash_attn_kernel.cu` and
`flash_attn_grad_kernel.cu` (wrapping the flashattn CUDA lib).
TPU-native: Pallas kernels with online-softmax tiling —

- forward: K blocks form the innermost ("arbitrary") grid dimension with VMEM
  scratch carrying (acc, m, l); emits the per-row logsumexp `lse` alongside the
  output so the backward never re-runs the full forward.
- backward: ONE tiled kernel recomputing p = exp(s - lse) blockwise and computing
  s, p, dp, ds once a (query block, key block) pair for all of dq, dk, dv (five
  products a pair) — no S×S materialization, causal block skip.  dk and dv
  accumulate in block-sized scratch over the inner (query) axis; dq accumulates
  over the outer (key) axis in a float32 VMEM scratch resident for the whole
  (batch, head).  Only a length whose accumulator VMEM cannot hold
  (`_fused_bwd_fits`: past 65,536 positions at 192/256 wide) takes the
  flash-attention-2 dq / dkv split, which computes s, p, dp, ds twice.

Remat interplay: the custom_vjp forward tags its residuals (`flash_out`,
`flash_lse`) with `checkpoint_name`, so a surrounding `jax.checkpoint(policy=
save_only_these_names('flash_out', 'flash_lse'))` saves exactly those and the
block replay skips re-running the attention kernel entirely — q/k/v residuals are
recomputed by the (cheap) qkv-matmul replay while the kernel outputs come from the
saved names.  This kills the round-1 "attention forward runs ~3x" remat tax.

Fallbacks: CPU/debug or masked/dropout paths use the XLA composed implementation;
the Pallas path covers the causal/no-mask hot case used by GPT pretraining.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import _on_tpu, _out_struct

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# XLA reference implementation (fallback + numerics oracle for tests)
# ---------------------------------------------------------------------------

def attention_xla(q, k, v, mask=None, causal=False, scale=None, dropout_p=0.0,
                  dropout_key=None):
    """q,k,v: [B, S, H, D] (paddle layout)."""
    D = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        Lq, Lk = q.shape[1], k.shape[1]
        row = jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (Lq, Lk), 1)
        cmask = row + (Lk - Lq) >= col
        logits = jnp.where(cmask[None, None], logits, NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, NEG_INF)
        else:
            logits = logits + mask.astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out


# ---------------------------------------------------------------------------
# Pallas forward kernel: grid (BH, n_q, n_k), K innermost with scratch carry
# ---------------------------------------------------------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                      *, block_q: int, block_k: int, n_k: int, causal: bool,
                      scale: float):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # causal: whole block above the diagonal contributes nothing — skip compute
    run = True
    if causal:
        run = q_start + block_q - 1 >= k_start

    @pl.when(run if causal else (ki >= 0))
    def _compute():
        # keep MXU operands in the input dtype (bf16 runs 4x f32 on v5e);
        # accumulation stays f32 via preferred_element_type
        q = q_ref[0]                                    # [bq, D]
        k = k_ref[0]                                    # [bk, D]
        v = v_ref[0]                                    # [bk, D]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            row = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            col = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)      # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)            # [bq, 1]


# block sizes: bigger q/k tiles amortize Mosaic per-cell overhead; 1024 measured
# ~2x faster than 256 on v5e for the fwd sweep (VMEM: s/p tile is bq*bk*4 bytes)
FWD_BLOCK = 1024
BWD_BLOCK = 1024


def _pick_block(S: int, pref: int) -> int:
    """Largest block <= pref that divides S (falling back through 512/256/128),
    so odd-but-aligned lengths like 1536 stay on the Pallas path with 512 tiles
    instead of silently hitting the XLA fallback."""
    for b in (pref, 1024, 512, 256, 128):
        if b <= pref and S >= b and S % b == 0:
            return b
    return S


def _kernel_name(base: str, D: int, Dv: int) -> str:
    """The dense kernels keep their names; a score width that differs from
    the value width (latent attention expanded: 192 against 128) gets names
    of its own, so that a trace tells the two apart."""
    return f"flash_{base}" if D == Dv else f"flash_mla_{base}"


def _flash_fwd_impl(q, k, v, causal, scale):
    """q, k [B,S,H,D], v [B,S,H,Dv] -> (out [B,S,H,Dv], lse [B*H, S, 1] f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * H, S, D)
    kt = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * H, Sk, D)
    vt = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * H, Sk, Dv)

    block_q = _pick_block(S, FWD_BLOCK)
    block_k = _pick_block(Sk, FWD_BLOCK)
    n_k = Sk // block_k
    grid = (B * H, S // block_q, n_k)
    kernel = functools.partial(_flash_fwd_kernel, block_q=block_q, block_k=block_k,
                               n_k=n_k, causal=causal, scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        name=_kernel_name("fwd", D, Dv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((B * H, S, Dv), q.dtype, qt, kt, vt),
            _out_struct((B * H, S, 1), jnp.float32, qt, kt, vt),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qt, kt, vt)
    return jnp.transpose(out.reshape(B, H, S, Dv), (0, 2, 1, 3)), lse


# ---------------------------------------------------------------------------
# Pallas backward kernel: ONE sweep, grid (BH, n_k, n_q), queries innermost.
# s, p, dp and ds are computed once a (query block, key block) pair and feed
# all three gradients (5 products a pair; the dq / dkv split computed s, p,
# dp, ds twice: 7).  dk, dv accumulate over the inner axis in block-sized
# scratch; dq accumulates over the OUTER axis, so its float32 accumulator
# stays in VMEM for the whole (batch, head): [n_q, block_q, D].
# ---------------------------------------------------------------------------

def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                      dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                      block_q: int, block_k: int, n_q: int, n_k: int,
                      causal: bool, scale: float):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when((ki == 0) & (qi == 0))
    def _init_head():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = q_start + block_q - 1 >= k_start

    @pl.when(run if causal else (qi >= 0))
    def _compute():
        q = q_ref[0]                                    # [bq, D]
        k = k_ref[0]                                    # [bk, D]
        v = v_ref[0]                                    # [bk, Dv]
        do = do_ref[0]                                  # [bq, Dv]
        lse = lse_ref[0]                                # [bq, 1]
        dl = dl_ref[0]                                  # [bq, 1] rowsum(dO*O)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            row = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            col = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        p = jnp.exp(s - lse)                            # [bq, bk] f32
        pt = p.astype(do.dtype).T
        dv_acc[...] += jnp.dot(pt, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)  # [bq, bk]
        ds = (p * (dp - dl) * scale).astype(q.dtype)
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        dq_acc[qi] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    # the head's last key block: every query block's sum is complete as its
    # turn comes (`_dq_block` holds the output block still until then)
    @pl.when(ki == n_k - 1)
    def _finalize_dq():
        dq_ref[0] = dq_acc[qi].astype(dq_ref.dtype)


# VMEM: a v5e core has 128 MiB and Mosaic's scoped default is 16.  The fused
# backward asks for 100: dq's resident accumulator (S rows of D columns held
# as whole 128-lane tiles: 8.4 MB at [8192, 192]) may take 64 of them, the
# kernel's own blocks and [bq, bk] float32 tiles take 14 to 24 at 1024 x 1024
# (both compiled for v5e: tests/test_tpu_compile.py)
_BWD_VMEM_LIMIT = 100 * 1024 * 1024
_BWD_DQ_RESIDENT_MAX = 64 * 1024 * 1024


def _fused_bwd_fits(S: int, D: int) -> bool:
    """Whether one (batch, head)'s dq accumulator stays in VMEM: up to
    131,072 positions at a width of 128, 65,536 at 192 or 256."""
    return S * -(-D // 128) * 128 * 4 <= _BWD_DQ_RESIDENT_MAX


def _bwd_blocks(S: int, Sk: int):
    """(block_q, block_k) of the backward, from the lengths the call has.
    1024 x 1024 was the fastest pair of {512, 1024, 2048}^2 on the chip at
    S = 8192 and at S = 2048 alike (PERF.md section 6, PR 35); a shorter or
    odd length takes the largest block that divides it."""
    return _pick_block(S, BWD_BLOCK), _pick_block(Sk, BWD_BLOCK)


def _flash_bwd_call(qt, kt, vt, dot, lse, delta, causal, scale, block_q,
                    block_k):
    """The fused kernel on head-major operands: qt, kt [BH, S, D]; vt, dot
    [BH, S, Dv]; lse, delta [BH, S, 1] f32 -> dq, dk, dv."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = qt.shape
    Sk, Dv = kt.shape[1], vt.shape[-1]
    n_q = S // block_q
    n_k = Sk // block_k

    def _q_block(b, j, i):
        # under the causal mask the query blocks above key block j's diagonal
        # are skipped: hold the first one that runs, so nothing is fetched
        # for a step that computes nothing
        if causal:
            i = jnp.maximum(i, (j * block_k) // block_q)
        return (b, i, 0)

    def _dq_block(b, j, i):
        # dq's block is complete only in the last key block's sweep; until
        # then the index stays put and nothing is written back
        return (b, jnp.where(j == n_k - 1, i, 0), 0)

    kernel = functools.partial(
        _flash_bwd_kernel, block_q=block_q, block_k=block_k, n_q=n_q,
        n_k=n_k, causal=causal, scale=scale)
    return pl.pallas_call(
        kernel,
        name=_kernel_name("bwd", D, Dv),
        grid=(BH, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), _q_block),                    # q
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, block_q, Dv), _q_block),                   # dO
            pl.BlockSpec((1, block_q, 1), _q_block),                    # lse
            pl.BlockSpec((1, block_q, 1), _q_block),                    # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), _dq_block),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((BH, S, D), qt.dtype, qt, kt, vt),
            _out_struct((BH, Sk, D), kt.dtype, qt, kt, vt),
            _out_struct((BH, Sk, Dv), vt.dtype, qt, kt, vt),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_q, block_q, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
    )(qt, kt, vt, dot, lse, delta)


# ---------------------------------------------------------------------------
# The flash-attention-2 split (a dk/dv sweep, then a dq sweep; s, p, dp, ds
# computed in both): every accumulator is block-sized, so it takes any
# length.  The route of a call whose dq accumulator does not fit beside the
# fused kernel's tiles (`_fused_bwd_fits`); no benchmark cell is that long.
# ---------------------------------------------------------------------------

def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *,
                          block_q: int, block_k: int, n_q: int, causal: bool,
                          scale: float):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = q_start + block_q - 1 >= k_start

    @pl.when(run if causal else (qi >= 0))
    def _compute():
        q = q_ref[0]                                    # [bq, D]
        k = k_ref[0]                                    # [bk, D]
        v = v_ref[0]                                    # [bk, D]
        do = do_ref[0]                                  # [bq, D]
        lse = lse_ref[0]                                # [bq, 1]
        dl = dl_ref[0]                                  # [bq, 1] rowsum(dO*O)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            row = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            col = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        p = jnp.exp(s - lse)                            # [bq, bk] f32
        pt = p.astype(do.dtype).T
        dv_acc[...] += jnp.dot(pt, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)  # [bq, bk]
        ds = (p * (dp - dl) * scale).astype(q.dtype)
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                         dq_ref, dq_acc, *, block_q: int, block_k: int,
                         n_k: int, causal: bool, scale: float):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = q_start + block_q - 1 >= k_start

    @pl.when(run if causal else (ki >= 0))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        dl = dl_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            row = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            col = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(row >= col, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - dl) * scale).astype(k.dtype)
        dq_acc[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_split_call(qt, kt, vt, dot, lse, delta, causal, scale,
                          block_q, block_k):
    """`_flash_bwd_call`'s contract through the two kernels."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = qt.shape
    Sk, Dv = kt.shape[1], vt.shape[-1]
    n_q = S // block_q
    n_k = Sk // block_k

    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, block_q=block_q, block_k=block_k, n_q=n_q,
        causal=causal, scale=scale)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name=_kernel_name("bwd_dkv", D, Dv),
        grid=(BH, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),   # q
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, block_q, Dv), lambda b, j, i: (b, i, 0)),  # dO
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),   # lse
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((BH, Sk, D), kt.dtype, qt, kt, vt),
            _out_struct((BH, Sk, Dv), vt.dtype, qt, kt, vt),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
    )(qt, kt, vt, dot, lse, delta)

    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, block_q=block_q, block_k=block_k, n_k=n_k,
        causal=causal, scale=scale)
    dq = pl.pallas_call(
        dq_kernel,
        name=_kernel_name("bwd_dq", D, Dv),
        grid=(BH, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),   # k
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, j, 0)),  # v
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0)),  # dO
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),   # lse
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),   # delta
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((BH, S, D), qt.dtype, qt, kt, vt),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_LIMIT),
    )(qt, kt, vt, dot, lse, delta)

    return dq, dk, dv


def _flash_bwd_impl(q, k, v, out, lse, g, causal, scale):
    """Tiled dq/dk/dv.  q, k: [B,S,H,D]; v, out, g: [B,S,H,Dv]; lse:
    [B*H,S,1] f32."""
    B, S, H, D = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * H, S, D)
    kt = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * H, Sk, D)
    vt = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * H, Sk, Dv)
    dot = jnp.transpose(g, (0, 2, 1, 3)).reshape(B * H, S, Dv)
    # delta_i = rowsum(dO_i * O_i) — the only residual beyond lse (cheap XLA fuse)
    delta = jnp.sum(dot.astype(jnp.float32) *
                    jnp.transpose(out, (0, 2, 1, 3)).reshape(B * H, S, Dv)
                    .astype(jnp.float32), axis=-1, keepdims=True)  # [BH,S,1]

    call = _flash_bwd_call if _fused_bwd_fits(S, D) else _flash_bwd_split_call
    dq, dk, dv = call(qt, kt, vt, dot, lse, delta, causal, scale,
                      *_bwd_blocks(S, Sk))

    tr = lambda x, L: jnp.transpose(x.reshape(B, H, L, x.shape[-1]),
                                    (0, 2, 1, 3))
    return tr(dq, S), tr(dk, Sk), tr(dv, Sk)


# ---------------------------------------------------------------------------
# custom_vjp wiring (+ checkpoint_name so block-level remat saves out/lse)
# ---------------------------------------------------------------------------

def _per_shard(fn, shard, ins: str, outs: str):
    """Run `fn` per shard of `shard = (mesh, axis_names, qkv_spec)`; `ins` /
    `outs` name each operand's layout: "q" = [B, S, H, D], "l" = lse.

    A Mosaic kernel cannot be partitioned by GSPMD ("wrap the call in a
    shard_map"), and attention never mixes batch rows or heads, so a caller
    whose step is partitioned names the mesh axes its [B, S, H, D] operands
    split over and the kernels run on each device's slice.  The region sits
    INSIDE the custom_vjp halves: autodiff then never differentiates through
    a shard_map (nested in the pipeline loop's manual region that produces
    residual shardings shardy rejects), it only calls the two halves.
    `lse` crosses the boundary as [B, H, S, 1] so its batch/head axes take the
    same mesh axes as q's.  mesh=None means "the context mesh" (the nested
    case, where the caller's region already made some axes Manual)."""
    from jax.sharding import PartitionSpec as P

    mesh, axis_names, spec = shard
    by_kind = {"q": spec, "l": P(spec[0], spec[2], None, None)}
    return jax.shard_map(
        fn, mesh=mesh, axis_names=set(axis_names),
        in_specs=tuple(by_kind[c] for c in ins),
        out_specs=tuple(by_kind[c] for c in outs))


def _flash_fwd(q, k, v, causal, scale, shard):
    """(out [B,S,H,D], lse) through the forward kernel; under `shard` the
    kernel runs per shard and lse comes back as [B, H, S, 1]."""
    if shard is None:
        return _flash_fwd_impl(q, k, v, causal, scale)

    def local(q_l, k_l, v_l):
        out, lse = _flash_fwd_impl(q_l, k_l, v_l, causal, scale)
        B, S, H, _ = q_l.shape
        return out, lse.reshape(B, H, S, 1)

    return _per_shard(local, shard, "qqq", "ql")(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_attention_core(q, k, v, causal, scale, shard=None):
    """q, k [B, S, H, D], v and the result [B, S, H, Dv]; Pallas forward
    AND backward."""
    out, _ = _flash_fwd(q, k, v, causal, scale, shard)
    return out


def _flash_core_fwd(q, k, v, causal, scale, shard):
    out, lse = _flash_fwd(q, k, v, causal, scale, shard)
    # named so jax.checkpoint(policy=save_only_these_names('flash_out',
    # 'flash_lse')) saves exactly these: the replay then recomputes q/k/v via the
    # cheap qkv matmul but never re-runs the attention kernel
    out = checkpoint_name(out, "flash_out")
    if q.shape[-1] != v.shape[-1]:
        # kept without its unit minor dimension: [.., S, 1] float32 lies in
        # HBM as 128 lanes a row, 0.5 GB a layer at 4 x 32 heads x 8192
        # positions, and a checkpoint policy keeps it for every layer.  (The
        # one-width kernels' residual is left as it is: the dense cell's
        # program is not this PR's to change.)
        lse = lse[..., 0]
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_core_bwd(causal, scale, shard, res, g):
    q, k, v, out, lse = res
    if q.shape[-1] != v.shape[-1]:
        lse = lse[..., None]
    if shard is None:
        return _flash_bwd_impl(q, k, v, out, lse, g, causal, scale)

    def local(q_l, k_l, v_l, out_l, g_l, lse_l):
        B, S, H, _ = q_l.shape
        return _flash_bwd_impl(q_l, k_l, v_l, out_l,
                               lse_l.reshape(B * H, S, 1), g_l, causal, scale)

    return _per_shard(local, shard, "qqqqql", "qqq")(q, k, v, out, g, lse)


_flash_attention_core.defvjp(_flash_core_fwd, _flash_core_bwd)

# the checkpoint policy matching the names above (used by models + trainers).
# 'flash_qkv' additionally saves the post-rope q/k/v at the call site (see
# models/gpt.py block_forward), letting the block replay DCE the qkv matmul +
# rope forward — they are only needed to produce values that are now saved.
remat_policy_save_attention = functools.partial(
    jax.checkpoint_policies.save_only_these_names,
    "flash_out", "flash_lse", "flash_qkv")


# (score width, value width) pairs the kernels take: one width for q, k and
# v, or latent attention's expanded 128 nope + 64 rope against 128 values
_WIDTHS = ((64, 64), (128, 128), (256, 256), (192, 128))


def _shapes_ok_for_pallas(q, k, v):
    B, S, H, D = q.shape
    Sk = k.shape[1]
    if (D, v.shape[-1]) not in _WIDTHS:
        return False
    if S < 128 or Sk < 128:
        return False
    # every length must land on an aligned divisor block
    return all(L % _pick_block(L, pref) == 0 and _pick_block(L, pref) % 128 == 0
               for L in (S, Sk) for pref in (FWD_BLOCK, BWD_BLOCK))


def flash_attention_fused(q, k, v, mask=None, causal=False, scale=None,
                          dropout_p=0.0, shard=None):
    """Entry used by incubate fused ops.  q, k: [B, S, H, D]; v (and the
    result): [B, S, H, Dv], Dv = D except for the pair (192, 128).

    shard: `(mesh, axis_names, qkv_spec)` from a caller whose step is
    partitioned over those mesh axes — the Pallas kernels then run per shard
    (`_per_shard`).  The XLA path ignores it: GSPMD partitions the einsums."""
    D = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    if (mask is None and dropout_p == 0.0 and _on_tpu()
            and _shapes_ok_for_pallas(q, k, v)):
        return _flash_attention_core(q, k, v, causal, s, shard)
    key = None
    if dropout_p > 0.0:
        from ...core import generator as _gen
        key = _gen.next_key()
    return attention_xla(q, k, v, mask=mask, causal=causal, scale=s,
                         dropout_p=dropout_p, dropout_key=key)


# ---------------------------------------------------------------------------
# Varlen (segment-ids) Pallas kernels — ref flash_attn varlen/unpadded
# (`nn/functional/flash_attention.py:200`): packed sequences attend only within
# their own segment.  Separate kernels so the dense hot path stays untouched.
# ---------------------------------------------------------------------------

def _seg_mask(sq, sk, s, q_start, k_start, block_q, block_k, causal):
    """Combine segment equality (and causality) into the score mask."""
    m = sq[:, 0][:, None] == sk[:, 0][None, :]
    if causal:
        row = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        col = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        m = m & (row >= col)
    return jnp.where(m, s, NEG_INF), m


def _flash_fwd_seg_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref,
                          acc_ref, m_ref, l_ref, *, block_q, block_k, n_k,
                          causal, scale):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = q_start + block_q - 1 >= k_start

    @pl.when(run if causal else (ki >= 0))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s, mask = _seg_mask(sq_ref[0], sk_ref[0], s, q_start, k_start,
                            block_q, block_k, causal)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)  # fully-masked rows: no exp(NEG-NEG) mass
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


def _flash_bwd_seg_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                              sq_ref, sk_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                              *, block_q, block_k, n_q, causal, scale):
    from jax.experimental import pallas as pl

    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = q_start + block_q - 1 >= k_start

    @pl.when(run if causal else (qi >= 0))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        dl = dl_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s, mask = _seg_mask(sq_ref[0], sk_ref[0], s, q_start, k_start,
                            block_q, block_k, causal)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        pt = p.astype(do.dtype).T
        dv_acc[...] += jnp.dot(pt, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - dl) * scale).astype(q.dtype)
        dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_seg_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                             sq_ref, sk_ref, dq_ref, dq_acc, *, block_q,
                             block_k, n_k, causal, scale):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True
    if causal:
        run = q_start + block_q - 1 >= k_start

    @pl.when(run if causal else (ki >= 0))
    def _compute():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        dl = dl_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        s, mask = _seg_mask(sq_ref[0], sk_ref[0], s, q_start, k_start,
                            block_q, block_k, causal)
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - dl) * scale).astype(k.dtype)
        dq_acc[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _seg3(seg, B, H, S):
    """[B, S] int32 -> [B*H, S, 1] (per-head broadcast for block indexing)."""
    s = jnp.broadcast_to(seg.astype(jnp.int32)[:, None, :], (B, H, S))
    return s.reshape(B * H, S, 1)


def _flash_seg_fwd_impl(q, k, v, seg_q, seg_k, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    Sk = k.shape[1]
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * H, S, D)
    kt = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * H, Sk, D)
    vt = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * H, Sk, D)
    sq = _seg3(seg_q, B, H, S)
    sk = _seg3(seg_k, B, H, Sk)

    block_q = _pick_block(S, FWD_BLOCK)
    block_k = _pick_block(Sk, FWD_BLOCK)
    n_k = Sk // block_k
    kernel = functools.partial(_flash_fwd_seg_kernel, block_q=block_q,
                               block_k=block_k, n_k=n_k, causal=causal,
                               scale=scale)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_seg_fwd",
        grid=(B * H, S // block_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((B * H, S, D), q.dtype, qt, kt, vt),
            _out_struct((B * H, S, 1), jnp.float32, qt, kt, vt),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qt, kt, vt, sq, sk)
    return jnp.transpose(out.reshape(B, H, S, D), (0, 2, 1, 3)), lse


def _flash_seg_bwd_impl(q, k, v, seg_q, seg_k, out, lse, g, causal, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    Sk = k.shape[1]
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(B * H, S, D)
    kt = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * H, Sk, D)
    vt = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * H, Sk, D)
    dot = jnp.transpose(g, (0, 2, 1, 3)).reshape(B * H, S, D)
    sq = _seg3(seg_q, B, H, S)
    sk = _seg3(seg_k, B, H, Sk)
    delta = jnp.sum(dot.astype(jnp.float32) *
                    jnp.transpose(out, (0, 2, 1, 3)).reshape(B * H, S, D)
                    .astype(jnp.float32), axis=-1, keepdims=True)

    block_q = _pick_block(S, BWD_BLOCK)
    block_k = _pick_block(Sk, BWD_BLOCK)
    n_q = S // block_q
    n_k = Sk // block_k

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_seg_dkv_kernel, block_q=block_q,
                          block_k=block_k, n_q=n_q, causal=causal, scale=scale),
        name="flash_seg_bwd_dkv",
        grid=(B * H, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, j, i: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((B * H, Sk, D), k.dtype, qt, kt, vt),
            _out_struct((B * H, Sk, D), v.dtype, qt, kt, vt),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qt, kt, vt, dot, lse, delta, sq, sk)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_seg_dq_kernel, block_q=block_q,
                          block_k=block_k, n_k=n_k, causal=causal, scale=scale),
        name="flash_seg_bwd_dq",
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((B * H, S, D), q.dtype, qt, kt, vt),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qt, kt, vt, dot, lse, delta, sq, sk)

    tr = lambda x, L: jnp.transpose(x.reshape(B, H, L, D), (0, 2, 1, 3))
    return tr(dq, S), tr(dk, Sk), tr(dv, Sk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash_attention_seg_core(q, k, v, seg_q, seg_k, causal, scale):
    out, _ = _flash_seg_fwd_impl(q, k, v, seg_q, seg_k, causal, scale)
    return out


def _flash_seg_fwd(q, k, v, seg_q, seg_k, causal, scale):
    out, lse = _flash_seg_fwd_impl(q, k, v, seg_q, seg_k, causal, scale)
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, seg_q, seg_k, out, lse)


def _flash_seg_bwd(causal, scale, res, g):
    q, k, v, seg_q, seg_k, out, lse = res
    dq, dk, dv = _flash_seg_bwd_impl(q, k, v, seg_q, seg_k, out, lse, g,
                                     causal, scale)
    return dq, dk, dv, None, None  # integer segment ids carry no tangent


_flash_attention_seg_core.defvjp(_flash_seg_fwd, _flash_seg_bwd)


def attention_xla_segmented(q, k, v, seg_q, seg_k, causal, scale):
    """XLA oracle for the varlen kernel (tests + CPU fallback)."""
    mask = seg_q[:, None, :, None] == seg_k[:, None, None, :]   # [B,1,S,Sk]
    return attention_xla(q, k, v, mask=mask, causal=causal, scale=scale)


def flash_attention_varlen(q, k, v, segment_ids, kv_segment_ids=None,
                           causal=True, scale=None):
    """Segment-masked flash attention (varlen packing): q, k, v [B, S, H, D],
    segment_ids [B, S] int — tokens attend only within their own segment."""
    D = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(D)
    seg_k = segment_ids if kv_segment_ids is None else kv_segment_ids
    if _on_tpu() and q.shape[-1] == v.shape[-1] and \
            _shapes_ok_for_pallas(q, k, v):
        return _flash_attention_seg_core(q, k, v, segment_ids, seg_k,
                                         causal, s)
    return attention_xla_segmented(q, k, v, segment_ids, seg_k, causal, s)
