"""The page pool is a loop carry, updated in place, in every paged pass.

`models.gpt._scan_paged_layers` runs the layer loop of `serve_step_paged`,
`prefill_paged` and `prefill_chunk_paged` (the standalone chunk program of a
prefix-hit tail, which shares the fused step's trunk) with the pool
`[L, P, page, KVH, hd]` viewed as `[L*P, page, KVH, hd]` and carried through
`lax.scan`; layer `l` writes at `l*P + page_id` and attends through
`page_table + l*P`.  The form it replaced
scanned over the pool (input sliced per layer, output stacked per layer),
which on the chip copied the whole pool about three times a program.

Two checks per pass and pool kind, at a tiny shape on the CPU:
- structure, read from `jax.make_jaxpr`: the layer loop carries every pool
  lane, and no scanned input or stacked output has a lane's per-layer shape;
- results: tokens/logits and the WHOLE returned pool are bitwise those of the
  old loop form, a frozen copy of which lives here as the oracle (built from
  the package's per-layer pieces, so only the loop form differs).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import gpt as G
from paddle_tpu.incubate.kernels import paged_attention as PA

L, P, PAGE, B, MAXP = 3, 7, 8, 3, 4      # layers, pages, page size, slots
T = 4                                    # fused-step tokens a slot
C = 6                                    # chunk program's tokens a slot
SB = 16                                  # prefill bucket (2 pages)


def _cfg(family):
    base = G.gpt_tiny(64) if family == "gpt" else G.llama_tiny(64)   # MHA/GQA
    return dataclasses.replace(base, num_layers=L)


@pytest.fixture(scope="module", params=["gpt", "llama"])
def model(request):
    cfg = _cfg(request.param)
    return cfg, G.init_params(cfg, jax.random.key(1))


def _random_pool(cfg, kv_dtype, seed):
    """A pool full of random content, so that what a pass leaves alone shows
    as much as what it writes."""
    rng = np.random.RandomState(seed)
    pool = G.init_paged_cache(cfg, P, PAGE, kv_dtype=kv_dtype)
    out = {}
    for n, a in pool.items():
        if a.dtype == jnp.int8:
            r = rng.randint(-127, 128, a.shape)
        elif n.endswith("_scale"):
            r = rng.uniform(0.005, 0.02, a.shape)
        else:
            r = rng.standard_normal(a.shape)
        out[n] = jnp.asarray(r, a.dtype)
    return out


def _table():
    # distinct pages per slot; slot 2 holds one page, the rest null entries
    t = np.zeros((B, MAXP), np.int32)
    t[0, :2] = [1, 2]
    t[1, :3] = [3, 4, 5]
    t[2, :1] = [6]
    return jnp.asarray(t)


# ---------------------------------------------------------------------------
# the oracle: the scan-over-pool loop this PR removed (pool a scanned input
# and a stacked output; each layer sees its own [P, page, KVH, hd] plane)
# ---------------------------------------------------------------------------

def _old_scan(params, x, cache, layer):
    return jax.lax.scan(layer, x, (params["blocks"], cache))


def _old_chunk_hidden(params, ids, c, cache, page_table, q_offset, valid,
                      attn_fn):
    Bn, W = ids.shape
    page = cache["k"].shape[2]
    quant = "k_scale" in cache
    pos = q_offset[:, None] + jnp.arange(W)
    real = jnp.arange(W)[None, :] < valid[:, None]
    x = G._embed(params, ids, c)
    if not c.use_rope:
        x = x + jnp.take(params["wpe"], pos, axis=0)
    pidx = jnp.take_along_axis(page_table, pos // page, axis=1)
    pidx = jnp.where(real, pidx, 0)
    off = pos % page

    def layer(x, layer_in):
        bp, kv = layer_in
        q, k, v = G._prefill_qkv(bp, x, c, pos=pos)
        if quant:
            k, ks = G._quantize_kv(k)
            v, vs = G._quantize_kv(v)
            kv = dict(kv, k_scale=kv["k_scale"].at[pidx, off].set(ks),
                      v_scale=kv["v_scale"].at[pidx, off].set(vs))
        kv = dict(kv, k=kv["k"].at[pidx, off].set(k),
                  v=kv["v"].at[pidx, off].set(v))
        attn = attn_fn(q, kv["k"], kv["v"], page_table, q_offset, valid,
                       kv_scales=G._kv_scales(kv))
        return G._layer_tail(bp, x, attn.reshape(Bn, W, c.hidden_size), c), kv

    return _old_scan(params, x, cache, layer)


def _old_serve(params, tokens, cache, page_table, q_offset, valid, c):
    x, cache = _old_chunk_hidden(params, tokens, c, cache, page_table,
                                 q_offset, valid, PA.paged_prefill_attention)
    logits = G.head_logits(G.epilogue(params, x, c), params, c)
    return G.sharded_argmax(logits, None), cache


def _old_prefill(params, ids, c, cache, pages, length):
    Bn, Sb = ids.shape
    H, KVH, hd = c.num_heads, c.kv_heads, c.head_dim
    page = cache["k"].shape[2]
    n = Sb // page
    quant = "k_scale" in cache
    x = G._embed(params, ids, c)
    if not c.use_rope:
        x = x + params["wpe"][:Sb]

    def layer(x, layer_in):
        bp, kv = layer_in
        q, k, v = G._prefill_qkv(bp, x, c)
        wk, wv = k, v
        if quant:
            wk, ks = G._quantize_kv(k)
            wv, vs = G._quantize_kv(v)
            kv = dict(kv,
                      k_scale=kv["k_scale"].at[pages].set(
                          ks.reshape(Bn, n, page, KVH)),
                      v_scale=kv["v_scale"].at[pages].set(
                          vs.reshape(Bn, n, page, KVH)))
        kv = dict(kv, k=kv["k"].at[pages].set(wk.reshape(Bn, n, page, KVH, hd)),
                  v=kv["v"].at[pages].set(wv.reshape(Bn, n, page, KVH, hd)))
        if KVH != H:
            k = jnp.repeat(k, H // KVH, axis=2)
            v = jnp.repeat(v, H // KVH, axis=2)
        attn = G.flash_attention_fused(q, k, v, causal=True)
        return G._layer_tail(bp, x, attn.reshape(Bn, Sb, c.hidden_size), c), kv

    x, cache = _old_scan(params, x, cache, layer)
    x = G.epilogue(params, x[jnp.arange(Bn), length - 1], c)
    return G.head_logits(x, params, c), cache


def _old_chunk(params, ids, c, cache, page_table, q_offset, valid):
    x, cache = _old_chunk_hidden(params, ids, c, cache, page_table, q_offset,
                                 valid, PA.paged_prefill_attention)
    x = G.epilogue(params, x[jnp.arange(ids.shape[0]), valid - 1], c)
    return G.head_logits(x, params, c), cache


# ---------------------------------------------------------------------------
# the three passes, each as (new, old, inputs): both take (params, pool)
# ---------------------------------------------------------------------------

def _case(pass_name, cfg, seed=0):
    rng = np.random.RandomState(seed)
    table = _table()
    if pass_name == "serve_step_paged":
        # slot 0 decodes (valid 1), slot 1 verifies 3 drafted tokens across a
        # page boundary, slot 2 prefills a 2-token chunk: padded rows in 0, 2
        tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, T)), jnp.int32)
        q_off = jnp.asarray([9, 14, 0], jnp.int32)
        valid = jnp.asarray([1, 4, 2], jnp.int32)

        def new(params, pool):
            out, _, pool, _ = G.serve_step_paged(params, tokens, pool, table,
                                                 q_off, valid, cfg)
            return out, pool

        def old(params, pool):
            return _old_serve(params, tokens, pool, table, q_off, valid, cfg)
    elif pass_name == "prefill_paged":
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (2, SB)), jnp.int32)
        pages = jnp.asarray([[1, 2], [3, 0]], jnp.int32)  # slot 1: null tail
        length = jnp.asarray([13, 5], jnp.int32)

        def new(params, pool):
            return G.prefill_paged(params, ids, cfg, pool, pages, length)

        def old(params, pool):
            return _old_prefill(params, ids, cfg, pool, pages, length)
    else:
        # a tail across a page boundary, one behind two cached pages with
        # padded rows, and a first chunk
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, C)), jnp.int32)
        q_off = jnp.asarray([4, 16, 0], jnp.int32)
        valid = jnp.asarray([6, 3, 5], jnp.int32)

        def new(params, pool):
            return G.prefill_chunk_paged(params, ids, cfg, pool, table, q_off,
                                         valid)

        def old(params, pool):
            return _old_chunk(params, ids, cfg, pool, table, q_off, valid)
    return new, old


PASSES = ["serve_step_paged", "prefill_paged", "prefill_chunk_paged"]
POOLS = [None, "int8"]


# ---------------------------------------------------------------------------
# structure
# ---------------------------------------------------------------------------

def _scans(jaxpr):
    """Every scan equation of a jaxpr, nested ones included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _scans(inner)


def _layer_loop_faults(fn, params, pool):
    """What is wrong with the layer loop of `fn`, as a list of strings: empty
    when the pool is carried and never scanned over or stacked."""
    jaxpr = jax.make_jaxpr(fn)(params, pool).jaxpr
    loops = [e for e in _scans(jaxpr) if e.params["length"] == L]
    assert len(loops) == 1, f"expected one layer loop, found {len(loops)}"
    eqn = loops[0]
    nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
    carries = [v.aval for v in eqn.invars[nc:nc + ncar]]
    xs = [v.aval for v in eqn.invars[nc + ncar:]]
    ys = [v.aval for v in eqn.outvars[ncar:]]
    faults = []
    for name, lane in pool.items():
        flat = (L * P,) + lane.shape[2:]
        plane = lane.shape[1:]
        if not any(a.shape == flat and a.dtype == lane.dtype for a in carries):
            faults.append(f"{name}: no carry of shape {flat}")
        # a scanned input / stacked output is [L, *per-layer shape]
        for kind, avals in (("scanned input", xs), ("stacked output", ys)):
            if any(a.shape[1:] == plane and a.dtype == lane.dtype
                   for a in avals):
                faults.append(f"{name}: {kind} of per-layer shape {plane}")
    return faults


@pytest.mark.parametrize("kv_dtype", POOLS, ids=["fp", "int8"])
@pytest.mark.parametrize("pass_name", PASSES)
def test_pool_is_a_carry_not_scanned(model, pass_name, kv_dtype):
    cfg, params = model
    new, _ = _case(pass_name, cfg)
    pool = _random_pool(cfg, kv_dtype, seed=2)
    assert _layer_loop_faults(new, params, pool) == []


@pytest.mark.parametrize("pass_name", PASSES)
def test_structure_check_sees_the_old_loop_form(pass_name):
    """The check is not vacuous: the frozen scan-over-pool form fails it on
    every count (no carry, a scanned input and a stacked output per lane)."""
    cfg = _cfg("gpt")
    params = G.init_params(cfg, jax.random.key(1))
    _, old = _case(pass_name, cfg)
    pool = _random_pool(cfg, "int8", seed=2)
    faults = _layer_loop_faults(old, params, pool)
    assert len(faults) == 3 * len(pool), faults


@pytest.mark.parametrize("pass_name", PASSES)
def test_cost_model_prices_no_pool_temporary(pass_name):
    """JXP008's liveness model agrees with the structure: one layer of the
    carried form holds no pool-sized temporary (its scatters write the carry
    in place), where a layer of the scanned form pays for its k and v planes
    beside the pool."""
    from paddle_tpu.analysis.cost_model import _carry_slice, _jaxpr_walk, \
        aval_bytes
    cfg = _cfg("gpt")
    params = G.init_params(cfg, jax.random.key(1))
    pool = _random_pool(cfg, None, seed=2)
    plane = aval_bytes(pool["k"][0])
    peak = {}
    for name, fn in zip(("new", "old"), _case(pass_name, cfg)):
        jaxpr = jax.make_jaxpr(fn)(params, pool).jaxpr
        (eqn,) = [e for e in _scans(jaxpr) if e.params["length"] == L]
        body = eqn.params["jaxpr"].jaxpr
        _, peak[name], _ = _jaxpr_walk(
            body, frozenset(), frozenset(body.invars[_carry_slice(eqn)]))
    assert peak["old"] >= 2 * plane
    assert peak["old"] - peak["new"] >= 2 * plane


# ---------------------------------------------------------------------------
# results
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kv_dtype", POOLS, ids=["fp", "int8"])
@pytest.mark.parametrize("pass_name", PASSES)
def test_bitwise_equal_to_old_loop_form(model, pass_name, kv_dtype):
    cfg, params = model
    new, old = _case(pass_name, cfg, seed=5)
    pool = _random_pool(cfg, kv_dtype, seed=7)
    got, got_pool = jax.jit(new)(params, pool)
    want, want_pool = jax.jit(old)(params, pool)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert set(got_pool) == set(pool)
    wrote = False
    for n in pool:
        assert got_pool[n].shape == pool[n].shape       # external layout kept
        assert got_pool[n].dtype == pool[n].dtype
        np.testing.assert_array_equal(np.asarray(got_pool[n]),
                                      np.asarray(want_pool[n]), err_msg=n)
        wrote |= not np.array_equal(np.asarray(got_pool[n]),
                                    np.asarray(pool[n]))
    assert wrote, "the pass wrote nothing into the pool"


def test_each_layer_writes_its_own_null_page():
    """Padded rows land on page 0 of the layer that wrote them (`l*P + 0` in
    the flat view), never on a real page of a neighbouring layer: every page
    no table row names — but the null page — is untouched in every layer."""
    cfg = _cfg("gpt")
    params = G.init_params(cfg, jax.random.key(1))
    new, _ = _case("serve_step_paged", cfg, seed=5)
    pool = _random_pool(cfg, None, seed=7)
    _, got = jax.jit(new)(params, pool)
    # written: pages of the table at the positions of the case (1|2, 4|5, 6)
    untouched = [1, 3]
    for n in pool:
        a, b = np.asarray(pool[n]), np.asarray(got[n])
        np.testing.assert_array_equal(a[:, untouched], b[:, untouched])
        for l in range(L):
            assert not np.array_equal(a[l, 0], b[l, 0]), (n, l)
