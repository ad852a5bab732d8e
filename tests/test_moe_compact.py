"""The compact expert layer of a long serving pass
(`moe_dropless_compact`): the held pairs placed by counting, in chunks of a
static row count under a while loop, against the N x k layer
(`moe_dropless`) on the same routing — output, the routing counters, and
the two counters of its own; and a DSA configuration's long prefill through
the engine against the program that ran the N x k layer a 2,048-style block
at a time.  Tiny widths (`hybrid.dsa_tiny`: 4 of 16 experts held, top-2),
CPU."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.incubate.distributed.models.moe import dropless as DL
from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models import hybrid

F32 = jnp.float32
ROUTING = ("random", "none-held", "all-held", "padding")


@functools.lru_cache(maxsize=None)
def _setup(dtype):
    cfg = hybrid.dsa_tiny(dtype=dtype)
    return cfg, hybrid.init_params(cfg, jax.random.key(3))


def _layer(routing, dtype, N=200):
    """(cfg, an expert layer's tree, h [N, D], real [N]) for a routing:
    the router's bias steers every pick onto the held experts, or away
    from all of them; "padding" leaves a third of the rows out."""
    cfg, params = _setup(dtype)
    lp = dict(params["layers"][3])
    first, E = cfg.expert_offset, cfg.experts_here
    held = (jnp.arange(cfg.n_routed_experts) >= first) & \
        (jnp.arange(cfg.n_routed_experts) < first + E)
    if routing in ("none-held", "all-held"):
        lp["router_bias"] = lp["router_bias"] + \
            jnp.where(held == (routing == "all-held"), 100.0, 0.0)
    rng = np.random.default_rng(ROUTING.index(routing))
    h = jnp.asarray(rng.normal(size=(N, cfg.hidden_size)), dtype)
    real = jnp.asarray(rng.random(N) < 0.67) if routing == "padding" \
        else jnp.ones((N,), bool)
    return cfg, lp, h, real


def _ints(ctr):
    return {k: int(v) for k, v in ctr.items()}


@pytest.mark.parametrize("dtype", [F32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("routing", ROUTING)
def test_the_compact_layer_is_the_nk_layer(routing, dtype):
    """Same output (in float32 to its rounding; within bfloat16's rounding
    in bfloat16) and the same routing counters, over the block's
    held pairs only; the held pairs counted as compacted, and the chunks
    past the first where the block's held pairs outgrow the static chunk
    (every pick held)."""
    cfg, lp, h, real = _layer(routing, dtype)
    N = h.shape[0]
    chunk, tile = DL.compact_tiling(N, cfg)
    with jax.default_matmul_precision("highest"):
        want, wctr = DL.moe_dropless(lp, h, cfg, real)
        got, ctr = jax.jit(functools.partial(
            DL.moe_dropless_compact, cfg=cfg, chunk=chunk, row_tile=tile))(
            lp, h, real=real)
    assert got.dtype == want.dtype == dtype
    want, got = np.asarray(want, np.float32), np.asarray(got, np.float32)
    # float32: its rounding; bfloat16: two steps at the output's largest
    # value (a product rounded on the other side of a tie carries through)
    tol = 1e-6 if dtype == F32 else float(np.abs(want).max()) * 2.0 ** -6
    assert float(np.abs(got - want).max()) <= tol
    ctr = _ints(ctr)
    assert {k: ctr[k] for k in DL.COUNTERS} == _ints(wctr)
    assert ctr["moe_pairs_compacted"] == ctr["moe_pairs_here"]
    k, E = cfg.num_experts_per_tok, cfg.experts_here
    n_real = int(np.sum(np.asarray(real)))
    if routing == "none-held":
        assert ctr["moe_pairs_here"] == ctr["moe_experts_touched"] == 0
        assert ctr["moe_compact_extra_chunks"] == 0
        shared = np.asarray(DL.shared_expert(lp, h), np.float32)
        assert float(np.abs(got - shared).max()) <= tol
    elif routing == "all-held":
        assert ctr["moe_pairs_here"] == N * min(k, E)
        padded = N * min(k, E) + E * (tile - 1)
        assert ctr["moe_compact_extra_chunks"] >= 1
        assert ctr["moe_compact_extra_chunks"] <= -(-padded // chunk) - 1
    else:
        assert 0 < ctr["moe_pairs_here"] < n_real * k
        assert ctr["moe_pairs_here"] + ctr["moe_pairs_away"] == n_real * k


@pytest.mark.parametrize("chunk_tiles", [1, 2, 7])
def test_any_chunk_size_gives_the_same_layer(chunk_tiles):
    """The chunk is a bound on rows alive at once, not on pairs computed:
    one row tile a chunk (every group its own chunks), two, or more than
    all the padded groups fill, the same output and counters, and as many
    extra chunks as the padded groups need."""
    cfg, lp, h, real = _layer("random", F32)
    tile = DL.compact_tiling(h.shape[0], cfg)[1]
    with jax.default_matmul_precision("highest"):
        want, wctr = DL.moe_dropless(lp, h, cfg, real)
        got, ctr = DL.moe_dropless_compact(lp, h, cfg, real,
                                           chunk_tiles * tile, tile)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6,
                               atol=1e-6)
    ctr = _ints(ctr)
    assert {k: ctr[k] for k in DL.COUNTERS} == _ints(wctr)
    idx, _ = DL.route(h, lp, cfg)
    idx = np.asarray(idx)[np.asarray(real)]
    first, E = cfg.expert_offset, cfg.experts_here
    loads = [int(np.sum(idx == e)) for e in range(first, first + E)]
    padded = sum(-(-n // tile) * tile for n in loads)
    assert ctr["moe_compact_extra_chunks"] == \
        max(-(-padded // (chunk_tiles * tile)) - 1, 0)


def test_the_tiling_follows_the_rows_an_expert_expects():
    """At GLM-5's router (256 wide, top-8, 16 held): 4,096 tokens expect 128
    rows an expert, 8,192 tokens 256; the tile follows, up to 512, and the
    chunk is whole tiles, room for the expected held pairs and half a tile
    a held expert's group, and no more than every pair held can fill."""
    cfg = hybrid.HybridConfig(layer_pattern="LE", num_layers=2,
                              n_routed_experts=256, experts_here=16,
                              num_experts_per_tok=8)
    got = {n: DL.compact_tiling(n, cfg) for n in (2048, 4096, 8192, 32768)}
    assert {n: t for n, (_, t) in got.items()} == {
        2048: 128, 4096: 128, 8192: 256, 32768: 512}
    for n, (chunk, tile) in got.items():
        even = n * 8 * 16 // 256
        assert chunk % tile == 0
        assert even + 16 * tile // 2 < chunk <= 1.25 * even + 16 * tile // 2 \
            + tile
    tiny = hybrid.dsa_tiny()
    chunk, tile = DL.compact_tiling(10, tiny)
    assert chunk <= -(-(10 * 2 + 4 * (tile - 1)) // tile) * tile


def _parent_layer(lp, h, c, real, chunk, row_tile):
    """The N x k layer under the compact layer's signature, as the program
    before it ran a long pass (the two counters of its own nought)."""
    y, ctr = DL.moe_dropless(lp, h, c, real)
    zero = jnp.zeros((), jnp.int32)
    return y, dict(ctr, moe_pairs_compacted=zero,
                   moe_compact_extra_chunks=zero)


def test_a_long_prefill_through_the_engine_serves_the_parents_tokens(
        monkeypatch):
    """`dsa_tiny` with the long-pass threshold lowered (`TOKEN_BLOCK` 32,
    expert blocks of 64): prompts of 40-100 tokens prefill in buckets past
    it, so their expert layers take the compact layer 64 tokens a block;
    the engine's greedy tokens are those of the program that ran the N x k
    layer 32 tokens a block, the pairs here and away the same, every held
    pair of a long prefill compacted (the fused steps' decode takes the
    N x k layer and counts none)."""
    cfg, params = _setup(F32)
    monkeypatch.setattr(hybrid, "TOKEN_BLOCK", 32)
    prompts = [np.random.default_rng(s).integers(0, 256, n, dtype=np.int32)
               for s, n in ((1, 100), (2, 70), (3, 40))]

    def run():
        eng = LLMEngine(params, cfg, num_slots=2, page_size=8,
                        max_model_len=256)
        for x in prompts:
            eng.add_request(x, max_new_tokens=8)
        outs = eng.run()
        return [outs[r].token_ids for r in sorted(outs)], eng.stats()

    with jax.default_matmul_precision("highest"):
        monkeypatch.setattr(hybrid, "EXPERT_TOKEN_BLOCKS", (64,))
        got, st = run()
        monkeypatch.setattr(hybrid, "EXPERT_TOKEN_BLOCKS", ())
        monkeypatch.setattr(hybrid, "moe_dropless_compact", _parent_layer)
        want, st0 = run()
    assert got == want
    for name in ("moe_pairs_here", "moe_pairs_away", "prefilled_tokens"):
        assert st[name] == st0[name], name
    assert st0["moe_pairs_compacted"] == st0["moe_compact_extra_chunks"] == 0
    assert 0 < st["moe_pairs_compacted"] < st["moe_pairs_here"]
    # the fused steps' share: one pick a held expert at most per token and
    # layer, so what the prefills did not compact is what decode computed
    decoded = sum(len(t) - 1 for t in got)
    assert st["moe_pairs_here"] - st["moe_pairs_compacted"] <= \
        decoded * cfg.count("E") * cfg.num_experts_per_tok


def test_the_prefill_counts_every_held_pair_as_compacted(monkeypatch):
    """A blocked bucketed prefill (the threshold lowered) returns
    `moe_pairs_compacted` equal to `moe_pairs_here` and the extra chunks it
    ran; a fused step of the same configuration returns nought for both."""
    cfg, params = _setup(F32)
    monkeypatch.setattr(hybrid, "TOKEN_BLOCK", 32)
    monkeypatch.setattr(hybrid, "EXPERT_TOKEN_BLOCKS", (64,))
    pool = hybrid.init_paged_cache(cfg, 40, 8, 2)
    ids = np.random.default_rng(4).integers(0, 256, (1, 128), dtype=np.int32)
    pages = np.arange(1, 17, dtype=np.int32)[None]
    _, pool, aux = hybrid.prefill_paged(
        params, jnp.asarray(ids), cfg, pool, jnp.asarray(pages),
        jnp.asarray([100]), jnp.asarray([0]))
    a = dict(zip(hybrid.aux_fields(cfg), np.asarray(aux).tolist()))
    assert a["moe_pairs_compacted"] == a["moe_pairs_here"] > 0
    assert a["moe_pairs_here"] + a["moe_pairs_away"] == \
        100 * cfg.count("E") * cfg.num_experts_per_tok
    table = np.zeros((2, 32), np.int32)
    table[0, :16] = pages[0]
    *_, aux2 = hybrid.serve_step_paged(
        params, jnp.asarray([[7], [0]], jnp.int32), pool, jnp.asarray(table),
        jnp.asarray([100, 0]), jnp.asarray([1, 0]), cfg)
    b = dict(zip(hybrid.aux_fields(cfg), np.asarray(aux2).tolist()))
    assert b["moe_pairs_compacted"] == b["moe_compact_extra_chunks"] == 0
    assert b["moe_pairs_here"] + b["moe_pairs_away"] == \
        cfg.count("E") * cfg.num_experts_per_tok
