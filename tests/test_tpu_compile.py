"""Compile-only checks against a real TPU target, with no TPU.

`jax.experimental.topologies` describes a v5e 2x2 host to the installed
libtpu, and `jit(f).lower(<ShapeDtypeStructs sharded on its devices>)
.compile()` then runs Mosaic and the TPU compiler — no device, no memory,
nothing executed.  That is enough to catch what the virtual CPU mesh cannot:
a kernel that asks for more VMEM than a core has, a Mosaic call GSPMD is asked
to partition, a `pallas_call` without `vma` inside `shard_map`.  It is NOT
evidence that a program runs or is right; `chip_smoke.py` on the chip is.

Shapes are GPT-3 1.3B widths (16 heads x 128, page 16, bf16) — the ones
`chip_smoke.py` executes; depth is cut to 2 layers (the layer scan compiles
its body once, so depth does not change what is checked).
"""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from paddle_tpu.incubate.kernels import flash_attention as FA
from paddle_tpu.incubate.kernels import grouped_matmul as GM
from paddle_tpu.incubate.kernels import paged_attention as PA
from paddle_tpu.incubate.kernels import rms_norm as RN
from paddle_tpu.models import gpt as G

H, HD, PAGE, SLOTS, MAX_LEN = 16, 128, 16, 32, 1024
MAX_PAGES = MAX_LEN // PAGE
POOL_PAGES = SLOTS * MAX_PAGES // 2 + 1          # the engine's default pool
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"cannot build a v5e:2x2 topology here: {e!r}")


@pytest.fixture(autouse=True)
def route_to_kernels(monkeypatch):
    """The entries route on the DEFAULT backend (CPU under pytest); the
    programs here are compiled for TPU devices, so force the kernel route."""
    for mod in (FA, GM, PA, RN):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)


def _compile(fn, *args, **jit_kw):
    text = jax.jit(fn, **jit_kw).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def one(topo):
    return functools.partial(_on, SingleDeviceSharding(topo.devices[0]))


def _s(*shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype)


def _flash_calls(text):
    """The kernels' `name=`s of a compiled program's Mosaic calls, sorted."""
    return sorted(
        re.search(r'op_name="[^"]*?(flash_\w+)\)*/pallas_call"', line).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)


def _flash_loss(q, k, v):
    return FA.flash_attention_fused(q, k, v, causal=True) \
        .astype(jnp.float32).sum()


def test_flash_fwd_bwd_and_varlen(one):
    """The dense training cell's shape (4 x 2048, 16 heads of 128): one
    forward and exactly ONE backward kernel a flash call."""
    qkv = one([_s(4, 2048, H, HD)] * 3)

    text = _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)), *qkv)
    assert _flash_calls(text) == ["flash_bwd", "flash_fwd"]

    def vloss(q, k, v, seg):
        return FA.flash_attention_varlen(q, k, v, seg) \
            .astype(jnp.float32).sum()

    _compile(jax.grad(vloss, argnums=(0, 1, 2)), *qkv,
             one(_s(4, 2048, dtype=jnp.int32)))


def test_flash_at_a_score_width_of_192_and_a_value_width_of_128(one):
    """Latent attention expanded, at the JoyAI-LLM-Flash training cell's
    shapes (4 x 8192, 32 heads): Mosaic addresses the 192-wide blocks as the
    full minor dimension; the kernels carry names of their own, and the
    backward is ONE of them, its dq accumulator ([8192, 192] float32)
    resident in VMEM beside the block-sized dk and dv."""
    q = k = _s(4, 8192, 32, 192)

    text = _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                    *one([q, k, _s(4, 8192, 32, 128)]))
    assert _flash_calls(text) == ["flash_mla_bwd", "flash_mla_fwd"]


@pytest.mark.parametrize("shape,names", [
    ((1, 65536, 2, 256), ["flash_bwd", "flash_fwd"]),
    ((1, 131072, 1, 256), ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"])],
    ids=["s65536_fused", "s131072_split"])
def test_the_flash_backward_at_the_longest_resident_length_and_past_it(
        one, shape, names):
    """256 wide, the widest pair: at 65,536 positions dq's accumulator is
    the 64 MiB the fused kernel may hold; past it the call takes the dk/dv
    and dq kernels, whose scratch is block-sized."""
    text = _compile(jax.grad(_flash_loss, argnums=(0, 1, 2)),
                    *one([_s(*shape)] * 3))
    assert _flash_calls(text) == names


def test_rms_norm(one):
    _compile(RN.rms_norm_fused, *one([_s(4 * 2048, 2048), _s(2048)]))


# (B, T, H, KVH, table entries): the three widths the benchmark's fused steps
# run at T = 1, then the default engine's verify width, the README's
# prefill_chunk and the bucketed engine's prefix-hit tail at GPT-3 widths;
# last an int8 pool (the kernel route needs whole (32, 128) int8 tiles:
# page 32) at the decode and the verify width
_PAGED_PREFILL_SHAPES = {
    "gpt3_t1": (SLOTS, 1, H, H, MAX_PAGES),
    "mistral_t1": (32, 1, 32, 8, 128),
    "hybrid_t1": (64, 1, 32, 2, 128),
    "verify5": (SLOTS, 5, H, H, MAX_PAGES),
    "mistral_verify5": (32, 5, 32, 8, 128),
    "chunk256": (SLOTS, 256, H, H, MAX_PAGES),
    "tail1024": (1, MAX_LEN, H, H, MAX_PAGES),
    "int8_t1": (SLOTS, 1, H, H, MAX_LEN // 32, "int8"),
    "int8_verify5": (SLOTS, 5, H, H, MAX_LEN // 32, "int8"),
}


@pytest.mark.parametrize("shape", list(_PAGED_PREFILL_SHAPES))
def test_paged_prefill_kernel_any_width(one, shape):
    """VMEM of the chunk/verify kernel must fit a v5e core at every width a
    cell or an engine mode runs it: it must not grow with T (256 and 1024
    asked for 17.6M / 24M of a 16M core before the query rows were tiled),
    and the walk's double buffers and score tile (`_pages_per_block`,
    `_heads_per_tile`) must fit beside the query tile at every page size -
    64 KB at GPT-3's 16 kv heads, 32 KB at Mistral's 8, 8 KB at the
    hybrid's 2.  An int8 pool's pages are copied as they lie too; only its
    scale lanes are gathered through the table."""
    B, T, heads, kvh, entries, *int8 = _PAGED_PREFILL_SHAPES[shape]
    page = 32 if int8 else PAGE
    pool_pages = B * entries // 2 + 1
    i32 = jnp.int32
    pool = _s(pool_pages, page, kvh, HD, dtype=jnp.int8 if int8 else BF16)
    args = [_s(B, T, heads, HD), pool, pool, _s(B, entries, dtype=i32),
            _s(B, dtype=i32), _s(B, dtype=i32)]
    if int8:
        args += [_s(pool_pages, page, kvh, dtype=jnp.float32)] * 2
    text = _compile(
        lambda q, k, v, t, qo, vl, *sc: PA.paged_prefill_attention(
            q, k, v, t, qo, vl, kv_scales=sc or None), *one(args))
    # the pools are read where they lie: no copy of one feeds the call
    assert f"[{pool_pages},{page},{kvh},{HD}]" in text
    assert not [ln for ln in text.splitlines()
                if " copy(" in ln and f"[{pool_pages},{page},{kvh},{HD}]"
                in ln]


def _model(layers=2):
    cfg = G.GPTConfig(vocab_size=50304, hidden_size=H * HD, num_layers=layers,
                      num_heads=H, max_seq_len=2048, dtype=BF16)
    params = jax.eval_shape(functools.partial(G.init_params, cfg),
                            jax.random.key(0))
    pool = jax.eval_shape(functools.partial(
        G.init_paged_cache, cfg, POOL_PAGES, PAGE))
    return cfg, params, pool


def _pool_sized_moves(text, layers):
    """Instructions of a compiled program that copy, slice out or write back
    a pool lane or one layer's plane of it (an in-place scatter is none)."""
    import re
    dims = f"{POOL_PAGES},{PAGE},{H},{HD}]"
    shapes = (f"[{layers},{dims}", f"[1,{dims}", f"[{dims}",
              f"[{layers * POOL_PAGES},{PAGE},{H},{HD}]")
    hits = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if m and m.group(2) in ("copy", "copy-start", "dynamic-slice",
                                "dynamic-update-slice") \
                and any(sh in m.group(1) for sh in shapes):
            hits.append(line.strip()[:120])
    return hits


def test_fused_serve_step_T5(one):
    """`serve_step_paged` as the default engine compiles it: 32 slots,
    spec_len 4 -> T=5, pool donated — and carried through the layer loop: the
    TPU compiler leaves no copy, slice or write-back of a pool plane around
    the Mosaic call."""
    cfg, params, pool = _model()
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.key(0))
    text = _compile(
        lambda p, tok, pl_, tbl, qo, vl, k, g: G.serve_step_paged(
            p, tok, pl_, tbl, qo, vl, cfg, key=k, greedy=g),
        *one([params, _s(SLOTS, 5, dtype=i32), pool,
              _s(SLOTS, MAX_PAGES, dtype=i32), _s(SLOTS, dtype=i32),
              _s(SLOTS, dtype=i32), key, _s(SLOTS, dtype=jnp.bool_)]),
        donate_argnums=(2,))
    assert _pool_sized_moves(text, cfg.num_layers) == []


def test_bucketed_prefill_moves_no_pool_plane(one):
    """`prefill_paged` at a 256 bucket, the program every admission runs:
    flash kernel inside, whole-page writes into the carried pool in place."""
    cfg, params, pool = _model()
    i32 = jnp.int32
    text = _compile(
        lambda p, ids, pl_, pg, ln: G.prefill_paged(p, ids, cfg, pl_, pg, ln),
        *one([params, _s(1, 256, dtype=i32), pool,
              _s(1, 256 // PAGE, dtype=i32), _s(1, dtype=i32)]),
        donate_argnums=(2,))
    assert _pool_sized_moves(text, cfg.num_layers) == []


def test_prefix_hit_tail_program_at_max_model_len(one):
    """Bucketed mode sends prefix-hit tails through `prefill_chunk_paged` at
    width max_model_len (engine `_chunk = buckets[-1]`): RESOURCE_EXHAUSTED
    24.00M > 16.00M at the seed."""
    cfg, params, pool = _model()
    i32 = jnp.int32
    _compile(
        lambda p, ids, pl_, tbl, qo, vl: G.prefill_chunk_paged(
            p, ids, cfg, pl_, tbl, qo, vl),
        *one([params, _s(1, MAX_LEN, dtype=i32), pool,
              _s(1, MAX_PAGES, dtype=i32), _s(1, dtype=i32),
              _s(1, dtype=i32)]),
        donate_argnums=(2,))


# ---- the hybrid configuration at its published widths ----------------------
# (Nemotron-3-Nano-30B-A3B: hidden 2688, experts 2688 -> 1856 -> 2688 with 64
# of 128 held, attention 32 Q / 2 KV heads of 128, Mamba-2 64 heads x 64 with
# state 128; depth cut to one layer of each kind)

def _hybrid(pattern="M*E*"):
    from paddle_tpu.models import hybrid
    cfg = hybrid.HybridConfig(
        vocab_size=65536, hidden_size=2688, num_layers=len(pattern),
        layer_pattern=pattern, num_heads=32, num_kv_heads=2, head_dim=128,
        max_seq_len=2048, dtype=BF16, mamba_num_heads=64, mamba_head_dim=64,
        ssm_state_size=128, mamba_n_groups=8, n_routed_experts=128,
        experts_here=64, num_experts_per_tok=6, moe_intermediate_size=1856,
        moe_shared_intermediate_size=3712, routed_scaling_factor=2.5)
    params = jax.eval_shape(functools.partial(hybrid.init_params, cfg),
                            jax.random.key(0))
    pool = jax.eval_shape(functools.partial(
        hybrid.init_paged_cache, cfg, 64 * 128 + 1, PAGE, 64))
    return hybrid, cfg, params, pool


@pytest.mark.parametrize("rows", [384, 6144], ids=["decode64x6", "prefill1024x6"])
def test_grouped_expert_matmul_at_published_widths(one, rows):
    """The megablox kernel under `grouped_matmul`'s tiling, both products of
    an expert (2688 -> 1856 -> 2688; 1856 is no multiple of 128), 64 held
    experts of a 128-wide router."""
    i32 = jnp.int32
    _compile(lambda x, w, sz: GM.grouped_matmul(x, w, sz, 0, True),
             *one([_s(rows, 2688), _s(64, 1856, 2688), _s(128, dtype=i32)]))
    _compile(lambda x, w, sz: GM.grouped_matmul(x, w, sz, 0),
             *one([_s(rows, 1856), _s(64, 1856, 2688), _s(128, dtype=i32)]))


def test_grouped_expert_matmul_backward_at_training_widths(one):
    """A training step's grouped products at JoyAI-LLM-Flash's widths (16
    held experts of a 256-wide router, 2048 -> 768 -> 2048, 32,768 gathered
    rows, 512 rows a tile): forward, the rows' gradient (`gmm` against the
    transposed matrices) and the matrices' (`tgmm`)."""
    i32 = jnp.int32
    tile = GM.TRAIN_ROW_TILE

    def up(x, w, sz):
        return GM.grouped_matmul(x, w, sz, 0, True, tile) \
            .astype(jnp.float32).sum()

    def down(x, w, sz):
        return GM.grouped_matmul(x, w, sz, 0, False, tile) \
            .astype(jnp.float32).sum()

    for fn, k in ((up, 2048), (down, 768)):
        text = _compile(jax.grad(fn, argnums=(0, 1)), *one(
            [_s(32768, k), _s(16, 768, 2048), _s(256, dtype=i32)]))
        assert text.count("tpu_custom_call") >= 2


def _lane_sized_moves(text, pool):
    """Instructions that copy or slice out a whole lane of the hybrid state
    tree (the K/V pool or a slot-indexed state lane), or all of a layer's
    expert matrices (a [D, F] up-projection with F = 1856 got a transposed
    layout on the chip and was copied for the kernel every call)."""
    import re
    # (the 2.4 MB convolution windows are re-tiled in passing: [64, 3, 6144]
    # has no tile-aligned layout; the lanes that weigh are the K/V pool's and
    # the 134 MB SSM states)
    shapes = {"[" + ",".join(map(str, a.shape)) + "]" for a in pool.values()
              if a.size * a.dtype.itemsize > 16e6}
    shapes.add("[64,1856,2688]")        # and of a layer's expert matrices
    hits = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(", line)
        if m and m.group(2) in ("copy", "copy-start", "dynamic-slice") \
                and any(sh in m.group(1) for sh in shapes):
            hits.append(line.strip()[:120])
    return hits


def test_hybrid_fused_step_keeps_both_pools_in_place(one):
    """`hybrid.serve_step_paged` as the engine compiles it for the benchmark
    cell (64 slots, T=1, the whole state tree donated): the paged kernel at
    G=16, the expert kernel, and no copy of the K/V pool or of a state lane."""
    hybrid, cfg, params, pool = _hybrid()
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.key(0))
    text = _compile(
        lambda p, tok, pl_, tbl, qo, vl, k, g: hybrid.serve_step_paged(
            p, tok, pl_, tbl, qo, vl, cfg, key=k, greedy=g),
        *one([params, _s(64, 1, dtype=i32), pool, _s(64, 128, dtype=i32),
              _s(64, dtype=i32), _s(64, dtype=i32), key,
              _s(64, dtype=jnp.bool_)]),
        donate_argnums=(2,))
    assert _lane_sized_moves(text, pool) == []


def test_hybrid_bucketed_prefill_keeps_both_pools_in_place(one):
    hybrid, cfg, params, pool = _hybrid()
    i32 = jnp.int32
    text = _compile(
        lambda p, ids, pl_, pg, ln, sl: hybrid.prefill_paged(
            p, ids, cfg, pl_, pg, ln, sl),
        *one([params, _s(1, 256, dtype=i32), pool,
              _s(1, 256 // PAGE, dtype=i32), _s(1, dtype=i32),
              _s(1, dtype=i32)]),
        donate_argnums=(2,))
    assert _lane_sized_moves(text, pool) == []


def _latent(pattern="LFLE"):
    """The Xing4.0 cell's program at its published widths and engine shapes
    (`benchmarks/configs/xing4-29b-a4b-d13e16.json` through its driver),
    cut to two layers."""
    import json
    import pathlib

    from benchmarks.drivers import serve_xing4
    from paddle_tpu.models import hybrid
    root = pathlib.Path(__file__).resolve().parents[1]
    conf = json.loads((root / "benchmarks/configs/"
                       "xing4-29b-a4b-d13e16.json").read_text())
    model = dict(serve_xing4.model_of(conf), mixer_pattern=pattern)
    cfg = serve_xing4.program_config(model)
    eng = conf["engine"]
    params = jax.eval_shape(functools.partial(hybrid.init_params, cfg),
                            jax.random.key(0))
    pool = jax.eval_shape(functools.partial(
        hybrid.init_paged_cache, cfg, eng["num_pages"], eng["page_size"],
        eng["num_slots"]))
    return hybrid, cfg, params, pool, eng


def _latent_moves(text, pool):
    """Copies or slices of the whole latent lane, or of a layer's expert
    matrices."""
    import re
    shapes = {"[" + ",".join(map(str, a.shape)) + "]" for a in pool.values()}
    shapes.add("[16,1024,3584]")
    return [line.strip()[:120] for line in text.splitlines()
            if (m := re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) ([\w\-]+)\(",
                              line))
            and m.group(2) in ("copy", "copy-start", "dynamic-slice")
            and any(sh in m.group(1) for sh in shapes)]


def test_latent_fused_step_keeps_the_lane_in_place(one):
    """`hybrid.serve_step_paged` as the engine compiles it for the Xing4.0
    cell (128 slots, T=1, 96 table entries of 64-token pages, the lane
    donated): the latent kernel at 32 heads on one 640-wide row, the gated
    expert products, four residual streams - and no copy of the lane."""
    hybrid, cfg, params, pool, eng = _latent()
    B, n = eng["num_slots"], eng["max_model_len"] // eng["page_size"]
    i32 = jnp.int32
    key = jax.eval_shape(lambda: jax.random.key(0))
    text = _compile(
        lambda p, tok, pl_, tbl, qo, vl, k, g: hybrid.serve_step_paged(
            p, tok, pl_, tbl, qo, vl, cfg, key=k, greedy=g),
        *one([params, _s(B, 1, dtype=i32), pool, _s(B, n, dtype=i32),
              _s(B, dtype=i32), _s(B, dtype=i32), key,
              _s(B, dtype=jnp.bool_)]),
        donate_argnums=(2,))
    assert pool["c"].shape == (2, eng["num_pages"], eng["page_size"], 640)
    assert _latent_moves(text, pool) == []
    assert "paged_latent" in text


@pytest.mark.parametrize("bucket", [64, 2048])
def test_latent_bucketed_prefill_keeps_the_lane_in_place(one, bucket):
    hybrid, cfg, params, pool, eng = _latent()
    i32 = jnp.int32
    text = _compile(
        lambda p, ids, pl_, pg, ln, sl: hybrid.prefill_paged(
            p, ids, cfg, pl_, pg, ln, sl),
        *one([params, _s(1, bucket, dtype=i32), pool,
              _s(1, bucket // eng["page_size"], dtype=i32), _s(1, dtype=i32),
              _s(1, dtype=i32)]),
        donate_argnums=(2,))
    assert _latent_moves(text, pool) == []


_GATHER_SHAPES = {
    # the Xing4.0 cell's latent lane, whole: 96 ids in pieces of 8
    "latent640": ({"c": (13, 6145, 64, 640)}, 96, 8),
    # the Mistral cell's K/V pool: 128 ids in pieces of 16
    "dense_kv": ({n: (16, 2049, PAGE, 8, HD) for n in ("k", "v")}, 128, 16),
}


@pytest.mark.parametrize("shape", list(_GATHER_SHAPES))
def test_swap_out_gather_touches_the_pages_it_is_given(one, shape):
    """The body of the engine's `swap_out_impl` (one `swap_out_pages` a
    piece of `_swap_w` over a slot's width of ids) at a cell's pool shape:
    what the compiled program accesses stays within a few times what it
    returns, with no temporaries to speak of.  `a[:, page_ids]`, the form
    before PR 38, made XLA copy the whole 640-wide lane out by columns
    first: 13.9 GB accessed (136 x the output) and 2.65 GB of temporaries."""
    lanes, width, W = _GATHER_SHAPES[shape]

    def swap_out_impl(pool, ids):
        return [G.swap_out_pages(pool, ids[i:i + W])
                for i in range(0, ids.shape[0], W)]

    compiled = jax.jit(swap_out_impl).lower(
        *one([{n: _s(*s) for n, s in lanes.items()},
              _s(width, dtype=jnp.int32)])).compile()
    out_bytes = sum(2 * width * int(np.prod(s)) // s[1]
                    for s in lanes.values())
    assert compiled.cost_analysis()["bytes accessed"] < 10 * out_bytes
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


def test_shard_mapped_kernels_on_four_devices(topo):
    """Pallas under a mesh: the paged kernel head-sharded over mp=4 (the
    serving route) and the flash kernel per shard of a dp2 x mp2 step (the
    trainer's GSPMD route) — `vma` on the out_shapes, every mesh axis manual."""
    mesh = Mesh(np.array(topo.devices), ("mp",))
    rep = NamedSharding(mesh, P())
    heads = lambda nd: NamedSharding(mesh, PA._head_spec(nd))   # noqa: E731
    pool_sh = NamedSharding(mesh, PA._POOL_SPEC)
    i32 = jnp.int32
    _compile(
        lambda q, k, v, t, qo, vl: PA.paged_prefill_attention(
            q, k, v, t, qo, vl, mesh=mesh),
        _on(heads(4), _s(SLOTS, 5, H, HD)),
        _on(pool_sh, _s(POOL_PAGES, PAGE, H, HD)),
        _on(pool_sh, _s(POOL_PAGES, PAGE, H, HD)),
        *_on(rep, [_s(SLOTS, MAX_PAGES, dtype=i32), _s(SLOTS, dtype=i32),
                   _s(SLOTS, dtype=i32)]))

    from paddle_tpu.parallel.hybrid import MeshConfig, _flash_per_shard, \
        build_mesh
    cfg, _, _ = _model()
    mesh = build_mesh(MeshConfig(dp=2, mp=2), topo.devices)
    attn = _flash_per_shard(mesh, cfg)
    qkv = _on(NamedSharding(mesh, P(("dp", "sharding", "ep"), None, "mp")),
              [_s(4, 2048, H, HD)] * 3)
    _compile(jax.grad(lambda q, k, v: attn(q, k, v).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2)), *qkv)
