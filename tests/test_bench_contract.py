"""What the benchmark's drivers (`benchmarks/drivers/serve*.py`) read from
the engine, pinned for the four serving families they drive: dense GPT, the
recurrent hybrid, latent attention (MLA) and DSA.  Each case builds a tiny
engine with only the options a cell's `engine` block sets, checks the
default mode the driver refuses to run without, and checks that every
attribute, method, `stats()` key and step-ring field the drivers read is
there with the type they use it as."""
import jax
import numpy as np
import pytest

from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.models import hybrid

FLAGS = ("fused", "double_buffer", "prefix_cache", "kv_tier", "recurrent",
         "chunked")
METHODS = ("warm_decode", "warm_spec", "warm_swap", "kv_pool_bytes",
           "reset_counters", "stats", "step_trace", "add_request", "step",
           "run", "abort", "progress", "queue_depth")
# numeric stats() keys: the drivers' direct reads, their slice counters and
# `say()` lists, and `benchmarks/checks/inside.py`'s counters
NUMERIC_KEYS = (
    "decode_tokens", "prefilled_tokens", "engine_steps", "decode_iterations",
    "prefix_evictions", "prefix_hit_requests", "preemptions",
    "decode_executables", "verify_executables", "prefill_executables",
    "copy_executables", "swap_executables",
    "swap_ms", "turnaround_ms", "swap_d2h_fetches", "swap_d2h_bytes",
    "swap_d2h_useful_bytes", "swap_h2d_bytes", "swap_h2d_useful_bytes",
    "moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
    "moe_load_max", "ssm_slots_live", "ssm_state_resets", "ssm_state_bytes",
    "ssm_state_pool_bytes", "prefix_lookups_skipped_no_state",
    "latent_tokens_written", "mla_absorbed_rows", "latent_page_bytes",
    "dsa_rows_scored", "dsa_rows_selected", "dsa_step_rows_scored",
    "dsa_step_rows_selected", "index_page_bytes")
KV_TIER_KEYS = ("spills", "restores", "pages_host")
RING_KEYS = ("t", "pages_free", "pages_in_use", "decode_batch", "dispatches",
             "dur_s", "sync_ms", "d2h_ms", "turnaround_ms")
PROGRESS_KEYS = ("token_ids", "finished", "output")


def _dense():
    cfg = gpt_mod.gpt_tiny(64)
    return cfg, {"prefix_cache": True, "chunked": False}


def _recurrent():
    cfg = hybrid.hybrid_tiny(seq_len=64, n_routed_experts=8, experts_here=4,
                             num_experts_per_tok=3)
    return cfg, {"recurrent": True, "prefix_cache": False, "chunked": False}


def _latent():
    cfg = hybrid.latent_tiny(seq_len=64, n_routed_experts=8, experts_here=4,
                             num_experts_per_tok=3)
    return cfg, {"prefix_cache": True, "kv_tier": True, "recurrent": False,
                 "chunked": False}


def _dsa():
    cfg = hybrid.dsa_tiny(seq_len=64)
    return cfg, {"prefix_cache": True, "kv_tier": True, "recurrent": False,
                 "chunked": False}


@pytest.mark.parametrize("family", [_dense, _recurrent, _latent, _dsa],
                         ids=["dense", "recurrent", "latent", "dsa"])
def test_engine_serves_what_the_drivers_read(family):
    cfg, mode = family()
    init = hybrid.init_params if isinstance(cfg, hybrid.HybridConfig) \
        else gpt_mod.init_params
    eng = LLMEngine(init(cfg, jax.random.key(0)), cfg, num_slots=2,
                    page_size=8, max_model_len=64, num_pages=17,
                    prefill_buckets=[32, 64])
    # the default mode each driver checks before it runs
    for name in FLAGS:
        assert isinstance(getattr(eng, name), bool), name
    assert eng.fused and eng.double_buffer
    assert {k: getattr(eng, k) for k in mode} == mode
    for name in METHODS:
        assert callable(getattr(eng, name)), name
    assert eng.buckets == [32, 64]
    assert isinstance(eng._pool, dict) and eng._pool
    assert isinstance(eng.kv_pool_bytes(), int) and eng.kv_pool_bytes() > 0

    eng.warm_decode()
    eng.warm_spec()
    eng.warm_swap()
    rid = eng.add_request(np.arange(1, 12, dtype=np.int32), max_new_tokens=3)
    assert isinstance(eng.queue_depth(), int) and eng.has_work is True
    out = eng.run()[rid]
    assert eng.has_work is False
    assert len(out.token_ids) == 3
    assert sum(n for _, n in out.metrics.emit_times) == 3
    prog = eng.progress(rid)
    assert set(PROGRESS_KEYS) <= set(prog) and prog["finished"]
    assert prog["output"] is out
    ring = eng.step_trace()
    assert ring and all(set(RING_KEYS) <= set(r) for r in ring)
    assert eng.metrics.snapshot()["counters"]["admitted_requests"] >= 1

    st = eng.stats()
    for key in NUMERIC_KEYS:
        assert isinstance(st[key], (int, float)) and \
            not isinstance(st[key], bool), key
    assert sum(v for k, v in st.items() if k.endswith("_executables")) >= 2
    assert st["decode_tokens"] > 0 and st["engine_steps"] > 0
    assert isinstance(st["kv_tier"], dict)
    for key in KV_TIER_KEYS:
        assert isinstance(st["kv_tier"][key], int), key

    eng.reset_counters()
    st = eng.stats()
    assert st["decode_tokens"] == st["engine_steps"] == 0
    assert eng.step_trace() == []
