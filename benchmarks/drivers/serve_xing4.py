"""Driver of the `xing4_0` configuration's serving cells: `drivers.serve.Driver`
with what names the dense model replaced — the weights
(`harness/weights_xing4.py`), the program's configuration
(`models.hybrid.HybridConfig` with latent attention, gated experts and four
residual streams), the plain reference (`reference/xing4.py`) and the facts
the new work functions take.  The window, the request records, the
end-to-end numbers and `correct`'s comparison are the parent class's; the
readings of `correct` are `serve_hybrid`'s (the same discontinuous top-k
choice: the 99th percentile of the served gaps and the share of inexact
picks).

The configuration file keeps the published `config.json` keys at its top
level (as the catalog has them, the reduced ones changed); `model_of` gathers
them, with the share this chip holds, into the `model` dict that the
reference, the weights and the work functions read.

This configuration has no recurrent state: the engine serves it as a paged
model (prefix index, parked pages, spill tier), and the run starts from a
full pool as the dense cells' do (`serve.Driver._fill_pool`).
"""
from __future__ import annotations

import time

import numpy as np

from ..harness import weights_xing4
from ..reference import xing4 as ref
from . import serve, serve_hybrid


def model_of(config: dict) -> dict:
    """The `model` dict: every scalar the published config has (top level of
    the file), its `rope_scaling` group, the share (`router_experts`,
    `expert_offset`), the assumed values, and `mixer_pattern`: two mixers a
    layer, attention then a dense FFN (the leading layers) or experts."""
    model = {k: v for k, v in config.items()
             if isinstance(v, (int, float, str, bool)) and
             k not in ("name", "source", "deployment", "engine_why")}
    model["rope_scaling"] = dict(config["rope_scaling"])
    model.update(config["share"])
    model.update({k: v for k, v in config["assumed_values"].items()
                  if k != "hc_phi_std"})
    dense = model["first_k_dense_replace"]
    model["mixer_pattern"] = "LF" * dense + \
        "LE" * (model["num_hidden_layers"] - dense)
    return model


def program_config(model: dict):
    """The program's configuration, derived from `model` (no width is
    written twice)."""
    import jax.numpy as jnp

    from paddle_tpu.models.hybrid import HybridConfig
    pattern = model["mixer_pattern"]
    return HybridConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_layers=len(pattern), layer_pattern=pattern,
        num_heads=model["num_attention_heads"],
        max_seq_len=model["max_position_embeddings"],
        intermediate_size=model["intermediate_size"],
        rms_norm_eps=model["rms_norm_eps"],
        initializer_range=model["initializer_range"],
        dtype=jnp.dtype(model["dtype"]),
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], rope_theta=model["rope_theta"],
        rope_scaling=model["rope_scaling"],
        hc_mult=model["hc_mult"],
        hc_sinkhorn_iters=model["hc_sinkhorn_iters"], hc_eps=model["hc_eps"],
        hc_res_clamp=(model["mhc_h_res_clamp_min"],
                      model["mhc_h_res_clamp_max"]),
        n_routed_experts=model["router_experts"],
        experts_here=model["n_routed_experts"],
        expert_offset=model["expert_offset"],
        num_experts_per_tok=model["num_experts_per_tok"],
        moe_intermediate_size=model["moe_intermediate_size"],
        moe_shared_intermediate_size=model["moe_intermediate_size"] *
        model["n_shared_experts"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"], moe_gated=True)


_SLICE_COUNTERS = ("moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
                   "latent_tokens_written", "mla_absorbed_rows",
                   "engine_steps")


class Driver(serve_hybrid.Driver):
    def __init__(self, cell, seed: int, say):
        cell.config["model"] = model_of(cell.config)
        # the parent's facts name a head_dim (the K/V kernel's roofline reads
        # it; no metric of this configuration does): the score's width
        cell.config["model"]["head_dim"] = \
            cell.config["qk_nope_head_dim"] + cell.config["qk_rope_head_dim"]
        serve.Driver.__init__(self, cell, seed, say)

    def setup(self) -> None:
        from paddle_tpu.inference.engine import LLMEngine
        t0 = time.perf_counter()
        # first, so that a program without this configuration fails at once
        cfg = program_config(self.model)
        self.params = weights_xing4.params_on_device(self.model, self.seed)
        t1 = time.perf_counter()
        self.eng = eng = LLMEngine(self.params, cfg, **self.engine_kwargs)
        if not (eng.fused and eng.double_buffer and eng.prefix_cache
                and eng.kv_tier and not eng.recurrent and not eng.chunked):
            raise SystemExit("not the engine's default mode for a paged "
                             "configuration (fused, double-buffered, prefix "
                             "index on, spill tier on, no recurrent state)")
        self._warm()
        self.say("setup", weights_s=round(t1 - t0, 3),
                 engine_and_warm_s=round(time.perf_counter() - t1, 3),
                 buckets_warmed=self.warmed, executables=self._executables(),
                 pool_fill_requests=self.pool_filled,
                 pool_bytes=eng.kv_pool_bytes(),
                 latent_page_bytes=eng.stats()["latent_page_bytes"],
                 parameters=weights_xing4.count_params(self.model))

    _fill_pool = serve.Driver._fill_pool

    def _snap(self) -> dict:
        st = self.eng.stats()
        snap = {k: st[k] for k in _SLICE_COUNTERS}
        snap["admitted_requests"] = \
            self.eng.metrics.snapshot()["counters"]["admitted_requests"]
        return snap

    def _collect(self, sent, outputs, t_close, tokens_in_window, marks,
                 tracer) -> None:
        serve.Driver._collect(self, sent, outputs, t_close, tokens_in_window,
                              marks, tracer)
        m = self.model
        self.facts.update(hidden=m["hidden_size"],
                          moe_width=m["moe_intermediate_size"],
                          latent=m["kv_lora_rank"],
                          rope=m["qk_rope_head_dim"])
        c = self._marked.marks
        if "slice_seconds" in self.facts and "c1" in c:
            d = {k: c["c1"][k] - c["c0"][k] for k in c["c0"]}
            programs = max(1, d["engine_steps"] + d["admitted_requests"])
            layers = m["mixer_pattern"].count("E") * programs
            self.facts.update(
                slice_prefills=d["admitted_requests"],
                slice_moe_pairs_here=d["moe_pairs_here"],
                slice_moe_pairs_away=d["moe_pairs_away"],
                slice_moe_pairs_per_call=d["moe_pairs_here"] / layers,
                slice_moe_experts_touched_per_call=d["moe_experts_touched"] /
                layers,
                # the latent kernel's calls: one a latent layer and program;
                # per call the rows written behind its slots and its query
                # tokens
                slice_latent_tokens=d["latent_tokens_written"],
                slice_latent_tokens_per_call=d["latent_tokens_written"] /
                programs,
                slice_mla_rows_per_call=d["mla_absorbed_rows"] / programs)
        st = self.eng.stats()
        self.say("xing4", **{k: st[k] for k in (
            "moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
            "moe_load_max", "latent_tokens_written", "mla_absorbed_rows",
            "latent_page_bytes", "prefix_hit_requests", "prefix_evictions",
            "preemptions")}, kv_tier=st["kv_tier"])

    def reference_logits(self, sample, prec: str = "f32", fault: str = ""):
        """(logits [n, V] at every served position of the sample, served
        tokens [n]); prompt + served tokens, teacher-forced, through this
        configuration's reference (`fault`: a wrong program in its place,
        for the readings' upper side)."""
        law_p, law_o = self.mix["prompt_len"], self.mix["output_len"]
        width = -(-(law_p["max"] + law_o["max"]) // 128) * 128
        toks = np.zeros((len(sample), width), np.int32)
        rows, cols, served = [], [], []
        for i, rec in enumerate(sample):
            prompt, out = self.served(rec)
            seq = np.concatenate([prompt, out[:-1]])
            toks[i, :seq.size] = seq
            rows += [i] * out.size
            cols += list(range(prompt.size - 1, prompt.size - 1 + out.size))
            served += out.tolist()
        logits = ref.logits_at(self.params, toks, np.asarray(rows),
                               np.asarray(cols), self.model, prec, fault)
        return logits, np.asarray(served)
