"""Driver of the hybrid configuration's serving cells: `drivers.serve.Driver`
with what names the dense model replaced — the weights
(`harness/weights_hybrid.py`), the program's configuration
(`models.hybrid.HybridConfig`), the plain reference
(`reference/nemotron_h.py`) and the facts the new work functions take.  The
window, the request records, the end-to-end numbers and `correct`'s comparison
are the parent class's.

The configuration file keeps the published `config.json` keys at its top
level (as the catalog has them, the reduced ones changed); `model_of` gathers
them, with the share this chip holds, into the `model` dict that the
reference, the weights and the work functions read.

A configuration with recurrent state has no prefix reuse (the engine switches
its index off: no state snapshot exists at a prefix boundary), so finished
requests' pages go back to the free list and there is no steady state of
parked pages to reach before the window: `_fill_pool` sends nothing.
"""
from __future__ import annotations

import time

import numpy as np

from ..harness import compare, weights_hybrid
from ..reference import nemotron_h as ref
from . import serve


def model_of(config: dict) -> dict:
    """The `model` dict: every scalar the published config has (top level of
    the file), the share (`router_experts`, `expert_offset`) and the assumed
    values (`initializer_range`, `dtype`)."""
    model = {k: v for k, v in config.items()
             if isinstance(v, (int, float, str, bool)) and
             k not in ("name", "source", "deployment")}
    model.update(config["share"])
    model.update(config["assumed_values"])
    if len(model["hybrid_override_pattern"]) != model["num_hidden_layers"]:
        raise SystemExit("hybrid_override_pattern and num_hidden_layers "
                         "disagree")
    return model


def program_config(model: dict):
    """The program's configuration, derived from `model` (no width is
    written twice)."""
    import jax.numpy as jnp

    from paddle_tpu.models.hybrid import HybridConfig
    return HybridConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        layer_pattern=model["hybrid_override_pattern"],
        num_heads=model["num_attention_heads"],
        num_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        max_seq_len=model["max_position_embeddings"],
        rms_norm_eps=model["norm_eps"],
        initializer_range=model["initializer_range"],
        dtype=jnp.dtype(model["dtype"]),
        mamba_num_heads=model["mamba_num_heads"],
        mamba_head_dim=model["mamba_head_dim"],
        ssm_state_size=model["ssm_state_size"],
        mamba_n_groups=model["n_groups"], conv_kernel=model["conv_kernel"],
        chunk_size=model["chunk_size"],
        time_step_min=model["time_step_min"],
        time_step_max=model["time_step_max"],
        time_step_floor=model["time_step_floor"],
        n_routed_experts=model["router_experts"],
        experts_here=model["n_routed_experts"],
        expert_offset=model["expert_offset"],
        num_experts_per_tok=model["num_experts_per_tok"],
        moe_intermediate_size=model["moe_intermediate_size"],
        moe_shared_intermediate_size=model[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"])


_SLICE_COUNTERS = ("moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
                   "ssm_slots_live", "engine_steps")


class _Marked:
    """The tracer, with the engine's counters read where it starts and
    stops (the parent's window reads only the three it needs)."""

    def __init__(self, tracer, snap):
        self._tracer, self._snap = tracer, snap
        self.marks = {}

    def __getattr__(self, name):
        return getattr(self._tracer, name)

    def start(self):
        self._tracer.start()
        self.marks["c0"] = self._snap()

    def stop(self):
        self.marks["c1"] = self._snap()
        self._tracer.stop()


class Driver(serve.Driver):
    def __init__(self, cell, seed: int, say):
        cell.config["model"] = model_of(cell.config)
        super().__init__(cell, seed, say)

    def setup(self) -> None:
        from paddle_tpu.inference.engine import LLMEngine
        t0 = time.perf_counter()
        self.params = weights_hybrid.params_on_device(self.model, self.seed)
        self.eng = eng = LLMEngine(self.params, program_config(self.model),
                                   **self.engine_kwargs)
        if not (eng.fused and eng.double_buffer and eng.recurrent
                and not eng.chunked and not eng.prefix_cache):
            raise SystemExit("not the engine's default mode for a recurrent "
                             "configuration")
        self._warm()
        self.say("setup", engine_and_warm_s=round(time.perf_counter() - t0, 3),
                 buckets_warmed=self.warmed, executables=self._executables(),
                 pool_bytes=eng.kv_pool_bytes(),
                 state_pool_bytes=eng.stats()["ssm_state_pool_bytes"],
                 parameters=weights_hybrid.count_params(self.model))

    def _fill_pool(self, rng) -> int:
        return 0

    def _snap(self) -> dict:
        st = self.eng.stats()
        snap = {k: st[k] for k in _SLICE_COUNTERS}
        snap["admitted_requests"] = \
            self.eng.metrics.snapshot()["counters"]["admitted_requests"]
        return snap

    def window(self, seconds: float, tracer) -> None:
        self._marked = _Marked(tracer, self._snap)
        super().window(seconds, self._marked)

    def _collect(self, sent, outputs, t_close, tokens_in_window, marks,
                 tracer) -> None:
        super()._collect(sent, outputs, t_close, tokens_in_window, marks,
                         tracer)
        m = self.model
        self.facts.update(hidden=m["hidden_size"],
                          moe_width=m["moe_intermediate_size"])
        c = self._marked.marks
        if "slice_seconds" in self.facts and "c1" in c:
            d = {k: c["c1"][k] - c["c0"][k] for k in c["c0"]}
            # expert-layer calls of the slice: every E layer of every fused
            # step and of every prefill
            calls = m["hybrid_override_pattern"].count("E") * \
                max(1, d["engine_steps"] + d["admitted_requests"])
            self.facts.update(
                slice_prefills=d["admitted_requests"],
                slice_moe_pairs_here=d["moe_pairs_here"],
                slice_moe_pairs_away=d["moe_pairs_away"],
                slice_moe_pairs_per_call=d["moe_pairs_here"] / calls,
                slice_moe_experts_touched_per_call=d["moe_experts_touched"] /
                calls,
                slice_ssm_slots_live_per_step=d["ssm_slots_live"] /
                max(1, d["engine_steps"]))
        st = self.eng.stats()
        self.say("hybrid", **{k: st[k] for k in (
            "moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
            "moe_load_max", "ssm_slots_live", "ssm_state_resets",
            "ssm_state_bytes", "prefix_lookups_skipped_no_state",
            "preemptions")})

    def readings(self) -> dict:
        """The numbers of `correct` over the sample's served positions: the
        99th percentile of the gaps `served_logit_gap_max` is the widest of,
        and the share of served tokens that are not the reference's best.
        The top-6 choice is discontinuous: where bfloat16 activations flip a
        near-tie against the float32 reference the gap of that position
        jumps, so the widest gap of a run is the tail of rare flips, and with
        the head centred (`weights_hybrid.centre_head`) it no longer tells the
        program from the fp8 control (PERF.md section 2): it is printed and
        returned, and the cell's file gives it no limit.  The 99th percentile
        and the share are the bulk of the positions and separate the two by
        two and by four.  No position is dropped."""
        sample = self.sample()
        if not sample:
            return {}
        logits, served = self.reference_logits(sample)
        gaps = compare.served_logit_gap(np.asarray(logits), served)
        numbers = {"served_logit_gap_p99": float(np.percentile(gaps, 99)),
                   "served_inexact_share": float((gaps > 0).mean()),
                   "served_logit_gap_max": float(gaps.max())}
        self.say("check", requests=len(sample), served_tokens=int(served.size),
                 gap_p50=float(np.median(gaps)), **numbers)
        return numbers

    def reference_logits(self, sample, prec: str = "f32"):
        """(logits [n, V] at every served position of the sample, served
        tokens [n]); prompt + served tokens, teacher-forced, through the
        hybrid reference."""
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
                               np.asarray(cols), self.model, prec)
        return logits, np.asarray(served)
