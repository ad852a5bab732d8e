"""Driver of the `joyai_llm_flash` configuration's training cell:
`HybridParallelTrainer.train_step` over a patterned configuration
(`models.hybrid.HybridConfig`: latent attention, dense FFN, dropless experts,
the next-n module), a new batch every step, one step in flight — `drivers/
train.py`'s loop, with what names the dense model replaced: the weights
(`harness/weights_joyai.py`), the program's configuration, the plain
reference with gradients (`reference/joyai_flash.py`) and the facts the new
work functions take.

Set-up builds ONE trainer, gives it the benchmark's weights, drives it from
the seed through its first `check_steps` steps by the window's own call and
feed, and keeps what `correct` compares: each step's two losses (as the step
program returned them), the first gradient's norms by leaf (from the
optimizer's first moment after one step), the parameters' change by leaf and
every router bias after the last of them, and the count of token-expert
pairs past the expert layer's static bound (nought, or the layer dropped
something).  The reference follows those steps once the window has closed and
the trainer's state is freed.

The configuration file keeps the published `config.json` keys at its top
level (as the catalog has them, the reduced ones changed); `model_of`
gathers them, with the share this chip holds and the assumed values, into
the `model` dict that the reference, the weights and the work functions
read.
"""
from __future__ import annotations

import time

import numpy as np

from ..harness import compare, traffic, weights, weights_joyai
from ..reference import joyai_flash as ref
from . import train


def model_of(config: dict) -> dict:
    """The `model` dict: every scalar the published config has (top level of
    the file; `rope_scaling` is null), the share (`router_experts`,
    `expert_offset`), the assumed values, and `mixer_pattern`: two mixers a
    layer, attention then a dense FFN (the leading layers) or experts."""
    model = {k: v for k, v in config.items()
             if isinstance(v, (int, float, str, bool)) and
             k not in ("name", "source", "deployment", "parameters_held")}
    model["rope_scaling"] = config["rope_scaling"]
    model.update(config["share"])
    model.update(config["assumed_values"])
    dense = model["first_k_dense_replace"]
    model["mixer_pattern"] = "LF" * dense + \
        "LE" * (model["num_hidden_layers"] - dense)
    return model


def program_config(model: dict, seq: int):
    """The program's configuration, derived from `model` (no width is
    written twice)."""
    import jax.numpy as jnp

    from paddle_tpu.models.hybrid import HybridConfig
    pattern = model["mixer_pattern"]
    return HybridConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_layers=len(pattern), layer_pattern=pattern,
        num_heads=model["num_attention_heads"], max_seq_len=seq,
        intermediate_size=model["intermediate_size"],
        rms_norm_eps=model["rms_norm_eps"],
        initializer_range=model["initializer_range"],
        dtype=jnp.dtype(model["dtype"]),
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"],
        qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], rope_theta=model["rope_theta"],
        rope_scaling=model["rope_scaling"], hc_mult=1,
        n_routed_experts=model["router_experts"],
        experts_here=model["n_routed_experts"],
        expert_offset=model["expert_offset"],
        num_experts_per_tok=model["num_experts_per_tok"],
        moe_intermediate_size=model["moe_intermediate_size"],
        moe_shared_intermediate_size=model["moe_intermediate_size"] *
        model["n_shared_experts"],
        routed_scaling_factor=model["routed_scaling_factor"],
        norm_topk_prob=model["norm_topk_prob"], moe_gated=True,
        num_nextn_predict_layers=model["num_nextn_predict_layers"],
        mtp_loss_weight=model["mtp_loss_weight"],
        router_bias_update_rate=model["router_bias_update_rate"])


def _norms(tree):
    """{leaf path: norm}; traced: call under jit."""
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def _without_bias(tree):
    """The tree without its `router_bias` leaves (they are the rule's, and
    are compared on their own)."""
    if isinstance(tree, dict):
        return {k: _without_bias(v) for k, v in tree.items()
                if k != "router_bias"}
    if isinstance(tree, list):
        return [_without_bias(v) for v in tree]
    return tree


def _host_norms(norms) -> dict:
    import jax
    return {k: float(v) for k, v in ref.flat(jax.device_get(norms)).items()}


class Driver(train.Driver):
    """`end_to_end`, `release` and `check` are the dense driver's."""

    def __init__(self, cell, seed: int, say):
        self.cell, self.seed, self.say = cell, seed, say
        self.model = cell.config["model"] = model_of(cell.config)
        self.mix = cell.traffic
        self.opt = cell.config["trainer"]["optimizer"]
        self.attempted = self.failed = 0
        self.step_trees = []        # each step's loss and counters, unread

    # ---- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from paddle_tpu.parallel import HybridParallelTrainer, MeshConfig

        tcfg = self.cell.config["trainer"]
        # first, so that a program without this configuration fails at once
        cfg = program_config(self.model, self.mix["seq"])
        o = self.opt
        t0 = time.perf_counter()
        self.trainer = HybridParallelTrainer(
            cfg, MeshConfig(**tcfg["mesh"]),
            learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
            beta1=o["beta1"], beta2=o["beta2"],
            grad_clip_norm=o["grad_clip_norm"], seed=0,
            moment_dtype=jnp.dtype(tcfg["moment_dtype"]))
        t1 = time.perf_counter()
        self._install_weights()
        self.say("setup", trainer_s=round(t1 - t0, 3),
                 weights_and_bias_fit_s=round(time.perf_counter() - t1, 3),
                 parameters=weights_joyai.count_params(self.model))

        self.batches = traffic.BatchSource(self.mix, self.seed,
                                           self.model["vocab_size"])
        norms = jax.jit(_norms)
        b1 = o["beta1"]
        n = self.mix["check_steps"]
        self.check_batches, self.got = [], {}
        t0 = time.perf_counter()
        marks = []
        for i in range(1, n + 1):
            tok, lab = self.batches.take()
            self.check_batches.append((tok, lab))
            float(self._step(tok, lab))         # the step has ended
            marks.append(round(time.perf_counter() - t0, 3))
            if i == 1:
                self.got["grad1"] = {
                    k: v / (1 - b1) for k, v in _host_norms(norms(
                        self.trainer.opt_state["m"])).items()}
        trees = jax.device_get(self.step_trees)
        for i, tree in enumerate(trees, start=1):
            self.got[f"loss_main{i}"] = float(tree["loss_main"])
            self.got[f"loss_mtp{i}"] = float(tree["loss_mtp"])
        self.got["pairs_over_bound"] = \
            self.trainer.stats()["moe_pairs_over_bound"]
        # the change since the weights were installed, a group of leaves at
        # a time against the host's copy (weights made again inside another
        # program differ in their last bit: the compiler may keep excess
        # precision where it fuses the draw with its use)
        diff = jax.jit(lambda a, b: _norms(jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
        self.got["change"] = {}
        for (where, now), (_, was) in zip(
                ref.groups(_without_bias(self.trainer.params)),
                ref.groups(self.params0)):
            prefix = ".".join(str(w) for w in where)
            self.got["change"].update({
                f"{prefix}.{k}" if prefix else k: v
                for k, v in _host_norms(diff(now, was)).items()})
        del self.params0
        self.got["router_bias"] = np.stack(jax.device_get(
            weights_joyai.router_biases(self.trainer.params)))
        self.say("setup", first_steps_s=round(time.perf_counter() - t0, 3),
                 step_marks_s=marks,
                 losses=[[self.got[f"loss_main{i}"], self.got[f"loss_mtp{i}"]]
                         for i in range(1, n + 1)],
                 pairs_here_by_layer=[t["moe_pairs_here"].tolist()
                                      for t in trees],
                 load_max_min=[[int(t["moe_load_max"].max()),
                                int(t["moe_load_min"].min())] for t in trees])

    def _install_weights(self) -> None:
        """The benchmark's weights in place of the trainer's own."""
        import jax
        tr = self.trainer
        for leaf in jax.tree_util.tree_leaves(tr.params):
            leaf.delete()
        params = weights_joyai.params_on_device(self.model, self.seed)
        self.bias0 = np.stack(jax.device_get(
            weights_joyai.router_biases(params)))
        # on the host, for the change after the first steps (the step
        # donates the device's copy)
        self.params0 = jax.device_get(_without_bias(params))
        tr.params = jax.device_put(params, tr.param_shardings)

    def _step(self, tok, lab):
        """The timed call; the step's tree (the loss and what the program
        counted) is kept as it came, unread."""
        loss = self.trainer.train_step(tok, lab)
        self.step_trees.append(self.trainer.last_step)
        return loss

    # ---- the window ---------------------------------------------------------
    def window(self, seconds: float, tracer) -> None:
        import jax
        import jax.profiler
        B, S = self.mix["batch"], self.mix["seq"]
        trace_at = seconds - self.mix["trace_seconds"]
        first = len(self.step_trees)    # the window's first step
        ends = []                       # host time at which each step's loss was read
        t0 = time.perf_counter()
        prev = None
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            if tracer.wanted and tracer.t_start is None and now >= trace_at:
                tracer.start()
            with jax.profiler.TraceAnnotation("bench.step.feed"):
                tok, lab = self.batches.take()
                loss = self._step(tok, lab)
            if prev is not None:
                with jax.profiler.TraceAnnotation("bench.step.wait"):
                    self.last_loss = float(prev)
                ends.append(time.perf_counter())
            prev = loss
        self.last_loss = float(prev)
        ends.append(time.perf_counter())
        self.elapsed = ends[-1] - t0
        if tracer.on:
            tracer.stop()
        self.steps = len(ends)
        self.attempted, self.failed = self.steps, int(
            not np.isfinite(self.last_loss))
        m = self.model
        e_layers = m["mixer_pattern"].count("E") + \
            m["num_nextn_predict_layers"] * ref.MTP_PATTERN.count("E")
        self.facts = {"batch": B, "seq": S,
                      "heads": m["num_attention_heads"],
                      "score_dim": m["qk_nope_head_dim"] +
                      m["qk_rope_head_dim"],
                      "v_dim": m["v_head_dim"], "hidden": m["hidden_size"],
                      "moe_width": m["moe_intermediate_size"],
                      "experts_held": m["n_routed_experts"],
                      "expert_layers": e_layers,
                      "host_spans": self.mix["host_spans"]}
        trees = jax.device_get(self.step_trees[first:])
        if tracer.t_start is not None:
            # the steps that began and ended inside the slice
            whole = [j for j in range(1, len(ends))
                     if ends[j - 1] >= tracer.t_start
                     and ends[j] <= tracer.t_stop]
            pairs = sum(int(trees[j]["moe_pairs_here"].sum()) for j in whole)
            self.facts.update(
                slice_tokens=len(whole) * B * S, slice_steps=len(whole),
                slice_seconds=sum(ends[j] - ends[j - 1] for j in whole),
                slice_moe_pairs_here=pairs,
                slice_moe_pairs_per_call=pairs / max(1, len(whole) * e_layers))
        self.say("window", steps=self.steps, elapsed_s=self.elapsed,
                 last_loss=self.last_loss,
                 step_s_median=float(np.median(np.diff([t0] + ends))))
        st = self.trainer.stats()
        self.say("joyai", **{k: st[k] for k in (
            "train_steps", "tokens_trained", "loss_main", "loss_mtp",
            "moe_pairs_here", "moe_pairs_away", "moe_experts_touched",
            "moe_layer_calls", "moe_load_max", "moe_load_min",
            "moe_pairs_over_bound", "router_bias_moves")},
            window_pairs_here_per_layer_call=float(np.mean(
                [t["moe_pairs_here"].mean() for t in trees])),
            window_pairs_here_first_last=[
                float(trees[0]["moe_pairs_here"].mean()),
                float(trees[-1]["moe_pairs_here"].mean())],
            window_load_max=int(max(t["moe_load_max"].max() for t in trees)),
            window_load_min=int(min(t["moe_load_min"].min() for t in trees)))
        # a bound that cut a pair anywhere in the run is a dropped token
        self.got["pairs_over_bound"] = st["moe_pairs_over_bound"]

    # ---- after the window ---------------------------------------------------
    def reference_readings(self, prec: str = "f32", fault: str = "",
                           rows=None) -> dict:
        """The reference through the first steps.  `prec`, `fault` and
        `rows` are for the controls (lower precision; a wrong program; a
        fault that leaves rows of the batch out)."""
        import jax
        import jax.numpy as jnp
        model, o = self.model, self.opt
        params = weights_joyai.params_on_device(model, self.seed)
        mdt = jnp.dtype(self.cell.config["trainer"]["moment_dtype"])
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, mdt), t))
        opt = {"m": zeros(params), "v": zeros(params)}
        out = {}
        for i, (tok, lab) in enumerate(self.check_batches, start=1):
            if rows is not None:
                tok, lab = tok[rows], lab[rows]
            params, opt, r = ref.train_step(
                params, opt, tok, lab, i, model, o, prec, fault,
                self.mix["reference_rows_per_block"])
            out[f"loss_main{i}"], out[f"loss_mtp{i}"] = \
                r["loss_main"], r["loss_mtp"]
            if i == 1:
                out["grad_norm1"], out["grad1"] = r["grad_norm"], r["grads"]
        del opt
        p0 = jax.jit(lambda k: weights_joyai.make_params(model, k))(
            weights.seed_key(self.seed))
        diff = jax.jit(lambda a, b: _norms(jax.tree_util.tree_map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            _without_bias(a), _without_bias(b))))
        out["change"] = _host_norms(diff(params, p0))
        out["router_bias"] = np.stack(jax.device_get(
            weights_joyai.router_biases(params)))
        return out

    def readings(self, got: dict, want: dict) -> dict:
        """The numbers compared, from the program's readings and the
        reference's."""
        n = self.mix["check_steps"]
        out = {}
        for i in range(1, n + 1):
            for term in ("main", "mtp"):
                out[f"loss_{term}{i}_rel_gap"] = compare.rel_gap(
                    got[f"loss_{term}{i}"], want[f"loss_{term}{i}"])
        out["grad1_worst_leaf_gap"], self.worst_grad_leaf = \
            compare.worst_leaf_gap(got["grad1"], want["grad1"])
        skip = compare.tiny_gradient_leaves(
            {k: v for k, v in want["grad1"].items()
             if not k.endswith("router_bias")})
        out[f"change{n}_worst_leaf_gap"], self.worst_change_leaf = \
            compare.worst_leaf_gap(got["change"], want["change"], skip)
        # a bias entry moves by the rule's step each step: entries further
        # apart than half a step took a different sign somewhere
        half = 0.5 * self.model["router_bias_update_rate"]
        out["router_bias_mismatch_share"] = float(np.mean(
            np.abs(got["router_bias"] - want["router_bias"]) > half))
        out["router_bias_moved_share"] = float(np.mean(
            np.abs(got["router_bias"] - self.bias0) > half))
        out["pairs_over_bound"] = float(got["pairs_over_bound"])
        med = sorted(want["change"][k] for k in want["change"]
                     if k not in skip)
        self.say("check", worst_grad_leaf=self.worst_grad_leaf,
                 worst_change_leaf=self.worst_change_leaf,
                 skipped_tiny_gradient_leaves=len(skip),
                 ref_grad_norm1=want.get("grad_norm1"),
                 ref_median_change=med[len(med) // 2])
        return out
