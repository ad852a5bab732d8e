"""Driver for `loop: steps` mixes: `HybridParallelTrainer.train_step`, a new
batch every step, one step in flight.

Set-up builds ONE trainer, gives it the benchmark's weights, drives it from
the seed through its first `check_steps` steps by the window's own call and
feed, keeps what `correct` compares (each step's loss, the first gradient's
norms worked out from the optimizer's state after one step, the parameters'
change after the last of them), and hands the same object to the window.  The
reference follows those steps once the window has closed and the trainer's
state is freed.
"""
from __future__ import annotations

import time

import numpy as np

from ..harness import compare, traffic, weights
from ..reference import dense_lm as ref


# benchmarks/checks/test_faults.py plants a broken step here: a function
# (trainer, tokens, labels) -> loss that stands in for trainer.train_step
FAULT = None


def _leaf_norms(tree) -> dict:
    """{leaf path: norm}, one entry per layer for the stacked block leaves.
    Traced: call under jit."""
    import jax.numpy as jnp
    out = {}
    for name, leaf in tree["blocks"].items():
        sq = jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                     axis=tuple(range(1, leaf.ndim)))
        out[f"blocks.{name}"] = jnp.sqrt(sq)
    for name, leaf in tree.items():
        if name != "blocks":
            out[name] = jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
    return out


def _flat(norms: dict) -> dict:
    """{'blocks.qkv_w[3]': float, 'wte': float, ...} on the host."""
    out = {}
    for k, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{k}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[k] = float(v)
    return out


class Driver:
    def __init__(self, cell, seed: int, say):
        self.cell, self.seed, self.say = cell, seed, say
        self.model = cell.config["model"]
        self.mix = cell.traffic
        self.opt = cell.config["trainer"]["optimizer"]
        self.attempted = self.failed = 0

    # ---- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from paddle_tpu.models.gpt import GPTConfig
        from paddle_tpu.parallel import HybridParallelTrainer, MeshConfig

        tcfg = self.cell.config["trainer"]
        cfg = GPTConfig(**self.cell.config["program"]["GPTConfig"],
                        dtype=jnp.dtype(self.model["dtype"]))
        o = self.opt
        t0 = time.perf_counter()
        self.trainer = HybridParallelTrainer(
            cfg, MeshConfig(**tcfg["mesh"]),
            learning_rate=o["learning_rate"], weight_decay=o["weight_decay"],
            beta1=o["beta1"], beta2=o["beta2"],
            grad_clip_norm=o["grad_clip_norm"], seed=0,
            moment_dtype=jnp.dtype(tcfg["moment_dtype"]))
        self._install_weights()
        self.say("setup", trainer_s=round(time.perf_counter() - t0, 3))

        self.batches = traffic.BatchSource(self.mix, self.seed,
                                           self.model["vocab_size"])
        norms = jax.jit(_leaf_norms)
        b1 = o["beta1"]
        self.check_batches, self.got = [], {}
        t0 = time.perf_counter()
        marks = []
        for i in range(1, self.mix["check_steps"] + 1):
            tok, lab = self.batches.take()
            self.check_batches.append((tok, lab))
            self.got[f"loss{i}"] = float(self._step(tok, lab))
            marks.append(round(time.perf_counter() - t0, 3))
            if i == 1:
                self.got["grad1"] = {
                    k: v / (1 - b1) for k, v in
                    _flat(jax.device_get(norms(
                        self.trainer.opt_state["m"]))).items()}
        model = self.model
        # the key is an argument, so that one program serves every seed
        change = jax.jit(lambda p, key: _leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), p,
            weights.make_params(model, key))))
        self.got["change"] = _flat(jax.device_get(change(
            self.trainer.params, weights.seed_key(self.seed))))
        self.say("setup", first_steps_s=round(time.perf_counter() - t0, 3),
                 step_marks_s=marks,
                 losses=[self.got[f"loss{i}"]
                         for i in range(1, self.mix["check_steps"] + 1)])

    def _install_weights(self) -> None:
        """The benchmark's weights in place of the trainer's own."""
        import jax
        tr = self.trainer
        for leaf in jax.tree_util.tree_leaves(tr.params):
            leaf.delete()
        tr.params = weights.params_on_device(self.model, self.seed,
                                             tr.param_shardings)

    def _step(self, tok, lab):
        """The timed call (FAULT is None outside the fault checks)."""
        if FAULT is not None:
            return FAULT(self.trainer, tok, lab)
        return self.trainer.train_step(tok, lab)

    # ---- the window ---------------------------------------------------------
    def window(self, seconds: float, tracer) -> None:
        import jax.profiler
        B, S = self.mix["batch"], self.mix["seq"]
        trace_at = seconds - self.mix["trace_seconds"]
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
        self.facts = {"batch": B, "seq": S,
                      "heads": self.model["num_attention_heads"],
                      "kv_heads": self.model["num_key_value_heads"],
                      "head_dim": self.model["head_dim"],
                      "host_spans": self.mix["host_spans"]}
        if tracer.t_start is not None:
            # the steps that began and ended inside the slice
            whole = [b - a for a, b in zip(ends, ends[1:])
                     if a >= tracer.t_start and b <= tracer.t_stop]
            self.facts.update(slice_tokens=len(whole) * B * S,
                              slice_steps=len(whole),
                              slice_seconds=sum(whole))
        self.say("window", steps=self.steps, elapsed_s=self.elapsed,
                 last_loss=self.last_loss,
                 step_s_median=float(np.median(np.diff([t0] + ends))))

    def end_to_end(self) -> dict:
        tokens = self.steps * self.mix["batch"] * self.mix["seq"]
        return {"train_tokens_per_s": tokens / self.elapsed}

    # ---- after the window ---------------------------------------------------
    def release(self) -> None:
        import jax
        tr = self.trainer
        for leaf in jax.tree_util.tree_leaves((tr.params, tr.opt_state)):
            leaf.delete()

    def reference_readings(self, prec: str = "f32", rows=None) -> dict:
        """The reference through the first steps.  `prec` and `rows` are for
        the controls (lower precision; a fault that leaves rows out)."""
        import jax
        import jax.numpy as jnp
        model, o = self.model, self.opt
        params = weights.params_on_device(model, self.seed)
        L = model["num_hidden_layers"]
        layers = [ref.layer(params, l) for l in range(L)]
        top = ref.top_of(params)
        for leaf in jax.tree_util.tree_leaves(params["blocks"]):
            leaf.delete()
        mdt = jnp.dtype(self.cell.config["trainer"]["moment_dtype"])
        zeros = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, mdt), t))
        m_l, v_l = [zeros(b) for b in layers], [zeros(b) for b in layers]
        m_t, v_t = zeros(top), zeros(top)
        update = jax.jit(lambda p, g, m, v, step, scale: ref.adamw_update(
            p, g, m, v, step, scale, o), static_argnums=(4,),
            donate_argnums=(0, 2, 3))
        nrm = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
        out = {}
        for i, (tok, lab) in enumerate(self.check_batches, start=1):
            if rows is not None:
                tok, lab = tok[rows], lab[rows]
            loss, g_l, g_t = ref.loss_and_grads(
                layers, top, tok, lab, model, prec,
                self.mix["reference_rows_per_block"])
            out[f"loss{i}"] = float(loss)
            gnorm = float(jnp.sqrt(ref.sq_norm(g_l) + ref.sq_norm(g_t)))
            clip = o["grad_clip_norm"]
            scale = min(clip / max(gnorm, clip), 1.0) if clip else 1.0
            if i == 1:
                out["grad_norm1"] = gnorm
                g1 = {}
                for l, g in enumerate(jax.device_get([nrm(g) for g in g_l])):
                    g1.update({f"blocks.{k}[{l}]": float(v) * scale
                               for k, v in g.items()})
                g1.update({k: float(v) * scale
                           for k, v in jax.device_get(nrm(g_t)).items()})
                out["grad1"] = g1
            s = jnp.asarray(scale, jnp.float32)
            for l in range(L):
                layers[l], m_l[l], v_l[l] = update(layers[l], g_l[l], m_l[l],
                                                   v_l[l], i, s)
            top, m_t, v_t = update(top, g_t, m_t, v_t, i, s)
            # float32 gradients cannot alias the bfloat16 results they were
            # donated for, so they are freed by hand
            for leaf in jax.tree_util.tree_leaves((g_l, g_t)):
                if not leaf.is_deleted():
                    leaf.delete()
        del g_l, g_t, m_l, v_l, m_t, v_t
        p0 = weights.params_on_device(model, self.seed)
        diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
                x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))
        ch = {}
        for l in range(L):
            d = jax.device_get(diff(layers[l], ref.layer(p0, l)))
            ch.update({f"blocks.{k}[{l}]": float(v) for k, v in d.items()})
        ch.update({k: float(v) for k, v in
                   jax.device_get(diff(top, ref.top_of(p0))).items()})
        out["change"] = ch
        return out

    def readings(self, got: dict, want: dict) -> dict:
        """The numbers compared, from the program's readings and the
        reference's."""
        n = self.mix["check_steps"]
        out = {f"loss{i}_rel_gap": compare.rel_gap(got[f"loss{i}"],
                                                   want[f"loss{i}"])
               for i in range(1, n + 1)}
        out["grad1_worst_leaf_gap"], self.worst_grad_leaf = \
            compare.worst_leaf_gap(got["grad1"], want["grad1"])
        skip = compare.tiny_gradient_leaves(want["grad1"])
        out[f"change{n}_worst_leaf_gap"], self.worst_change_leaf = \
            compare.worst_leaf_gap(got["change"], want["change"], skip)
        med = sorted(want["change"][k] for k in want["change"]
                     if k not in skip)
        self.say("check", worst_grad_leaf=self.worst_grad_leaf,
                 worst_change_leaf=self.worst_change_leaf,
                 skipped_tiny_gradient_leaves=len(skip),
                 ref_grad_norm1=want.get("grad_norm1"),
                 ref_median_change=med[len(med) // 2])
        return out

    def check(self) -> list:
        t0 = time.perf_counter()
        want = self.reference_readings()
        numbers = self.readings(self.got, want)
        self.say("check", reference_s=round(time.perf_counter() - t0, 3),
                 numbers=numbers)
        return compare.checks_from(numbers, self.cell.limits)
