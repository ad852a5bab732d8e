"""Driver for `loop: open` and `loop: closed` mixes: `LLMEngine.add_request`
and `LLMEngine.step` in the engine's default mode, from one thread, under
`jax.transfer_guard("disallow")`.

Open loop: a request is added when it is due, whatever the engine is doing;
latencies run from the schedule's due time, so generator lateness and queueing
are inside them.  Closed loop: each client sends its next request when its
last one finishes.  When the window closes nothing more is sent; the engine is
stepped on until every request that was due has its first token (the wait is
in that request's latency), and what is still decoding is then aborted.

`correct`: once the window has closed, the peak has been read and the engine's
pool is freed, the reference runs once over a sample of the finished
requests (drawn from the seed, the longest among them): prompt plus served
tokens, teacher-forced; the number compared is the widest gap by which a
served token's reference logit lies below the reference's best.
"""
from __future__ import annotations

import time

import numpy as np

from ..harness import compare, traffic, weights
from ..reference import dense_lm as ref

_OK_REASONS = ("length", "stop")

# benchmarks/checks/test_faults.py plants an altered answer here: a function
# (served token ids) -> token ids, applied where the driver reads an output
FAULT = None


def _stat(values, stat: str) -> float:
    v = np.sort(np.asarray(values, float))
    if stat == "mean":
        return float(v.mean())
    if stat.startswith("tail"):
        k = max(1, int(round(len(v) * float(stat[4:]) / 100.0)))
        return float(v[-k:].mean())
    if stat.startswith("p"):
        return float(np.percentile(v, float(stat[1:])))
    raise SystemExit(f"unknown statistic {stat!r} in a metric's name")


class Driver:
    def __init__(self, cell, seed: int, say):
        self.cell, self.seed, self.say = cell, seed, say
        self.model = cell.config["model"]
        self.mix = cell.traffic
        self.engine_kwargs = dict(cell.config["engine"])
        self.attempted = self.failed = 0

    # ---- set-up -----------------------------------------------------------
    def setup(self) -> None:
        import jax.numpy as jnp

        from paddle_tpu.inference.engine import LLMEngine
        from paddle_tpu.models.gpt import GPTConfig

        cfg = GPTConfig(**self.cell.config["program"]["GPTConfig"],
                        dtype=jnp.dtype(self.model["dtype"]))
        t0 = time.perf_counter()
        self.params = weights.params_on_device(self.model, self.seed)
        self.eng = eng = LLMEngine(self.params, cfg, **self.engine_kwargs)
        if not (eng.fused and eng.double_buffer and eng.prefix_cache
                and not eng.chunked):
            raise SystemExit("not the engine's default mode")
        self._warm()
        self.say("setup", engine_and_warm_s=round(time.perf_counter() - t0, 3),
                 buckets_warmed=self.warmed, executables=self._executables(),
                 pool_fill_requests=self.pool_filled,
                 pool_bytes=eng.kv_pool_bytes())

    def _warm(self) -> None:
        """Every program the mix can reach, and no other: one prompt for each
        prefill bucket that a prompt length of the mix falls into, then the
        fused step and the swap pair (as `EngineFleet.warm` does)."""
        eng, law = self.eng, self.mix["prompt_len"]
        rng = np.random.default_rng(0)
        reach = sorted({min(b for b in eng.buckets if n <= b)
                        for n in range(law["min"], law["max"] + 1)})
        self.warmed = reach
        for b in reach:
            n = min(b, law["max"])
            eng.add_request(rng.integers(0, self.model["vocab_size"], n,
                                         dtype=np.int32), max_new_tokens=2)
        eng.run()
        eng.warm_decode()
        eng.warm_spec()
        eng.warm_swap()
        self.pool_filled = self._fill_pool(rng)
        eng.reset_counters()

    def _fill_pool(self, rng) -> int:
        """A server that has run for a while has no free page left: finished
        requests' pages stay in the prefix cache until something needs them,
        and from then on every admission evicts (and, in the engine's default
        mode, spills) pages.  A run starts in that steady state: distinct
        one-token requests of the mix's longest prompt are sent until fewer
        free pages are left than one more of them needs."""
        eng, n = self.eng, self.mix["prompt_len"]["max"]
        need = -(-(n + 1) // self.engine_kwargs["page_size"])
        sent = 0
        while sent < 4096:
            eng.add_request(rng.integers(0, self.model["vocab_size"], n,
                                         dtype=np.int32), max_new_tokens=1)
            eng.run()
            sent += 1
            if eng.step_trace()[-1]["pages_free"] < need:
                break
        return sent

    def _executables(self) -> int:
        st = self.eng.stats()
        return sum(v for k, v in st.items() if k.endswith("_executables"))

    # ---- the window ---------------------------------------------------------
    def window(self, seconds: float, tracer) -> None:
        import jax
        import jax.profiler

        eng, mix = self.eng, self.mix
        src = traffic.RequestSource(mix, self.seed, self.model["vocab_size"],
                                    seconds)
        open_loop = mix["loop"] == "open"
        sent = {}                   # rid -> record
        outputs = []
        trace_at = seconds - mix["trace_seconds"]
        slice_marks = {}
        n_exec = self._executables()
        ann = jax.profiler.TraceAnnotation

        def send(req, t0):
            now = time.perf_counter()
            rid = eng.add_request(req.prompt, max_new_tokens=req.max_new_tokens)
            sent[rid] = {"index": req.index, "t_due": t0 + req.due_s
                         if open_loop else now, "t_sent": now,
                         "n_prompt": int(req.prompt.size),
                         "max_new": req.max_new_tokens}

        def counters():
            st = eng.stats()
            return st["decode_tokens"], st["prefilled_tokens"], \
                st["engine_steps"]

        c_start = counters()
        depth = []                  # (time into the window, requests queued)
        with jax.transfer_guard("disallow"):
            t0 = time.perf_counter()
            nxt = src.take() if open_loop else None
            if not open_loop:
                for _ in range(mix["clients"]):
                    send(src.take(), t0)
            while True:
                now = time.perf_counter() - t0
                if now >= seconds:
                    break
                if tracer.wanted and tracer.t_start is None \
                        and now >= trace_at:
                    tracer.start()
                    slice_marks["c0"] = counters()
                if open_loop:
                    with ann("bench.add"):
                        while nxt is not None and nxt.due_s <= now:
                            send(nxt, t0)
                            nxt = None if src.exhausted() else src.take()
                if eng.has_work:
                    for out in eng.step():
                        outputs.append(out)
                        if not open_loop:
                            with ann("bench.add"):
                                send(src.take(), t0)
                    depth.append((now, eng.queue_depth()))
                else:
                    with ann("bench.idle"):
                        wait = (nxt.due_s - now) if nxt is not None else 0.001
                        time.sleep(max(0.0, min(wait, 0.001)))
            t_close = time.perf_counter()
            tokens_in_window = counters()[0] - c_start[0]
            third = seconds / 3
            self.queue_depth = {
                f"third{k + 1}": float(np.mean(
                    [q for t, q in depth
                     if k * third <= t < (k + 1) * third] or [0]))
                for k in range(3)}
            self.queue_depth["at_close"] = depth[-1][1] if depth else 0
            if tracer.on:
                slice_marks["c1"] = counters()
                tracer.stop()
            self.elapsed = t_close - t0
            # after the close: wait for the first token of every request sent
            deadline = t_close + 60.0
            waiting = set(sent) - {o.request_id for o in outputs}
            while open_loop and waiting and time.perf_counter() < deadline:
                outputs.extend(eng.step())
                done = {o.request_id for o in outputs}
                waiting = {rid for rid in waiting if rid not in done
                           and not eng.progress(rid)["token_ids"]}
            for rid in list(sent):
                if not eng.progress(rid)["finished"]:
                    eng.abort(rid)
            while eng.has_work:         # the harvest an abort leaves behind
                outputs.extend(eng.step())
        self.executables = (n_exec, self._executables())
        self._collect(sent, outputs, t_close, tokens_in_window, slice_marks,
                      tracer)

    def _collect(self, sent, outputs, t_close, tokens_in_window, marks,
                 tracer) -> None:
        eng = self.eng
        open_loop = self.mix["loop"] == "open"
        by_rid = {o.request_id: o for o in outputs}
        for rid in sent:
            if rid not in by_rid:
                out = eng.progress(rid)["output"]
                if out is not None:
                    by_rid[rid] = out
        recs, failed = [], 0
        for rid, s in sent.items():
            out = by_rid.get(rid)
            m = out.metrics if out is not None else None
            bad = out is None or m is None or \
                out.finish_reason not in _OK_REASONS + ("abort",) or \
                (open_loop and m.t_first_token is None)
            failed += bad
            rec = dict(s, rid=rid, ok=not bad)
            if not bad and m.t_first_token is not None:
                rec.update(
                    finish_reason=out.finish_reason,
                    n_generated=m.n_generated,
                    ttft_s=m.t_first_token - s["t_due"],
                    late_s=s["t_sent"] - s["t_due"],
                    queue_s=m.queue_s,
                    finished_in_window=out.finish_reason in _OK_REASONS
                    and m.t_finish <= t_close,
                    tpot_s=(m.t_finish - m.t_first_token) /
                    (m.n_generated - 1) if m.n_generated > 1 else None)
            recs.append(rec)
        self.records, self.by_rid = recs, by_rid
        self.attempted, self.failed = len(sent), failed
        self.tokens_in_window = tokens_in_window
        done = [r for r in recs if r.get("finished_in_window")]
        self.finished_in_window = done
        ctx = sum(r["n_prompt"] * r["n_generated"] + r["n_generated"] ** 2 / 2
                  for r in done)
        gen = sum(r["n_generated"] for r in done)
        pre = sum(r["n_prompt"] ** 2 / 2 for r in done)
        self.facts = {
            "slots": self.engine_kwargs["num_slots"],
            "heads": self.model["num_attention_heads"],
            "kv_heads": self.model["num_key_value_heads"],
            "head_dim": self.model["head_dim"],
            "layers": self.model["num_hidden_layers"],
            "host_spans": self.mix["host_spans"],
            "requests": done,
        }
        if tracer.t_start is not None and "c1" in marks:
            d_tok, p_tok, steps = (b - a for a, b in zip(marks["c0"],
                                                         marks["c1"]))
            ring = [r for r in eng.step_trace()
                    if tracer.t_start <= r["t"] <= tracer.t_stop
                    and r["decode_batch"] > 0]
            live = [r["pages_in_use"] * self.engine_kwargs["page_size"]
                    for r in ring]
            n_pre = sum(r["n_prompt"] for r in done) or 1
            self.facts.update(
                slice_seconds=tracer.t_stop - tracer.t_start,
                slice_tokens=d_tok + p_tok, slice_steps=steps,
                slice_decode_tokens=d_tok, slice_prefilled_tokens=p_tok,
                # keys read by attention: per decoded token its request's
                # mean context, per prefilled token half its prompt
                slice_context_sum=d_tok * (ctx / gen if gen else 0.0) +
                p_tok * (pre / n_pre),
                slice_mean_live_tokens=float(np.mean(live)) if live else 0.0,
                slice_mean_decode_batch=float(np.mean(
                    [r["decode_batch"] for r in ring])) if ring else 0.0)
        late = [r["late_s"] for r in recs if "late_s" in r]

        def pcts(key, rows):
            vals = [r[key] for r in rows if r.get(key) is not None]
            return {f"p{q}": 1e3 * float(np.percentile(vals, q))
                    for q in (50, 75, 85, 90, 95)} if vals else None
        self.say("window", elapsed_s=self.elapsed, sent=len(sent),
                 finished_in_window=len(done), failed=failed,
                 not_finished_at_close=sum(
                     not r.get("finished_in_window") for r in recs),
                 decode_tokens=tokens_in_window,
                 queue_depth=self.queue_depth,
                 generator_late_ms_p95=1e3 * float(np.percentile(late, 95))
                 if late else None,
                 ttft_ms=pcts("ttft_s", recs), tpot_ms=pcts("tpot_s", done),
                 executables_before_after=self.executables)
        self.say("requests", ttft_ms=[round(1e3 * r["ttft_s"], 1)
                                      for r in recs if "ttft_s" in r],
                 tpot_ms=[round(1e3 * r["tpot_s"], 1) for r in done
                          if r["tpot_s"] is not None])
        if self.executables[0] != self.executables[1]:
            raise SystemExit("a program was compiled inside the window: "
                             f"{self.executables}")

    def end_to_end(self) -> dict:
        """`serve_tokens_per_s`, and whichever of `ttft_<stat>_ms` (open loop:
        every request that was due; one that failed has no first token and
        counts as the minute it was waited for) and `tpot_<stat>_ms` (every
        request that finished in the window) the manifest names for the
        cell; <stat> is p<q>, mean, or tail<k> (the mean of the slowest k %)."""
        out = {"serve_tokens_per_s": self.tokens_in_window / self.elapsed}
        series = {"tpot": [r["tpot_s"] for r in self.finished_in_window
                           if r["tpot_s"] is not None]}
        if self.mix["loop"] == "open":
            series["ttft"] = [r.get("ttft_s", 60.0) for r in self.records]
        for m in self.cell.end_to_end:
            what, _, stat = m["name"].partition("_")
            if what in series and series[what] and stat.endswith("_ms"):
                out[m["name"]] = 1e3 * _stat(series[what], stat[:-3])
        return out

    # ---- after the window ---------------------------------------------------
    def release(self) -> None:
        """Free the pool; the weights are the benchmark's own and stay for
        the reference."""
        import jax
        for leaf in jax.tree_util.tree_leaves(self.eng._pool):
            leaf.delete()

    def sample(self) -> list:
        """Finished requests the reference follows: the longest, and others
        drawn from the seed."""
        done = [r for r in self.finished_in_window if r["n_generated"] >= 1]
        if not done:
            return []
        longest = max(done, key=lambda r: r["n_prompt"] + r["n_generated"])
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng([int(self.seed), 4])
        k = min(self.mix["check_requests"] - 1, len(rest))
        picks = [rest[i] for i in rng.choice(len(rest), k, replace=False)] \
            if k else []
        return [longest] + picks

    def served(self, rec) -> tuple:
        out = self.by_rid[rec["rid"]]
        toks = list(out.token_ids)
        if FAULT is not None:
            toks = FAULT(toks)
        return np.asarray(out.prompt, np.int32), np.asarray(toks, np.int32)

    def reference_logits(self, sample, prec: str = "f32"):
        """(logits [n, V] at every served position of the sample, served
        tokens [n]); prompt + served tokens, teacher-forced."""
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

    def readings(self) -> dict:
        sample = self.sample()
        if not sample:
            return {}
        logits, served = self.reference_logits(sample)
        gaps = compare.served_logit_gap(np.asarray(logits), served)
        self.say("check", requests=len(sample), served_tokens=int(served.size),
                 gap_p50=float(np.median(gaps)), gap_max=float(gaps.max()),
                 exact_matches=int((gaps == 0).sum()))
        return {"served_logit_gap_max": float(gaps.max())}

    def check(self) -> list:
        t0 = time.perf_counter()
        numbers = self.readings()
        self.say("check", reference_s=round(time.perf_counter() - t0, 3))
        return compare.checks_from(numbers, self.cell.limits)
