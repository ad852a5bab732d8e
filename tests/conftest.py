"""Test config: force the CPU backend with 8 virtual devices so multi-chip sharding
paths compile and execute without TPU hardware (the reference's fake-device CI pattern,
`test/custom_runtime/`)."""
import os

os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=8"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier-1 runs `-m "not slow"` (ROADMAP): 'slow' holds the compile-heavy
    # deep parallel-combo parity tests that would blow the tier-1 time budget
    config.addinivalue_line("markers", "slow: excluded from the tier-1 suite")


import threading  # noqa: E402

import pytest  # noqa: E402


class HeldWorker:
    """Holds an engine's fetch worker (`inference.engine._fetch_piece`, the
    device->host copy of one gathered piece) back until the engine thread
    comes for a piece that has not landed (its take opens the gate first,
    then waits as it always does) or the test opens it: what is in flight
    when is then the test's to decide, not the machine's."""

    def __init__(self, monkeypatch):
        from paddle_tpu.inference import engine as E
        self.gate = threading.Event()
        self.waited_for = 0         # takes that found their piece in flight
        real = E._fetch_piece

        def held(data, n):
            assert self.gate.wait(60), "the gate never opened"
            return real(data, n)
        monkeypatch.setattr(E, "_fetch_piece", held)

    @staticmethod
    def settle(eng):
        """Block until every copy handed to the worker so far has landed
        (the test waits here, so that the engine will not have to)."""
        import concurrent.futures
        concurrent.futures.wait(
            [f for rec in list(eng._pending_d2h) + list(
                eng._preempted.values()) for f, *_ in rec.get("pieces", ())])

    def watch(self, eng):
        take = eng._take_piece

        def opening_take(piece):
            assert eng._d2h_inflight <= eng._d2h_bound
            if not piece[0].done():
                self.waited_for += 1
                self.gate.set()
            return take(piece)
        eng._take_piece = opening_take
        return eng


def narrow_d2h_pieces(monkeypatch, cfg, pages=2, page_size=8):
    """Engines built after this gather `pages` pages a piece, as a real
    model's page bytes make them (the tiny model's whole slot would fit one
    piece); returns a page's bytes."""
    import numpy as np
    from paddle_tpu.inference import engine as E
    page = 2 * cfg.num_layers * page_size * cfg.kv_heads * cfg.head_dim * \
        np.dtype(cfg.dtype).itemsize
    monkeypatch.setattr(E, "_D2H_PIECE_BYTES", pages * page)
    return page


class InlineWorker:
    """Stands in for an engine's fetch worker: every copy has landed by the
    time `submit` returns, so the engine never finds one in flight."""

    def submit(self, fn, *args):
        import concurrent.futures
        fut = concurrent.futures.Future()
        fut.set_result(fn(*args))
        return fut

    def shutdown(self):
        pass


@pytest.fixture
def held_worker(monkeypatch):
    held = HeldWorker(monkeypatch)
    yield held
    held.gate.set()             # no worker thread outlives its test held


# ---------------------------------------------------------------------------
# launch-ahead scheduling (double_buffer=True) against the synchronous
# schedule: one mixed run per case, shared by the dense, Nemotron-tiny and
# xing4-tiny files (each parametrises `AHEAD_CASES` minus what its family
# refuses)
# ---------------------------------------------------------------------------

class StepClock:
    """A clock the test sets: every reading inside one `step()` is the same
    instant, so a deadline falls in the same step whatever the schedule."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# case -> (engine kwargs, how the run is driven)
AHEAD_CASES = {
    # finishes by max_new_tokens, more requests than slots: every freed
    # slot is refilled by an admission
    "budget_and_admissions": dict(),
    # no queue behind the slots: every step after the first launches ahead
    "steady_full_batch": dict(),
    # an EOS token that turns up mid-batch (chosen from a first run)
    "eos_mid_batch": dict(temperature=0.8),     # greedy tiny models repeat
    "abort_in_flight": dict(),
    "deadline_in_flight": dict(),
    "sampling": dict(temperature=0.8),
    "spec3": dict(spec_len=3),
    "optimistic_forced_preemption": dict(admission="optimistic"),
    "chunked": dict(prefill_chunk=8),
    "chunked_eos": dict(prefill_chunk=8, temperature=0.8),
}


def ahead_parity_case(cfg, params, case, vocab, new=12):
    """Run `case` under double_buffer=True and False on a two-slot engine
    over (`cfg`, `params`) and compare, token for token and reason for
    reason; returns the launch-ahead engine and its outputs in request
    order.  Checked on the way: at most one program in flight at every
    return from `step()`, ONE decode-side executable and none compiled
    after `warm_decode`, the page partition whole, the run clean under
    `jax.transfer_guard("disallow")`, no launch ahead without
    double_buffer."""
    import numpy as np
    from paddle_tpu.inference.engine import LLMEngine
    from paddle_tpu.inference.faults import FaultPlan

    def make(**kw):
        kw = dict(dict(num_slots=2, page_size=8, max_model_len=64, seed=3),
                  **kw)
        if kw.get("admission") == "optimistic":
            kw.setdefault("num_pages", 12)      # growth meets pressure
        return LLMEngine(params, cfg, **kw)

    kw = dict(AHEAD_CASES[case])
    full = case in ("steady_full_batch", "eos_mid_batch", "chunked_eos")
    lengths = (9, 14) if full else (9, 14, 21, 5, 30, 12)
    budgets = [new + 3 * (i % 3) for i in range(len(lengths))]
    for seed in range(17, 25):
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(0, vocab, n, dtype=np.int32)
                   for n in lengths]
        if case not in ("eos_mid_batch", "chunked_eos"):
            break
        # an EOS that a request emits a few tokens in (and not before) while
        # the other goes on: taken from what the model says without one
        eng = make(double_buffer=False, **kw)
        eng.warm_decode()               # as `drive` does: one key split
        rids = [eng.add_request(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        said = [eng.run()[r].token_ids for r in rids]
        eos = [t[i] for t in said for i in range(2, len(t) - 2)
               if t[i] not in t[:i]]
        if eos:
            kw["eos_token_id"] = int(eos[0])
            break
    else:
        raise AssertionError("no prompt seed gives a mid-stream EOS")
    if case == "spec3":
        prompts[0] = np.tile(prompts[0][:3], 4)     # drafts that land

    def drive(db):
        clk = StepClock()
        extra = dict(clock=clk) if case == "deadline_in_flight" else {}
        if case == "optimistic_forced_preemption":
            extra["fault_plan"] = FaultPlan(pressure_steps=(5,))
        eng = make(double_buffer=db, **kw, **extra)
        eng.warm_decode()
        n_exec = eng.stats()["decode_executables"]
        # programs launched and not yet read, counted at the two calls
        pending = [0]
        launch, harvest = eng._decode_fn, eng._harvest

        def counted_launch(*a):
            pending[0] += 1
            assert pending[0] <= 2
            return launch(*a)

        def counted_harvest(finished, inflight=None, **kw):
            if inflight is not None or eng._inflight is not None:
                pending[0] -= 1
            return harvest(finished, inflight, **kw)
        eng._decode_fn, eng._harvest = counted_launch, counted_harvest
        rids = []
        for i, (p, b) in enumerate(zip(prompts, budgets)):
            dl = 6.5 if case == "deadline_in_flight" and i == 1 else None
            rids.append(eng.add_request(p, max_new_tokens=b, deadline_s=dl))
        steps = 0
        with jax.transfer_guard("disallow"):
            while eng.has_work:
                clk.t += 1.0
                eng.step()
                steps += 1
                # two programs exist only inside step()
                assert pending[0] == (eng._inflight is not None) <= db
                eng.cache.check_invariants()
                if case == "abort_in_flight" and steps == 5:
                    assert db == (eng._inflight is not None)
                    assert eng.abort(rids[0])
                    assert eng._inflight is None
            outs = eng.run()
        eng._decode_fn = launch
        st = eng.stats()
        assert st["decode_executables"] == n_exec == 1
        assert st["steady_state_recompiles"] == 0
        assert eng.cache.pages_in_use() == 0
        if not db:
            assert st["fused_launched_ahead"] == 0
            assert st["fused_ahead_discarded_lanes"] == 0
            assert not any(r["ahead"] for r in eng.step_trace())
        return eng, [outs[r] for r in rids]

    eng, ahead = drive(True)
    _, sync = drive(False)
    assert [o.token_ids for o in ahead] == [o.token_ids for o in sync]
    assert [o.finish_reason for o in ahead] == \
        [o.finish_reason for o in sync]
    return eng, ahead
