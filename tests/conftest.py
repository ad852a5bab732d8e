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
