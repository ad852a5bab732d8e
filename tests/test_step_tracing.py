"""The serving step measured from inside (ISSUE 26): `engine.turnaround` and
the spans that tile it, the split swap fetch with its byte counters, a stamp
per emission, and the trainer's spans and step marker — all through the one
recorder (`profiler.RecordEvent` behind the `is_recording()` gate).  Since
ISSUE 36 also the admission step's own account: one span per order of step,
the prefill's wait, the reservation, the gather and the fetch worker under
their own names, and the two counters at the decisions (`fused_ahead_late`,
`fused_serial_steps{reason}`).  CPU, tiny engine; an auto-ticking clock where
exact sums are asserted."""
import numpy as np
import pytest

import jax

from paddle_tpu.inference import engine as E
from paddle_tpu.inference.engine import ENGINE_SPANS, LLMEngine
from paddle_tpu.models import gpt as G
from paddle_tpu.parallel import HybridParallelTrainer, MeshConfig
from paddle_tpu.parallel.hybrid import TRAINER_SPANS
from paddle_tpu.profiler import profiler as prof


@pytest.fixture(scope="module")
def tiny():
    cfg = G.gpt_tiny(64)
    return cfg, G.init_params(cfg, jax.random.key(0))


class TickClock:
    """Every reading is 1 ms after the last: durations count clock reads."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1e-3
        return self.t


def engine(tiny, **kw):
    cfg, params = tiny
    base = dict(num_slots=2, page_size=8, num_pages=9, max_model_len=64,
                prefill_chunk=16, seed=3, swap_pool_pages=64)
    base.update(kw)
    return LLMEngine(params, cfg, **base)


def session_stream(eng, churn=6):
    """A session's first turn, distinct prompts that evict (and spill) its
    pages, then the returning turn, which restores them from the host tier."""
    rng = np.random.RandomState(7)
    V = eng.config.vocab_size
    shared = rng.randint(0, V, (20,)).astype(np.int32)
    r1 = eng.add_request(shared, max_new_tokens=5)
    outs = dict(eng.run())
    for _ in range(churn):
        eng.add_request(rng.randint(0, V, (30,)).astype(np.int32),
                        max_new_tokens=4)
    outs.update(eng.run())
    eng.add_request(np.concatenate(
        [shared, np.asarray(outs[r1].token_ids, np.int32),
         rng.randint(0, V, (4,)).astype(np.int32)]), max_new_tokens=5)
    outs.update(eng.run())
    return outs


# ---------------------------------------------------------------------------
# spans nest and tile
# ---------------------------------------------------------------------------

def record(tiny, **kw):
    """[(name, start, end, thread)] of one spilling, restoring stream under
    a host-only Profiler."""
    eng = engine(tiny, **kw)
    with prof.Profiler(timer_only=True):
        session_stream(eng)
        # every copy has landed at rest: the worker's spans are all closed
        events = [(e.name, e.start, e.end, e.tid) for e in prof._events]
    assert eng.stats()["kv_tier"]["spills"] > 0
    return events


@pytest.fixture(scope="module")
def recorded(tiny):
    """Chunked prefill: the prompt rides the fused program's lanes."""
    return record(tiny)


@pytest.fixture(scope="module")
def recorded_bucketed(tiny):
    """Bucketed prefill: a program of its own at admission, with its
    blocking first-token read (and the standalone chunk program for the
    returning turn's prefix-hit tail)."""
    return record(tiny, prefill_chunk=None)


def inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def named(recorded, name):
    return [e for e in recorded if e[0] == name]


def steps_of(recorded, ahead):
    """The `engine.step` spans that launched a fused program, by the order
    their own span names: `engine.step.serial` (harvest, admit, build,
    launch) or `engine.step.ahead` (the launch went out ahead of the last
    result)."""
    order = named(recorded, "engine.step.ahead" if ahead
                  else "engine.step.serial")
    launches = named(recorded, "engine.fused.dispatch")
    return [s for s in named(recorded, "engine.step")
            if any(inside(d, [s]) for d in launches)
            and any(inside(o, [s]) for o in order)]


@pytest.mark.parametrize("parent,child", [
    ("engine.step", "engine.turnaround"),
    ("engine.step", "engine.emit"),
    ("engine.step", "engine.batch.build"),
    ("engine.step", "engine.fused.dispatch"),
    ("engine.step", "engine.sample.sync"),
    ("engine.turnaround", "engine.emit"),
    ("engine.turnaround", "engine.admit"),
    ("engine.turnaround", "engine.batch.build"),
    ("engine.turnaround", "engine.fused.dispatch"),
    ("engine.fused.dispatch", "engine.fused.h2d"),
    ("engine.swap.d2h", "engine.swap.d2h.ready"),
    ("engine.swap.d2h", "engine.swap.d2h.copy"),
    # ISSUE 36: the two orders of a step, and the admission's parts
    ("engine.step", "engine.step.ahead"),
    ("engine.step", "engine.step.serial"),
    ("engine.step.serial", "engine.turnaround"),
    ("engine.step.serial", "engine.admit"),
    ("engine.step.ahead", "engine.batch.build"),
    ("engine.admit", "engine.admit.reserve"),
    ("engine.admit.reserve", "engine.swap.gather"),
    ("engine.sample.sync", "engine.prefill.sync"),
    ("engine.step.serial", "engine.prefill.sync"),
])
def test_spans_nest(recorded, recorded_bucketed, parent, child):
    if child == "engine.prefill.sync":
        recorded = recorded_bucketed    # chunked mode has no such program
    parents = [e for e in recorded if e[0] == parent]
    children = [e for e in recorded if e[0] == child]
    assert parents and children
    if parent == "engine.step.ahead":
        # build, puts and launch belong to both orders
        children = [c for c in children
                    if inside(c, steps_of(recorded, ahead=True))]
        assert children
    if parent == "engine.turnaround":
        # the stretch the device waits for exists in the steps that kept
        # today's order (and admission also runs in steps that launch
        # nothing); a step that launched ahead has no such stretch
        kept = steps_of(recorded, ahead=False) if child != "engine.admit" \
            else [s for s in recorded if s[0] == "engine.step"
                  and any(inside(t, [s]) for t in parents)]
        children = [c for c in children if inside(c, kept)]
        assert children
    assert all(inside(c, parents) for c in children)


@pytest.mark.parametrize("mode", ["chunked", "bucketed"])
def test_a_step_that_launched_ahead_has_no_turnaround(
        recorded, recorded_bucketed, mode):
    """Launch first, then read: build, the puts and the launch, then the
    fetch of the LAST program's tokens and their emission, all under
    `engine.step.ahead`; no `engine.turnaround`, no admission and no wait
    for a prefill program in such a step."""
    recorded = recorded if mode == "chunked" else recorded_bucketed
    ahead = steps_of(recorded, ahead=True)
    assert ahead and steps_of(recorded, ahead=False)
    for s in ahead:
        kids = sorted((e for e in recorded if e is not s and inside(e, [s])),
                      key=lambda e: e[1])
        names = [e[0] for e in kids if e[0] in (
            "engine.step.ahead", "engine.step.serial",
            "engine.batch.build", "engine.fused.dispatch",
            "engine.sample.sync", "engine.emit", "engine.admit",
            "engine.admit.reserve", "engine.prefill.sync",
            "engine.turnaround")]
        assert names == ["engine.step.ahead", "engine.batch.build",
                         "engine.fused.dispatch", "engine.sample.sync",
                         "engine.emit"]


@pytest.mark.parametrize("mode", ["chunked", "bucketed"])
def test_a_step_names_its_order(recorded, recorded_bucketed, mode):
    """Every `engine.step` holds at most one of `.ahead` / `.serial`; one
    that launched holds exactly one, and it is `.serial` just where the
    device waited for the host (`engine.turnaround`: how the orders were
    told apart before the spans had names); every stretch the device waits
    for lies in a serial step; a prefill program is waited for (bucketed
    mode) once a prefill dispatch whose result the step reads, and only in
    a serial step."""
    recorded = recorded if mode == "chunked" else recorded_bucketed
    ahead = named(recorded, "engine.step.ahead")
    serial = named(recorded, "engine.step.serial")
    turns = named(recorded, "engine.turnaround")
    launches = named(recorded, "engine.fused.dispatch")
    assert ahead and serial
    for s in named(recorded, "engine.step"):
        a = sum(inside(o, [s]) for o in ahead)
        b = sum(inside(o, [s]) for o in serial)
        assert a + b <= 1
        if any(inside(d, [s]) for d in launches):
            assert a + b == 1
            assert (b == 1) == any(inside(t, [s]) for t in turns)
    assert all(inside(t, serial) for t in turns)
    waits = named(recorded, "engine.prefill.sync")
    if mode == "chunked":
        assert not waits
    else:
        assert waits and all(inside(w, serial) for w in waits)
        assert not any(inside(w, ahead) for w in waits)
        # the harvest's read of the fused program never carries the name
        syncs = named(recorded, "engine.sample.sync")
        assert len(syncs) > len(waits)
        assert all(sum(inside(w, [y]) for y in syncs) == 1 for w in waits)


@pytest.mark.parametrize("mode", ["chunked", "bucketed"])
def test_the_fetch_worker_is_on_the_trace(recorded, recorded_bucketed, mode):
    """`engine.swap.fetch` is recorded by the `kv-d2h` worker, on a thread
    of its own, one span a piece it copies; one `engine.swap.gather` a
    dispatch of the gather program, on the engine thread."""
    recorded = recorded if mode == "chunked" else recorded_bucketed
    engine_threads = {e[3] for e in recorded if e[0] == "engine.step"}
    fetches = named(recorded, "engine.swap.fetch")
    gathers = named(recorded, "engine.swap.gather")
    assert len(engine_threads) == 1 and fetches and gathers
    assert len({e[3] for e in fetches}) == 1
    assert {e[3] for e in fetches}.isdisjoint(engine_threads)
    assert {e[3] for e in recorded
            if e[0] != "engine.swap.fetch"} == engine_threads
    # a piece is fetched by the worker or, where it had not reached it,
    # copied by the engine thread under `.copy`: never more fetch spans
    # than takes
    assert len(fetches) <= len(named(recorded, "engine.swap.d2h"))
    assert len(fetches) >= len(gathers)


def test_spans_tile_the_turnaround_and_are_all_named(recorded):
    names = {e[0] for e in recorded}
    assert names <= set(ENGINE_SPANS)
    tiles = ("engine.emit", "engine.admit", "engine.spec.propose",
             "engine.batch.build", "engine.fused.dispatch")
    turns = [e for e in recorded if e[0] == "engine.turnaround"]
    launched = 0
    for t in turns:
        kids = sorted((e for e in recorded if e[0] in tiles and inside(e, [t])),
                      key=lambda e: e[1])
        for a, b in zip(kids, kids[1:]):
            assert a[2] <= b[1]                     # side by side, no overlap
        assert sum(e[2] - e[1] for e in kids) <= t[2] - t[1]
        if any(e[0] == "engine.fused.dispatch" for e in kids):
            launched += 1
            assert kids[-1][0] == "engine.fused.dispatch"
            # the stretch ends with the launch's return: nothing of the
            # tiling set opens between that return and the stretch's end
            assert not [e for e in recorded if e[0] in tiles
                        and kids[-1][2] <= e[1] <= t[2]]
    assert launched > 0
    # one fetch's two halves fill its parent
    for d in (e for e in recorded if e[0] == "engine.swap.d2h"):
        halves = [e for e in recorded if e[0].startswith("engine.swap.d2h.")
                  and inside(e, [d])]
        assert sorted(e[0] for e in halves) == ["engine.swap.d2h.copy",
                                                "engine.swap.d2h.ready"]


# ---------------------------------------------------------------------------
# nothing is built when nothing records
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ENGINE_SPANS)
def test_span_is_the_null_span_when_nothing_records(tiny, name):
    eng = engine(tiny)
    assert not prof.is_recording()
    assert eng._span(name) is E._NULL_SPAN
    assert eng._step_marker() is E._NULL_SPAN


def test_no_span_object_is_built_in_a_step_when_nothing_records(
        tiny, monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("a span was built with nothing recording")
    monkeypatch.setattr(prof, "RecordEvent", refuse)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", refuse)
    eng = engine(tiny)
    outs = session_stream(eng)
    assert eng._turn_span is E._NULL_SPAN and len(outs) == 8
    assert eng.stats()["swap_d2h_fetches"] > 0      # the fetch path ran


# ---------------------------------------------------------------------------
# ring records and counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    dict(), dict(double_buffer=False), dict(spec_len=3),
    dict(prefill_chunk=None), dict(prefill_chunk=None, spec_len=3),
    dict(spec_len=3, double_buffer=False)])
def test_ring_carries_turnaround_and_d2h(tiny, mode):
    eng = engine(tiny, clock=TickClock(), **mode)
    session_stream(eng)
    ring = eng.step_trace()
    assert ring
    for r in ring:
        assert r["turnaround_ms"] >= 0.0 and r["d2h_ms"] >= 0.0
        assert r["turnaround_ms"] + r["sync_ms"] <= 1e3 * r["dur_s"] + 1e-6
        if not r["dispatches"]:
            assert r["turnaround_ms"] == 0.0
    st = eng.stats()
    assert st["turnaround_ms"] == pytest.approx(
        sum(r["turnaround_ms"] for r in ring))
    assert st["turnaround_ms"] == pytest.approx(
        eng.metrics.snapshot()["counters"]["turnaround_ms"])
    launched = [r for r in ring if r["decode_batch"] or r["slots"]["chunk"]]
    # a launch in today's order has the device wait for the host's stretch;
    # one made ahead of the last result (`ahead`) has not
    assert launched and all((r["turnaround_ms"] > 0) != r["ahead"]
                            for r in launched)
    assert st["fused_launched_ahead"] == sum(r["ahead"] for r in ring)
    if not eng.double_buffer:
        assert st["fused_launched_ahead"] == 0
    elif not eng.spec_len:
        assert st["fused_launched_ahead"] > 0
    # the fetches' time lands in the step that drained them
    assert st["swap_d2h_fetches"] > 0
    assert sum(r["d2h_ms"] for r in ring) > 0
    assert sum(r["d2h_ms"] for r in ring) <= st["swap_ms"] + 1e-6


@pytest.mark.parametrize("mode", [
    dict(), dict(double_buffer=False), dict(prefill_chunk=None),
    dict(prefill_chunk=None, eos_token_id="drawn", temperature=0.9)])
def test_launch_ahead_counters_reach_every_surface(tiny, mode):
    """`fused_launched_ahead` and `fused_ahead_discarded_lanes` in stats(),
    the registry snapshot and the exposition; the ring's `ahead` adds up to
    the first, and `turnaround_ms` counts only the steps that kept today's
    order."""
    def run(**mode):
        eng = engine(tiny, clock=TickClock(), num_pages=17, **mode)
        rng = np.random.RandomState(5)
        for n in (6, 11):
            eng.add_request(rng.randint(1, 64, (n,)).astype(np.int32),
                            max_new_tokens=24)
        return eng, eng.run()

    if mode.get("eos_token_id") == "drawn":
        # the EOS is a token the sampled stream holds a few tokens in
        said = run(**dict(mode, eos_token_id=None))[1][0].token_ids
        mode = dict(mode, eos_token_id=next(
            t for i, t in enumerate(said) if i >= 3 and t not in said[:i]))
    eng, outs = run(**mode)
    st, ring = eng.stats(), eng.step_trace()
    snap = eng.metrics.snapshot()["counters"]
    text = eng.metrics.to_prometheus()
    for name in ("fused_launched_ahead", "fused_ahead_discarded_lanes"):
        assert st[name] == snap[name]
        assert f"llm_engine_{name}" in text
    assert st["fused_launched_ahead"] == sum(r["ahead"] for r in ring)
    assert st["turnaround_ms"] == pytest.approx(
        sum(r["turnaround_ms"] for r in ring if not r["ahead"]))
    if eng.double_buffer:
        # two requests on two slots, nothing queued: every launch but the
        # first goes out ahead
        assert st["fused_launched_ahead"] >= st["decode_iterations"] - 1
    else:
        assert st["fused_launched_ahead"] == 0
    stops = sum(o.finish_reason == "stop" and len(o.token_ids) > 1
                for o in outs.values())
    assert st["fused_ahead_discarded_lanes"] == \
        (stops if eng.double_buffer else 0)
    if eng.eos_token_id is not None:
        assert stops                                    # the case bites
    eng.reset_counters()
    assert eng.stats()["fused_launched_ahead"] == 0


class Finished:
    """Stands in for a fused program's token buffer on the device: answers
    `is_ready()` as the test says, reads as the array it wraps."""

    def __init__(self, out, ready):
        self.out, self.ready = out, ready

    def is_ready(self):
        return self.ready

    def __array__(self, *a, **k):
        return np.asarray(self.out)


@pytest.mark.parametrize("ready", [True, False])
def test_a_launch_that_came_too_late_is_counted(tiny, ready):
    """`fused_ahead_late`: at a launch ahead, whether the program in flight
    had already finished (`is_ready()` of its token buffer, asked once,
    right before the launch) — on stats(), the registry, the exposition and
    the ring's `late`; never on a step that read before it launched."""
    eng = engine(tiny, clock=TickClock(), num_pages=17)
    real, asked = eng._decode_fn, []

    class Told(Finished):
        def is_ready(self):
            asked.append(eng._step_idx)
            return self.ready

    def program(*args):
        args = list(args)
        if isinstance(args[8], Finished):       # `prev_out`
            args[8] = args[8].out
        out, *rest = real(*args)
        return (Told(out, ready), *rest)
    eng._decode_fn = program
    rng = np.random.RandomState(5)
    for n in (6, 11):
        eng.add_request(rng.randint(1, 64, (n,)).astype(np.int32),
                        max_new_tokens=12)
    outs = eng.run()
    assert all(len(o.token_ids) == 12 for o in outs.values())
    st, ring = eng.stats(), eng.step_trace()
    assert st["fused_launched_ahead"] > 0
    want = st["fused_launched_ahead"] if ready else 0
    assert st["fused_ahead_late"] == want
    assert eng.metrics.snapshot()["counters"]["fused_ahead_late"] == want
    assert f"llm_engine_fused_ahead_late_total {want}\n" in \
        eng.metrics.to_prometheus()
    assert [r["late"] for r in ring] == [r["ahead"] and ready for r in ring]
    # one question a launch ahead, none in a step of the other order
    assert len(asked) == len(set(asked)) == st["fused_launched_ahead"]
    eng.reset_counters()
    assert eng.stats()["fused_ahead_late"] == 0


def serial_case(tiny, reason):
    """An engine driven into a step that keeps the harvest-first order for
    `reason` (the ones a CPU run can reach: all six)."""
    rng = np.random.RandomState(11)

    def prompt(n):
        return rng.randint(1, 64, (n,)).astype(np.int32)
    if reason == "idle_start":
        eng = engine(tiny, num_pages=17)
        eng.add_request(prompt(6), max_new_tokens=6)
    elif reason == "admission_due":
        # a request arrives beside a program in flight and a free slot
        eng = engine(tiny, num_pages=17)
        eng.add_request(prompt(6), max_new_tokens=12)
        for _ in range(3):
            eng.step()
        eng.add_request(prompt(7), max_new_tokens=4)
    elif reason == "budget_end":
        # three clients on two slots: a lane ends by its budget, one waits
        eng = engine(tiny, num_pages=17)
        for n in (6, 7, 8):
            eng.add_request(prompt(n), max_new_tokens=5)
    elif reason == "prefilling":
        # bucketed mode: two prefix-hit tails admitted in one step, the
        # standalone chunk program takes one a step
        eng = engine(tiny, num_slots=3, num_pages=33, prefill_chunk=None)
        shared = prompt(20)
        eng.add_request(shared, max_new_tokens=2)
        eng.run()
        eng.add_request(prompt(9), max_new_tokens=16)
        for _ in range(3):
            eng.step()
        for _ in range(2):
            eng.add_request(np.concatenate([shared, prompt(3)]),
                            max_new_tokens=3)
    elif reason == "draft":
        eng = engine(tiny, num_pages=17, spec_len=3)
        eng.add_request(np.tile(np.arange(6, dtype=np.int32), 4),
                        max_new_tokens=10)
    else:
        assert reason == "pages"
        from paddle_tpu.inference.faults import FaultPlan
        eng = engine(tiny, num_pages=17, admission="optimistic",
                     fault_plan=FaultPlan(pressure_steps=(4,)))
        for n in (4, 6):
            eng.add_request(prompt(n), max_new_tokens=12)
    eng.run()
    return eng


@pytest.mark.parametrize("reason", E.SERIAL_REASONS)
def test_why_a_step_kept_the_older_order_is_counted(tiny, reason):
    """`fused_serial_steps{reason}` and the ring's `serial_reason`, counted
    where `_plan_ahead` decides, on every surface: stats(), the registry
    snapshot, the exposition (one family, a sample a reason) and the ring;
    the schema `tools/check_metrics.py` freezes holds all of them."""
    from tools import check_metrics as schema
    eng = serial_case(tiny, reason)
    st, ring = eng.stats(), eng.step_trace()
    counts = st["fused_serial_steps"]
    assert tuple(counts) == E.SERIAL_REASONS == schema.SERIAL_REASONS
    assert counts[reason] > 0
    snap = eng.metrics.snapshot()["counters"]
    text = eng.metrics.to_prometheus()
    assert text.count("# TYPE llm_engine_fused_serial_steps_total ") == 1
    for why, n in counts.items():
        assert snap[f'fused_serial_steps{{reason="{why}"}}'] == n
        assert f'llm_engine_fused_serial_steps_total{{reason="{why}"}} ' \
            f'{n}\n' in text
        assert sum(r["serial_reason"] == why for r in ring) == n
    # a step is ahead, serial (one reason) or neither (it only read the
    # last program, or polled); a serial step with a program to read but
    # nothing in flight before it cannot be: `idle_start` is the first
    for r in ring:
        assert not (r["ahead"] and r["serial_reason"])
        assert r["serial_reason"] in (None,) + E.SERIAL_REASONS
        if r["turnaround_ms"] > 0:
            assert r["serial_reason"] is not None
    assert ring[0]["serial_reason"] == "idle_start"
    assert sum(counts.values()) + st["fused_launched_ahead"] <= len(ring)
    assert schema.REQUIRED_STATS_KEYS <= set(st)
    assert schema.REQUIRED_COUNTERS <= set(snap)
    assert schema.REQUIRED_STEP_RECORD_KEYS <= set(ring[-1])
    schema.check_exposition(text, errors := [])
    assert not errors
    eng.reset_counters()
    assert not any(eng.stats()["fused_serial_steps"].values())


@pytest.mark.parametrize("direction", ["d2h", "h2d"])
def test_swap_bytes_match_the_shapes(tiny, direction, monkeypatch):
    from conftest import narrow_d2h_pieces
    page_bytes = narrow_d2h_pieces(monkeypatch, tiny[0])
    eng = engine(tiny)
    session_stream(eng)
    st, mgr = eng.stats(), eng.cache
    assert eng._kv_page_bytes == page_bytes and mgr.page_size == 8
    if direction == "d2h":
        moved, useful = st["swap_d2h_bytes"], st["swap_d2h_useful_bytes"]
        calls = st["swap_d2h_fetches"]
        buffer_bytes = eng._swap_w * page_bytes
        assert eng._swap_w == 2 < mgr.max_pages_per_slot
        assert useful == st["kv_tier"]["spills"] * page_bytes
        # moved against USEFUL: every piece but the last of an eviction is
        # full, so a fetch moves under one piece more than it was for — not
        # a slot's width whatever the page count, as it did before PR 31
        assert moved - useful < calls * page_bytes * eng._swap_w
        assert moved - useful <= st["prefix_evictions"] * page_bytes
        assert moved < 2 * useful
    else:
        moved, useful = st["swap_h2d_bytes"], st["swap_h2d_useful_bytes"]
        calls = st["kv_tier"]["restores"]
        buffer_bytes = mgr.max_pages_per_slot * page_bytes
    assert calls > 0
    assert moved == calls * buffer_bytes
    assert moved >= useful > 0 and useful % page_bytes == 0
    assert useful <= calls * buffer_bytes
    snap = eng.metrics.snapshot()["counters"]
    assert snap[f"swap_{direction}_bytes"] == moved
    assert snap[f"swap_{direction}_useful_bytes"] == useful


def test_one_d2h_span_a_fetch_on_the_engine_thread(tiny, monkeypatch,
                                                   held_worker):
    """`engine.swap.d2h` (with `.ready` and `.copy` inside) is recorded
    where the ENGINE thread takes a piece's bytes, once a fetch, landed or
    waited for — the worker thread that copies records `engine.swap.fetch`
    and nothing else — and the ring's `d2h_ms`, `swap_ms` and
    `swap_d2h_blocked_ms` follow the same rule: what a fetch still costs
    the step."""
    import threading
    from conftest import narrow_d2h_pieces
    narrow_d2h_pieces(monkeypatch, tiny[0])
    eng = held_worker.watch(engine(tiny, clock=TickClock()))
    with prof.Profiler(timer_only=True):
        session_stream(eng)
        events = list(prof._events)
    st = eng.stats()
    me = threading.get_ident()
    for name in ("engine.swap.d2h", "engine.swap.d2h.ready",
                 "engine.swap.d2h.copy"):
        spans = [e for e in events if e.name == name]
        assert len(spans) == st["swap_d2h_fetches"] > 0
        assert all(e.tid == me for e in spans)
    worker = [e for e in events if e.tid != me]
    assert {e.name for e in worker} == {"engine.swap.fetch"}
    assert 0 < len(worker) <= st["swap_d2h_fetches"]
    assert not [e for e in events if e.name == "engine.swap.fetch"
                and e.tid == me]
    # some pieces were waited for (the gate opened at the first such take),
    # the others had landed: both kinds are fetches, both have their span
    assert 0 < held_worker.waited_for <= st["swap_d2h_fetches"]
    assert st["swap_d2h_landed_free"] == \
        st["swap_d2h_fetches"] - held_worker.waited_for
    ring = eng.step_trace()
    # TickClock: a take reads the clock three times, 2 ms a fetch, 1 ms of
    # it the wait's half
    assert st["swap_d2h_blocked_ms"] == pytest.approx(
        1.0 * st["swap_d2h_fetches"])
    assert st["swap_d2h_blocked_ms"] <= st["swap_ms"]
    assert sum(r["d2h_ms"] for r in ring) <= 2.0 * st["swap_d2h_fetches"] + 1e-6
    assert st["swap_d2h_inflight_pages"] == 0 and not eng._pending_d2h


# ---------------------------------------------------------------------------
# a stamp per emission
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", [
    dict(), dict(double_buffer=False), dict(prefill_chunk=None),
    dict(spec_len=3), dict(prefill_chunk=None, spec_len=3),
    dict(spec_len=3, double_buffer=False),
    dict(num_pages=7, admission="optimistic")])
def test_emit_times_account_for_every_token(tiny, mode):
    eng = engine(tiny, clock=TickClock(), **mode)
    if eng.spec_len:
        # a repeating prompt, so that the n-gram proposer's drafts land
        rep = np.tile(np.arange(6, dtype=np.int32), 5)
        eng.add_request(rep, max_new_tokens=12)
    outs = session_stream(eng)
    multi = 0
    for out in outs.values():
        m = out.metrics
        stamps = m.emit_times
        assert sum(n for _, n in stamps) == m.n_generated == len(out.token_ids)
        times = [t for t, _ in stamps]
        assert times == sorted(times)
        assert stamps[0] == (m.t_first_token, 1)
        assert len(stamps) <= len(out.token_ids)
        assert times[-1] <= m.t_finish
        multi += sum(n > 1 for _, n in stamps)
    if eng.spec_len:
        assert eng.stats()["spec_accepted_tokens"] > 0 and multi > 0
    else:
        assert multi == 0                       # one token a harvest
    # the postmortem bundle is the stamps' reader today
    states = eng.debug_bundle()["requests"]
    rid, out = next(iter(outs.items()))
    assert states[str(rid)]["emit_times"] == [list(p) for p in
                                              out.metrics.emit_times]


# ---------------------------------------------------------------------------
# the trainer's spans and the step markers
# ---------------------------------------------------------------------------

class Marks(list):
    """Stands in for jax.profiler.StepTraceAnnotation: records its calls."""

    def __call__(self, name, **kw):
        self.append((name, kw))
        return E._NULL_SPAN


@pytest.fixture(scope="module")
def trainer(tiny):
    cfg, _ = tiny
    return HybridParallelTrainer(cfg, MeshConfig(), seed=0,
                                 devices=jax.devices()[:1])


@pytest.mark.parametrize("recording", [True, False])
def test_trainer_spans_and_step_marker(trainer, monkeypatch, recording):
    marks = Marks()
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", marks)
    tok = np.zeros((2, 16), np.int32)
    n0 = trainer.steps_dispatched
    if recording:
        with prof.Profiler(timer_only=True):
            losses = [trainer.train_step(tok, tok) for _ in range(2)]
        names = [e.name for e in prof._events]
        assert names == list(TRAINER_SPANS) * 2
        assert marks == [("train_step", {"step_num": n0 + 1}),
                         ("train_step", {"step_num": n0 + 2})]
    else:
        before = len(prof._events)
        losses = [trainer.train_step(tok, tok) for _ in range(2)]
        assert marks == [] and len(prof._events) == before
    assert trainer.steps_dispatched == n0 + 2
    assert all(np.isfinite(float(l)) for l in losses)


@pytest.mark.parametrize("recording", [True, False])
def test_engine_step_marker_numbers_the_ring_record(tiny, monkeypatch,
                                                    recording):
    marks = Marks()
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", marks)
    eng = engine(tiny)
    eng.add_request(np.arange(5, dtype=np.int32), max_new_tokens=3)
    if recording:
        with prof.Profiler(timer_only=True):
            eng.run()
        assert [kw["step_num"] for _, kw in marks] == \
            [r["step"] for r in eng.step_trace()]
        assert {name for name, _ in marks} == {"engine_step"}
    else:
        eng.run()
        assert marks == []
