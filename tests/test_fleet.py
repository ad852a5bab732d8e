"""Serving front door + dp engine fleet (router/frontend PR).

Covers the routing plane three ways:

- pure units: `rank_replicas` scoring over `ReplicaView` fakes (affinity
  vs sticky vs load ordering, victim-aware pre-filter, overloaded
  exclusion), no engines involved;
- fleet integration: sticky-session routing with the tier-probe override,
  shed path when every replica is unroutable, abort freeing KV pages,
  byte-exact fleet-vs-single-engine parity on a multi-turn session
  stream, executable adoption, and `create_predictor` fleet routing;
- HTTP: the front door on a real loopback socket — non-stream and SSE
  streaming round-trips, validation errors, rate-limit 429, the obs
  routes through the one door, and client-disconnect -> abort.
"""
from __future__ import annotations

import http.client
import json
import socket
import time

import numpy as np
import pytest

import jax

from paddle_tpu.inference.engine import LLMEngine
from paddle_tpu.inference.router import (EngineFleet, FleetHandle,
                                         FleetOverloaded, ReplicaView,
                                         rank_replicas)
from paddle_tpu.models import gpt as G

EKW = dict(num_slots=2, page_size=8, max_model_len=64, prefill_chunk=16,
           seed=0)


@pytest.fixture(scope="module")
def cfg():
    return G.gpt_tiny(64)


@pytest.fixture(scope="module")
def params(cfg):
    return G.init_params(cfg, jax.random.key(0))


# ---------------------------------------------------------------------------
# rank_replicas units (pure — no engines)
# ---------------------------------------------------------------------------

def _v(label, **kw):
    return ReplicaView(label=label, **kw)


def test_affinity_prefers_longest_cached_prefix():
    views = [_v("engine0", matched_tokens=8),
             _v("engine1", matched_tokens=24),
             _v("engine2", matched_tokens=0)]
    assert rank_replicas(views).label == "engine1"


def test_sticky_wins_ties_but_strictly_more_cache_overrides():
    # equal match: the session's last replica wins the tie
    tie = [_v("engine0", matched_tokens=16),
           _v("engine1", matched_tokens=16, sticky=True)]
    assert rank_replicas(tie).label == "engine1"
    # a replica whose cache/tier holds strictly MORE of the conversation
    # beats stickiness — after an eviction/respill the pages decide
    probe = [_v("engine0", matched_tokens=40),
             _v("engine1", matched_tokens=16, sticky=True)]
    assert rank_replicas(probe).label == "engine0"


def test_affinity_load_tiebreak_depth_then_throughput():
    views = [_v("engine0", depth=3, tokens_per_sec=50.0),
             _v("engine1", depth=1, tokens_per_sec=10.0)]
    assert rank_replicas(views).label == "engine1"
    views = [_v("engine0", depth=2, tokens_per_sec=50.0),
             _v("engine1", depth=2, tokens_per_sec=10.0)]
    assert rank_replicas(views).label == "engine0"


def test_overloaded_and_error_replicas_excluded():
    views = [_v("engine0", state="overloaded", matched_tokens=99),
             _v("engine1", state="error", matched_tokens=99),
             _v("engine2", matched_tokens=0)]
    assert rank_replicas(views).label == "engine2"
    views = [_v("engine0", state="overloaded"), _v("engine1", state="error")]
    assert rank_replicas(views) is None


def test_victim_aware_prefilter_for_low_priority():
    hot = _v("engine0", matched_tokens=30, pool_pressure=0.95)
    churny = _v("engine1", matched_tokens=30, preemptions_per_sec=2.0)
    calm = _v("engine2", matched_tokens=0, pool_pressure=0.1)
    # priority >= 0: cache affinity wins, pressure is not a veto
    assert rank_replicas([hot, churny, calm], priority=0).label == "engine0"
    # priority < 0: the preemption victims go to the calm replica
    assert rank_replicas([hot, churny, calm], priority=-1).label == "engine2"
    # ...unless nowhere is calm — then affinity ordering still applies
    assert rank_replicas([hot, churny], priority=-1).label == "engine0"


def test_least_loaded_and_policy_errors():
    views = [_v("engine0", depth=2), _v("engine1", depth=0)]
    assert rank_replicas(views, policy="least_loaded").label == "engine1"
    with pytest.raises(ValueError):
        rank_replicas(views, policy="round_robin")  # needs fleet state
    with pytest.raises(ValueError):
        rank_replicas(views, policy="nope")


def test_fleet_handle_roundtrip():
    h = FleetHandle(label="engine1", rid=7, session="s0")
    assert str(h) == "engine1/7"
    assert FleetHandle.parse("engine1/7") == FleetHandle("engine1", 7)


# ---------------------------------------------------------------------------
# fleet integration (real engines)
# ---------------------------------------------------------------------------

def _sessions(cfg, n=3, seed=7):
    rng = np.random.RandomState(seed)
    first = {f"s{i}": rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
             for i in range(n)}
    chunk = {k: rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
             for k in first}
    return first, chunk


def _run_two_turns(fleet, first, chunk):
    outs = {}
    for k, p in first.items():
        outs[(k, 1)] = fleet.result(
            fleet.submit(p, session=k, max_new_tokens=5), timeout=120.0)
    for k, p in first.items():
        conv = np.concatenate([p, np.asarray(outs[(k, 1)].token_ids,
                                             np.int32), chunk[k]])
        outs[(k, 2)] = fleet.result(
            fleet.submit(conv, session=k, max_new_tokens=5), timeout=120.0)
    assert all(o is not None for o in outs.values())
    return outs


def test_fleet_parity_and_affinity_vs_round_robin(params, cfg):
    """Byte-exact parity single vs 2-replica (both routers) on the same
    session stream; affinity's returning turns hit the cache (finish-time
    registration included: cached >= the whole turn-1 conversation KV),
    round-robin's shifted assignment hits nothing; replicas adopt the
    leader's executables."""
    first, chunk = _sessions(cfg)

    def run(replicas, router):
        fleet = EngineFleet(params, cfg, replicas=replicas, router=router,
                            engine_kwargs=EKW)
        assert fleet.shared_executables()
        with fleet:
            outs = _run_two_turns(fleet, first, chunk)
            fleet.check_invariants()
        digest = {k: list(map(int, o.token_ids)) for k, o in outs.items()}
        cached = {k: int(o.cached_tokens) for k, o in outs.items()}
        return digest, cached

    d1, _ = run(1, "affinity")
    d2, c2 = run(2, "affinity")
    d3, c3 = run(2, "round_robin")
    assert d1 == d2 == d3
    for k in first:
        # sticky affinity: turn 2 reuses the ENTIRE turn-1 KV — prompt
        # pages plus the generated pages finish-time registration published
        # (kvlen = 10 prompt + 5 generated - 1; the final sampled token's
        # KV never lands, so 14 is full reuse, not a partial hit)
        assert c2[(k, 2)] == 14, c2
    # 3 sessions over 2 replicas: round-robin's turn-2 assignment shifts
    # off the turn-1 replica for every session — zero cache reuse
    assert all(c3[(k, 2)] == 0 for k in first), c3


def test_finish_time_registration_stops_reprefill(params, cfg):
    """Satellite: a returning session's last REPLY must not re-prefill —
    finish-time registration upgrades the prompt-time partial node to
    cover the generated pages (engine.cache.register_prefix upgrade mode),
    so turn-2 cached_tokens reaches the full turn-1 kvlen instead of
    stopping at the prompt pages."""
    eng = LLMEngine(params, cfg, **EKW)
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, cfg.vocab_size, (10,)).astype(np.int32)
    rid = eng.add_request(prompt, max_new_tokens=5)
    out = eng.result(rid)
    kvlen = prompt.size + len(out.token_ids) - 1
    probe = eng.probe_affinity(np.concatenate(
        [prompt, np.asarray(out.token_ids, np.int32)]))
    assert probe["cached_tokens"] == kvlen, probe
    conv = np.concatenate([prompt, np.asarray(out.token_ids, np.int32),
                           rng.randint(0, cfg.vocab_size,
                                       (4,)).astype(np.int32)])
    out2 = eng.result(eng.add_request(conv, max_new_tokens=4))
    # without finish-time registration this stopped at the prompt's pages
    # (page 8 + rolling-hash partial 2 = 10); with it, the reply rides too
    assert out2.cached_tokens == kvlen, out2.cached_tokens
    eng.cache.check_invariants()


def test_shed_when_all_replicas_overloaded(params, cfg):
    fleet = EngineFleet(params, cfg, replicas=2, engine_kwargs=EKW,
                        shed_retry_after_s=2.5)
    bad = {"state": "overloaded", "code": 2, "reasons": [], "signals": {},
           "burn_rates": {}}
    originals = {l: e.health for l, e in fleet.engines.items()}
    try:
        # one overloaded member: traffic still routes, to the healthy one
        fleet.engines["engine0"].health = lambda: bad
        assert fleet.select(np.arange(4, dtype=np.int32)) == "engine1"
        # every member overloaded: shed with the retry-after hint
        fleet.engines["engine1"].health = lambda: bad
        with pytest.raises(FleetOverloaded) as ei:
            fleet.submit(np.arange(4, dtype=np.int32), max_new_tokens=2)
        assert ei.value.retry_after_s == 2.5
        assert fleet.stats()["shed"] == 1
    finally:
        for l, h in originals.items():
            fleet.engines[l].health = h


def test_abort_frees_pages_and_invariants():
    # a request too long to finish before the abort lands (a 40-token one
    # raced it and, on a quiet machine, won): nearly 2,000 steps to go when
    # the first token shows
    cfg = G.gpt_tiny(2048)
    params = G.init_params(cfg, jax.random.key(0))
    fleet = EngineFleet(params, cfg, replicas=2,
                        engine_kwargs=dict(EKW, max_model_len=2048))
    with fleet:
        h = fleet.submit(np.arange(20, dtype=np.int32) % cfg.vocab_size,
                         max_new_tokens=2000)
        # let it get in flight, then abort mid-generation
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            p = fleet.progress(h)
            if p["finished"] or p["token_ids"]:
                break
            time.sleep(0.01)
        fleet.abort(h)
        out = fleet.result(h, timeout=60.0)
        assert out is not None and out.finish_reason == "abort"
        assert fleet.drain(timeout=60.0)
        fleet.check_invariants()
        eng = fleet.engines[h.label]
        assert eng.stats()["aborted_requests"] == 1
    # the aborted request released its slot: nothing live remains anywhere
    for e in fleet.engines.values():
        st = e.stats()
        assert st["running"] == 0 and st["prefilling"] == 0
        assert st["queued"] == 0


def test_create_predictor_routes_to_engine_and_fleet(params, cfg):
    import paddle_tpu.inference as pinf

    # duck-typed model config + params -> LLMEngine behind the ONE door
    eng = pinf.create_predictor(cfg, params=params, **EKW)
    assert isinstance(eng, LLMEngine)
    out = eng.result(eng.add_request(np.arange(6, dtype=np.int32),
                                     max_new_tokens=3))
    assert len(out.token_ids) == 3
    # Config.enable_llm_engine with replicas > 1 -> EngineFleet
    config = pinf.Config().enable_llm_engine(cfg, params, replicas=2,
                                             **EKW)
    fleet = pinf.create_predictor(config)
    assert isinstance(fleet, EngineFleet)
    assert fleet.shared_executables()
    with fleet:
        h = fleet.submit(np.arange(6, dtype=np.int32), max_new_tokens=3)
        fout = fleet.result(h, timeout=120.0)
    assert list(fout.token_ids) == list(out.token_ids)
    # a broken kind still fails loudly
    with pytest.raises(TypeError):
        pinf.create_predictor(object())


# ---------------------------------------------------------------------------
# the HTTP front door (real loopback socket)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def door(params, cfg):
    from paddle_tpu.inference.frontend import ServingFrontend
    fleet = EngineFleet(params, cfg, replicas=2, engine_kwargs=EKW).start()
    fe = ServingFrontend(fleet, rate_limit_rps=200.0,
                         rate_limit_burst=50).start()
    yield fe
    fe.close()
    fleet.stop()


def _post(door, path, payload, read=True):
    conn = http.client.HTTPConnection("127.0.0.1", door.port, timeout=60)
    conn.request("POST", path, json.dumps(payload),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    if not read:
        return conn, resp
    body = resp.read()
    conn.close()
    return resp, body


def test_http_completion_roundtrip(door, params, cfg):
    prompt = [int(x) for x in np.arange(8)]
    resp, body = _post(door, "/v1/completions",
                       {"prompt": prompt, "max_tokens": 4, "session": "h0"})
    assert resp.status == 200, body
    out = json.loads(body)
    assert out["object"] == "text_completion"
    toks = out["choices"][0]["token_ids"]
    assert len(toks) == 4
    assert out["usage"]["completion_tokens"] == 4
    # parity with a direct single-engine run of the same prompt
    eng = LLMEngine(params, cfg, **EKW)
    ref = eng.result(eng.add_request(np.asarray(prompt, np.int32),
                                     max_new_tokens=4))
    assert toks == [int(x) for x in ref.token_ids]


def test_http_chat_stream_sse(door):
    conn, resp = _post(door, "/v1/chat/completions",
                       {"messages": [{"role": "user",
                                      "content": [1, 2, 3, 4, 5]}],
                        "max_tokens": 4, "stream": True}, read=False)
    assert resp.status == 200
    assert resp.getheader("Content-Type").startswith("text/event-stream")
    raw = resp.read().decode("utf-8")
    conn.close()
    frames = [json.loads(x[len("data: "):])
              for x in raw.strip().split("\n\n")
              if x.startswith("data: ") and x != "data: [DONE]"]
    assert raw.strip().endswith("data: [DONE]")
    streamed = []
    for f in frames[:-1]:
        streamed += f["choices"][0]["delta"]["token_ids"]
    assert len(streamed) == 4
    final = frames[-1]["choices"][0]
    assert final["finish_reason"] in ("stop", "length")
    assert final["message"]["token_ids"] == streamed


def test_http_validation_and_rate_limit(door):
    from paddle_tpu.inference.frontend import ServingFrontend

    resp, body = _post(door, "/v1/completions", {"prompt": "not tokens"})
    assert resp.status == 400
    assert "token ids" in json.loads(body)["error"]
    resp, _ = _post(door, "/v1/completions", {})
    assert resp.status == 400
    resp, body = _post(door, "/v1/completions",
                       {"prompt": [1, 2], "priority_class": "warp-speed"})
    assert resp.status == 400
    assert "priority_class" in json.loads(body)["error"]
    # a second door on the SAME fleet with a near-zero refill: burst 1 means
    # exactly one admit per tenant, then deterministic 429 + Retry-After
    fe2 = ServingFrontend(door.fleet, rate_limit_rps=0.001,
                          rate_limit_burst=1.0).start()
    try:
        resp, _ = _post(fe2, "/v1/completions",
                        {"prompt": [1, 2, 3], "max_tokens": 2})
        assert resp.status == 200
        resp, body = _post(fe2, "/v1/completions",
                           {"prompt": [1, 2, 3], "max_tokens": 2})
        assert resp.status == 429, body
        assert int(resp.getheader("Retry-After")) >= 1
        assert "rate-limited" in json.loads(body)["error"]
        # ...per tenant: a different X-Tenant still has its own bucket
        conn = http.client.HTTPConnection("127.0.0.1", fe2.port, timeout=60)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": [1, 2, 3], "max_tokens": 2}),
                     {"Content-Type": "application/json",
                      "X-Tenant": "other"})
        r = conn.getresponse()
        r.read()
        assert r.status == 200
        conn.close()
    finally:
        fe2.close()


def test_http_obs_routes_one_door(door):
    for path, want in (("/healthz", 200), ("/stats", 200), ("/metrics", 200)):
        conn = http.client.HTTPConnection("127.0.0.1", door.port, timeout=30)
        conn.request("GET", path)
        r = conn.getresponse()
        body = r.read()
        conn.close()
        assert r.status == want, (path, r.status, body[:200])
    # fleet exposition through the door: per-engine series present
    conn = http.client.HTTPConnection("127.0.0.1", door.port, timeout=30)
    conn.request("GET", "/metrics")
    text = conn.getresponse().read().decode("utf-8")
    conn.close()
    assert 'engine="engine0"' in text and 'engine="engine1"' in text
    assert "llm_fleet_" in text
    # unknown route: 404 advertising BOTH planes
    conn = http.client.HTTPConnection("127.0.0.1", door.port, timeout=30)
    conn.request("GET", "/nope")
    r = conn.getresponse()
    routes = json.loads(r.read())["routes"]
    conn.close()
    assert r.status == 404
    assert "/metrics" in routes and "POST /v1/completions" in routes


def test_http_disconnect_aborts_and_frees_pages(door):
    """A dropped client connection must abort the in-flight request so its
    KV pages free — dead streams cannot pin pool capacity."""
    fleet = door.fleet
    def retired():
        # the hangup races the 48-token request to its end on a loaded
        # worker: aborted, or finished before the abort reached it
        return {l: e.stats()["aborted_requests"] +
                e.stats()["finished_requests"]
                for l, e in fleet.engines.items()}
    before = retired()
    payload = json.dumps({"prompt": [9, 8, 7, 6, 5, 4, 3, 2],
                          "max_tokens": 48, "stream": True}).encode("utf-8")
    # raw socket: http.client hands Connection:close sockets to the
    # response object, so a clean shutdown needs the fd directly
    sock = socket.create_connection(("127.0.0.1", door.port), timeout=60)
    sock.sendall(b"POST /v1/completions HTTP/1.1\r\n"
                 b"Host: door\r\nContent-Type: application/json\r\n"
                 b"Content-Length: " + str(len(payload)).encode() +
                 b"\r\n\r\n" + payload)
    first = sock.recv(64)
    assert first.startswith(b"HTTP/1.1 200"), first
    # hard client hangup mid-stream
    sock.shutdown(socket.SHUT_RDWR)
    sock.close()
    deadline = time.monotonic() + 60.0
    gone = False
    while time.monotonic() < deadline and not gone:
        gone = retired() != before
        time.sleep(0.05)
    assert gone, "disconnect never retired the in-flight request"
    assert fleet.drain(timeout=60.0)
    fleet.check_invariants()
    for eng in fleet.engines.values():
        st = eng.stats()
        assert st["running"] == 0 and st["prefilling"] == 0
        assert st["pages_in_use"] == 0      # aborted or finished: all freed
