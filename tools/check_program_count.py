#!/usr/bin/env python
"""CI guard: the serving engine's compiled-program budget.

Continuous batching is only viable on TPU because the engine runs a FIXED set
of executables regardless of traffic shape (README "Serving" section).  The
documented budget, which this script re-measures on every run so a future PR
cannot silently reintroduce per-shape recompiles:

- decode-side: <= 1 program — THE fused `serve_step_paged` executable
  (vanilla decode, spec verify and the interleaved prefill chunk all ride
  one fixed-shape batch, sampling + acceptance on device);
- prefill-side (chunked mode): <= 2 programs for the cold paths (the chunk
  rides the fused batch, so a chunked run measures 0);
- copy: <= 1 program (the COW page copy);
- swap: <= 2 programs — the KV swap-out gather + swap-in scatter, SHARED by
  preemption swap parking and the (default-on) KV tier's prefix
  spill/restore; warmed by `warm_swap`, so this stream measures exactly 2
  with zero tier-specific programs on top;
- total: <= 6.

The budget holds PER MESH CONFIG: a second pass re-measures under mp=2
tensor-parallel serving (8 forced CPU host devices — the same simulation the
multichip training dryrun uses) and asserts decode-side <= 1 there too.  The
mp engine AOT-compiles its executables, so the measured counts are exact
distinct-program counts, not dispatch-cache sizes.

A third pass measures a 2-replica dp `EngineFleet` (the serving front
door's scale-out unit): replication must ADD ZERO programs — replicas run
on the leader's mesh and adopt its compiled executables, so every
replica's counts stay inside the SAME single-engine budget and the
executable objects are asserted literally identical
(`EngineFleet.shared_executables`), not merely equal in number.

A fourth pass measures a disaggregated 1P:1D `EngineFleet` (ISSUE 17):
prefill/decode role separation moves KV between engines through the durable
host/disk tier store — pure host-side numpy + npz, so the handoff must mint
ZERO compiled programs.  The prefill replica's export rides the same warmed
swap-out gather and the decode replica's restore rides the same warmed
swap-in scatter that preemption parking declared, so BOTH role replicas
measure inside the unchanged single-engine budget with the executable
objects literally shared (leader adoption, same mesh) — and the pass
asserts at least one handoff actually crossed the store, so a silent
degrade to colocated serving cannot fake compliance.

Serves the tests' deterministic stream (`tests/serve_stream.py`: chunked
prefill + prefix cache + speculative decoding — every lane the scheduler can
dispatch) and exits non-zero with a diff against the budget on violation.

The budget itself is DECLARED in `paddle_tpu/analysis/registry.py` (the
central program registry) — this script re-measures the live counts against
it, and `tools/tpu_lint.py` (TPL002) statically verifies no unregistered
jit/shard_map site can mint programs outside it.  One declaration, two
guards: the runtime check and the linter cannot drift apart.

Usage: JAX_PLATFORMS=cpu python tools/check_program_count.py
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the mp=2 pass needs virtual chips; must land before jax initializes
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")

from paddle_tpu.analysis.registry import (  # noqa: E402
    SERVE_PROGRAM_BUDGET as BUDGET,
    SERVE_PROGRAM_BUDGET_MP as BUDGET_MP)


def measure(mp=1):
    from tests.serve_stream import run_stream
    stats = run_stream(num_requests=12, num_slots=2, page_size=8,
                       max_model_len=64, max_new_tokens=6,
                       prefill_chunk=16, prefix_cache=True,
                       shared_prefix_frac=0.5, spec_len=4, seed=11, mp=mp)
    got = {
        "decode_side_executables": stats["decode_executables"] +
                                   stats["verify_executables"],
        "prefill_executables": stats["prefill_executables"],
        "copy_executables": stats["copy_executables"],
        # swap gather/scatter: warmed (and used by the default-on KV tier's
        # prefix spill/restore) on this stream — the tier must stay inside
        # the same <= 2 bucket preemption swapping declared
        "swap_executables": stats["swap_executables"],
    }
    got["total_executables"] = (got["decode_side_executables"] +
                                got["prefill_executables"] +
                                got["copy_executables"] +
                                got["swap_executables"])
    return got, stats


def measure_fleet(replicas=2):
    """dp replication adds ZERO programs: a 2-replica `EngineFleet` serving
    a mixed stream (chunked prefill + prefix hits + spec decode, spread
    round-robin so BOTH replicas dispatch) must keep every replica's
    executable counts inside the single-engine budget, with the executable
    objects literally shared (leader-adoption, same mesh).  Returns
    ({label: counts}, shared_executables)."""
    import jax
    import numpy as np

    from paddle_tpu.inference.router import EngineFleet
    from paddle_tpu.models import gpt as G

    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(11))
    fleet = EngineFleet(params, cfg, replicas=replicas,
                        engine_kwargs=dict(num_slots=2, page_size=8,
                                           max_model_len=64,
                                           prefill_chunk=16, spec_len=4,
                                           seed=11))
    fleet.warm()
    rng = np.random.RandomState(11)
    shared_prefix = rng.randint(0, cfg.vocab_size, (20,)).astype(np.int32)
    prompts = [shared_prefix,
               rng.randint(0, cfg.vocab_size, (9,)).astype(np.int32),
               np.concatenate([shared_prefix,
                               rng.randint(0, cfg.vocab_size,
                                           (7,)).astype(np.int32)]),
               rng.randint(0, cfg.vocab_size, (33,)).astype(np.int32)]
    with fleet:
        handles = [fleet.submit(p, session=f"s{i}", policy="round_robin",
                                max_new_tokens=6)
                   for i, p in enumerate(prompts)]
        for h in handles:
            if fleet.result(h, timeout=120.0) is None:
                raise RuntimeError(f"fleet program-count stream timed out "
                                   f"on {h}")
    per = {}
    for label, eng in fleet.engines.items():
        st = eng.stats()
        got = {
            "decode_side_executables": st["decode_executables"] +
                                       st["verify_executables"],
            "prefill_executables": st["prefill_executables"],
            "copy_executables": st["copy_executables"],
            "swap_executables": st["swap_executables"],
        }
        got["total_executables"] = sum(got.values())
        per[label] = got
    return per, fleet.shared_executables()


def measure_disagg():
    """Disaggregated serving adds ZERO programs: a 1P:1D role fleet serving
    a 2-session x 2-turn conversation stream (every returning turn is a
    store handoff: prefill exports through the durable tier, decode
    tier-restores) must keep BOTH role replicas' executable counts inside
    the single-engine budget with the compiled objects literally shared.
    Returns ({label: counts}, shared_executables, handoffs)."""
    import jax
    import numpy as np

    from paddle_tpu.inference.router import EngineFleet
    from paddle_tpu.models import gpt as G

    cfg = G.gpt_tiny(64)
    params = G.init_params(cfg, jax.random.key(11))
    fleet = EngineFleet(params, cfg, roles="P:D",
                        engine_kwargs=dict(num_slots=2, page_size=8,
                                           max_model_len=64,
                                           prefill_chunk=16, spec_len=4,
                                           seed=11))
    fleet.warm()
    rng = np.random.RandomState(11)
    convs = [list(rng.randint(0, cfg.vocab_size, (18,)).astype(np.int32))
             for _ in range(2)]
    with fleet:
        for _turn in range(2):
            for s in range(2):
                h = fleet.submit(np.asarray(convs[s], np.int32),
                                 session=f"s{s}", max_new_tokens=6)
                out = fleet.result(h, timeout=120.0)
                if out is None:
                    raise RuntimeError("disagg program-count stream timed "
                                       f"out on session s{s}")
                convs[s] = convs[s] + list(out.token_ids)
    per = {}
    for label, eng in fleet.engines.items():
        st = eng.stats()
        got = {
            "decode_side_executables": st["decode_executables"] +
                                       st["verify_executables"],
            "prefill_executables": st["prefill_executables"],
            "copy_executables": st["copy_executables"],
            "swap_executables": st["swap_executables"],
        }
        got["total_executables"] = sum(got.values())
        per[f"{label}:{eng.role}"] = got
    handoffs = fleet.stats()["disagg"]["handoffs"]
    return per, fleet.shared_executables(), handoffs


def main() -> int:
    rc = 0
    report = {"metric": "serve_compiled_program_count", "ok": True}
    digests = {}
    # mp4 rides the same MP budget: the fused program PARTITIONS over the
    # mesh, it does not fork — the vocab-sharded head included (the sharded
    # argmax/sample merges live inside the one fused executable)
    for mp, budget in ((1, BUDGET), (2, BUDGET_MP), (4, BUDGET_MP)):
        got, stats = measure(mp=mp)
        digests[mp] = stats["outputs_digest"]
        over = {k: (got[k], budget[k]) for k in budget if got[k] > budget[k]}
        tag = f"mp{mp}"
        report[tag] = {"budget": budget, "measured": got,
                       "accepted_per_step": stats["accepted_per_step"],
                       "ok": not over}
        if over:
            report["ok"] = False
            rc = 1
            for k, (g, b) in over.items():
                print(f"FAIL[{tag}]: {k} = {g} exceeds documented budget {b} "
                      f"— a code path is recompiling per shape; see README "
                      f"'Serving'", file=sys.stderr)
    # mp serving must be a pure partitioning of the same computation: every
    # pass replays the same stream, so greedy outputs must match BYTE-exactly
    # across the whole mesh ladder (the sharded argmax/top-k tie-break is
    # deterministic by construction)
    report["mp_parity"] = digests[1] == digests[2] == digests[4]
    if not report["mp_parity"]:
        report["ok"] = False
        rc = 1
        print("FAIL: mp>1 serving outputs diverge from single-chip (greedy "
              "token parity broken across the mesh ladder)", file=sys.stderr)
    # dp fleet pass: replication shares the leader's compiled set — every
    # replica inside the SAME single-engine budget, executables identical
    fleet_per, fleet_shared = measure_fleet()
    report["fleet"] = {"replicas": len(fleet_per), "budget": BUDGET,
                       "shared_executables": fleet_shared,
                       "per_replica": fleet_per, "ok": fleet_shared}
    if not fleet_shared:
        report["ok"] = False
        rc = 1
        print("FAIL[fleet]: replicas are not sharing the leader's compiled "
              "executables — dp replication is minting duplicate programs",
              file=sys.stderr)
    for label, got in fleet_per.items():
        over = {k: (got[k], BUDGET[k]) for k in BUDGET if got[k] > BUDGET[k]}
        if over:
            report["ok"] = report["fleet"]["ok"] = False
            rc = 1
            for k, (g, b) in over.items():
                print(f"FAIL[fleet/{label}]: {k} = {g} exceeds documented "
                      f"budget {b} — dp replication must not widen the "
                      f"per-replica program set", file=sys.stderr)
    # disagg pass: role separation must not widen the program set — the
    # handoff is host-side store traffic riding the warmed swap bucket
    dis_per, dis_shared, dis_handoffs = measure_disagg()
    report["disagg"] = {"roles": "P:D", "budget": BUDGET,
                        "shared_executables": dis_shared,
                        "handoffs": dis_handoffs,
                        "per_replica": dis_per,
                        "ok": dis_shared and dis_handoffs >= 1}
    if not dis_shared:
        report["ok"] = False
        rc = 1
        print("FAIL[disagg]: role replicas are not sharing the leader's "
              "compiled executables — disaggregation is minting duplicate "
              "programs", file=sys.stderr)
    if dis_handoffs < 1:
        report["ok"] = False
        rc = 1
        print("FAIL[disagg]: no prefill->decode handoff crossed the store "
              "(the pass degraded to colocated serving and proves nothing)",
              file=sys.stderr)
    for label, got in dis_per.items():
        over = {k: (got[k], BUDGET[k]) for k in BUDGET if got[k] > BUDGET[k]}
        if over:
            report["ok"] = report["disagg"]["ok"] = False
            rc = 1
            for k, (g, b) in over.items():
                print(f"FAIL[disagg/{label}]: {k} = {g} exceeds documented "
                      f"budget {b} — the tier-store handoff must stay "
                      f"host-side (zero new programs)", file=sys.stderr)
    print(json.dumps(report))
    return rc


if __name__ == "__main__":
    sys.exit(main())
