"""The hybrid configuration's benchmark files on the CPU: `run.py --rehearse`
through the new driver at a tiny size (a manifest of its own beside this
file), the new work functions against hand counts, an altered served token
and the lower-precision control against `correct`, and the real
configuration file against the catalog's rules."""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.drivers import serve, serve_hybrid
from benchmarks.harness import compare, manifest, reducers, tracer, \
    weights_hybrid
from benchmarks.work import hybrid_lm, moe, ssm

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = "benchmarks/checks/tiny_hybrid/BENCHMARK.json"
CELL = "serve.hybrid-tiny.closed"
REAL = "serve.nemotron3-nano-30b-a3b-d13e64.reason-closed128"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_and_is_correct(trace):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--manifest", TINY,
         "--rehearse", "--workload", CELL, "--seed", str(2**31 + 11),
         "--seconds", "1.5", "--trace", trace],
        cwd=ROOT, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    hybrid = next(json.loads(l.split("] ", 1)[1])
                  for l in p.stdout.splitlines() if l.startswith("[hybrid]"))
    # top-3 of 8 with 4 held: about half of the picks fall here
    assert hybrid["moe_pairs_here"] > 0 and hybrid["moe_pairs_away"] > 0
    # one reset and one skipped prefix lookup for every request admitted
    assert hybrid["ssm_state_resets"] == \
        hybrid["prefix_lookups_skipped_no_state"] > 0


def last_line(capsys):
    rc = bench.main(["--manifest", TINY, "--rehearse", "--workload", CELL,
                     "--seed", "7", "--seconds", "1", "--trace", "0"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def altered_token(tokens):
    tokens = list(tokens)
    tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 256
    return tokens


def test_an_altered_token_is_not_correct(monkeypatch, capsys):
    monkeypatch.setattr(serve, "FAULT", altered_token)
    line = last_line(capsys)
    assert line["correct"] is False
    gap = line["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_control_lower_precision_is_not_correct():
    cell = manifest.load_cell(CELL, TINY)
    drv = serve_hybrid.Driver(cell, 3, lambda tag, **facts: None)
    drv.setup()
    drv.window(1.0, tracer.NoTracer())
    drv.release()
    sample = drv.sample()
    logits, served = drv.reference_logits(sample)
    logits = np.asarray(logits)
    limit = cell.limits["served_logit_gap_max"]
    assert compare.served_logit_gap(logits, served).max() <= limit
    low, _ = drv.reference_logits(sample, "fp8")
    picks = np.asarray(low).argmax(-1)
    assert compare.served_logit_gap(logits, picks).max() > limit
    # every limit of the real cell is a number this driver reads (a limit
    # without a reading counts as failed)
    assert set(manifest.load_cell(REAL).limits) <= set(drv.readings())


# ---- work functions against hand counts -----------------------------------

MODEL = dict(hidden_size=8, vocab_size=100, hybrid_override_pattern="ME*M",
             mamba_num_heads=2, mamba_head_dim=4, ssm_state_size=3, n_groups=1,
             num_attention_heads=2, num_key_value_heads=1, head_dim=4,
             router_experts=6, n_routed_experts=3, num_experts_per_tok=2,
             moe_intermediate_size=5, moe_shared_expert_intermediate_size=7)


def test_dense_parameters_per_token():
    # M: in 8 x (2 x 8 + 2 x 3 + 2) = 192, out 8 x 8 = 64 -> 256, twice
    # *: qkv 8 x (2 + 2) x 4 = 128, proj 8 x 8 = 64 -> 192
    # E: router 8 x 6 = 48, shared 2 x 8 x 7 = 112 -> 160
    assert hybrid_lm.dense_params_per_token(MODEL) == 2 * 256 + 192 + 160


def test_forward_operations():
    # 10 tokens, 4 through the head, 55 keys read, 9 pairs computed here;
    # scan 6 x 2 x 4 x 3 = 144 a token and M layer
    want = (2 * 864 + 2 * 144) * 10 + 4 * 1 * 2 * 4 * 55 + \
        4 * 8 * 5 * 9 + 2 * 8 * 100 * 4
    assert hybrid_lm.forward_flops(MODEL, 10, 4, 55, 9) == want
    facts = dict(slice_tokens=10, slice_decode_tokens=3, slice_prefills=1,
                 slice_context_sum=55, slice_moe_pairs_here=9)
    assert hybrid_lm.serve_slice(MODEL, facts) == want


def test_grouped_matmul_and_state_update_counts():
    w = moe.grouped_matmul(pairs=12, experts_touched=5, K=8, N=6)
    assert w["flops"] == 2 * 12 * 8 * 6
    assert w["bytes"] == 5 * 8 * 6 * 2 + 12 * (8 + 6) * 2
    u = ssm.update(slots=3, H=2, P=4, N=5)
    assert u["flops"] == 6 * 3 * 2 * 4 * 5 and u["bytes"] == 2 * 120 * 4


def test_expert_roofline_reducer_reads_the_kernel_and_stays_silent():
    spec = manifest.layer_metric("moe_expert_roofline")
    peaks = {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    facts = dict(slice_moe_pairs_per_call=12,
                 slice_moe_experts_touched_per_call=5, hidden=8, moe_width=6)
    name = "%gmm.3 custom-call tpu_custom_call out=bf16[384,1856] in=7"
    ctx = {"peaks": peaks, "facts": facts, "ops": {name: [4e-6, 2]}}
    per_call = (5 * 8 * 6 * 2 + 12 * 14 * 2) / 1e9
    assert reducers.reduce(spec, ctx) == pytest.approx(
        100 * 2 * per_call / 4e-6)
    for other in ("%rms custom-call tpu_custom_call out=bf16[64,2688] in=2",
                  "%p custom-call tpu_custom_call out=bf16[64,1,32,128] in=6"):
        assert reducers.reduce(spec, {**ctx, "ops": {other: [1.0, 3]}}) is None
    share = manifest.layer_metric("moe_expert_time_share")
    assert reducers.reduce(share, {"ops": {name: [0.5, 2]},
                                   "busy": {"busy_s": 2.0}}) == 25.0


def test_fitted_router_bias_evens_the_load():
    """`fit_router_bias` on a sample whose scores share a large common part
    (what seeded weights give at long context): a zero bias sends most picks
    to a few experts, the fitted one loads all of them about evenly."""
    import jax
    import jax.numpy as jnp
    r = np.random.default_rng(0)
    common = r.normal(size=(1, 16))
    scores = jax.nn.sigmoid(jnp.asarray(
        common + 0.2 * r.normal(size=(2048, 16)), jnp.float32))

    def load(b):
        _, idx = jax.lax.top_k(scores + b, 3)
        return np.bincount(np.asarray(idx).reshape(-1), minlength=16)
    zero = load(jnp.zeros((16,)))
    assert zero.min() == 0 and zero.max() > 4 * 2048 * 3 / 16
    fitted = load(weights_hybrid._fit_bias(scores, 3, 400, 0.02))
    assert fitted.min() > 0.8 * 2048 * 3 / 16
    assert fitted.max() < 1.2 * 2048 * 3 / 16


def test_centred_head_follows_the_context():
    """`centre_head` on final states that share one large direction (what
    seeded weights give): the head as drawn gives a few tokens the highest
    logit at every position, the centred one as many as there are
    positions, and it has nothing left along that direction."""
    import jax.numpy as jnp
    r = np.random.default_rng(1)
    D, V, n = 64, 4096, 256
    final = jnp.asarray(4.0 * r.normal(size=(1, 1, D)) +
                        r.normal(size=(2, n // 2, D)), jnp.float32)
    params = {"lm_head": jnp.asarray(r.normal(size=(D, V)), jnp.float32),
              "lnf_w": jnp.ones((D,), jnp.float32)}
    model = {"norm_eps": 1e-5}
    centred = weights_hybrid.centre_head(params, final, model)
    assert centred["lnf_w"] is params["lnf_w"]
    x = np.asarray(final).reshape(n, D)
    h = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-5)

    def distinct(head):
        return len(set((h @ np.asarray(head)).argmax(-1).tolist()))
    assert distinct(params["lm_head"]) < n // 4
    assert distinct(centred["lm_head"]) > n // 2
    u = h.mean(0) / np.linalg.norm(h.mean(0))
    assert np.abs(u @ np.asarray(centred["lm_head"])).max() < 1e-4


# ---- the real configuration file ------------------------------------------

def test_configuration_file_keeps_every_published_width():
    cell = manifest.load_cell(REAL)
    cfg = cell.config
    catalog = pathlib.Path("/opt/skills/guides/model-configs/"
                           "architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.open())
                   if r["source_url"] == cfg["source"])
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value
            else:
                assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == sorted(cfg["published"])
    assert cfg["hybrid_override_pattern"] == \
        cfg["published"]["hybrid_override_pattern"][:13]
    model = serve_hybrid.model_of(cfg)
    assert model["router_experts"] == 128 and model["n_routed_experts"] == 64
    # the issue's arithmetic: 3,926 M parameters, 7.85 GB in bf16
    assert round(weights_hybrid.count_params(model) / 1e6) == 3926
    c = serve_hybrid.program_config(model)
    assert (c.count("M"), c.count("E"), c.count("*")) == (6, 5, 2)
    assert c.state_bytes_per_slot() == 6 * (64 * 64 * 128 * 4 + 3 * 6144 * 2)


def test_traffic_file_holds_the_issues_parameters():
    mix = manifest.load_cell(REAL).traffic
    assert (mix["driver"], mix["loop"], mix["clients"]) == \
        ("serve_hybrid", "closed", 128)
    assert mix["prompt_len"] == dict(law="lognormal", median=256, sigma=0.8,
                                     min=16, max=768)
    assert mix["output_len"] == dict(law="lognormal", median=384, sigma=0.6,
                                     min=64, max=1024)
    assert mix["pool"] == dict(block=16, blocks=64, pairing_seed=7,
                               order_seed=11)
    assert (mix["check_requests"], mix["trace_seconds"],
            mix["shared_prefix"], mix["sampling"]) == (6, 3, None, "greedy")
