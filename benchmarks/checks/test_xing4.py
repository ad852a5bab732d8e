"""The `xing4_0` configuration's benchmark files on the CPU: `run.py
--rehearse` through the new driver at a tiny size (a manifest of its own
beside this file), the new work functions against hand counts, the three
faults (a served token altered, the Sinkhorn skipped, the rotary left off
the cached key) and the lower-precision control against `correct`, and the
real configuration file against the catalog's rules."""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.drivers import serve, serve_xing4
from benchmarks.harness import compare, manifest, reducers, tracer, \
    weights_xing4
from benchmarks.work import mla, mla_moe_lm

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = "benchmarks/checks/tiny_xing4/BENCHMARK.json"
CELL = "serve.xing4-tiny.closed"
REAL = "serve.xing4-29b-a4b-d13e16.reason-long-closed256"


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
    said = next(json.loads(l.split("] ", 1)[1])
                for l in p.stdout.splitlines() if l.startswith("[xing4]"))
    # top-3 of 8 with 4 held: about half of the picks fall here
    assert said["moe_pairs_here"] > 0 and said["moe_pairs_away"] > 0
    assert said["latent_tokens_written"] > said["mla_absorbed_rows"] > 0
    assert said["latent_page_bytes"] == 3 * 8 * 128 * 4
    # a paged model: the pool was filled before the window and pages parked
    assert said["kv_tier"]["enabled"] and said["prefix_evictions"] > 0


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


@pytest.fixture(scope="module")
def served():
    """One tiny window and the reference's logits at its served positions."""
    cell = manifest.load_cell(CELL, TINY)
    drv = serve_xing4.Driver(cell, 3, lambda tag, **facts: None)
    drv.setup()
    drv.window(1.0, tracer.NoTracer())
    drv.release()
    sample = drv.sample()
    logits, tokens = drv.reference_logits(sample)
    return cell, drv, sample, np.asarray(logits), tokens


def test_the_program_is_correct_and_every_real_limit_has_a_reading(served):
    cell, drv, _, logits, tokens = served
    limit = cell.limits["served_logit_gap_max"]
    assert compare.served_logit_gap(logits, tokens).max() <= limit
    assert drv.eng.prefix_cache and not drv.eng.recurrent
    # every limit of the real cell is a number this driver reads (a limit
    # without a reading counts as failed)
    assert set(manifest.load_cell(REAL).limits) <= set(drv.readings())


@pytest.mark.parametrize("prec,fault", [
    ("fp8", ""), ("f32", "sinkhorn_skipped"), ("f32", "k_rope_off")])
def test_a_wrong_program_in_the_references_place_is_not_correct(
        served, prec, fault):
    """The picks of the reference computed in fp8, with the Sinkhorn
    skipped, or with the cached key unrotated, judged on the sound
    reference's logits: wider than the limit."""
    cell, drv, sample, logits, _ = served
    wrong, _ = drv.reference_logits(sample, prec, fault)
    picks = np.asarray(wrong).argmax(-1)
    assert compare.served_logit_gap(logits, picks).max() > \
        cell.limits["served_logit_gap_max"]


# ---- work functions against hand counts -----------------------------------

MODEL = dict(hidden_size=8, vocab_size=100, mixer_pattern="LFLE", hc_mult=2,
             num_attention_heads=2, q_lora_rank=6, kv_lora_rank=4,
             qk_nope_head_dim=3, qk_rope_head_dim=2, v_head_dim=3,
             intermediate_size=10, router_experts=6, n_routed_experts=3,
             n_shared_experts=1, num_experts_per_tok=2,
             moe_intermediate_size=5)


def test_parameters_every_token_multiplies():
    # L: q_a 8x6, q_b 6x2x5, kv_a 8x6, W^K 2x3x4, W^V 2x4x3, W^O 2x3x8
    assert mla_moe_lm.latent_params_per_token(MODEL) == \
        48 + 60 + 48 + 24 + 24 + 48 == 252
    # a mix: phi 16 x (2 + 2 + 4) = 128, read 16, res 2x2x8 = 32, post 16
    assert mla_moe_lm.mix_params_per_token(MODEL) == 128 + 16 + 32 + 16 == 192
    # F: 3 x 8 x 10 = 240; E: router 8 x 6 = 48, shared 3 x 8 x 5 = 120
    assert mla_moe_lm.dense_params_per_token(MODEL) == \
        2 * 252 + 240 + 168 + 4 * 192


def test_forward_operations():
    # 10 tokens, 4 through the head, 55 rows read, 9 pairs computed here;
    # absorbed attention 2 x H x (2 x 4 + 2) a row = 40, two latent layers
    want = 2 * 1680 * 10 + 2 * 40 * 55 + 6 * 8 * 5 * 9 + 2 * 8 * 100 * 4
    assert mla_moe_lm.forward_flops(MODEL, 10, 4, 55, 9) == want
    # a slice: 7 decoded + 3 prefilled tokens; the counter read 52 rows, 3 of
    # them the prefill's own length; the finished requests' prompts (4 and
    # 2 tokens) make a prefilled token read 10 / 6 rows: 49 + 5 = 54 rows
    facts = dict(slice_tokens=10, slice_decode_tokens=7, slice_prefills=1,
                 slice_prefilled_tokens=3, slice_latent_tokens=52,
                 slice_moe_pairs_here=9,
                 requests=[{"n_prompt": 4}, {"n_prompt": 2}])
    assert mla_moe_lm.serve_slice(MODEL, facts) == pytest.approx(
        2 * 1680 * 10 + 2 * 40 * 54 + 6 * 8 * 5 * 9 + 2 * 8 * 100 * 8)


def test_absorbed_attention_counts():
    w = mla.absorbed_attention(live_tokens=1000, rows=8, H=4, latent=16,
                               rope=4)
    assert w["flops"] == 2 * 1000 * 4 * (20 + 16)
    assert w["bytes"] == 1000 * 20 * 2 + 8 * 4 * (20 + 16) * 2


def test_latent_roofline_reducer_reads_its_kernel_and_no_other():
    spec = manifest.layer_metric("mla_paged_roofline")
    peaks = {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    facts = dict(slice_latent_tokens_per_call=1000, slice_mla_rows_per_call=8,
                 heads=4, latent=16, rope=4)
    name = "%paged_latent.3 custom-call tpu_custom_call " \
           "out=bf16[128,1,32,512] in=5"
    ctx = {"peaks": peaks, "facts": facts, "ops": {name: [4e-4, 2]}}
    per_call = (1000 * 20 * 2 + 8 * 4 * 36 * 2) / 1e9
    assert reducers.reduce(spec, ctx) == pytest.approx(
        100 * 2 * per_call / 4e-4)
    for other in ("%p custom-call tpu_custom_call out=bf16[64,1,32,128] in=6",
                  "%gmm.3 custom-call tpu_custom_call out=bf16[512,1024] in=7",
                  "%rms custom-call tpu_custom_call out=bf16[128,3584] in=2"):
        assert reducers.reduce(spec, {**ctx, "ops": {other: [1.0, 3]}}) is None
    share = manifest.layer_metric("mla_attention_time_share")
    assert reducers.reduce(share, {"ops": {name: [0.5, 2]},
                                   "busy": {"busy_s": 2.0}}) == 25.0
    # the K/V kernel's roofline does not read the latent kernel
    kv = manifest.layer_metric("paged_serve_roofline")
    assert reducers.reduce(kv, {**ctx, "facts": dict(
        facts, slice_mean_live_tokens=1, slots=1, kv_heads=1, head_dim=1)}
    ) is None


def test_the_expert_roofline_reads_the_gated_products():
    """Gate, up and down are three calls of the accepted pattern and of the
    accepted work function's shape (K x N = hidden x moe width)."""
    spec = manifest.layer_metric("moe_expert_roofline")
    peaks = {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e9}
    facts = dict(slice_moe_pairs_per_call=12,
                 slice_moe_experts_touched_per_call=5, hidden=8, moe_width=6)
    ops = {"%gmm.%d custom-call tpu_custom_call out=bf16[128,%d] in=7"
           .replace("%d", str(n), 1).replace("%d", str(w)): [2e-6, 1]
           for n, w in ((1, 6), (2, 6), (3, 8))}
    per_call = (5 * 8 * 6 * 2 + 12 * 14 * 2) / 1e9
    assert reducers.reduce(spec, {"peaks": peaks, "facts": facts,
                                  "ops": ops}) == pytest.approx(
        100 * 3 * per_call / 6e-6)


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
    assert sorted(cfg["reduced"]) == sorted(cfg["published"]) == \
        sorted(cfg["reduced_why"])
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in man["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    model = serve_xing4.model_of(cfg)
    assert model["mixer_pattern"] == "LF" + "LE" * 12
    assert (model["router_experts"], model["n_routed_experts"],
            model["vocab_size"]) == (64, 16, 32768)
    # the issue's arithmetic: 2,961 M parameters, 5.92 GB in bf16
    assert round(weights_xing4.count_params(model) / 1e6) == 2961
    c = serve_xing4.program_config(model)
    assert (c.count("L"), c.count("F"), c.count("E")) == (13, 1, 12)
    assert (c.latent_row, c.latent_lane) == (576, 640)
    eng = cfg["engine"]
    assert (eng["num_pages"] - 1) * eng["page_size"] >= 393216
    assert eng["num_slots"] == 128 and eng["max_model_len"] == 6144
    assert c.page_bytes(eng["page_size"]) == 13 * eng["page_size"] * 640 * 2
    assert c.mla_softmax_scale == pytest.approx(
        (0.1 * np.log(64) + 1) ** 2 / np.sqrt(192))


def test_traffic_file_holds_the_issues_parameters():
    mix = manifest.load_cell(REAL).traffic
    assert (mix["driver"], mix["loop"], mix["clients"]) == \
        ("serve_xing4", "closed", 256)
    assert mix["prompt_len"] == dict(law="lognormal", median=512, sigma=0.8,
                                     min=64, max=2048)
    assert mix["output_len"] == dict(law="lognormal", median=1536, sigma=0.5,
                                     min=384, max=4096)
    hybrid_mix = manifest.load_cell(
        "serve.nemotron3-nano-30b-a3b-d13e64.reason-closed128").traffic
    assert mix["pool"] == hybrid_mix["pool"]
    assert (mix["check_requests"], mix["trace_seconds"],
            mix["shared_prefix"], mix["sampling"]) == (6, 3, None, "greedy")
