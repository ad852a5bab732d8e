"""The `joyai_llm_flash` configuration's benchmark files on the CPU: `run.py
--rehearse` through the new training driver at a tiny size (a manifest of its
own beside this file), the five faults that have to fail the cell's `correct`
(the reference in fp8, half the batch left out, the next-n loss left out,
the router's bias rule skipped, the rotary left off the key), the new work
functions against hand counts, the new metrics' readers on made-up events,
and the real configuration file against the catalog's row key by key."""
import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from benchmarks.checks import readings_joyai
from benchmarks.drivers import train_joyai
from benchmarks.harness import compare, manifest, reducers, weights_joyai
from benchmarks.work import flash_mla, joyai_lm, moe_train

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = "benchmarks/checks/tiny_joyai/BENCHMARK.json"
CELL = "train.joyai-tiny.steps"
REAL = "train.joyai-llm-flash-d6e16.b4s8192"


@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_and_is_correct(trace):
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--manifest", TINY,
         "--rehearse", "--workload", CELL, "--seed", str(2**31 + 11),
         "--seconds", "1.5", "--trace", trace],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    said = next(json.loads(l.split("] ", 1)[1])
                for l in p.stdout.splitlines() if l.startswith("[joyai]"))
    # top-3 of 16 with 4 held: about a quarter of the picks fall here
    assert 0 < said["moe_pairs_here"] < said["moe_pairs_away"]
    assert said["moe_pairs_over_bound"] == 0 and said["router_bias_moves"] > 0
    steps = said["train_steps"]
    assert said["tokens_trained"] == steps * 4 * 32
    assert said["moe_layer_calls"] == steps * 3
    assert said["moe_pairs_here"] + said["moe_pairs_away"] == \
        steps * 3 * 4 * 32 * 3


@pytest.fixture(scope="module")
def checked():
    """One tiny trainer through its first steps, and the reference's
    readings of them."""
    cell = manifest.load_cell(CELL, TINY)
    drv = train_joyai.Driver(cell, 3, lambda tag, **facts: None)
    drv.setup()
    drv.release()
    return cell, drv, drv.reference_readings()


def test_the_program_is_correct_and_every_real_limit_has_a_reading(checked):
    cell, drv, want = checked
    numbers = drv.readings(drv.got, want)
    assert all(c.ok for c in compare.checks_from(numbers, cell.limits))
    assert numbers["router_bias_moved_share"] > 0.9
    # every limit of the real cell is a number this driver reads (a limit
    # without a reading counts as failed)
    assert set(manifest.load_cell(REAL).limits) <= set(numbers)


@pytest.mark.parametrize("name,prec,fault,half", readings_joyai.CONTROLS,
                         ids=[c[0] for c in readings_joyai.CONTROLS])
def test_a_wrong_program_in_the_references_place_is_not_correct(
        checked, name, prec, fault, half):
    cell, drv, want = checked
    numbers = drv.readings(readings_joyai.control(drv, prec, fault, half),
                           want)
    failed = [c.name for c in compare.checks_from(numbers, cell.limits)
              if not c.ok]
    assert failed, numbers
    if fault == "bias_rule_off":
        assert failed == ["router_bias_mismatch_share"]
    if fault == "mtp_off":
        # the module's leaves take no gradient at all
        assert numbers["grad1_worst_leaf_gap"] == pytest.approx(1.0)


# ---- work functions against hand counts -----------------------------------

MODEL = dict(hidden_size=8, vocab_size=100, mixer_pattern="LFLE",
             num_attention_heads=2, q_lora_rank=6, kv_lora_rank=4,
             qk_nope_head_dim=3, qk_rope_head_dim=2, v_head_dim=3,
             intermediate_size=10, router_experts=6, n_routed_experts=3,
             n_shared_experts=1, num_experts_per_tok=2,
             moe_intermediate_size=5, num_nextn_predict_layers=1)


def test_parameters_every_token_multiplies_in_training():
    # L: q_a 8x6, q_b 6x2x5, kv_a 8x6, W^K 2x3x4, W^V 2x4x3, W^O 2x3x8
    assert joyai_lm.latent_params_per_token(MODEL) == \
        48 + 60 + 48 + 24 + 24 + 48 == 252
    assert joyai_lm.patterns(MODEL) == "LFLELE"
    # F: 3 x 8 x 10 = 240; E: router 8 x 6 = 48 + shared 3 x 8 x 5 = 120;
    # two heads 2 x 8 x 100; eh_proj 16 x 8
    assert joyai_lm.dense_params_per_token(MODEL) == \
        3 * 252 + 240 + 2 * 168 + 1600 + 128
    assert joyai_lm.expert_params_per_pair(MODEL) == 3 * 8 * 5
    no_mtp = dict(MODEL, num_nextn_predict_layers=0)
    assert joyai_lm.dense_params_per_token(no_mtp) == \
        2 * 252 + 240 + 168 + 800


def test_training_operations():
    # 10 tokens of sequences of 5, 9 pairs computed here; attention: 2 heads
    # x (5 + 3) columns x 2 x 3 keys on average, three latent layers
    dense = 3 * 252 + 240 + 2 * 168 + 1600 + 128
    want = 6 * (dense * 10 + 120 * 9) + 3 * 3 * (2 * 2 * 8 * 3) * 10
    assert joyai_lm.train_flops(MODEL, 10, 5, 9) == want
    facts = dict(slice_tokens=10, seq=5, slice_moe_pairs_here=9)
    assert joyai_lm.train_slice(MODEL, facts) == want


def test_flash_counts_at_two_widths():
    pairs = 2 * 3 * 8 * 9 / 2
    f = flash_mla.fwd(2, 8, 3, 192, 128)
    assert f["flops"] == 2 * pairs * 320
    assert f["bytes"] == 2 * 2 * 8 * 3 * 320 * 2
    assert flash_mla.bwd_dkv(2, 8, 3, 192, 128)["flops"] == 2 * pairs * 640
    assert flash_mla.bwd_dq(2, 8, 3, 192, 128)["flops"] == 2 * pairs * 512
    # at one width the counts are the dense kernels'
    from benchmarks.work import flash
    for mine, theirs in ((flash_mla.fwd, flash.fwd),
                         (flash_mla.bwd_dkv, flash.bwd_dkv),
                         (flash_mla.bwd_dq, flash.bwd_dq)):
        assert mine(2, 8, 3, 128, 128) == theirs(2, 8, 3, 128)


def test_grouped_product_counts():
    g = moe_train.gmm(pairs=1000, experts=4, K=8, N=6)
    assert g["flops"] == 2 * 1000 * 48
    assert g["bytes"] == (4 * 48 + 1000 * 14) * 2
    assert moe_train.tgmm(1000, 4, 8, 6) == g
    # the published widths at 1,024 rows an expert: compute-bound
    w = moe_train.gmm(16 * 1024, 16, 2048, 768)
    assert w["flops"] / 197e12 > w["bytes"] / 819e9


PEAKS = {"flops_per_s_bf16": 1e12, "hbm_bytes_per_s": 1e9}
FWD = "%flash_mla_fwd.3 custom-call tpu_custom_call " \
      "out=(bf16[128,8192,128], f32[128,8192,1]) in=3"
DKV = "%flash_mla_bwd_dkv.8 custom-call tpu_custom_call " \
      "out=(bf16[128,8192,192], bf16[128,8192,128]) in=6"
DQ = "%flash_mla_bwd_dq.9 custom-call tpu_custom_call " \
     "out=bf16[128,8192,192] in=6"
GMM = "%gmm.4 custom-call tpu_custom_call out=bf16[32768,768] in=7"
GMM2 = "%gmm.9 custom-call tpu_custom_call out=bf16[32768,2048] in=7"
TGMM = "%tgmm.2 custom-call tpu_custom_call out=bf16[16,2048,768] in=7"
DENSE_DQ = "%flash_bwd_dq custom-call tpu_custom_call " \
           "out=bf16[64,2048,128] in=6"


def test_the_new_rooflines_read_their_kernels_and_no_other():
    facts = dict(batch=1, seq=8, heads=2, score_dim=192, v_dim=128,
                 slice_moe_pairs_per_call=100, experts_held=4, hidden=8,
                 moe_width=6)
    spec = manifest.layer_metric("flash_mla_roofline")
    ops = {FWD: [3.0, 3], DKV: [6.0, 3], DQ: [5.0, 3]}
    pairs = 2 * 8 * 9 / 2
    least = 3 * 2 * pairs * (320 + 640 + 512) / 1e12
    # with bytes for nothing the operations rule, as at the real size
    ctx = {"peaks": dict(PEAKS, hbm_bytes_per_s=1e15), "facts": facts,
           "ops": ops}
    assert reducers.reduce(spec, ctx) == pytest.approx(100 * least / 14.0)
    for other in (GMM, TGMM, DENSE_DQ,
                  "%rms custom-call tpu_custom_call out=bf16[128,2048] in=2"):
        assert reducers.reduce(spec, {**ctx, "ops": {other: [1.0, 3]}}) is None
    share = manifest.layer_metric("flash_mla_time_share")
    assert reducers.reduce(share, {"ops": {**ops, GMM: [9.0, 1]},
                                   "busy": {"busy_s": 28.0}}) == 50.0

    spec = manifest.layer_metric("moe_train_roofline")
    ops = {GMM: [2e-6, 2], GMM2: [1e-6, 1], TGMM: [3e-6, 1]}
    per_call = (4 * 48 + 100 * 14) * 2 / 1e9        # bandwidth rules here
    ctx["peaks"] = PEAKS
    assert reducers.reduce(spec, {**ctx, "ops": ops}) == pytest.approx(
        100 * 4 * per_call / 6e-6)
    for other in (FWD, DKV, DQ):
        assert reducers.reduce(spec, {**ctx, "ops": {other: [1.0, 3]}}) is None
    share = manifest.layer_metric("moe_train_time_share")
    assert reducers.reduce(share, {"ops": {**ops, DQ: [1.0, 1]},
                                   "busy": {"busy_s": 12e-6}}) == 50.0
    # the dense kernels' roofline tells its kernels by operand counts alone
    # and would read these too: it is reported in the dense cell only
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    dense = next(m for m in man["per_layer"] if m["name"] == "flash_roofline")
    assert REAL not in dense["workloads"]


# ---- the real configuration file ------------------------------------------

def test_configuration_file_keeps_every_published_width():
    cell = manifest.load_cell(REAL)
    cfg = cell.config
    catalog = pathlib.Path("/opt/skills/guides/model-configs/"
                           "architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.open())
                   if r["source_url"] == cfg["source"])
        assert row["name"] == "JoyAI-LLM-Flash"
        for key, value in row["config"].items():
            if key in cfg["reduced"]:
                assert cfg["published"][key] == value
            else:
                assert cfg[key] == value, key
    assert sorted(cfg["reduced"]) == sorted(cfg["published"]) == \
        sorted(cfg["reduced_why"]) == \
        ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["num_nextn_predict_layers"] == 1
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in man["configs"] if c["name"] == cfg["name"])
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    model = train_joyai.model_of(cfg)
    assert model["mixer_pattern"] == "LF" + "LE" * 5
    assert (model["router_experts"], model["n_routed_experts"],
            model["vocab_size"]) == (256, 16, 16160)
    assert model["vocab_size"] * 8 == cfg["published"]["vocab_size"]
    # the issue's arithmetic: 787.5 M parameters held
    assert round(weights_joyai.count_params(model) / 1e5) == 7875
    # 348.7 M matrix parameters a token at an even router's 0.5 pairs a
    # token and expert layer
    per_token = joyai_lm.dense_params_per_token(model) + \
        6 * 0.5 * joyai_lm.expert_params_per_pair(model)
    assert round(per_token / 1e5) == 3487
    assert joyai_lm.attention_flops_per_token(model, 8192) == \
        pytest.approx(83.9e6, rel=1e-3)
    c = train_joyai.program_config(model, 8192)
    assert (c.count("L"), c.count("F"), c.count("E")) == (6, 1, 5)
    assert (c.n_routed_experts, c.experts_here, c.num_experts_per_tok) == \
        (256, 16, 8)
    assert c.mla_softmax_scale == pytest.approx(192 ** -0.5)
    assert c.num_nextn_predict_layers == 1 and c.hc_mult == 1
    for key in ("mtp_module", "mtp_loss_weight", "router_bias_rule",
                "optimizer", "master_copy", "router_bias_fit"):
        assert key in cfg["assumed"]


def test_traffic_file_holds_the_issues_parameters():
    mix = manifest.load_cell(REAL).traffic
    assert (mix["driver"], mix["loop"], mix["batch"], mix["seq"]) == \
        ("train_joyai", "steps", 4, 8192)
    assert (mix["token_ids"], mix["check_steps"], mix["trace_seconds"],
            mix["reference_rows_per_block"]) == ("uniform", 3, 4, 1)
    dense = manifest.load_cell("train.gpt3-1p3b.b4s2048").traffic
    assert mix["host_spans"] == dense["host_spans"]


def test_the_new_cell_reports_what_the_manifest_says():
    cell = manifest.load_cell(REAL)
    assert [m["name"] for m in cell.end_to_end] == \
        ["train_tokens_per_s", "setup_s"]
    assert {m["name"] for m in cell.per_layer} == {
        "train_step_device_ms", "device_idle_share.train",
        "trainer_dispatch_ms_per_step", "step_mfu.train_moe",
        "flash_mla_roofline", "flash_mla_time_share", "moe_train_roofline",
        "moe_train_time_share"}
    assert np.isfinite(list(cell.limits.values())).all()
