"""`run.py --rehearse` end to end at a tiny size on the CPU, once for each
driver path (train steps, open-loop serving, closed-loop serving with GQA),
plain and traced; and the refusal to measure without a chip."""
import json
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
TINY = "benchmarks/checks/tiny/BENCHMARK.json"
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run(*args, rehearse=True, timeout=600):
    cmd = [sys.executable, "benchmarks/run.py", "--manifest", TINY, *args]
    if rehearse:
        cmd.append("--rehearse")
    return subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.parametrize("cell", ["train.gpt-tiny.steps",
                                  "serve.gpt-tiny.open",
                                  "serve.gqa-tiny.closed"])
@pytest.mark.parametrize("trace", ["0", "1"])
def test_rehearsal_runs_and_is_correct(cell, trace):
    p = run("--workload", cell, "--seed", str(2**31 + 11), "--seconds", "1.5",
            "--trace", trace)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(line)
    assert list(line)[-1] == "checks"           # the compared numbers come last
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["metrics"] == {}                # no metric from a CPU run
    assert line["device"]["platform"] == "cpu"
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]
    assert "correct: True" in p.stderr.strip().splitlines()[-1]


def test_no_chip_no_result():
    p = run("--workload", "train.gpt-tiny.steps", "--seed", "1", "--seconds",
            "1", "--trace", "0", rehearse=False)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_unknown_device_kind_is_an_error():
    sys.path.insert(0, str(ROOT))
    from benchmarks.harness import manifest
    assert manifest.peaks("TPU v5 lite")["flops_per_s_bf16"] == 197e12
    with pytest.raises(SystemExit):
        manifest.peaks("TPU v99")
