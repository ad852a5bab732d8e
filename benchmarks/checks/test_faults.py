"""`correct` has to come out false when the timed path is broken, and when the
reference, put in the program's place, computes one precision lower (the
control).  Tiny sizes on the CPU; the same readings were taken on the chip at
the cells' own sizes (PERF.md, section 2).

The harness's look for a chip is skipped (`--rehearse`); the rest of a run is
driven as it is, with the fault planted underneath."""
import json

import numpy as np
import pytest

from benchmarks import run as bench
from benchmarks.drivers import serve, train
from benchmarks.harness import compare, manifest, tracer

TINY = "benchmarks/checks/tiny/BENCHMARK.json"


def last_line(capsys, cell, seconds="1"):
    rc = bench.main(["--manifest", TINY, "--rehearse", "--workload", cell,
                     "--seed", "7", "--seconds", seconds, "--trace", "0"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def state_unchanged(trainer, tok, lab):
    """A step that returns a loss and leaves its state as it was."""
    return trainer.eval_loss(tok, lab)


def half_batch(trainer, tok, lab):
    """Half of the batch left out, the mean taken over the rest."""
    n = tok.shape[0] // 2
    return trainer.train_step(np.concatenate([tok[:n], tok[:n]]),
                              np.concatenate([lab[:n], lab[:n]]))


@pytest.mark.parametrize("fault", [state_unchanged, half_batch])
def test_a_broken_train_step_is_not_correct(monkeypatch, capsys, fault):
    monkeypatch.setattr(train, "FAULT", fault)
    line = last_line(capsys, "train.gpt-tiny.steps")
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["checks"].values())


def test_the_sound_train_step_is_correct(capsys):
    assert last_line(capsys, "train.gpt-tiny.steps")["correct"] is True


def altered_token(tokens):
    """One answer altered where it is produced."""
    tokens = list(tokens)
    tokens[len(tokens) // 2] = (tokens[len(tokens) // 2] + 1) % 256
    return tokens


@pytest.mark.parametrize("cell", ["serve.gpt-tiny.open",
                                  "serve.gqa-tiny.closed"])
def test_an_altered_token_is_not_correct(monkeypatch, capsys, cell):
    monkeypatch.setattr(serve, "FAULT", altered_token)
    line = last_line(capsys, cell)
    assert line["correct"] is False
    assert line["checks"]["served_logit_gap_max"]["value"] > \
        line["checks"]["served_logit_gap_max"]["limit"]


def quiet(tag, **facts):
    pass


def test_train_control_lower_precision_is_not_correct():
    cell = manifest.load_cell("train.gpt-tiny.steps", TINY)
    drv = train.Driver(cell, 3, quiet)
    drv.setup()
    drv.release()
    want = drv.reference_readings()
    sound = compare.checks_from(drv.readings(drv.got, want), cell.limits)
    assert all(c.ok for c in sound)
    ctrl = drv.reference_readings(prec="fp8")
    failed = [c.name for c in compare.checks_from(drv.readings(ctrl, want),
                                                  cell.limits) if not c.ok]
    assert failed, "the fp8 control passed every number"


def test_serve_control_lower_precision_is_not_correct():
    cell = manifest.load_cell("serve.gqa-tiny.closed", TINY)
    drv = serve.Driver(cell, 3, quiet)
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
