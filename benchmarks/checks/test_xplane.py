"""The trace reduction on a hand-sized trace: every value below is worked out
by hand from small_trace.json.

The slice is [1000, 10000) ns.  Device operations (containers left out of the
table, kept for the union): busy [2000, 4000) + [6000, 7500) + [8000, 10000)
(the last program runs to 11000 and is clipped at the slice's end) = 5500 ns.
Idle gaps: [1000, 2000), [4000, 6000), [7500, 8000)."""
import json
import pathlib

import pytest

from benchmarks.harness import xplane

TRACE = json.loads((pathlib.Path(__file__).parent / "small_trace.json")
                   .read_text())
SPANS = ["engine.step", "engine.admit", "engine.prefill.dispatch",
         "engine.fused.dispatch", "engine.sample.sync"]


def test_busy_union_and_idle_share():
    b = xplane.busy(TRACE)
    assert b["window_s"] == pytest.approx(9000e-9)
    assert b["busy_s"] == pytest.approx(5500e-9)
    assert 1 - b["busy_s"] / b["window_s"] == pytest.approx(3500 / 9000)


def test_op_sums_leave_out_loops_and_clip_at_the_slice():
    ops = xplane.op_seconds(TRACE)
    assert not any("while" in k for k in ops)
    # %fusion.7: 500 + 500 + (9500..10000 of 9500..11000) = 1500 ns, 3 events
    assert ops["%fusion.7 fusion"] == [pytest.approx(1500e-9), 3]
    secs, n = xplane.matching(ops, r"tpu_custom_call out=bf16\[\d+,1,\d+,\d+\] in=6$")
    assert (secs, n) == (pytest.approx(2500e-9), 2)
    assert xplane.top(ops, 1)[0][0].startswith("%paged.1")


def test_program_events_wholly_inside_the_slice():
    durs = xplane.whole_events(TRACE, xplane.MODULES_LINE, "serve_step_paged")
    assert durs == [pytest.approx(2000e-9)]      # the second runs past the end


def test_gap_attribution_by_innermost_open_span():
    gaps = xplane.idle_gaps(TRACE, SPANS)
    # [1000,2000): middle 1500 -> engine.step opens at 1500 (admit at 1600)
    # [4000,6000): middle 5000 -> the second engine.step
    # [7500,8000): middle 7750 -> engine.fused.dispatch inside engine.step
    assert gaps == {"engine.step": pytest.approx(3000e-9),
                    "engine.fused.dispatch": pytest.approx(500e-9)}


def test_span_self_time():
    secs, n = xplane.span_self_seconds(
        TRACE, "engine.step",
        ["engine.prefill.dispatch", "engine.fused.dispatch",
         "engine.sample.sync"])
    # step 1: 3000 - fused 1000 - sync (3600..4200) 600 = 1400
    # step 2: 4000 - prefill 1800 - fused 500 = 1700
    assert n == 2 and secs == pytest.approx(3100e-9)


def test_short_name_of_hlo_text():
    assert xplane.short_name(
        "%fusion.8 = bf16[4,2048]{1,0:T(8,128)(2,1)} fusion(bf16[8]{0} %x), "
        "kind=kOutput") == "%fusion.8 fusion"
    assert xplane.short_name(
        "%closed_call.34 = (bf16[64,2048,128]{2,1,0:T(8,128)(2,1)}, "
        "f32[64,2048,1]{2,1,0:T(8,128)}) custom-call(bf16[64,2048,128]{2,1,0} "
        "%a, bf16[64,2048,128]{2,1,0} %b, bf16[64,2048,128]{2,1,0} %c), "
        'custom_call_target="tpu_custom_call", operand_layout_constraints={}'
    ) == ("%closed_call.34 custom-call tpu_custom_call "
          "out=(bf16[64,2048,128], f32[64,2048,1]) in=3")
    assert xplane.short_name("jit_step(123)") == "jit_step(123)"
