"""Trace reduction: on a hand-made trace, and on a cut of a trace that a
``--trace 1`` run recorded on one TPU v5e (phi3-mini-3.8b, chat mix)."""

import copy
import gzip
import json
import os
import re

import pytest

from bench_testlib import BENCH, DATA
from harness import spec, trace

RECORDED = os.path.join(DATA, "trace-phi3-chat-v5e.json.gz")
PROGRAMS = {"prefill": r"jit__prefill", "decode": r"jit__decode"}
KERNELS = {"paged_decode": r"custom-call:tpu_custom_call"}
ROOFLINE = spec.load_module(
    os.path.join(BENCH, "metrics", "paged_decode_roofline.py"),
    "bench_metric_paged_decode_roofline_test")


def phi3():
    with open(os.path.join(BENCH, "configs", "phi3-mini-3.8b.json")) as f:
        return json.load(f)


def test_hand_made_trace():
    ev = {"host": [["bench.step", 0, 100], ["bench.wait", 100, 50],
                   ["bench.step", 150, 50]],
          "device": [{"modules": [["jit__decode(1)", 10, 60],
                                  ["jit__prefill(2)", 160, 30]],
                      "ops": [["%a = f32[2]", 10, 30, "fusion:kLoop"],
                              ["%b = f32[2]", 30, 40, "custom-call:"
                               "tpu_custom_call"],
                              ["%w = (s32[])", 10, 60, "while"],
                              ["%c = f32[2]", 160, 30, "copy"]]}]}
    r = trace.reduce(ev, programs=PROGRAMS, kernels=KERNELS)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["busy_s"] == pytest.approx(90e-9)          # [10,70] + [160,190]
    assert r["programs"]["decode"] == {"calls": 1,
                                       "seconds": pytest.approx(60e-9)}
    assert r["programs"]["prefill"]["calls"] == 1
    assert r["kernels"]["paged_decode"]["calls"] == 1
    assert r["kernels"]["paged_decode"]["seconds"] == pytest.approx(40e-9)
    # The loop that holds other ops is busy time, not an op of its own.
    assert [n for n, _ in r["device_ops"]] == [
        "%b = f32[2] custom-call:tpu_custom_call", "%a = f32[2] fusion:kLoop",
        "%c = f32[2] copy"]
    gaps = r["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(90e-9)]    # [70, 160]
    assert sum(g for _, g in gaps) == pytest.approx(110e-9)
    assert trace.kernel_time_in(r, "paged_decode", [[0, 100], [150, 50]]) \
        == [pytest.approx(40e-9), 0]


def test_op_label_and_kind():
    text = ('%closed_call.12 = f32[384,1,96]{2,1,0:T(1,128)S(1)} custom-call('
            's32[12,17]{1,0} %copy-done.2), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={s32[12,17]{1,0}}')
    assert trace.op_label(text) == ["%closed_call.12 = f32[384,1,96]",
                                    "custom-call:tpu_custom_call"]
    fused = ("%fusion.168 = (f32[32,256]{1,0}, f32[1,4352]{1,0}) fusion("
             "bf16[256,32,48]{0,2,1} %x), kind=kOutput, calls=%f")
    assert trace.op_label(fused)[1] == "fusion:kOutput"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_trace(recorded):
    r = trace.reduce(recorded, programs=PROGRAMS, kernels=KERNELS)
    assert 0 < r["busy_s"] <= r["window_s"] < 1.0
    # Two steps carried a prefill chunk; five decode steps are whole.
    assert r["programs"]["prefill"]["calls"] == 2
    assert r["programs"]["decode"]["calls"] == 5
    # One paged_decode launch per layer per decode step.
    assert r["kernels"]["paged_decode"]["calls"] == 5 * 32
    per_call = (r["kernels"]["paged_decode"]["seconds"]
                / r["kernels"]["paged_decode"]["calls"])
    assert 1e-5 < per_call < 1e-2
    decode_ms = 1e3 * r["programs"]["decode"]["seconds"] / 5
    assert 50 < decode_ms < 200
    times = [t for _, t in r["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) == 10
    assert all(n.startswith("bench.") for n, _ in r["idle_gaps"])
    steps = [h for h in recorded["host"] if h[0] == "bench.step"]
    per_step = trace.kernel_time_in(r, "paged_decode",
                                    [[s, d] for _, s, d in steps])
    assert sum(per_step) == pytest.approx(
        r["kernels"]["paged_decode"]["seconds"])


def test_paged_decode_is_found_by_its_result_shape(recorded):
    """The recorded decode steps hold one paged attention kernel a layer,
    f32[384,1,96] for 12 sequences of 32 heads of 96; the pattern finds
    each of them and nothing else."""
    pat = ROOFLINE.kernel_pattern(phi3())
    r = trace.reduce(recorded, programs=PROGRAMS,
                     kernels={"paged_decode": pat, "any": KERNELS[
                         "paged_decode"]})
    assert r["kernels"]["paged_decode"]["calls"] == 5 * 32
    assert r["kernels"]["paged_decode"]["seconds"] == pytest.approx(
        r["kernels"]["any"]["seconds"])
    for packed in ("f32[384,1,96]", "bf16[96,4,96]", "f32[12,32,96]"):
        assert re.search(pat, f"%x.1 = {packed} custom-call:tpu_custom_call")
    for other in ("f32[384,1,128]", "bf16[12,3072]", "f32[384,2,96]"):
        assert not re.search(
            pat, f"%x.1 = {other} custom-call:tpu_custom_call")


def test_second_kernel_in_the_decode_step_is_not_counted(recorded):
    """Another Pallas kernel in the decode step (here a fused norm after
    each attention call) adds to the step's time, not to paged_decode's;
    a reduction that counted every TPU custom call would double it."""
    ev = copy.deepcopy(recorded)
    pat = ROOFLINE.kernel_pattern(phi3())
    ops = ev["device"][0]["ops"]
    added = [["%fused_norm.1 = bf16[12,3072]", s + d, 1000,
              "custom-call:tpu_custom_call"] for label, s, d, kind in ops
             if re.search(pat, f"{label} {kind}")]
    assert len(added) == 5 * 32
    ops.extend(added)
    ops.sort(key=lambda o: o[1])
    before = trace.reduce(recorded, programs={},
                          kernels={"paged_decode": pat})
    after = trace.reduce(ev, programs={}, kernels={"paged_decode": pat,
                                                   "any": KERNELS[
                                                       "paged_decode"]})
    assert after["kernels"]["paged_decode"]["calls"] == 5 * 32
    assert after["kernels"]["paged_decode"]["seconds"] == pytest.approx(
        before["kernels"]["paged_decode"]["seconds"])
    assert after["kernels"]["any"]["calls"] == 2 * 5 * 32


def test_no_device_events_reduce_to_nothing():
    assert trace.reduce({"host": [], "device": []}, programs={},
                        kernels={}) == {}
