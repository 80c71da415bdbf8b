"""Trace reduction: busy share, time per kernel, idle gaps by host span and
roofline shares, on a hand-made event list and on a small trace recorded
on a v5e chip (``fixtures/trace-decode-long.json``)."""
from __future__ import annotations

import json
import os
import types

import numpy as np
import pytest

import chipbench
from chipbench import harness as H
from chipbench import tracereduce as TR
from chipbench import work

FIXTURE = os.path.join(H.BENCH_DIR, "fixtures", "trace-decode-long.json")
KERNELS = H.load_json(H.BENCH_DIR, "kernels.json")


def _ev(kind, name, t, d):
    return {"kind": kind, "name": name, "t": float(t), "d": float(d)}


def test_reduce_by_hand():
    # one 100 ns step: a loop op (10-60) around two kernels (10-30, 35-60),
    # a copy (70-80); the host admits (0-10) and fans out after (90-100)
    events = [
        _ev("host", "engine.step", 0, 100), _ev("host", "engine.admit", 0, 10),
        _ev("host", "worker.fan_out", 90, 10),
        _ev("device", "while", 10, 50),
        _ev("device", "%gptq_matmul.3 custom-call bf16[8,128]", 10, 20),
        _ev("device", "%paged_prefill.1 custom-call bf16[8,128]", 35, 25),
        _ev("device", "copy", 70, 10),
        _ev("device", "outside", 120, 5)]
    r = TR.reduce(events, KERNELS)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(60e-9)
    assert r["ops"]["while"] == pytest.approx(5e-9)     # 50 - 20 - 25
    assert r["ops"]["%gptq_matmul.3 custom-call bf16[8,128]"] == \
        pytest.approx(20e-9)
    assert r["kernels"]["gptq"] == pytest.approx(20e-9)
    assert r["kernels"]["paged_prefill"] == pytest.approx(25e-9)
    # idle 0-10 (admit), 60-70 (step), 80-100: one gap whose middle, 90,
    # is where the fan-out starts
    assert r["idle"] == pytest.approx({"engine.admit": 10e-9,
                                       "engine.step": 10e-9,
                                       "worker.fan_out": 20e-9})
    assert r["steps"] == 1
    b = TR.breakdown(r, top=2)
    assert [n.split()[0] for n, _ in b["device_ops"]] == [
        "%paged_prefill.1", "%gptq_matmul.3"]


def test_short_names():
    hlo = ("%paged_attention.9 = bf16[32,8,4,128]{3,2,1,0:T(4,128)(2,1)S(1)} "
           "custom-call(s32[32,256]{1,0:T(8,128)S(1)} %get-tuple-element.1)")
    assert TR.short_name(hlo) == "%paged_attention.9 custom-call bf16[32,8,4,128]"
    loop = "%while.5 = (s32[]{:T(128)}, bf16[32,1,2560]{2,0,1}) while(%tuple.3)"
    assert TR.short_name(loop) == "%while.5 while tuple"


def test_kernel_classes_join_the_familys():
    """A family's ``KERNELS`` join ``kernels.json``'s classes; the dense
    family has none, and a class named twice is refused."""
    dense = chipbench.load("families", "dense_gqa")
    assert TR.classes(dense) == KERNELS
    extra = types.SimpleNamespace(KERNELS={"expert_gptq": ["^%expert\\b"]})
    assert TR.classes(extra) == {**KERNELS, **extra.KERNELS}
    with pytest.raises(ValueError, match="gptq"):
        TR.classes(types.SimpleNamespace(KERNELS={"gptq": ["x"]}))


def test_no_step_no_reduction():
    assert TR.reduce([_ev("device", "k1", 0, 5)], KERNELS) is None


def _fixture():
    with open(FIXTURE) as f:
        return json.load(f)


def _busy_by_grid(events, w0, w1):
    """Busy nanoseconds by marking a 1 ns grid — a slow, obvious union."""
    grid = np.zeros(int(w1 - w0) + 1, bool)
    for e in events:
        a = int(max(e["t"], w0) - w0)
        b = int(min(e["t"] + e["d"], w1) - w0)
        if b > a:
            grid[a:b] = True
    return grid.sum()


def test_recorded_trace_reduces():
    data = _fixture()
    events = data["events"]
    r = TR.reduce(events, KERNELS)
    steps = [e for e in events if e["name"] == "engine.step"]
    w0 = min(e["t"] for e in steps)
    w1 = max(e["t"] + e["d"] for e in steps)
    assert r["window_s"] == pytest.approx((w1 - w0) / 1e9)
    dev = [e for e in events if e["kind"] == "device"]
    assert r["busy_s"] == pytest.approx(_busy_by_grid(dev, w0, w1) / 1e9,
                                        rel=1e-6)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["kernels"]["gptq"] > 0 and r["kernels"]["paged_attention"] > 0
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_recorded_trace_roofline_shares():
    """The fixture's steps and events give roofline shares and an MFU
    strictly between 0 and 100%, each with the least time (or useful
    operations) and the value pinned before the work counts moved into
    the dense family, to the last digit."""
    data = _fixture()
    pinned = H.load_json(H.BENCH_DIR, "fixtures", "dense-gqa-pins.json")[
        "fixture_trace"]
    run = H.Run(config={**data["config"], "family": "dense_gqa"}, traffic={},
                seconds=1.0, records=[],
                window=(0.0, 1.0), setup_s=0.0, counters_open={},
                counters_close={}, peaks=H.device_peaks("TPU v5 lite"),
                chips=1, trace=TR.reduce(data["events"], KERNELS),
                steps=data["steps"])
    for name in ("gptq_roofline", "paged_attn_roofline", "step_mfu",
                 "device_idle_share"):
        value = H.reader(name)(run)
        assert 0 < value < 100, (name, value)
        assert value == pinned["values"][name], name
    by_class = [run.family.step_calls(run.config, s) for s in run.steps]

    def least(*classes):
        return work.roofline_seconds(
            [c for calls in by_class for k in classes
             for c in calls.get(k, [])],
            run.peaks["bf16_flops_per_s"], run.peaks["hbm_bytes_per_s"])

    assert least("gptq") == pinned["numerators"]["gptq_roofline"]
    assert least("paged_attention", "paged_prefill") == \
        pinned["numerators"]["paged_attn_roofline"]
    assert sum(run.family.useful_flops(run.config, s) for s in run.steps) \
        == pinned["numerators"]["step_mfu"]


def test_prompt_tokens_count_where_they_are_prefilled():
    """``prompt_tok_s``: each prompt's tokens spread evenly from its send
    to its first token; the part of that interval inside the window
    counts."""
    records = [
        {"prompt": [1] * 100, "sent": 0.0, "times": [10.0, 11.0]},  # half in
        {"prompt": [1] * 40, "sent": 6.0, "times": [8.0]},          # all in
        {"prompt": [1] * 50, "sent": 16.0, "times": [20.0]},         # after
        {"prompt": [1] * 30, "sent": 7.0, "times": []}]              # no token
    run = H.Run(config={}, traffic={}, seconds=10.0, records=records,
                window=(5.0, 15.0), setup_s=0.0, counters_open={},
                counters_close={}, peaks=None, chips=1)
    assert H.reader("prompt_tok_s")(run) == pytest.approx((50 + 40) / 10.0)
