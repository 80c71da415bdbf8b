"""Step phases as spans and counters inside the program (DESIGN.md §15):
the profiler sees every phase of ``Engine.step`` in order, with no
benchmark wrappers installed; the worker's front-end time, the step's
device wait and jit cache misses inside steps are unlabelled counters on
``/metrics``; ``Engine.last_step`` is what the chip benchmark's
``StepRecorder`` records of each step; and the benchmark's readers of the
new counters (``benchmarks/chip/metrics/``) read them from a window."""
import gc
import glob
import json
import math
import os
import sys
import threading
import urllib.request

import jax
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import metrics as M
from repro.serving.api import EngineConfig
from repro.serving.engine import Engine
from repro.serving.http_api import make_server
from repro.serving.sampler import SamplingParams
from tests.test_serving_faults import _prompts

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks", "chip"))
from chipbench import harness as H            # noqa: E402
from chipbench import tracereduce as TR       # noqa: E402

GREEDY = SamplingParams(greedy=True)
PHASES = ["engine.admit", "engine.plan", "engine.stage", "engine.dispatch",
          "engine.wait", "engine.emit", "engine.end_step"]
NEW_COUNTERS = ("engine_device_wait_seconds_total",
                "engine_frontend_seconds_total", "engine_compiles_total")


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config("qwen3_4b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _paged(model, params, **kw):
    conf = dict(batch_slots=4, max_len=96, cache="paged", page_size=8,
                eos_id=-1)
    conf.update(kw)
    return Engine(model, params, EngineConfig(**conf))


def _profile(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def test_profiler_sees_every_step_phase_in_order(small_lm, tmp_path):
    """A profiler trace of a plain engine (no benchmark wrappers): every
    step that ran the fused program holds admit, plan, stage, dispatch,
    wait, emit and end_step in that order, its annotation carries the
    step's composition, and garbage-collector pauses are spanned too."""
    cfg, model, params = small_lm
    eng = _paged(model, params, max_step_tokens=32)
    prompts = _prompts(cfg, [40, 9], seed=31)
    _profile(tmp_path)
    try:
        eng.generate(prompts, max_new_tokens=3, sampling=GREEDY)
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    events = TR.load(str(tmp_path))
    host = sorted((e for e in events if e["kind"] == "host"),
                  key=lambda e: e["t"])
    steps = [e for e in host if e["name"] == "engine.step"]
    ran = 0
    for s in steps:
        inside = [e["name"] for e in host
                  if e["name"] in PHASES and s["t"] <= e["t"]
                  and e["t"] + e["d"] <= s["t"] + s["d"]]
        if "engine.dispatch" not in inside:
            assert inside == ["engine.admit", "engine.end_step"], inside
            continue
        ran += 1
        assert inside == PHASES, inside
    assert ran >= 4     # two prefill chunks of the long prompt, two decodes
    assert any(e["name"] == "engine.gc" for e in host)
    # the step annotation's arguments: live rows, width, real tokens
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    stats = [dict(e.stats) for p in data.planes if p.name.startswith("/host")
             for line in p.lines for e in line.events
             if e.name == "engine.step" and dict(e.stats)]
    # the first step is the 40-token prompt's first 32-token chunk alone:
    # the budget leaves nothing for the second prompt
    first = stats[0]
    assert (first["rows"], first["width"]) == (1, 32)
    assert (first["tokens"], first["prefill_tokens"]) == (32, 32)


def test_new_counters_are_unlabelled_and_scraped(small_lm):
    """One completion over HTTP: the device wait, the front end's time and
    the compile count are unlabelled counters (what the benchmark reads)
    on ``/metrics``, and the step's device wait lies inside its wall
    time."""
    cfg, model, params = small_lm
    eng = _paged(model, params)
    srv = make_server(eng, model_name=cfg.name)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        body = json.dumps({"prompt": _prompts(cfg, [10], seed=32)[0],
                           "max_tokens": 4, "temperature": 0.0}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/v1/completions", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=30) as r:
            parsed = M.parse_prometheus_text(r.read().decode())
    finally:
        srv.shutdown()
    read = H.counters(eng)
    for name in NEW_COUNTERS:
        assert parsed[name]["type"] == "counter"
        (_, labels, value), = parsed[name]["samples"]
        assert set(labels) == {"layout", "kv_quant"}    # const labels only
        assert read[name] == value
    assert 0 < read["engine_device_wait_seconds_total"] \
        <= read["engine_wall_seconds_total"]
    assert read["engine_frontend_seconds_total"] > 0
    assert read["engine_compiles_total"] > 0      # a fresh engine traces


def test_compiles_count_new_widths_not_repeats(small_lm):
    """``engine_compiles_total`` rises on a step of a width not seen before
    and stays put on a repeat of widths already compiled."""
    cfg, model, params = small_lm
    eng = _paged(model, params)
    compiles = eng.metrics.compiles
    a, b, long = _prompts(cfg, [10, 12, 40], seed=33)
    eng.generate([a], max_new_tokens=3, sampling=GREEDY)   # widths 32, 1
    first = compiles.value
    assert first > 0
    eng.generate([b], max_new_tokens=3, sampling=GREEDY)   # the same widths
    assert compiles.value == first
    eng.generate([long], max_new_tokens=1, sampling=GREEDY)  # width 64
    assert compiles.value > first


def test_last_step_matches_the_benchmark_record(small_lm, tmp_path):
    """A mixed run (chunked prefills beside decodes, a request joining
    mid-run, requests finishing) with the benchmark's ``StepRecorder``
    installed:
    ``Engine.last_step`` equals what ``_describe`` recorded, step by
    step."""
    cfg, model, params = small_lm
    eng = _paged(model, params, max_step_tokens=16)
    rec = H.StepRecorder(eng, str(tmp_path))
    rec.arm(0.0, math.inf)
    prompts = _prompts(cfg, [30, 7, 21, 12], seed=34)
    for p, n in zip(prompts[:3], (6, 3, 5)):
        eng.submit(p, max_new_tokens=n, sampling=GREEDY)
    compared = 0
    try:
        for i in range(60):
            if i == 4:
                eng.submit(prompts[3], max_new_tokens=4, sampling=GREEDY)
            before = len(rec.steps)
            eng.step()
            if len(rec.steps) > before:
                assert eng.last_step == rec.steps[-1], i
                compared += 1
            else:
                assert eng.last_step is None
            if eng.sched.idle:
                break
    finally:
        jax.profiler.stop_trace()
    assert eng.sched.idle
    kinds = {(n > 1, emit) for s in rec.steps for _, n, emit in s["rows"]}
    assert (True, False) in kinds and (True, True) in kinds   # prefill
    assert (False, True) in kinds                             # decode
    assert any(len({n > 1 for _, n, _ in s["rows"]}) == 2
               for s in rec.steps)                      # mixed in one step
    assert compared == len(rec.steps) > 6


def _run(o, c):
    return H.Run(config={}, traffic={}, seconds=10.0, records=[],
                 window=(0.0, 10.0), setup_s=0.0, counters_open=o,
                 counters_close=c, peaks=None, chips=1)


OPEN = {"engine_steps_total": 10, "engine_wall_seconds_total": 13.0,
        "engine_device_wait_seconds_total": 12.9,
        "engine_frontend_seconds_total": 0.01, "engine_compiles_total": 40}
CLOSE = {"engine_steps_total": 20, "engine_wall_seconds_total": 26.0,
         "engine_device_wait_seconds_total": 25.7,
         "engine_frontend_seconds_total": 0.03, "engine_compiles_total": 40}


@pytest.mark.parametrize("name,want", [
    ("step_host_ms.decode", 1000.0 * (13.0 - 12.8) / 10),
    ("frontend_ms.decode", 1000.0 * 0.02 / 10),
    ("step_compiles.decode", 0)])
def test_counter_readers_on_a_window(name, want):
    """Each reader differences its counters between the window's opening
    and closing; without the counters (a program that has none) it reads
    nothing and does not raise."""
    read = H.reader(name)
    assert read(_run(OPEN, CLOSE)) == pytest.approx(want)
    steps_only = {"engine_steps_total": 10, "engine_wall_seconds_total": 1}
    assert read(_run(steps_only, steps_only)) is None
    if name != "step_compiles.decode":
        idle = dict(OPEN)
        assert read(_run(idle, idle)) is None        # no step in the window
