"""Observability-layer tests (ISSUE 7 / DESIGN.md §15): the typed metrics
registry (counters/gauges/histograms, Prometheus text exposition round
trip), the step-span tracer (byte-deterministic Perfetto export under a
ManualClock, schema validation), the HTTP ``/metrics`` + ``/healthz``
surface, exact /metrics-vs-EngineStats agreement after a mixed workload,
counter/span accounting under preemption + injected faults, clock-driven
``wall_s``, and the zero-cost guarantees: greedy outputs identical with
observability on or off, and still exactly one device->host transfer per
decode step."""
import json
import threading
import urllib.error
import urllib.request

import jax
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import faults as F
from repro.serving import metrics as M
from repro.serving.api import EngineConfig, FinishReason
from repro.serving.clock import ManualClock
from repro.serving.engine import Engine
from repro.serving.http_api import make_server
from repro.serving.sampler import SamplingParams
from repro.serving.tracing import (PID_ENGINE, PID_REQUESTS, Tracer, span,
                                   validate_trace)
from tests.test_serving_faults import _drain, _prompts

GREEDY = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config("qwen3_4b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# ------------------------------------------------------------------- registry
def test_counter_gauge_histogram_basics():
    r = M.MetricsRegistry()
    c = r.counter("c_total", "a counter")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = r.gauge("g", "a gauge")
    g.set(5)
    g.dec(2)
    g.set_max(2)                    # below current -> no-op
    assert g.value == 3.0
    g.set_max(9)
    assert g.value == 9.0
    h = r.histogram("h_seconds", "a histogram", (0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 100.0):
        h.observe(v)
    assert h.total_count == 5 and h.total_sum == pytest.approx(106.05)
    # p50 of 5 samples lands in the (0.1, 1.0] bucket, interpolated
    assert 0.1 < h.quantile(0.5) <= 1.0
    # top quantile falls in +Inf bucket -> clamped to the last finite bound
    assert h.quantile(0.99) == 10.0


def test_histogram_needs_buckets_and_reregistration_consistency():
    r = M.MetricsRegistry()
    with pytest.raises(ValueError, match="bucket"):
        r.histogram("h", "no buckets", ())
    r.counter("x_total", "x")
    assert r.counter("x_total", "x").value == 0.0   # same schema: same family
    with pytest.raises(ValueError, match="re-registered"):
        r.gauge("x_total", "now a gauge")


def test_labeled_family_children_and_zero_label_guard():
    r = M.MetricsRegistry()
    c = r.counter("req_total", "by reason", labels=("reason",))
    c.labels(reason="stop").inc(2)
    c.labels(reason="abort").inc()
    assert c.value == 3.0                      # aggregate across children
    with pytest.raises(ValueError, match="labels"):
        c.inc()                                # labeled family needs .labels
    with pytest.raises(ValueError, match="takes labels"):
        c.labels(nope="x")


def test_exposition_round_trip_with_const_labels():
    r = M.MetricsRegistry(const_labels={"layout": "paged", "kv_quant": "int8"})
    r.counter("t_total", "tokens").inc(7)
    h = r.histogram("lat_seconds", "latency", (0.5, 2.0), labels=("prio",))
    h.labels(prio=0).observe(0.3)
    h.labels(prio=1).observe(1.0)
    text = r.expose()
    parsed = M.parse_prometheus_text(text)
    assert parsed["t_total"]["type"] == "counter"
    (_, labels, value), = parsed["t_total"]["samples"]
    assert labels == {"layout": "paged", "kv_quant": "int8"} and value == 7.0
    # histogram: cumulative buckets + _sum/_count per child
    names = [n for n, _, _ in parsed["lat_seconds"]["samples"]]
    assert names.count("lat_seconds_bucket") == 6    # 2 children x 3 buckets
    assert names.count("lat_seconds_count") == 2
    infs = [(lab, v) for n, lab, v in parsed["lat_seconds"]["samples"]
            if lab.get("le") == "+Inf"]
    assert all(v == 1.0 for _, v in infs)
    with pytest.raises(ValueError):
        M.parse_prometheus_text("garbage_without_type 1.0")


def test_null_registry_is_inert():
    m = M.make_engine_metrics("slot", "fp32", enabled=False)
    m.tokens_generated.inc(100)
    m.ttft.labels(priority=1).observe(3.0)
    m.peak_active.set_max(5)
    assert m.tokens_generated.value == 0.0
    assert m.ttft.quantile(0.99) == 0.0
    assert m.registry.expose() == ""
    assert m.registry.snapshot()["families"] == {}


# -------------------------------------------------------------------- tracer
def test_tracer_spans_and_validation():
    clk = ManualClock(2.0)
    tr = Tracer()
    tr.clock = clk
    tr.request_state(3, "QUEUED", 1.0, prompt_len=4)
    tr.request_state(3, "RUNNING", 2.0)
    with span("engine.step", tr, step=0) as sp:
        clk.advance(0.5)
        sp.set(batch=1)
    tr.fault_instant("stall", 2.25)
    tr.request_end(3, "stop", 4.0, tokens=6)
    d = tr.to_dict()
    assert validate_trace(d) == []
    evs = d["traceEvents"]
    queued = next(e for e in evs if e["name"] == "QUEUED")
    assert queued == {"name": "QUEUED", "cat": "request", "ph": "X",
                      "pid": PID_REQUESTS, "tid": 3, "ts": 1e6, "dur": 1e6,
                      "args": {"prompt_len": 4}}
    step = next(e for e in evs if e["name"] == "engine.step")
    assert (step["ts"], step["dur"], step["args"]) == (
        2e6, 5e5, {"step": 0, "batch": 1})
    assert any(e["name"] == "fault:stall" and e["pid"] == PID_ENGINE
               for e in evs)
    assert any(e["name"] == "finish" and e["args"]["reason"] == "stop"
               for e in evs)
    # disabled tracer records nothing
    off = Tracer(enabled=False)
    off.request_state(1, "QUEUED", 0.0)
    with span("engine.step", off):
        pass
    assert off.events == []


def test_validate_trace_catches_malformed_events():
    assert validate_trace({"nope": 1})
    bad = {"traceEvents": [
        {"name": "x", "ph": "X", "pid": 1, "tid": 0, "ts": -5, "dur": 1,
         "args": {}}]}
    probs = validate_trace(bad)
    assert any("bad ts" in p for p in probs)
    assert any("thread_name" in p for p in probs)


# ------------------------------------------- engine: accounting + determinism
def _mixed_workload(model, params, *, clock, tracer=None, metrics=True):
    """Prefill + decode + preemption + offload/restore + shed on one tiny
    paged engine, all in simulated time."""
    conf = EngineConfig(batch_slots=4, max_len=96, cache="paged",
                        page_size=8, num_pages=6, eos_id=-1, clock=clock,
                        default_queue_timeout_s=4.0, preemption=True,
                        tracer=tracer, metrics=metrics)
    eng = Engine(model, params, conf)
    return eng


def _pump(eng, clk, prompts, max_steps=120):
    ra = eng.submit(prompts[0], max_new_tokens=10, sampling=GREEDY,
                    priority=0)
    outs = {}
    for _ in range(4):
        for o in eng.step():
            outs[o.rid] = o
        clk.advance(0.5)
    rb = eng.submit(prompts[1], max_new_tokens=10, sampling=GREEDY,
                    priority=1)                # preempts A (pool is tight)
    rc = eng.submit(prompts[2], max_new_tokens=4, sampling=GREEDY,
                    priority=0, queue_timeout_s=0.25)   # will be shed
    clk.advance(0.5)
    steps = 0
    while not eng.sched.idle and steps < max_steps:
        for o in eng.step():
            outs[o.rid] = o
        eng._events.clear()
        clk.advance(0.5)
        steps += 1
    assert eng.sched.idle
    return outs, (ra, rb, rc)


def test_metrics_agree_exactly_with_engine_stats(small_lm):
    """After a mixed prefill/decode/preemption/shed workload, every counter
    a /metrics scrape reports equals the EngineStats read-view exactly."""
    cfg, model, params = small_lm
    clk = ManualClock(0.0)
    eng = _mixed_workload(model, params, clock=clk)
    outs, (ra, rb, rc) = _pump(eng, clk, _prompts(cfg, [24, 24, 16], seed=21))
    s = eng.stats
    assert s.preemptions >= 1 and s.restored_pages > 0
    assert outs[rc].finish_reason is FinishReason.SHED

    parsed = M.parse_prometheus_text(eng.metrics.registry.expose())

    def scraped(family):
        return sum(v for n, _, v in parsed[family]["samples"] if n == family)

    for family, attr in [
            ("engine_tokens_generated_total", "tokens_generated"),
            ("engine_prefill_tokens_total", "prefill_tokens"),
            ("engine_steps_total", "steps"),
            ("engine_wall_seconds_total", "wall_s"),
            ("engine_prefix_hit_pages_total", "prefix_hit_pages"),
            ("engine_prefix_hit_tokens_total", "prefix_hit_tokens"),
            ("engine_preemptions_total", "preemptions"),
            ("engine_offloaded_pages_total", "offloaded_pages"),
            ("engine_offloaded_bytes_total", "offloaded_bytes"),
            ("engine_restored_pages_total", "restored_pages"),
            ("engine_shed_requests_total", "shed_requests"),
            ("engine_deferred_admissions_total", "deferred_admissions"),
            ("engine_peak_active", "peak_active")]:
        assert scraped(family) == getattr(s, attr), family

    # finished-by-reason counters sum to the requests that left the engine
    finished = {lab["reason"]: v
                for n, lab, v in parsed["engine_requests_finished_total"]
                ["samples"] if n == "engine_requests_finished_total"}
    assert finished.get("shed") == 1
    assert sum(finished.values()) == len(outs)
    # const labels stamp every sample
    _, lab, _ = parsed["engine_steps_total"]["samples"][0]
    assert lab["layout"] == "paged" and lab["kv_quant"] == "float32"
    # histograms saw the lifecycle: one ttft per served request
    served = [o for o in outs.values()
              if o.finish_reason is not FinishReason.SHED]
    assert scraped("engine_ttft_seconds") == 0     # no raw-name samples
    counts = [v for n, _, v in parsed["engine_ttft_seconds"]["samples"]
              if n == "engine_ttft_seconds_count"]
    assert sum(counts) == len(served)


def test_wall_s_is_clock_driven_in_every_pump(small_lm):
    """wall_s accumulates inside step() from the injectable clock — a stall
    that advances the ManualClock mid-step is charged to exactly that step,
    whether the engine is pumped via run(), generate(), or bare step()."""
    cfg, model, params = small_lm
    clk = ManualClock(0.0)
    inj = F.FaultInjector().stall_at(2, F.clock_stall(clk, 7.0))
    eng = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, cache="paged", page_size=8, eos_id=-1,
        clock=clk, faults=inj))
    eng.submit(_prompts(cfg, [12], seed=22)[0], max_new_tokens=6,
               sampling=GREEDY)
    for _ in range(3):                         # bare step() pump
        eng.step()
    assert eng.stats.wall_s == pytest.approx(7.0)   # only the stall advanced
    assert eng.metrics.step_duration.quantile(0.99) > 0
    assert eng.metrics.faults_injected.labels(kind="stall").value == 1


def test_trace_is_byte_deterministic_and_complete(small_lm):
    """Two identical ManualClock runs export byte-identical Perfetto JSON,
    and the trace carries the full lifecycle: QUEUED/PREFILL/RUNNING spans,
    PREEMPTED span with an offload instant, restore instant, step spans
    with page-pool occupancy, and one finish instant per request."""
    cfg, model, params = small_lm
    prompts = _prompts(cfg, [24, 24, 16], seed=21)
    blobs, tracers = [], []
    for _ in range(2):
        clk = ManualClock(0.0)
        tr = Tracer()
        eng = _mixed_workload(model, params, clock=clk, tracer=tr)
        _pump(eng, clk, prompts)
        assert eng.stats.preemptions >= 1
        tr.flush_open(clk.now())
        assert validate_trace(tr.to_dict()) == []
        blobs.append(tr.to_json())
        tracers.append(tr)
    assert blobs[0] == blobs[1], "ManualClock trace not byte-deterministic"

    evs = tracers[0].events
    names = [e["name"] for e in evs]
    for state in ("QUEUED", "PREFILL", "RUNNING", "PREEMPTED"):
        assert state in names, f"missing lifecycle span {state}"
    assert "offload" in names and "restore" in names
    finishes = [e for e in evs if e["name"] == "finish"]
    assert {e["args"]["reason"] for e in finishes} == {"length", "shed"}
    steps = [e for e in evs if e["name"] == "engine.step"]
    assert steps and all("free_pages" in e["args"] for e in steps)
    assert all(e["pid"] == PID_ENGINE for e in steps)
    prefills = [e for e in evs if e["name"] == "prefill"]
    assert prefills and all("prefill_chunk" in e["args"] for e in prefills)


@pytest.mark.parametrize("layout,kvq", [("slot", None), ("paged", None),
                                        ("paged", "int8")],
                         ids=["slot-bf16", "paged-bf16", "paged-int8"])
def test_greedy_tokens_identical_with_observability_on_and_off(
        small_lm, layout, kvq):
    cfg, model, params = small_lm
    prompts = _prompts(cfg, [9, 14], seed=23)

    def run(metrics, tracer):
        eng = Engine(model, params, EngineConfig(
            batch_slots=2, max_len=64, cache=layout, page_size=8,
            eos_id=-1, kv_quant=kvq, metrics=metrics, tracer=tracer))
        return [o.output for o in eng.generate(prompts, max_new_tokens=8,
                                               sampling=GREEDY)]

    on = run(True, Tracer())
    off = run(False, None)
    assert on == off, "observability changed sampled tokens"


def test_decode_still_one_transfer_per_step_with_observability(
        small_lm, monkeypatch):
    """Metrics + tracing are host-side only: the decode loop still makes
    exactly one device->host transfer per step (the sampled tokens)."""
    import repro.serving.engine as engine_mod
    cfg, model, params = small_lm
    eng = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=32, eos_id=-1, cache="paged", page_size=8,
        tracer=Tracer()))
    for p in _prompts(cfg, [5, 7], seed=24):
        eng.submit(p, max_new_tokens=16, sampling=GREEDY)
    eng._admit([])                        # prefill outside the counted loop

    transfers = {"n": 0}
    real_get = jax.device_get

    def counting_get(x):
        transfers["n"] += 1
        return real_get(x)

    monkeypatch.setattr(engine_mod.jax, "device_get", counting_get)
    steps = 3
    for _ in range(steps):
        eng.step()
    assert transfers["n"] == steps


def test_fault_injection_lands_in_counters_and_trace(small_lm):
    cfg, model, params = small_lm
    clk = ManualClock(0.0)
    tr = Tracer()
    inj = (F.FaultInjector().exhaust_pages_at(0, 999).release_pages_at(4)
           .stall_at(2, F.clock_stall(clk, 3.0)))
    eng = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, cache="paged", page_size=8, num_pages=6,
        eos_id=-1, clock=clk, faults=inj, tracer=tr, preemption=False))
    eng.submit(_prompts(cfg, [16], seed=25)[0], max_new_tokens=4,
               sampling=GREEDY)
    _drain(eng)
    fired = [k for _, k, _ in inj.log]
    assert fired == ["exhaust_pages", "stall", "release_pages"]
    # every fired fault: one counter increment, labeled by kind...
    fam = eng.metrics.faults_injected
    assert {k: fam.labels(kind=k).value for k in set(fired)} == {
        "exhaust_pages": 1.0, "stall": 1.0, "release_pages": 1.0}
    # ...and one instant on the engine trace track, in firing order
    instants = [e for e in tr.events if e["name"].startswith("fault:")]
    assert [e["name"] for e in instants] == [f"fault:{k}" for k in fired]
    assert all(e["pid"] == PID_ENGINE for e in instants)
    assert instants[0]["args"]["pages"] == 6
    assert validate_trace(tr.to_dict()) == []


# ---------------------------------------------------------------- HTTP layer
def _get(port, path, timeout=30):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


@pytest.fixture()
def http_server(small_lm):
    cfg, model, params = small_lm
    eng = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, cache="paged", page_size=8, eos_id=-1))
    srv = make_server(eng, model_name=cfg.name)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield cfg, srv, eng
    srv.shutdown()


def test_http_metrics_scrape_matches_engine(http_server):
    cfg, srv, eng = http_server
    prompt = _prompts(cfg, [10], seed=26)[0]
    body = json.dumps({"prompt": prompt, "max_tokens": 5,
                       "temperature": 0.0}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        assert r.status == 200

    st, hdr, raw = _get(srv.port, "/metrics")
    assert st == 200
    assert hdr["Content-Type"].startswith("text/plain; version=0.0.4")
    parsed = M.parse_prometheus_text(raw.decode())
    toks = [v for n, _, v in parsed["engine_tokens_generated_total"]
            ["samples"] if n == "engine_tokens_generated_total"]
    # 5 output tokens = 1 sampled at prefill + 4 in the decode loop (the
    # counter's long-standing decode-only semantics)
    assert sum(toks) == eng.stats.tokens_generated == 4
    finished = [v for n, lab, v
                in parsed["engine_requests_finished_total"]["samples"]
                if lab.get("reason") == "length"]
    assert sum(finished) == 1


def test_http_healthz_reports_watchdog_state(small_lm):
    cfg, model, params = small_lm
    clk = ManualClock(0.0)
    eng = Engine(model, params, EngineConfig(
        batch_slots=1, max_len=32, eos_id=-1, clock=clk))
    srv = make_server(eng, stall_timeout_s=10.0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        st, _, raw = _get(srv.port, "/healthz")
        body = json.loads(raw)
        assert st == 200 and body["status"] == "ok"
        assert body["watchdog"] == "armed" and body["missed"] == 0

        clk.advance(25.0)                  # worker heartbeat goes stale
        st, _, raw = _get(srv.port, "/healthz")
        body = json.loads(raw)
        assert st == 503 and body["status"] == "stalled"
        assert body["heartbeat_stale_s"] >= 25.0
    finally:
        srv.shutdown()


def test_http_healthz_without_watchdog_is_disarmed(http_server):
    _cfg, srv, _eng = http_server
    st, _, raw = _get(srv.port, "/healthz")
    body = json.loads(raw)
    assert st == 200 and body == {"status": "ok", "watchdog": "disarmed"}


def test_http_unknown_paths_return_json_404(http_server):
    """Unknown routes get a clean JSON error envelope — for a plain blocking
    client and for an SSE-intending client alike (no hung stream, no HTML
    error page)."""
    cfg, srv, _eng = http_server
    # blocking GET client
    st, hdr, raw = _get(srv.port, "/v1/nope")
    assert st == 404 and hdr["Content-Type"] == "application/json"
    assert json.loads(raw) == {"error": {"message": "no route /v1/nope"}}
    # SSE-intending client: stream=true POSTed at a wrong path must get the
    # same JSON envelope immediately, not an event-stream that never opens
    body = json.dumps({"prompt": _prompts(cfg, [4], seed=27)[0],
                       "max_tokens": 2, "stream": True}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}/v1/complete", data=body,
        headers={"Content-Type": "application/json",
                 "Accept": "text/event-stream"})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            st, hdr, raw = r.status, dict(r.headers), r.read()
    except urllib.error.HTTPError as e:
        st, hdr, raw = e.code, dict(e.headers), e.read()
    assert st == 404 and hdr["Content-Type"] == "application/json"
    assert json.loads(raw)["error"]["message"] == "no route /v1/complete"
