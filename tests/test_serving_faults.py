"""Overload-resilience tests (ISSUE 6 / DESIGN.md §14): priority preemption
with host-memory page offload (lossless round trip, bf16 and int8 KV),
PagedCache offload/restore bookkeeping (refcounts, shared prefixes, donor
eviction), bounded admission + deadline shedding (engine and HTTP: 429 with
Retry-After, 503), the engine-worker watchdog (no stream hangs on a stalled
engine), the serving fault-injection harness, monitor-side heartbeat
staleness, and quant-mode-seeded prefix-cache hashing."""
import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import build_model
from repro.perf import memory_model as MM
from repro.runtime.fault_tolerance import Heartbeat
from repro.serving import faults as F
from repro.serving.api import (EngineConfig, FinishReason, QueueFullError,
                               RequestState)
from repro.serving.clock import ManualClock, SystemClock
from repro.serving.engine import Engine
from repro.serving.http_api import make_server
from repro.serving.kv_cache import PagedCache
from repro.serving.sampler import SamplingParams
from repro.serving.spec_decode import SpecConfig

GREEDY = SamplingParams(greedy=True)

# ISSUE 8 satellite: CI runs this suite a second time with REPRO_SPEC=1 so
# the overload machinery (preemption, shedding, watchdog, fault injection)
# is exercised composed with speculative decoding — greedy outputs are
# token-identical either way, so every assertion below holds unchanged.
_SPEC = (SpecConfig(method="ngram", k=2)
         if os.environ.get("REPRO_SPEC") else None)


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config("qwen3_4b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _prompts(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, cfg.vocab_size, size=n).tolist() for n in sizes]


def _drain(eng, max_steps=300):
    outs = {}
    steps = 0
    while not eng.sched.idle and steps < max_steps:
        for o in eng.step():
            outs[o.rid] = o
        eng._events.clear()
        steps += 1
    assert eng.sched.idle, "engine did not drain"
    return outs


# ---------------------------------------------------------------- PagedCache
def _stamped_cache(n_layers=1, kv_heads=1, head_dim=2, page_size=4,
                   num_pages=16):
    pc = PagedCache(num_pages=num_pages, page_size=page_size,
                    n_layers=n_layers, kv_heads=kv_heads, head_dim=head_dim)
    # make every physical page's payload identifiable (pools are head-major:
    # (layers, Hkv, pages, page_size, D))
    n = pc.k_pages.shape[2]
    pc.k_pages = jnp.arange(n, dtype=pc.dtype).reshape(1, 1, n, 1, 1) * (
        jnp.ones_like(pc.k_pages))
    pc.v_pages = pc.k_pages * 2 + 1
    return pc


def test_offload_restore_round_trip_bit_identical():
    pc = _stamped_cache()
    toks = list(range(10))
    assert pc.alloc_seq(0, 10, tokens=toks, reserve=2)
    tab = list(pc.tables[0])
    want_k = np.asarray(pc.k_pages)[:, :, tab[:3]]
    want_v = np.asarray(pc.v_pages)[:, :, tab[:3]]
    free_before = len(pc.free_list)

    rec = pc.offload(0)
    assert rec.shared_pages == 0 and rec.n_payload_pages == 3
    assert rec.nbytes > 0 and pc.offloaded_bytes == rec.nbytes
    # everything released: row, pages (incl. reserve), length
    assert 0 not in pc.tables and 0 not in pc.rows
    assert len(pc.free_list) == free_before + len(tab)
    # host checkpoint bytes match the analytic model (K + V pools)
    from repro.serving.kv_quant import page_bytes
    assert rec.nbytes == page_bytes(
        pc.n_layers, pc.kv_heads, pc.head_dim, pc.page_size,
        dtype=pc.compute_dtype) * rec.n_payload_pages

    # scribble the pool; restore must rewrite the snapshot exactly
    pc.k_pages = jnp.zeros_like(pc.k_pages)
    pc.v_pages = jnp.zeros_like(pc.v_pages)
    r = pc.restore(0, toks, reserve=2)
    assert r is not None and r.restored_pages == 3
    assert r.hit_pages == 0 and r.snap_start_page == 0
    tab2 = pc.tables[0]
    assert pc.lengths[0] == 10
    np.testing.assert_array_equal(np.asarray(pc.k_pages)[:, :, tab2[:3]],
                                  want_k)
    np.testing.assert_array_equal(np.asarray(pc.v_pages)[:, :, tab2[:3]],
                                  want_v)
    assert not pc.offloaded and pc.offloaded_bytes == 0


def test_offload_releases_shared_prefix_without_copy():
    pc = _stamped_cache()
    toks = list(range(8)) + [99, 98]          # 2 full prefix pages + tail
    assert pc.alloc_seq(0, 10, tokens=toks)
    pc.register_prefix(0, toks)
    assert pc.alloc_seq(1, 10, tokens=toks)
    assert pc.prefix_hits[1] == 2
    shared_pages = pc.tables[1][:2]

    rec = pc.offload(1)
    # only the private tail page was copied; prefix pages just deref'd
    assert rec.shared_pages == 2 and rec.n_payload_pages == 1
    assert all(pc.refcount[p] == 1 for p in shared_pages)

    r = pc.restore(1, toks)
    # donor still live -> prefix re-shared through the hash index
    assert r.hit_pages == 2 and r.restored_pages == 1
    assert pc.tables[1][:2] == shared_pages
    assert all(pc.refcount[p] == 2 for p in shared_pages)


def test_restore_reports_gap_when_donor_evicted():
    pc = _stamped_cache()
    toks = list(range(8)) + [99, 98]
    assert pc.alloc_seq(0, 10, tokens=toks)
    pc.register_prefix(0, toks)
    assert pc.alloc_seq(1, 10, tokens=toks)
    rec = pc.offload(1)
    assert rec.shared_pages == 2
    pc.free_seq(0)                            # donor evicts: prefix gone
    r = pc.restore(1, toks)
    # pages [hit, snap_start) = [0, 2) hold nothing; caller must recompute
    assert r.hit_pages == 0 and r.snap_start_page == 2
    assert r.restored_pages == 1              # the private tail came back


def test_restore_returns_none_when_pool_exhausted():
    pc = _stamped_cache(num_pages=4)
    toks = list(range(10))
    assert pc.alloc_seq(0, 10, tokens=toks)
    rec = pc.offload(0)
    assert pc.alloc_seq(7, 16, tokens=list(range(100, 116)))  # eat the pool
    assert pc.restore(0, toks) is None        # no state change,
    assert pc.offloaded[0] is rec             # checkpoint kept for retry
    pc.free_seq(7)
    assert pc.restore(0, toks) is not None


def test_double_offload_and_drop():
    pc = _stamped_cache()
    assert pc.alloc_seq(0, 6, tokens=list(range(6)))
    pc.offload(0)
    with pytest.raises(ValueError, match="already offloaded"):
        pc.offload(0)
    assert pc.drop_offloaded(0) is not None
    assert pc.drop_offloaded(0) is None and not pc.offloaded


def test_prefix_hash_is_seeded_by_quant_mode():
    """Pages written under one KV-quant mode must never be served to a
    lookup under another: the prefix-hash chain is seeded by the quant
    config, so the same tokens give disjoint key sets (regression for the
    ROADMAP carry-over)."""
    from repro.serving.kv_quant import KVQuantConfig
    toks = list(range(16))
    args = dict(num_pages=8, page_size=4, n_layers=1, kv_heads=1, head_dim=2)
    fp = PagedCache(**args)
    fp2 = PagedCache(**args)
    q8 = PagedCache(kv_quant=KVQuantConfig(dtype="int8"), **args)
    bf = PagedCache(dtype=jnp.bfloat16, **args)
    assert fp._prefix_keys(toks) == fp2._prefix_keys(toks)  # deterministic
    assert not set(fp._prefix_keys(toks)) & set(q8._prefix_keys(toks))
    assert not set(fp._prefix_keys(toks)) & set(bf._prefix_keys(toks))
    assert not set(q8._prefix_keys(toks)) & set(bf._prefix_keys(toks))


# ------------------------------------------------- engine: priority preemption
@pytest.mark.parametrize("kvq", [None, "int8"], ids=["fp32", "int8"])
def test_preemption_round_trip_is_lossless(small_lm, kvq):
    """A high-priority arrival preempts the running low-priority request
    (pages offloaded to host); once capacity frees, the victim restores and
    finishes with greedy output identical to an unconstrained run."""
    cfg, model, params = small_lm
    pA, pB = _prompts(cfg, [24, 24], seed=3)

    roomy = EngineConfig(batch_slots=4, max_len=96, cache="paged",
                         page_size=8, eos_id=-1, kv_quant=kvq,
                         speculation=_SPEC)
    ref = Engine(model, params, roomy).generate(
        [pA, pB], max_new_tokens=12, sampling=GREEDY)
    ref = {o.rid: o.output for o in ref}

    tight = EngineConfig(batch_slots=4, max_len=96, cache="paged",
                         page_size=8, num_pages=6, eos_id=-1, kv_quant=kvq,
                         preemption=True, speculation=_SPEC)
    eng = Engine(model, params, tight)
    ra = eng.submit(pA, max_new_tokens=12, sampling=GREEDY, priority=0)
    for _ in range(4):                        # A decodes a few tokens first
        eng.step()
    rb = eng.submit(pB, max_new_tokens=12, sampling=GREEDY, priority=1)
    outs = _drain(eng)

    assert eng.stats.preemptions >= 1
    assert eng.stats.offloaded_pages > 0
    assert eng.stats.restored_pages > 0
    # host bytes match the analytic model (payload + scale pools)
    assert eng.stats.offloaded_bytes == MM.host_offload_bytes(
        cfg, eng.stats.offloaded_pages, 8, dtype=eng.cache_dtype,
        kv_quant=eng.kv_quant)
    assert outs[ra].output == ref[0], "victim's tokens changed"
    assert outs[rb].output == ref[1], "preemptor's tokens changed"
    assert outs[ra].finish_reason is FinishReason.LENGTH


def test_preemption_never_targets_equal_or_higher_priority(small_lm):
    cfg, model, params = small_lm
    pA, pB = _prompts(cfg, [24, 24], seed=4)
    conf = EngineConfig(batch_slots=4, max_len=96, cache="paged",
                        page_size=8, num_pages=6, eos_id=-1, preemption=True,
                        speculation=_SPEC)
    eng = Engine(model, params, conf)
    ra = eng.submit(pA, max_new_tokens=8, sampling=GREEDY, priority=1)
    for _ in range(2):
        eng.step()
    rb = eng.submit(pB, max_new_tokens=8, sampling=GREEDY, priority=1)
    outs = _drain(eng)
    assert eng.stats.preemptions == 0         # equal priority: defer, not evict
    assert eng.stats.deferred_admissions > 0
    assert {outs[ra].finish_reason, outs[rb].finish_reason} == {
        FinishReason.LENGTH}


def test_abort_while_preempted_drops_checkpoint(small_lm):
    cfg, model, params = small_lm
    pA, pB = _prompts(cfg, [24, 24], seed=5)
    conf = EngineConfig(batch_slots=4, max_len=96, cache="paged",
                        page_size=8, num_pages=6, eos_id=-1, preemption=True,
                        speculation=_SPEC)
    eng = Engine(model, params, conf)
    ra = eng.submit(pA, max_new_tokens=12, sampling=GREEDY, priority=0)
    for _ in range(4):
        eng.step()
    eng.submit(pB, max_new_tokens=12, sampling=GREEDY, priority=1)
    eng.step()                                # preempts A
    row = eng.sched.find_active(ra)
    assert row is None and ra in eng.pc.offloaded
    saved = next(r for r in eng.sched.waiting if r.rid == ra)
    assert saved.state is RequestState.PREEMPTED and saved.saved_output
    out = eng.abort(ra)
    assert out.finish_reason is FinishReason.ABORT
    assert out.output == saved.saved_output   # partial progress surfaced
    assert ra not in eng.pc.offloaded         # host checkpoint dropped
    _drain(eng)
    assert not eng.pc.offloaded and eng.pc.offloaded_bytes == 0


# ------------------------------------- engine: bounded admission + shedding
def test_bounded_admission_and_deadline_shed(small_lm):
    cfg, model, params = small_lm
    clk = ManualClock(100.0)
    conf = EngineConfig(batch_slots=1, max_len=64, cache="paged",
                        page_size=8, num_pages=5, eos_id=-1, max_queued=2,
                        default_queue_timeout_s=5.0, clock=clk,
                        preemption=False, speculation=_SPEC)
    eng = Engine(model, params, conf)
    ps = _prompts(cfg, [16] * 4, seed=6)
    r0 = eng.submit(ps[0], max_new_tokens=8, sampling=GREEDY)
    eng.step()                                # r0 occupies the only slot
    r1 = eng.submit(ps[1], max_new_tokens=8, sampling=GREEDY)
    r2 = eng.submit(ps[2], max_new_tokens=8, sampling=GREEDY,
                    queue_timeout_s=200.0)    # per-request override
    with pytest.raises(QueueFullError) as ei:
        eng.submit(ps[3], max_new_tokens=8, sampling=GREEDY)
    assert ei.value.retry_after_s > 0
    assert eng.stats.rejected_submits == 1

    clk.advance(10.0)                         # past r1's default deadline only
    outs = _drain(eng)
    assert outs[r1].finish_reason is FinishReason.SHED
    assert outs[r1].output == [] and outs[r1].ttft == 0.0
    assert outs[r0].finish_reason is FinishReason.LENGTH
    assert outs[r2].finish_reason is FinishReason.LENGTH  # override held
    assert eng.stats.shed_requests == 1


def test_preempted_request_is_never_shed(small_lm):
    """A preempted request already met its admission deadline and holds
    generated tokens — expiring the queue must not discard it."""
    cfg, model, params = small_lm
    clk = ManualClock(0.0)
    pA, pB = _prompts(cfg, [24, 24], seed=7)
    conf = EngineConfig(batch_slots=4, max_len=96, cache="paged",
                        page_size=8, num_pages=6, eos_id=-1,
                        default_queue_timeout_s=1.0, clock=clk,
                        preemption=True, speculation=_SPEC)
    eng = Engine(model, params, conf)
    ra = eng.submit(pA, max_new_tokens=12, sampling=GREEDY, priority=0)
    for _ in range(4):
        eng.step()
    rb = eng.submit(pB, max_new_tokens=12, sampling=GREEDY, priority=1)
    eng.step()                                # preempts A, far past deadline
    clk.advance(100.0)
    outs = _drain(eng)
    assert eng.stats.preemptions >= 1
    assert outs[ra].finish_reason is FinishReason.LENGTH
    assert outs[rb].finish_reason is FinishReason.LENGTH
    assert eng.stats.shed_requests == 0


# ----------------------------------------------------------- fault injection
def test_fault_injector_page_seizure_defers_then_recovers(small_lm):
    cfg, model, params = small_lm
    inj = F.FaultInjector().exhaust_pages_at(0, 999).release_pages_at(6)
    conf = EngineConfig(batch_slots=2, max_len=64, cache="paged",
                        page_size=8, num_pages=6, eos_id=-1, faults=inj,
                        preemption=False, speculation=_SPEC)
    eng = Engine(model, params, conf)
    rid = eng.submit(_prompts(cfg, [16], seed=8)[0], max_new_tokens=4,
                     sampling=GREEDY)
    for _ in range(5):                        # pool fully seized: no admission
        eng.step()
    assert eng.sched.find_active(rid) is None
    assert eng.stats.deferred_admissions >= 5
    assert inj.seized_pages == 6
    outs = _drain(eng)                        # release fires at step 6
    assert inj.seized_pages == 0
    assert outs[rid].finish_reason is FinishReason.LENGTH
    kinds = [k for _, k, _ in inj.log]
    assert kinds == ["exhaust_pages", "release_pages"]


def test_fault_injector_mid_stream_abort(small_lm):
    cfg, model, params = small_lm
    inj = F.FaultInjector().abort_at(4, 0)
    conf = EngineConfig(batch_slots=2, max_len=64, cache="paged",
                        page_size=8, eos_id=-1, faults=inj,
                        speculation=_SPEC)
    eng = Engine(model, params, conf)
    rid = eng.submit(_prompts(cfg, [16], seed=9)[0], max_new_tokens=32,
                     sampling=GREEDY)
    _drain(eng)
    (step_no, kind, out), = inj.log           # abort's RequestOutput is
    assert kind == "abort" and step_no == 4   # returned through the log
    assert out.rid == rid
    assert out.finish_reason is FinishReason.ABORT
    assert 0 < len(out.output) < 32           # stopped mid-decode
    # everything released
    assert not eng.pc.tables and len(eng.pc.free_list) == eng.pc.num_pages


# ------------------------------------------------------------------ heartbeat
def test_heartbeat_staleness_observable_from_monitor():
    clk = ManualClock(0.0)
    hb = Heartbeat(timeout_s=10.0, clock=clk.now)
    assert hb.check() and hb.missed == 0
    clk.advance(25.0)                         # worker silent for 2.5 windows
    assert not hb.check()                     # monitor sees it without beat()
    assert hb.missed == 2
    assert hb.stale_s == 25.0 and not hb.healthy
    assert not hb.check() and hb.missed == 2  # re-check doesn't double-charge
    clk.advance(10.0)
    assert not hb.check() and hb.missed == 3
    hb.beat()                                 # worker recovers
    assert hb.check() and hb.missed == 3 and hb.healthy


def test_heartbeat_late_beat_still_counts_missed():
    clk = ManualClock(0.0)
    hb = Heartbeat(timeout_s=10.0, clock=clk.now)
    clk.advance(15.0)
    hb.beat()                                 # no monitor ever looked
    assert hb.missed == 1


# ------------------------------------------------------------------ HTTP layer
def _post(port, body, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.headers), json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), json.loads(e.read())


@pytest.fixture()
def overload_server(small_lm):
    """Tiny engine whose page pool is seized up front: nothing ever admits,
    so HTTP requests exercise the queue-full / shed paths deterministically."""
    cfg, model, params = small_lm
    clk = ManualClock(0.0)
    inj = F.FaultInjector()
    eng = Engine(model, params, EngineConfig(
        batch_slots=1, max_len=64, cache="paged", page_size=8, num_pages=5,
        eos_id=-1, max_queued=1, clock=clk, preemption=False,
        speculation=_SPEC))
    inj.seize_pages(eng.pc, 5)
    srv = make_server(eng)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    yield cfg, srv, clk, inj, eng
    srv.shutdown()


def test_http_429_and_shed_503(overload_server):
    cfg, srv, clk, inj, eng = overload_server
    prompt = _prompts(cfg, [8], seed=10)[0]
    results = {}

    def queued_req():
        results["shed"] = _post(srv.port, {
            "prompt": prompt, "max_tokens": 4, "temperature": 0.0,
            "queue_timeout_s": 5.0})
    th = threading.Thread(target=queued_req, daemon=True)
    th.start()
    deadline = time.time() + 30
    while not eng.sched.waiting and time.time() < deadline:
        time.sleep(0.01)                      # wait until it is queued
    assert eng.sched.waiting

    # queue is at max_queued=1: next submit is rejected with Retry-After
    st, hdr, body = _post(srv.port, {"prompt": prompt, "max_tokens": 4,
                                     "temperature": 0.0})
    assert st == 429
    assert int(hdr["Retry-After"]) >= 1
    assert body["error"]["type"] == "overloaded_error"

    clk.advance(10.0)                         # expire the queued deadline
    th.join(timeout=60)
    assert not th.is_alive(), "shed request's HTTP response never arrived"
    st, hdr, body = results["shed"]
    assert st == 503
    assert "Retry-After" in hdr
    assert "shed" in body["error"]["message"]


def test_http_watchdog_fails_stalled_streams(small_lm):
    """A stalled engine step must not hang clients: the watchdog observes
    the missed heartbeat (through the injected clock) and terminates every
    in-flight request with FinishReason.STALL."""
    cfg, model, params = small_lm
    clk = ManualClock(0.0)
    inj = F.FaultInjector()

    def stall():                              # simulate a wedged step: jump
        clk.advance(99.0)                     # past the watchdog timeout and
        time.sleep(0.4)                       # hold the worker long enough
                                              # (real time) to be observed
    inj.stall_at(2, stall)
    eng = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, cache="paged", page_size=8, eos_id=-1,
        clock=clk, faults=inj, speculation=_SPEC))
    srv = make_server(eng, stall_timeout_s=10.0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        prompt = _prompts(cfg, [8], seed=11)[0]
        st, _hdr, body = _post(srv.port, {"prompt": prompt, "max_tokens": 40,
                                          "temperature": 0.0})
        assert st == 503
        assert "stall" in body["error"]["message"]
        assert srv.worker.stalled_requests >= 1
        assert srv.worker.heartbeat.missed >= 1
    finally:
        srv.shutdown()


# ------------------------------------------------------- overload accounting
def test_overload_counters_account_for_every_request(small_lm):
    """Synthetic overload burst: every submitted request is accounted for —
    finished, shed, or rejected — and the §14 counters are all exercised."""
    cfg, model, params = small_lm
    clk = ManualClock(0.0)
    conf = EngineConfig(batch_slots=4, max_len=96, cache="paged",
                        page_size=8, num_pages=7, eos_id=-1, max_queued=3,
                        default_queue_timeout_s=6.0, clock=clk,
                        preemption=True, speculation=_SPEC)
    eng = Engine(model, params, conf)
    prompts = _prompts(cfg, [24] * 6, seed=12)
    accepted, rejected = [], 0
    # low-priority occupant first, then a burst of mixed priorities
    accepted.append(eng.submit(prompts[0], max_new_tokens=10,
                               sampling=GREEDY, priority=0))
    for _ in range(3):
        eng.step()
        clk.advance(1.0)
    for i, p in enumerate(prompts[1:], start=1):
        try:
            accepted.append(eng.submit(
                p, max_new_tokens=10, sampling=GREEDY, priority=i % 2))
        except QueueFullError:
            rejected += 1
    outs = {}
    steps = 0
    while not eng.sched.idle and steps < 400:
        for o in eng.step():
            outs[o.rid] = o
        eng._events.clear()
        clk.advance(1.0)
        steps += 1
    s = eng.stats
    assert rejected == s.rejected_submits and rejected > 0
    assert set(outs) == set(accepted), "a request vanished"
    n_shed = sum(o.finish_reason is FinishReason.SHED for o in outs.values())
    n_done = sum(o.finish_reason is FinishReason.LENGTH
                 for o in outs.values())
    assert n_shed == s.shed_requests
    assert n_done + n_shed == len(accepted)
    assert s.preemptions > 0 and s.offloaded_pages > 0
    assert s.restored_pages > 0 and s.offloaded_bytes > 0
    assert s.deferred_admissions > 0
    assert not eng.pc.offloaded, "an offloaded checkpoint leaked"
    assert len(eng.pc.free_list) == eng.pc.num_pages, "pages leaked"
