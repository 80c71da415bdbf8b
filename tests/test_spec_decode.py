"""Speculative decoding tests (ISSUE 8 / DESIGN.md §16): n-gram prompt-lookup
and draft-model proposers, the batched verify/accept core, greedy
token-identity with plain decode across cache layouts and kv-quant modes
(property-tested), rollback losslessness under int8 per-page scales at the
PagedCache data path, mid-stream preemption of a speculating request, and
the spec counters surfaced through EngineStats / RequestOutput / metrics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st
from repro.configs import smoke_config
from repro.models import build_model
from repro.serving import metrics as M
from repro.serving.api import EngineConfig, FinishReason
from repro.serving.engine import Engine
from repro.serving.kv_cache import PagedCache
from repro.serving.kv_quant import KVQuantConfig
from repro.serving.sampler import SamplingParams, accept_speculative
from repro.serving.spec_decode import (MAX_SPEC_K, NGramSpeculator,
                                       SpecConfig, ngram_propose)

GREEDY = SamplingParams(greedy=True)


_LM: list = []


def _lm():
    """Module-memoized smoke model — shared by the fixture-based tests and
    the ``@given`` property tests (the hypothesis shim hides the wrapped
    signature from pytest, so those can't take fixtures)."""
    if not _LM:
        cfg = smoke_config("qwen3_4b")
        model = build_model(cfg)
        _LM.append((cfg, model, model.init(jax.random.key(0))))
    return _LM[0]


@pytest.fixture(scope="module")
def small_lm():
    return _lm()


def _prompts(cfg, seed=0):
    """One repetitive prompt (n-gram bait) and one random prompt."""
    rng = np.random.default_rng(seed)
    pat = rng.integers(2, cfg.vocab_size, size=4).tolist()
    return [pat * 3 + pat[:2],
            rng.integers(2, cfg.vocab_size, size=9).tolist()]


# ------------------------------------------------------------- ngram proposer
def test_ngram_propose_longest_suffix_match():
    ctx = [1, 2, 3, 4, 1, 2, 3, 4, 1, 2]
    # suffix 4-gram [3, 4, 1, 2] recurs at index 2 -> continuation [3, 4, 1]
    assert ngram_propose(ctx, k=3, ngram_max=4, ngram_min=1) == [3, 4, 1]
    # a request past the context end extrapolates the period
    assert ngram_propose(ctx, k=8, ngram_max=4, ngram_min=1) \
        == [3, 4, 1, 2, 3, 4, 1, 2]


def test_ngram_propose_extrapolates_constant_run():
    ctx = [9, 9, 9, 9, 9, 9]
    assert ngram_propose(ctx, k=4, ngram_max=4, ngram_min=1) == [9, 9, 9, 9]


def test_ngram_propose_prefers_most_recent_occurrence():
    ctx = [7, 1, 2, 9, 1, 2, 8, 1, 2]
    assert ngram_propose(ctx, k=1, ngram_max=4, ngram_min=1) == [8]


def test_ngram_propose_no_match_and_degenerate():
    assert ngram_propose([1, 2, 3, 4, 5], k=4, ngram_max=4, ngram_min=1) == []
    assert ngram_propose([1, 2, 1, 2], k=0, ngram_max=4, ngram_min=1) == []
    assert ngram_propose([5], k=4, ngram_max=4, ngram_min=1) == []


def test_ngram_speculator_respects_caps():
    spec = NGramSpeculator(SpecConfig(method="ngram", k=4), batch_rows=3)
    ctx = [1, 2, 3, 1, 2, 3, 1, 2]
    prop = spec.propose({0: (10, ctx, 4), 1: (11, ctx, 1), 2: (12, ctx, 0)},
                        all_greedy=True)
    assert prop.draft_lens.tolist() == [4, 1, 0]
    assert prop.drafts[0].tolist() == [3, 1, 2, 3]
    assert prop.drafts[1, 0] == 3


# ------------------------------------------------------------ accept (greedy)
def _onehot_logits(targets, v=16):
    """(B, S) target ids -> (B, S, V) logits whose argmax is ``targets``."""
    t = np.asarray(targets)
    out = np.full(t.shape + (v,), -5.0, np.float32)
    np.put_along_axis(out, t[..., None], 5.0, axis=-1)
    return jnp.asarray(out)


def test_accept_speculative_greedy_prefix():
    # row 0: drafts match targets at positions 0,1, mismatch at 2
    # row 1: zero drafts proposed -> plain decode step (bonus only)
    logits = _onehot_logits([[3, 4, 9, 6], [7, 1, 1, 1]])
    drafts = jnp.asarray([[3, 4, 5], [2, 2, 2]], jnp.int32)
    lens = jnp.asarray([3, 0], jnp.int32)
    n_acc, emitted = accept_speculative(logits, drafts, lens, all_greedy=True)
    assert n_acc.tolist() == [2, 0]
    assert emitted[0, :4].tolist() == [3, 4, 9, 0]   # d0 d1 bonus, zero tail
    assert emitted[1, :2].tolist() == [7, 0]


def test_accept_speculative_greedy_full_accept_takes_bonus():
    logits = _onehot_logits([[3, 4, 5, 6]])
    drafts = jnp.asarray([[3, 4, 5]], jnp.int32)
    lens = jnp.asarray([3], jnp.int32)
    n_acc, emitted = accept_speculative(logits, drafts, lens, all_greedy=True)
    assert n_acc.tolist() == [3]
    assert emitted[0].tolist() == [3, 4, 5, 6]       # all drafts + bonus


def test_accept_speculative_draft_lens_mask():
    """Positions past draft_lens never count as accepted even if they would
    match the target argmax."""
    logits = _onehot_logits([[3, 4, 5, 6]])
    drafts = jnp.asarray([[3, 4, 5]], jnp.int32)
    lens = jnp.asarray([1], jnp.int32)
    n_acc, emitted = accept_speculative(logits, drafts, lens, all_greedy=True)
    assert n_acc.tolist() == [1]
    assert emitted[0, :3].tolist() == [3, 4, 0]


# --------------------------------------------- engine: greedy token identity
_ENGINES: dict = {}


def _engine_pair(model, params, layout, k, kvq=None):
    """Plain + speculating engine pair, cached across property examples so
    each (layout, k) compiles once."""
    key = (layout, k, kvq)
    if key not in _ENGINES:
        base = dict(batch_slots=2, max_len=64, eos_id=-1, cache=layout,
                    kv_quant=kvq)
        _ENGINES[key] = (
            Engine(model, params, EngineConfig(**base)),
            Engine(model, params, EngineConfig(
                **base, speculation=SpecConfig(method="ngram", k=k))))
    return _ENGINES[key]


def _check_greedy_identity(layout, seed, k):
    cfg, model, params = _lm()
    plain, spec = _engine_pair(model, params, layout, k)
    prompts = _prompts(cfg, seed=seed)
    ref = plain.generate(prompts, max_new_tokens=8, sampling=GREEDY,
                         ignore_eos=True)
    out = spec.generate(prompts, max_new_tokens=8, sampling=GREEDY,
                        ignore_eos=True)
    for r, o in zip(ref, out):
        assert r.output == o.output, (layout, seed, k)
        assert len(o.output) == 8 and o.finish_reason is FinishReason.LENGTH


@settings(max_examples=4)
@given(st.integers(min_value=0, max_value=7),
       st.integers(min_value=1, max_value=4))
def test_spec_greedy_identical_to_plain_slot(seed, k):
    """The tentpole invariant: greedy speculative decode is token-for-token
    identical to plain decode — any seed, any draft length."""
    _check_greedy_identity("slot", seed, k)


@settings(max_examples=4)
@given(st.integers(min_value=0, max_value=7),
       st.integers(min_value=1, max_value=4))
def test_spec_greedy_identical_to_plain_paged(seed, k):
    _check_greedy_identity("paged", seed, k)


@pytest.mark.parametrize("layout", ["slot", "paged"])
@pytest.mark.parametrize("kvq", ["bf16", "int8"])
def test_spec_greedy_identical_under_kv_quant(small_lm, layout, kvq):
    cfg, model, params = small_lm
    plain, spec = _engine_pair(model, params, layout, 3, kvq=kvq)
    prompts = _prompts(cfg, seed=1)
    ref = plain.generate(prompts, max_new_tokens=8, sampling=GREEDY,
                         ignore_eos=True)
    out = spec.generate(prompts, max_new_tokens=8, sampling=GREEDY,
                        ignore_eos=True)
    for r, o in zip(ref, out):
        assert r.output == o.output, (layout, kvq)


def test_spec_never_exceeds_max_new(small_lm):
    """A full acceptance plus bonus on the last verify span must land
    exactly on max_new_tokens, never past it (per-row draft caps)."""
    cfg, model, params = small_lm
    _, spec = _engine_pair(model, params, "paged", 4)
    prompts = _prompts(cfg, seed=2)
    for mn in (1, 2, 5):
        outs = spec.generate(prompts, max_new_tokens=mn, sampling=GREEDY,
                             ignore_eos=True)
        assert all(len(o.output) == mn for o in outs)


# ----------------------------------------------------- engine: draft proposer
def test_draft_speculator_self_draft_full_acceptance(small_lm):
    """Draft == target: every draft accepts, so each verify step commits
    k + 1 tokens and the engine takes ~1/(k+1) the steps of plain decode."""
    cfg, model, params = small_lm
    prompts = _prompts(cfg, seed=3)
    plain = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, eos_id=-1, cache="paged"))
    ref = plain.generate(prompts, max_new_tokens=8, sampling=GREEDY,
                         ignore_eos=True)
    spec = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, eos_id=-1, cache="paged",
        speculation=SpecConfig(method="draft", k=3, draft_model=model,
                               draft_params=params)))
    out = spec.generate(prompts, max_new_tokens=8, sampling=GREEDY,
                        ignore_eos=True)
    for r, o in zip(ref, out):
        assert r.output == o.output
    assert spec.stats.acceptance_rate == 1.0
    assert spec.stats.steps < plain.stats.steps
    assert spec.stats.tokens_per_step > plain.stats.tokens_per_step


def test_draft_speculator_bad_draft_still_identical(small_lm):
    """Correctness must not depend on draft quality: a randomly-initialized
    draft model (low acceptance) still yields the plain greedy tokens."""
    cfg, model, params = small_lm
    dmodel = build_model(cfg)
    dparams = dmodel.init(jax.random.key(99))
    prompts = _prompts(cfg, seed=4)
    plain = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, eos_id=-1, cache="paged"))
    ref = plain.generate(prompts, max_new_tokens=8, sampling=GREEDY,
                         ignore_eos=True)
    spec = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, eos_id=-1, cache="paged",
        speculation=SpecConfig(method="draft", k=3, draft_model=dmodel,
                               draft_params=dparams)))
    out = spec.generate(prompts, max_new_tokens=8, sampling=GREEDY,
                        ignore_eos=True)
    for r, o in zip(ref, out):
        assert r.output == o.output
    assert spec.stats.spec_proposed > 0


def test_draft_vocab_mismatch_raises(small_lm):
    import dataclasses as dc
    cfg, model, params = small_lm
    other = dc.replace(smoke_config("qwen3_4b"),
                       vocab_size=cfg.vocab_size * 2)
    dmodel = build_model(other)
    dparams = dmodel.init(jax.random.key(1))
    with pytest.raises(ValueError, match="vocab"):
        Engine(model, params, EngineConfig(
            batch_slots=2, max_len=64, eos_id=-1,
            speculation=SpecConfig(method="draft", k=2, draft_model=dmodel,
                                   draft_params=dparams)))


# ------------------------------------------- engine: preemption mid-stream
def test_preemption_of_speculating_request_is_lossless(small_lm):
    """A speculating victim preempted mid-stream (pages offloaded) restores
    and finishes with greedy output identical to an unconstrained plain
    run — speculator state is invalidated and rebuilt transparently."""
    cfg, model, params = small_lm
    rng = np.random.default_rng(5)
    pat = rng.integers(2, cfg.vocab_size, size=4).tolist()
    pA = pat * 5 + pat[:2]                       # long + repetitive
    pB = rng.integers(2, cfg.vocab_size, size=24).tolist()

    roomy = EngineConfig(batch_slots=4, max_len=96, cache="paged",
                         page_size=8, eos_id=-1)
    ref = Engine(model, params, roomy).generate(
        [pA, pB], max_new_tokens=12, sampling=GREEDY, ignore_eos=True)
    ref = {o.rid: o.output for o in ref}

    tight = EngineConfig(batch_slots=4, max_len=96, cache="paged",
                         page_size=8, num_pages=6, eos_id=-1,
                         preemption=True,
                         speculation=SpecConfig(method="ngram", k=3))
    eng = Engine(model, params, tight)
    ra = eng.submit(pA, max_new_tokens=12, sampling=GREEDY, priority=0,
                    ignore_eos=True)
    for _ in range(4):                           # A speculates a few steps
        eng.step()
    rb = eng.submit(pB, max_new_tokens=12, sampling=GREEDY, priority=1,
                    ignore_eos=True)
    outs = {}
    steps = 0
    while not eng.sched.idle and steps < 300:
        for o in eng.step():
            outs[o.rid] = o
        eng._events.clear()
        steps += 1
    assert eng.sched.idle
    assert eng.stats.preemptions >= 1
    assert outs[ra].output == ref[0], "victim's tokens changed"
    assert outs[rb].output == ref[1], "preemptor's tokens changed"


# --------------------------------------------------- engine: sampled batches
def test_spec_sampled_batches_run(small_lm):
    """Non-greedy speculation: correct lengths, sane counters, and mixed
    greedy/sampled batches share one verify trace."""
    cfg, model, params = small_lm
    prompts = _prompts(cfg, seed=6)
    sp = SamplingParams(temperature=0.8, top_k=50, top_p=0.95)
    for method, kw in (("ngram", {}),
                       ("draft", dict(draft_model=model,
                                      draft_params=params))):
        eng = Engine(model, params, EngineConfig(
            batch_slots=2, max_len=64, eos_id=-1, cache="paged",
            speculation=SpecConfig(method=method, k=3, **kw)))
        outs = eng.generate(prompts, max_new_tokens=8, sampling=sp,
                            ignore_eos=True)
        assert all(len(o.output) == 8 for o in outs)
        assert eng.stats.spec_accepted <= eng.stats.spec_proposed
        mixed = eng.generate(prompts, max_new_tokens=6,
                             sampling=[GREEDY, sp], ignore_eos=True)
        assert all(len(o.output) == 6 for o in mixed)


def test_draft_rejection_sampling_exact_on_self_draft(small_lm):
    """With q == p the rejection test ``u * q(d) <= p(d)`` accepts every
    draft: sampled self-draft speculation must show acceptance rate 1."""
    cfg, model, params = small_lm
    eng = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, eos_id=-1, cache="paged",
        speculation=SpecConfig(method="draft", k=3, draft_model=model,
                               draft_params=params)))
    outs = eng.generate(_prompts(cfg, seed=7), max_new_tokens=8,
                        sampling=SamplingParams(temperature=0.7),
                        ignore_eos=True)
    assert all(len(o.output) == 8 for o in outs)
    assert eng.stats.acceptance_rate == 1.0


# ------------------------------------------------ counters / config plumbing
def test_spec_counters_and_metrics_surface(small_lm):
    cfg, model, params = small_lm
    eng = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=64, eos_id=-1, cache="paged",
        speculation=SpecConfig(method="draft", k=3, draft_model=model,
                               draft_params=params)))
    outs = eng.generate(_prompts(cfg, seed=8), max_new_tokens=8,
                        sampling=GREEDY, ignore_eos=True)
    s = eng.stats
    assert s.spec_proposed > 0 and s.spec_accepted > 0
    assert s.spec_verify_steps > 0
    assert s.tokens_per_step > 1.0
    assert "spec_proposed" in repr(s) and "spec_accepted" in repr(s)
    # per-request accounting survives into RequestOutput
    for o in outs:
        assert o.spec_proposed >= o.spec_accepted > 0
        assert 0.0 < o.acceptance_rate <= 1.0
    assert sum(o.spec_proposed for o in outs) == s.spec_proposed
    assert sum(o.spec_accepted for o in outs) == s.spec_accepted
    # Prometheus exposition carries the counters and the accept histogram
    parsed = M.parse_prometheus_text(eng.metrics.registry.expose())
    for fam, attr in (("engine_spec_proposed_total", "spec_proposed"),
                      ("engine_spec_accepted_total", "spec_accepted"),
                      ("engine_spec_verify_steps_total",
                       "spec_verify_steps")):
        (_, _, value), = parsed[fam]["samples"]
        assert value == getattr(s, attr)
    assert parsed["engine_spec_accept_length"]["type"] == "histogram"


def test_spec_config_validation():
    with pytest.raises(ValueError, match="method"):
        SpecConfig(method="oracle")
    with pytest.raises(ValueError, match="k must be"):
        SpecConfig(k=0)
    with pytest.raises(ValueError, match="k must be"):
        SpecConfig(k=MAX_SPEC_K + 1)
    with pytest.raises(ValueError, match="ngram_min"):
        SpecConfig(ngram_min=3, ngram_max=2)
    with pytest.raises(ValueError, match="draft"):
        SpecConfig(method="draft")


def test_engine_config_speculation_validation():
    with pytest.raises(ValueError, match="SpecConfig"):
        EngineConfig(batch_slots=2, max_len=64, speculation="ngram")
    with pytest.raises(ValueError, match="max_len"):
        EngineConfig(batch_slots=2, max_len=8,
                     speculation=SpecConfig(method="ngram", k=8))


# ------------------------------------- PagedCache: int8-per-page rollback
def _rand_kv(rng, n_layers, n, heads, dim):
    k = jnp.asarray(rng.normal(size=(n_layers, n, heads, dim)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(n_layers, n, heads, dim)), jnp.float32)
    return k, v


def _seq_bytes(pc, seq_id):
    """Raw payload (+scale) bytes of a sequence's pages, valid extent only
    implicitly included — page granularity stores whole-page state."""
    idx = np.asarray(pc.tables[seq_id], np.int32)
    out = [np.asarray(pc.k_pages[:, :, idx]),
           np.asarray(pc.v_pages[:, :, idx])]
    if pc.k_scales is not None:
        out += [np.asarray(pc.k_scales[:, :, idx]),
                np.asarray(pc.v_scales[:, :, idx])]
    return out


@pytest.mark.parametrize("kvq", [
    None,
    KVQuantConfig(dtype="int8", granularity="token"),
    KVQuantConfig(dtype="int8", granularity="page"),
], ids=["fp32", "int8-token", "int8-page"])
def test_spec_rollback_roundtrips_losslessly(kvq):
    """The rollback contract (DESIGN.md §16): snapshot -> speculative write
    of k tokens -> truncate -> re-append the accepted prefix must produce
    bytes identical to having only ever written the accepted prefix.  This
    is the int8-per-*page* coverage — appends requantize whole pages, so
    only the snapshot's tail-payload restore makes the round trip exact
    (the engine itself runs per-token scales; per-page is data-path-only)."""
    rng = np.random.default_rng(11)
    mk = lambda: PagedCache(num_pages=6, page_size=8, n_layers=2,
                            kv_heads=2, head_dim=4, kv_quant=kvq)
    a, b = mk(), mk()
    assert a._hash_seed == b._hash_seed

    base_k, base_v = _rand_kv(rng, 2, 5, 2, 4)       # 5-token prompt
    spec_k, spec_v = _rand_kv(rng, 2, 4, 2, 4)       # 4 speculative tokens
    n_accept = 2

    for pc in (a, b):
        assert pc.alloc_seq(0, 5)
        pc.write_prefill(0, 0, base_k, base_v)

    # cache A speculates 4 tokens then rolls back to 2 accepted
    snap = a.spec_snapshot(0)
    assert a.extend_seq(0, 4)
    a.write_prefill(0, 5, spec_k, spec_v)
    a.truncate_seq(0, snap)
    assert a.lengths[0] == 5
    assert a.extend_seq(0, n_accept)
    a.write_prefill(0, 5, spec_k[:, :n_accept], spec_v[:, :n_accept])

    # cache B only ever writes the accepted prefix
    assert b.extend_seq(0, n_accept)
    b.write_prefill(0, 5, spec_k[:, :n_accept], spec_v[:, :n_accept])

    for got, want in zip(_seq_bytes(a, 0), _seq_bytes(b, 0)):
        np.testing.assert_array_equal(got, want)
    for layer in range(2):
        ka, va = a.gather_kv(0, layer)
        kb, vb = b.gather_kv(0, layer)
        np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
    # rollback freed the page the speculative span had grown into
    assert len(a.tables[0]) == len(b.tables[0])
    assert sorted(a.free_list) == sorted(b.free_list)


def test_truncate_seq_refuses_shorter_than_snapshot():
    pc = PagedCache(num_pages=4, page_size=8, n_layers=1, kv_heads=1,
                    head_dim=4)
    assert pc.alloc_seq(0, 5)
    snap = pc.spec_snapshot(0)
    pc.lengths[0] = 3
    with pytest.raises(ValueError, match="shorter"):
        pc.truncate_seq(0, snap)
