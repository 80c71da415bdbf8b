"""Chunked paged-prefill kernel + prefill edge-case fixes (ISSUE 5).

Kernel-vs-ref parity for bf16/fp32 and both int8 scale granularities across
aligned and ragged ``write_lens``, kernel-on-hot-path dispatch (and the
gather oracle staying *off* it), greedy engine parity slot == paged ==
int8-paged on the prefix workload, the full-prefix-hit admission backoff,
null-page routing of overrun writes, the slot bucket-padding capacity fix,
``bucket_len`` edge cases, and the prefill peak-bytes memory model.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels import paged_attention as PA
from repro.kernels.paged_attention import paged_prefill
from repro.kernels.ref import flash_attention_ref, paged_prefill_ref
from repro.models import attention as A
from repro.models import build_model
from repro.perf import memory_model as MM
from repro.serving import kv_cache as KV
from repro.serving.api import EngineConfig
from repro.serving.engine import Engine
from repro.serving.kv_quant import KVQuantConfig, quantize
from repro.serving.scheduler import bucket_len


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config("qwen3_4b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# -------------------------------------------------------------------- kernel
def _random_prefill(rng, b, s, h, hkv, d, pages, ps, maxp, starts, wlens):
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    # head-major pools (Hkv, P, page_size, D)
    kp = jnp.asarray(rng.normal(size=(hkv, pages, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(hkv, pages, ps, d)), jnp.float32)
    bt = jnp.asarray((rng.permutation(pages - 1) + 1)[:b * maxp]
                     .reshape(b, maxp), jnp.int32)
    st = jnp.asarray(starts, jnp.int32)
    lens = st + jnp.asarray(wlens, jnp.int32)
    return q, kp, vp, bt, st, lens


@pytest.mark.parametrize("granularity", [None, "token", "page"])
@pytest.mark.parametrize("ragged", [False, True])
@pytest.mark.parametrize("h,hkv", [(8, 2), (4, 4)])
def test_paged_prefill_matches_ref(granularity, ragged, h, hkv):
    """Kernel vs gather oracle over (dtype-family) x (aligned, ragged
    write_lens) x GQA/MHA, including prefix-offset query positions."""
    rng = np.random.default_rng(0)
    b, s, d, pages, ps, maxp = 3, 8, 32, 40, 4, 7
    wlens = [5, 8, 3] if ragged else [s, s, s]
    q, kp, vp, bt, st, lens = _random_prefill(
        rng, b, s, h, hkv, d, pages, ps, maxp, [0, 4, 12], wlens)
    ks = vs = None
    if granularity is not None:
        axes = (-1,) if granularity == "token" else (2, 3)
        kp, ks = quantize(kp, axes=axes)
        vp, vs = quantize(vp, axes=axes)
    out = paged_prefill(q, kp, vp, bt, st, lens, k_scales=ks, v_scales=vs,
                        q_chunk=4)
    ref = paged_prefill_ref(q, kp, vp, bt, st, lens, k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# Live-block cases: 16-token pages make 8-page blocks of 128 keys, and a
# 24-page table holds 384 keys, so rows span several blocks of the loop.
PS, MAXP = 16, 24
# name: (seq_start, write_lens, S, q_chunk)
LIVE_CASES = {
    # rows end mid-page and mid-block; everything past them is NaN
    "stale-nan": ([0, 100, 250], [32, 20, 7], 32, 32),
    # one row prefills, the others decode: their later q-chunks are all pad
    "decode-rows": ([40, 130, 300, 7], [64, 1, 1, 1], 64, 32),
    # lengths ending mid-page (27) and mid-block (182), one past the table
    "table-end": ([0, 150, 370], [27, 32, 32], 32, 32),
    # prefix hits whose first chunk straddles a block boundary (128, 256)
    "prefix-straddle": ([100, 120, 250, 5], [32, 32, 16, 32], 32, 32),
}


def _live_case(case, pool, h=4, hkv=2, d=32, pages=100, seed=6):
    """Inputs of one live-block case on an fp32, bf16 or int8 pool, plus
    the oracle computed on the clean pools.  In ``stale-nan`` every pool
    entry outside the rows' live keys is then made NaN (on int8 pools the
    scales, where a whole page or token is dead) — the kernel must never
    let it reach an output."""
    starts, wlens, s, q_chunk = LIVE_CASES[case]
    rng = np.random.default_rng(seed)
    q, kp, vp, bt, st, lens = _random_prefill(
        rng, len(starts), s, h, hkv, d, pages, PS, MAXP, starts, wlens)
    ks = vs = None
    if pool == "bf16":
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    elif pool != "fp32":
        axes = (-1,) if pool == "int8-token" else (2, 3)
        kp, ks = quantize(kp, axes=axes)
        vp, vs = quantize(vp, axes=axes)
    ref = paged_prefill_ref(q, kp, vp, bt, st, lens, k_scales=ks, v_scales=vs)
    if case == "stale-nan":
        live = np.zeros((pages, PS), bool)       # (page, slot) holding a key
        for row, n in zip(np.asarray(bt), np.asarray(lens)):
            for key in range(min(int(n), MAXP * PS)):
                live[row[key // PS], key % PS] = True
        if pool in ("fp32", "bf16"):
            kp = jnp.where(live[None, :, :, None], kp, jnp.nan).astype(kp.dtype)
            vp = jnp.where(live[None, :, :, None], vp, jnp.nan).astype(vp.dtype)
        else:
            live_scale = live.any(axis=1) if ks.ndim == 2 else live
            ks = jnp.where(live_scale[None], ks, jnp.nan)
            vs = jnp.where(live_scale[None], vs, jnp.nan)
    return (q, kp, vp, bt, st, lens), dict(k_scales=ks, v_scales=vs,
                                           q_chunk=q_chunk), ref


@pytest.mark.parametrize("pool", ["fp32", "bf16", "int8-token", "int8-page"])
@pytest.mark.parametrize("case", list(LIVE_CASES))
def test_paged_prefill_live_blocks_match_ref(case, pool):
    """The block loop over each chunk's live pages agrees with the gather
    oracle across multi-block rows, pad chunks, table overrun, prefix
    starts straddling a block boundary and NaN in every dead pool entry
    (outputs stay finite), on float pools and both int8 granularities."""
    args, kw, ref = _live_case(case, pool)
    out = np.asarray(paged_prefill(*args, **kw))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("pool", ["fp32", "int8-token"])
def test_paged_prefill_fetches_only_live_pages(monkeypatch, pool):
    """The grid is (rows, kv heads, q-chunks), with no page axis, and a
    program starts one DMA per pool for each page of the keys its chunk can
    see and none beyond: a chunk wholly past its row's length (the decode
    rows' pad chunks) fetches nothing.  DMA starts are counted as the
    interpreter runs them."""
    starts, wlens, s, q_chunk = LIVE_CASES["decode-rows"]
    hkv = 2
    args, kw, ref = _live_case("decode-rows", pool, hkv=hkv, d=48)
    started = []
    make_copy = PA.pltpu.make_async_copy

    class CountedCopy:
        def __init__(self, *a):
            self.copy = make_copy(*a)

        def start(self):
            jax.debug.callback(lambda: started.append(1))
            self.copy.start()

        def wait(self):
            self.copy.wait()

    monkeypatch.setattr(PA.pltpu, "make_async_copy", CountedCopy)
    PA.paged_prefill.clear_cache()
    fn = jax.jit(lambda *a: paged_prefill(*a, **kw))
    out = jax.block_until_ready(fn(*args))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    pages = 0
    for start, n in zip(starts, np.asarray(args[5])):
        for c in range(-(-s // q_chunk)):
            q0 = start + c * q_chunk
            if q0 < n:                           # else a chunk of pad queries
                pages += -(-min(n, q0 + q_chunk, MAXP * PS) // PS)
    per_page = 2 if pool == "fp32" else 4        # K, V (and their scales)
    assert len(started) == hkv * pages * per_page
    eqns = list(jax.make_jaxpr(fn)(*args).eqns)
    grids = []
    while eqns:                                  # search nested jaxprs
        e = eqns.pop()
        if e.primitive.name == "pallas_call":
            grids.append(e.params["grid_mapping"].grid)
        eqns += [x for p in e.params.values() if hasattr(p, "eqns")
                 for x in p.eqns]
    assert grids == [(len(starts), hkv, -(-s // q_chunk))]


def test_paged_prefill_chunking_invariant():
    """Output is independent of the query chunking, including a chunk that
    does not divide S (internal padding path)."""
    rng = np.random.default_rng(1)
    q, kp, vp, bt, st, lens = _random_prefill(
        rng, 2, 8, 4, 2, 16, 24, 4, 5, [0, 4], [8, 6])
    outs = [paged_prefill(q, kp, vp, bt, st, lens, q_chunk=c)
            for c in (2, 3, 8, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=2e-5, atol=2e-5)


def test_paged_prefill_matches_contiguous_flash_ref():
    """A cold full prefill through the block table agrees with plain causal
    attention over the same KV laid out contiguously."""
    rng = np.random.default_rng(2)
    b, s, h, hkv, d, ps, maxp = 2, 8, 4, 2, 16, 4, 2
    kc = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    kp = jnp.zeros((hkv, 5, ps, d), jnp.float32)
    vp = jnp.zeros((hkv, 5, ps, d), jnp.float32)
    for i in range(b):
        for lp in range(maxp):
            span = slice(lp * ps, (lp + 1) * ps)
            kp = kp.at[:, bt[i, lp]].set(kc[i, span].transpose(1, 0, 2))
            vp = vp.at[:, bt[i, lp]].set(vc[i, span].transpose(1, 0, 2))
    st = jnp.zeros((b,), jnp.int32)
    lens = jnp.full((b,), s, jnp.int32)
    out = paged_prefill(q, kp, vp, bt, st, lens, q_chunk=4)
    ref = flash_attention_ref(q, kc, vc, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_prefill_requires_both_scales():
    rng = np.random.default_rng(3)
    q, kp, vp, bt, st, lens = _random_prefill(
        rng, 1, 4, 2, 1, 8, 8, 4, 2, [0], [4])
    _, ks = quantize(kp)
    with pytest.raises(ValueError, match="both"):
        paged_prefill(q, kp, vp, bt, st, lens, k_scales=ks)


# ------------------------------------------------------------ write masking
def test_overrun_write_routes_to_null_page(small_lm):
    """A sequence running past its block table must not alias its write into
    the last table column's live page: the overflow position lands in the
    null page and every neighbor page is bit-identical afterwards."""
    cfg, model, params = small_lm
    p = A.gqa_init(jax.random.key(1), cfg)
    ps, maxp, pages = 4, 2, 5
    rng = np.random.default_rng(4)
    shape = (cfg.num_kv_heads, pages + 1, ps, cfg.head_dim)
    cache = {"k_pages": jnp.asarray(rng.normal(size=shape), jnp.float32),
             "v_pages": jnp.asarray(rng.normal(size=shape), jnp.float32)}
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    # row 0 sits exactly at capacity maxp*ps: its decode write has no cell
    seq_lens = jnp.asarray([maxp * ps, 1], jnp.int32)
    x = jnp.asarray(rng.normal(size=(2, 1, cfg.d_model)), cfg.dtype)
    _, nc = A.gqa_apply(p, x, cfg=cfg, cache=cache, seq_lens=seq_lens,
                        block_tables=bt)
    for page in (1, 2, 4, 5):      # row 0's own pages + unowned neighbors
        np.testing.assert_array_equal(
            np.asarray(nc["k_pages"][:, page]),
            np.asarray(cache["k_pages"][:, page]),
            err_msg=f"page {page} corrupted by overrun write")
    # the overrun write went somewhere: the null page absorbed it
    assert not np.array_equal(np.asarray(nc["k_pages"][:, 0]),
                              np.asarray(cache["k_pages"][:, 0]))
    # row 1 (in range) still wrote normally: page 3, offset 1
    assert not np.array_equal(np.asarray(nc["k_pages"][:, 3]),
                              np.asarray(cache["k_pages"][:, 3]))


def test_slot_bucket_padding_never_writes_past_capacity(small_lm):
    """Regression (ISSUE 5): a prefill bucket overhanging the slot capacity
    used to clamp every padded position's write into cell cap-1.  Padded
    writes are dropped now — every cell past the true length stays
    bit-identical (zero), cap-1 included — and the last-real-token logits
    match an exact-length prefill."""
    cfg, model, params = small_lm
    cap, true_len, blen = 8, 5, 16          # bucket overhangs capacity
    toks = np.zeros((1, blen), np.int32)
    toks[0, :true_len] = [5, 6, 7, 8, 9]
    cache = model.init_cache(1, cap, dtype=jnp.float32)
    logits, cache2, _ = model.prefill(
        params, {"tokens": jnp.asarray(toks)}, cache,
        jnp.zeros((1,), jnp.int32),
        true_lengths=jnp.asarray([true_len], jnp.int32))
    k = np.asarray(cache2["group0"]["attn"]["k"])
    assert np.all(k[:, :, true_len:] == 0.0), "padding leaked into the cache"
    assert np.any(k[:, :, :true_len] != 0.0)
    exact, _, _ = model.prefill(
        params, {"tokens": jnp.asarray(toks[:, :true_len])},
        model.init_cache(1, cap, dtype=jnp.float32),
        jnp.zeros((1,), jnp.int32),
        true_lengths=jnp.asarray([true_len], jnp.int32))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(exact),
                               rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------------ scheduler
def test_bucket_len_edges():
    assert bucket_len(0) == 0               # was 32: a pure-padding prefill
    assert bucket_len(-3) == 0
    assert bucket_len(1) == 32
    assert bucket_len(32) == 32             # exact bucket
    assert bucket_len(33) == 64
    assert bucket_len(4096) == 4096
    assert bucket_len(4097) == 8192         # >4096 tail: 4096 multiples
    assert bucket_len(12289) == 16384


# --------------------------------------------------------------------- engine
def test_engine_paged_prefill_kernel_on_hot_path(small_lm, monkeypatch):
    """The paged prefill path must run the chunked Pallas kernel; the
    gather-materializing oracle must never be reachable from the engine."""
    cfg, model, params = small_lm
    calls = {"n": 0}
    real = A.PA.paged_prefill

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    def boom(*a, **k):
        raise AssertionError("gather oracle reached from the engine hot path")

    monkeypatch.setattr(A.PA, "paged_prefill", counting)
    monkeypatch.setattr(A.KR, "paged_prefill_ref", boom)
    eng = Engine(model, params, EngineConfig(batch_slots=2, max_len=32,
                                             eos_id=-1, cache="paged",
                                             page_size=4))
    eng.submit([5, 6, 7, 8, 9], max_new_tokens=2)
    done = eng.run()
    assert len(done) == 1 and len(done[0].output) == 2
    assert calls["n"] > 0                  # kernel traced on prefill


def test_engine_greedy_parity_slot_paged_int8(small_lm):
    """Greedy outputs are token-identical across slot, paged and int8-paged
    engines on the mixed-length prefix workload (suffix prefill included)."""
    cfg, model, params = small_lm
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist()
               for n in (7, 13, 3)]
    base = rng.integers(2, cfg.vocab_size, size=8).tolist()  # 2 full pages
    prompts.append(base + rng.integers(2, cfg.vocab_size, size=5).tolist())
    prompts.append(base + rng.integers(2, cfg.vocab_size, size=3).tolist())
    outs = {}
    for name, conf in (
            ("slot", EngineConfig(batch_slots=3, max_len=64, eos_id=-1)),
            ("paged", EngineConfig(batch_slots=3, max_len=64, eos_id=-1,
                                   cache="paged", page_size=4)),
            ("int8-paged", EngineConfig(batch_slots=3, max_len=64, eos_id=-1,
                                        cache="paged", page_size=4,
                                        kv_quant="int8"))):
        eng = Engine(model, params, conf)
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        outs[name] = {f.rid: f.output for f in eng.run()}
        if name != "slot":
            assert eng.stats.prefix_hit_pages > 0
    assert outs["slot"] == outs["paged"]
    assert outs["paged"] == outs["int8-paged"]


def test_engine_prefill_ref_impl_matches_kernel(small_lm):
    """The bench's gather-vs-kernel comparison is apples-to-apples: the
    ``paged_prefill_impl="ref"`` engine generates identical greedy tokens."""
    cfg, model, params = small_lm
    from repro.models import layers as L
    prompt = [5, 6, 7, 8, 9, 10, 11]
    outs = []
    for impl in ("kernel", "ref"):
        conf = EngineConfig(batch_slots=1, max_len=32, eos_id=-1,
                            cache="paged", page_size=4,
                            kernels=L.KernelConfig(paged_prefill_impl=impl))
        eng = Engine(model, params, conf)
        outs.append(eng.generate([prompt], max_new_tokens=4,
                                 ignore_eos=True)[0].output)
    assert outs[0] == outs[1]


def test_full_prefix_hit_recomputes_last_token(small_lm, monkeypatch):
    """Regression (ISSUE 5): a prefix hit covering the *whole* prompt used to
    prefill a zero-real-token bucket and sample the first token from padding
    logits.  With the admission backoff the last prompt page is recomputed:
    donor and follower are token-identical to a cold-cache run, and the
    donor's shared pages are swapped private before the rewrite."""
    cfg, model, params = small_lm
    rng = np.random.default_rng(5)
    prompt = rng.integers(2, cfg.vocab_size, size=8).tolist()  # 2 full pages

    def fresh():
        return Engine(model, params, EngineConfig(
            batch_slots=2, max_len=32, eos_id=-1, cache="paged", page_size=4))

    cold = fresh().generate([prompt], max_new_tokens=4,
                            ignore_eos=True)[0].output
    # simulate the historical uncapped prefix lookup (full-prompt coverage)
    monkeypatch.setattr(KV.PagedCache, "_max_shared_pages",
                        lambda self, n_tokens: n_tokens // self.page_size)
    eng = fresh()
    r0 = eng.submit(prompt, max_new_tokens=4, ignore_eos=True)
    r1 = eng.submit(prompt, max_new_tokens=4, ignore_eos=True)
    outs = {f.rid: f.output for f in eng.run()}
    assert outs[r0] == cold, "donor diverged from cold run"
    assert outs[r1] == cold, "full-prefix-hit follower diverged from cold run"
    # the hit was backed off to leave one recomputed page
    assert eng.stats.prefix_hit_pages == 1
    assert eng.pc.utilization == 0.0        # everything released cleanly


def test_release_prefix_swaps_only_shared_pages():
    pc = KV.PagedCache(num_pages=8, page_size=4, n_layers=1, kv_heads=1,
                       head_dim=4, alloc_pools=False)
    assert pc.alloc_seq(0, 8)
    assert pc.alloc_seq(1, 8, share_from=0)
    donor_table = list(pc.tables[0])
    assert pc.tables[1][:2] == donor_table[:2]
    assert pc.release_prefix(1, 1) == 1     # page 0 kept shared, page 1 swapped
    assert pc.tables[1][0] == donor_table[0]
    assert pc.tables[1][1] != donor_table[1]
    assert pc.tables[0] == donor_table      # donor untouched
    assert pc.refcount[donor_table[1]] == 1
    # device table follows the swap
    row = np.asarray(pc.block_tables[pc.row_of(1)])
    assert list(row[:2]) == pc.tables[1]
    assert pc.release_prefix(1, 0) == 1     # now swap the remaining shared one
    assert pc.tables[1][0] != donor_table[0]


# --------------------------------------------------------------- memory model
def test_paged_prefill_peak_bytes(small_lm):
    cfg, _, _ = small_lm
    kw = dict(batch=1, max_pages=8, page_size=16)
    gather = MM.paged_prefill_peak_bytes(cfg, dtype=jnp.float32,
                                         impl="gather", **kw)
    assert gather == 2 * 8 * 16 * cfg.num_kv_heads * cfg.head_dim * 4
    assert MM.paged_prefill_peak_bytes(cfg, impl="kernel", **kw) == 0
    int8 = MM.paged_prefill_peak_bytes(
        cfg, dtype=jnp.int8, kv_quant=KVQuantConfig(dtype="int8"),
        impl="gather", **kw)
    assert int8 > gather                    # gather + dense fp32 dequant copy
    with pytest.raises(ValueError, match="impl"):
        MM.paged_prefill_peak_bytes(cfg, impl="nope", **kw)
