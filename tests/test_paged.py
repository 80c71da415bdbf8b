"""Paged-KV serving subsystem tests (ISSUE 2): Pallas paged-attention kernel
vs oracle, PagedCache copy-on-write / prefix-cache / free-list invariants,
and Engine(cache="paged") parity with the slot engine."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st
from repro.configs import smoke_config
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import flash_attention_ref, paged_attention_ref
from repro.models import build_model
from repro.serving.engine import Engine
from repro.serving.kv_cache import DEFAULT_CACHE_DTYPE, PagedCache


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config("qwen3_4b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# ------------------------------------------------------------------- kernel
def _random_paged(rng, b, h, hkv, d, pages, ps, maxp, lens):
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    # head-major pools (Hkv, P, page_size, D)
    kp = jnp.asarray(rng.normal(size=(hkv, pages, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(hkv, pages, ps, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(pages)[:b * maxp].reshape(b, maxp),
                     jnp.int32)
    return q, kp, vp, bt, jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("h,hkv", [(8, 2), (4, 4)])
def test_paged_attention_matches_ref(h, hkv):
    rng = np.random.default_rng(0)
    b, d, pages, ps, maxp = 3, 64, 17, 8, 5
    q, kp, vp, bt, lens = _random_paged(rng, b, h, hkv, d, pages, ps, maxp,
                                        [1, 11, maxp * ps])
    out = paged_attention(q, kp, vp, bt, lens)
    ref = paged_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_matches_contiguous_flash_ref():
    """Gathering each sequence's pages into a contiguous cache and running
    plain masked attention must agree with the block-table kernel."""
    rng = np.random.default_rng(1)
    b, h, hkv, d, pages, ps, maxp = 2, 8, 2, 32, 11, 4, 4
    q, kp, vp, bt, lens = _random_paged(rng, b, h, hkv, d, pages, ps, maxp,
                                        [7, 13])
    out = paged_attention(q, kp, vp, bt, lens)
    for i in range(b):
        L = int(lens[i])
        kc = kp[:, bt[i]].transpose(1, 2, 0, 3).reshape(-1, hkv, d)[:L][None]
        vc = vp[:, bt[i]].transpose(1, 2, 0, 3).reshape(-1, hkv, d)[:L][None]
        ref = flash_attention_ref(q[i][None, None], kc, vc, causal=True)
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(ref[0, 0]),
                                   rtol=2e-5, atol=2e-5)


def test_paged_attention_ignores_pages_past_length():
    """Block-table padding (null page) and garbage in unowned pages must not
    leak into the output: clobbering every page past each sequence's length
    with huge values leaves the result unchanged."""
    rng = np.random.default_rng(2)
    b, h, hkv, d, pages, ps, maxp = 2, 4, 2, 16, 9, 4, 4
    q, kp, vp, bt, lens = _random_paged(rng, b, h, hkv, d, pages, ps, maxp,
                                        [5, 9])
    out = paged_attention(q, kp, vp, bt, lens)
    used = {int(bt[i, j]) for i in range(b)
            for j in range(-(-int(lens[i]) // ps))}
    clobber = [p for p in range(pages) if p not in used]
    kp2 = kp.at[:, jnp.asarray(clobber)].set(1e9)
    vp2 = vp.at[:, jnp.asarray(clobber)].set(-1e9)
    out2 = paged_attention(q, kp2, vp2, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2))


# ---------------------------------------------------------------- PagedCache
def test_paged_cache_cow_protects_donor():
    """Regression (seed bug): a follower sharing a donor's pages then writing
    past the shared prefix silently corrupted the donor's KV.  With
    copy-on-write the donor's gather is bit-identical after the follower
    overwrites every shared position."""
    pc = PagedCache(num_pages=8, page_size=4, n_layers=2, kv_heads=1,
                    head_dim=4, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    kd = jnp.asarray(rng.normal(size=(10, 1, 4)), jnp.float32)
    vd = jnp.asarray(rng.normal(size=(10, 1, 4)), jnp.float32)
    assert pc.alloc_seq(0, 10)                     # 3 pages, last partial
    for layer in range(2):
        pc.write_tokens(0, layer, 0, kd, vd)
    donor_table = list(pc.tables[0])

    # follower shares all 3 pages (incl. the donor's partial last page),
    # then writes its own 12 tokens over [0, 12)
    assert pc.alloc_seq(1, 12, share_from=0)
    kf = jnp.asarray(rng.normal(size=(12, 1, 4)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(12, 1, 4)), jnp.float32)
    for layer in range(2):
        pc.write_tokens(1, layer, 0, kf, vf)

    assert pc.tables[1] != donor_table             # COW re-pointed the writes
    assert pc.tables[0] == donor_table             # donor untouched
    for layer in range(2):
        k0, v0 = pc.gather_kv(0, layer)
        np.testing.assert_array_equal(np.asarray(k0), np.asarray(kd))
        np.testing.assert_array_equal(np.asarray(v0), np.asarray(vd))
        k1, v1 = pc.gather_kv(1, layer)
        np.testing.assert_array_equal(np.asarray(k1), np.asarray(kf))
    # refcounts dropped back to exclusive ownership everywhere
    for p in donor_table:
        assert pc.refcount[p] == 1


def test_paged_cache_partial_cow_keeps_untouched_pages_shared():
    """Writing only the divergent suffix copies just the pages it touches:
    the untouched prefix pages stay physically shared (refcount 2)."""
    pc = PagedCache(num_pages=8, page_size=4, n_layers=1, kv_heads=1,
                    head_dim=4, dtype=jnp.float32)
    rng = np.random.default_rng(1)
    kd = jnp.asarray(rng.normal(size=(12, 1, 4)), jnp.float32)
    assert pc.alloc_seq(0, 12)                     # 3 full pages
    pc.write_tokens(0, 0, 0, kd, kd)
    donor_table = list(pc.tables[0])
    assert pc.alloc_seq(1, 12, share_from=0)       # shares all 3
    # divergent suffix only: positions [8, 12) live in shared page 2
    kf = jnp.asarray(rng.normal(size=(4, 1, 4)), jnp.float32)
    pc.write_tokens(1, 0, 8, kf, kf)
    assert pc.tables[1][:2] == donor_table[:2]     # prefix still shared
    assert pc.tables[1][2] != donor_table[2]       # suffix page COW'd
    assert pc.refcount[donor_table[0]] == 2
    assert pc.refcount[donor_table[2]] == 1
    np.testing.assert_array_equal(np.asarray(pc.gather_kv(0, 0)[0]),
                                  np.asarray(kd))
    np.testing.assert_array_equal(np.asarray(pc.gather_kv(1, 0)[0][8:]),
                                  np.asarray(kf))


def test_paged_cache_write_tokens_is_batched(monkeypatch):
    """write_tokens must dispatch one scatter per pool per call, not one per
    token (the seed's O(n) loop): count `.at` indexed-update dispatches."""
    pc = PagedCache(num_pages=8, page_size=4, n_layers=1, kv_heads=2,
                    head_dim=4, dtype=jnp.float32)
    assert pc.alloc_seq(0, 14)
    arr_cls = type(pc.k_pages)
    orig = arr_cls.at
    calls = {"n": 0}

    class CountingAt:
        def __get__(self, obj, objtype=None):
            calls["n"] += 1
            return orig.__get__(obj, objtype)

    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(14, 2, 4)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(14, 2, 4)), jnp.float32)
    monkeypatch.setattr(arr_cls, "at", CountingAt())
    pc.write_tokens(0, 0, 0, k, v)
    monkeypatch.undo()
    assert calls["n"] == 2                     # one per pool (k, v)
    k2, v2 = pc.gather_kv(0, 0)
    np.testing.assert_allclose(np.asarray(k2), np.asarray(k), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v), rtol=1e-6)


def test_paged_cache_all_layer_write_paths():
    """Standalone data-path API: ``write_prefill`` (all layers, one scatter
    per pool) and ``write_decode_token`` (one fused scatter for the decode
    token) agree with per-layer ``write_tokens``."""
    L, n, hkv, d, ps = 3, 10, 2, 4, 4
    rng = np.random.default_rng(2)
    k = jnp.asarray(rng.normal(size=(L, n, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, n, hkv, d)), jnp.float32)
    kd = jnp.asarray(rng.normal(size=(L, hkv, d)), jnp.float32)
    vd = jnp.asarray(rng.normal(size=(L, hkv, d)), jnp.float32)

    pc = PagedCache(num_pages=8, page_size=ps, n_layers=L, kv_heads=hkv,
                    head_dim=d, dtype=jnp.float32)
    assert pc.alloc_seq(0, n)
    pc.write_prefill(0, 0, k, v)
    assert pc.extend_seq(0, 1)
    pc.write_decode_token(0, kd, vd)

    ref = PagedCache(num_pages=8, page_size=ps, n_layers=L, kv_heads=hkv,
                     head_dim=d, dtype=jnp.float32)
    assert ref.alloc_seq(0, n)
    for layer in range(L):
        ref.write_tokens(0, layer, 0, k[layer], v[layer])
    assert ref.extend_seq(0, 1)
    for layer in range(L):
        ref.write_tokens(0, layer, n, kd[layer][None], vd[layer][None])

    for layer in range(L):
        ka, va = pc.gather_kv(0, layer)
        kb, vb = ref.gather_kv(0, layer)
        np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))


def test_paged_cache_prefix_cache_reuse_and_eviction():
    ps = 4
    pc = PagedCache(num_pages=8, page_size=ps, n_layers=1, kv_heads=1,
                    head_dim=4, dtype=jnp.float32)
    tokens = list(range(100, 111))                 # 11 tokens: 2 full pages
    assert pc.alloc_seq(0, len(tokens), tokens=tokens)
    assert pc.prefix_hits[0] == 0                  # cold cache
    pc.register_prefix(0, tokens)

    assert pc.alloc_seq(1, len(tokens), tokens=tokens)
    assert pc.prefix_hits[1] == 2                  # both full pages reused
    assert pc.tables[1][:2] == pc.tables[0][:2]    # physically shared
    assert pc.tables[1][2] != pc.tables[0][2]      # private partial page

    pc.free_seq(0)                                 # follower keeps pages alive
    assert all(pc.refcount[p] == 1 for p in pc.tables[1][:2])
    pc.free_seq(1)
    assert pc.utilization == 0.0
    # eviction: freed pages left the index; a fresh alloc sees a cold cache
    assert pc.alloc_seq(2, len(tokens), tokens=tokens)
    assert pc.prefix_hits[2] == 0


def test_paged_cache_block_table_device_sync():
    pc = PagedCache(num_pages=8, page_size=4, n_layers=1, kv_heads=1,
                    head_dim=4)
    assert pc.alloc_seq(5, 9)
    row = pc.row_of(5)
    bt = np.asarray(pc.block_tables[row])
    assert list(bt[:3]) == pc.tables[5]
    assert (bt[3:] == 0).all()                     # padding -> null page
    assert pc.extend_seq(5, 4)                     # crosses a page boundary
    assert list(np.asarray(pc.block_tables[row])[:4]) == pc.tables[5]
    pc.free_seq(5)
    assert (np.asarray(pc.block_tables[row]) == 0).all()


@settings(max_examples=12)
@given(st.integers(0, 2 ** 31 - 1))
def test_paged_cache_free_list_invariants(seed):
    """Randomized alloc/extend/free/share sequences keep the manager sane:
    refcounts count exactly the table references, the free list is disjoint
    from live pages, and every page is either free or referenced."""
    rng = np.random.default_rng(seed)
    pc = PagedCache(num_pages=12, page_size=4, n_layers=1, kv_heads=1,
                    head_dim=4)
    next_id = 0
    for _ in range(40):
        op = rng.integers(0, 4)
        live = list(pc.tables)
        if op == 0 or not live:
            share = int(rng.choice(live)) if live and rng.integers(2) else None
            pc.alloc_seq(next_id, int(rng.integers(1, 20)), share_from=share)
            next_id += 1
        elif op == 1:
            pc.extend_seq(int(rng.choice(live)), int(rng.integers(1, 6)))
        elif op == 2:
            pc.free_seq(int(rng.choice(live)))
        else:
            sid = int(rng.choice(live))
            n = pc.lengths[sid]
            k = jnp.zeros((n, 1, 4), jnp.float32)
            try:
                pc.write_tokens(sid, 0, 0, k, k)   # may trigger COW
            except RuntimeError:
                pass                               # COW with an empty pool
        refs = {}
        for t in pc.tables.values():
            for p in t:
                refs[p] = refs.get(p, 0) + 1
        assert 0 not in refs                       # null page never allocated
        for p, n in refs.items():
            assert pc.refcount[p] == n, (p, n, pc.refcount[p])
        assert set(pc.free_list).isdisjoint(refs)
        assert len(pc.free_list) + len(refs) == pc.num_pages
        assert 0.0 <= pc.utilization <= 1.0
        for sid, t in pc.tables.items():
            row_bt = np.asarray(pc.block_tables[pc.row_of(sid)])
            assert list(row_bt[:len(t)]) == t


# -------------------------------------------------------------- paged engine
def test_engine_paged_matches_slot_greedy(small_lm):
    """Greedy outputs of the paged engine are token-identical to the slot
    engine over a mixed-length multi-request queue that includes a
    prefix-sharing pair; the pair produces nonzero prefix-hit stats."""
    cfg, model, params = small_lm
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist()
               for n in (7, 13, 3)]
    base = rng.integers(2, cfg.vocab_size, size=8).tolist()  # 2 full pages
    prompts.append(base + rng.integers(2, cfg.vocab_size, size=5).tolist())
    prompts.append(base + rng.integers(2, cfg.vocab_size, size=3).tolist())

    eng_s = Engine(model, params, batch_slots=3, max_len=64, eos_id=-1)
    eng_p = Engine(model, params, batch_slots=3, max_len=64, eos_id=-1,
                   cache="paged", page_size=4)
    for p in prompts:
        eng_s.submit(p, max_new_tokens=4)
        eng_p.submit(p, max_new_tokens=4)
    done_s = {f.rid: f.output for f in eng_s.run()}
    done_p = {f.rid: f.output for f in eng_p.run()}
    assert done_s.keys() == done_p.keys()
    for rid in done_s:
        assert done_s[rid] == done_p[rid], rid
    assert eng_p.stats.prefix_hit_pages > 0
    assert eng_p.stats.prefix_hit_tokens == \
        eng_p.stats.prefix_hit_pages * eng_p.pc.page_size
    assert eng_p.pc.utilization == 0.0             # everything released


def test_engine_paged_kernel_on_hot_path(small_lm, monkeypatch):
    """The decode hot path must run the Pallas paged-attention kernel, not
    the jnp gather reference."""
    import repro.models.attention as attn_mod
    cfg, model, params = small_lm
    calls = {"n": 0}
    real = attn_mod.PA.paged_attention

    def counting(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(attn_mod.PA, "paged_attention", counting)
    eng = Engine(model, params, batch_slots=2, max_len=32, eos_id=-1,
                 cache="paged", page_size=4)
    eng.submit([5, 6, 7, 8, 9], max_new_tokens=3)
    done = eng.run()
    assert len(done) == 1 and len(done[0].output) == 3
    assert calls["n"] > 0                          # kernel traced on decode


def test_engine_paged_exhaustion_defers_admission(small_lm):
    """A queue whose working set exceeds the page pool drains completely —
    admission defers until pages free up instead of crashing."""
    cfg, model, params = small_lm
    # pool of 8 pages x 4 tokens; each request reserves 3 pages -> at most 2
    # concurrent, queue of 6
    eng = Engine(model, params, batch_slots=4, max_len=32, eos_id=-1,
                 cache="paged", page_size=4, num_pages=8)
    rng = np.random.default_rng(3)
    for _ in range(6):
        eng.submit(rng.integers(2, cfg.vocab_size, size=7).tolist(),
                   max_new_tokens=3)
    max_active = 0
    done = []
    for _ in range(200):
        done.extend(eng.step())
        max_active = max(max_active, len(eng.sched.active))
        if eng.sched.idle:
            break
    assert len(done) == 6
    assert max_active <= 2                         # page budget enforced
    assert eng.pc.utilization == 0.0


def test_engine_paged_rejects_impossible_request(small_lm):
    cfg, model, params = small_lm
    eng = Engine(model, params, batch_slots=2, max_len=32, eos_id=-1,
                 cache="paged", page_size=4, num_pages=4)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(list(range(2, 30)), max_new_tokens=8)


def test_engine_paged_admits_beyond_slot_reservation(small_lm):
    """The paged pool admits a workload whose summed prompt lengths exceed
    the slot layout's batch_slots x max_len worst-case reservation, using
    half the slot cache's token memory."""
    cfg, model, params = small_lm
    batch_slots, max_len = 2, 64
    eng = Engine(model, params, batch_slots=batch_slots, max_len=max_len,
                 eos_id=-1, cache="paged", page_size=4, num_pages=16)
    assert eng.pc.num_pages * eng.pc.page_size < batch_slots * max_len
    rng = np.random.default_rng(4)
    prompts = [rng.integers(2, cfg.vocab_size, size=24).tolist()
               for _ in range(6)]
    assert sum(map(len, prompts)) > batch_slots * max_len
    for p in prompts:
        eng.submit(p, max_new_tokens=3)
    done = eng.run()
    assert len(done) == 6
    assert all(len(f.output) == 3 for f in done)


def test_engine_paged_prefill_recompiles_are_bucketed(small_lm, monkeypatch):
    """Distinct prompt lengths inside one step-width bucket share a single
    fused-step trace (the padded positions' writes go to the null page) —
    the paged path must not recompile per exact chunk length."""
    cfg, model, params = small_lm
    traces = {"n": 0}
    orig = Engine._fused_step_impl

    def counting(*args, **kwargs):
        traces["n"] += 1                       # runs once per jit trace
        return orig(*args, **kwargs)

    monkeypatch.setattr(Engine, "_fused_step_impl", staticmethod(counting))
    eng = Engine(model, params, batch_slots=4, max_len=64, eos_id=-1,
                 cache="paged", page_size=4)
    rng = np.random.default_rng(6)
    outs = {}
    for n in (3, 7, 12, 9):                    # all within the 32 bucket
        rid = eng.submit(rng.integers(2, cfg.vocab_size, size=n).tolist(),
                         max_new_tokens=3)
        outs[rid] = n
    done = eng.run()
    assert len(done) == 4
    # one trace for the width-32 prefill step, one for width-1 decode steps
    assert traces["n"] == 2, traces["n"]

    # parity against the slot engine for the same bucketed workload
    eng_s = Engine(model, params, batch_slots=4, max_len=64, eos_id=-1)
    rng = np.random.default_rng(6)
    for n in (3, 7, 12, 9):
        eng_s.submit(rng.integers(2, cfg.vocab_size, size=n).tolist(),
                     max_new_tokens=3)
    done_s = {f.rid: f.output for f in eng_s.run()}
    for f in done:
        assert f.output == done_s[f.rid], f.rid


def test_engine_paged_mixed_sampling(small_lm):
    from repro.serving.sampler import SamplingParams
    cfg, model, params = small_lm
    eng = Engine(model, params, batch_slots=3, max_len=64, eos_id=-1,
                 cache="paged", page_size=4)
    rng = np.random.default_rng(5)
    rids = [
        eng.submit(rng.integers(2, cfg.vocab_size, size=6).tolist(),
                   max_new_tokens=4, sampling=sp)
        for sp in (SamplingParams(greedy=True),
                   SamplingParams(temperature=0.7, top_k=3),
                   SamplingParams(temperature=1.1, top_p=0.8))]
    done = eng.run()
    assert sorted(f.rid for f in done) == sorted(rids)
    for f in done:
        assert len(f.output) == 4
        assert all(0 <= t < cfg.vocab_size for t in f.output)


# ------------------------------------------------------------------ dtypes
def test_cache_dtype_single_source_and_respected(small_lm):
    cfg, model, params = small_lm
    # the engine's default is the model's dtype in both layouts; a
    # standalone cache manager defaults to DEFAULT_CACHE_DTYPE
    eng = Engine(model, params, batch_slots=1, max_len=16, eos_id=-1)
    leaf = jax.tree_util.tree_leaves(eng.slots.cache)[0]
    assert leaf.dtype == jnp.dtype(cfg.dtype)
    engp = Engine(model, params, batch_slots=1, max_len=16, eos_id=-1,
                  cache="paged", page_size=4)
    leafp = jax.tree_util.tree_leaves(engp.cache)[0]
    assert leafp.dtype == jnp.dtype(cfg.dtype)
    assert PagedCache(num_pages=2, page_size=2, n_layers=1, kv_heads=1,
                      head_dim=2).k_pages.dtype == DEFAULT_CACHE_DTYPE
    # and an explicit override is respected in both layouts
    eng16 = Engine(model, params, batch_slots=1, max_len=16, eos_id=-1,
                   cache_dtype=jnp.bfloat16)
    assert jax.tree_util.tree_leaves(eng16.slots.cache)[0].dtype == jnp.bfloat16
    engp16 = Engine(model, params, batch_slots=1, max_len=16, eos_id=-1,
                    cache="paged", page_size=4, cache_dtype=jnp.bfloat16)
    assert jax.tree_util.tree_leaves(engp16.cache)[0].dtype == jnp.bfloat16
