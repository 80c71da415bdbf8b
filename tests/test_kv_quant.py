"""Quantized KV-cache subsystem tests (ISSUE 4): int8 kernel-vs-ref parity
(paged + slot attention), quantize→dequantize round-trip bounds, PagedCache
scale-pool COW/prefix invariants, engine greedy parity under int8 KV, config
validation, and byte-budget pool derivation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, strategies as st
from repro.configs import smoke_config
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import flash_attention_ref, paged_attention_ref
from repro.models import build_model
from repro.models.attention import attend
from repro.perf import memory_model as MM
from repro.serving import kv_quant as KQ
from repro.serving.api import EngineConfig
from repro.serving.engine import Engine
from repro.serving.kv_cache import PagedCache
from repro.serving.kv_quant import KVQuantConfig


@pytest.fixture(scope="module")
def small_lm():
    cfg = smoke_config("qwen3_4b")
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# ------------------------------------------------------------------- config
def test_kv_quant_config_validation():
    assert KVQuantConfig(dtype="int8").quantized
    assert not KVQuantConfig(dtype="bf16").quantized
    # dtype aliases normalize to the canonical spelling
    assert KVQuantConfig(dtype="float32").dtype == "fp32"
    assert KVQuantConfig(dtype="bfloat16").jnp_dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="dtype"):
        KVQuantConfig(dtype="int3")
    with pytest.raises(ValueError, match="granularity"):
        KVQuantConfig(granularity="tensor")
    with pytest.raises(ValueError, match="scale_dtype"):
        KVQuantConfig(scale_dtype="int8")   # fp scale pool dtype mismatch


def test_engine_config_kv_quant_validation():
    # string shorthand normalizes; unknown strings reject
    assert EngineConfig(kv_quant="int8").kv_quant == KVQuantConfig("int8")
    with pytest.raises(ValueError, match="dtype"):
        EngineConfig(kv_quant="int4")
    # quantized KV makes cache_dtype meaningless -> reject the combination
    with pytest.raises(ValueError, match="cache_dtype"):
        EngineConfig(kv_quant="int8", cache_dtype=jnp.bfloat16)
    # fp passthrough must agree with an explicit cache_dtype
    with pytest.raises(ValueError, match="conflicts"):
        EngineConfig(kv_quant="bf16", cache_dtype=jnp.float32)
    assert EngineConfig(kv_quant="bf16", cache_dtype=jnp.bfloat16)
    # the engine's fused path is per-token only
    with pytest.raises(ValueError, match="per-token"):
        EngineConfig(kv_quant=KVQuantConfig(granularity="page"))
    with pytest.raises(ValueError, match="KVQuantConfig"):
        EngineConfig(kv_quant=42)
    with pytest.raises(ValueError, match="not both"):
        EngineConfig(num_pages=4, page_pool_bytes=1 << 20)
    with pytest.raises(ValueError, match="page_pool_bytes"):
        EngineConfig(page_pool_bytes=0)


# ---------------------------------------------------------------- round-trip
@settings(max_examples=12)
@given(st.integers(0, 2 ** 31 - 1), st.integers(1, 24))
def test_quantize_roundtrip_error_bound(seed, scale_pow):
    """Symmetric int8 round-trip error is bounded by scale/2 = amax/254 per
    reduction group, at any magnitude."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(5, 3, 16)) * 2.0 ** (scale_pow - 12),
                    jnp.float32)
    q, s = KQ.quantize(x)
    assert q.dtype == jnp.int8 and s.shape == x.shape[:-1]
    err = np.abs(np.asarray(KQ.dequantize(q, s)) - np.asarray(x))
    amax = np.max(np.abs(np.asarray(x)), axis=-1)
    bound = np.maximum(amax, 1e-8) / 254.0 * (1 + 1e-6)
    assert (err <= bound[..., None]).all()
    # per-page reduction obeys the same bound over its (position, D) group
    qp, sp = KQ.quantize(x, axes=(0, 2))
    errp = np.abs(np.asarray(qp.astype(jnp.float32)
                             * sp[None, :, None]) - np.asarray(x))
    amaxp = np.max(np.abs(np.asarray(x)), axis=(0, 2))
    assert (errp <= (np.maximum(amaxp, 1e-8) / 254.0
                     * (1 + 1e-6))[None, :, None]).all()


def test_quantize_zero_vector_is_exact():
    q, s = KQ.quantize(jnp.zeros((2, 3, 8)))
    assert (np.asarray(q) == 0).all()
    assert np.isfinite(np.asarray(s)).all()
    assert (np.asarray(KQ.dequantize(q, s)) == 0).all()


def test_dequantize_rejects_rank_mismatch():
    with pytest.raises(ValueError, match="rank"):
        KQ.dequantize(jnp.zeros((4, 2, 8), jnp.int8), jnp.zeros(()))


# ------------------------------------------------------------------- kernels
def _random_paged(rng, b, h, hkv, d, pages, ps, maxp, lens):
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    # head-major pools (Hkv, P, page_size, D)
    kp = jnp.asarray(rng.normal(size=(hkv, pages, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(hkv, pages, ps, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(pages)[:b * maxp].reshape(b, maxp),
                     jnp.int32)
    return q, kp, vp, bt, jnp.asarray(lens, jnp.int32)


# (granularity, h, hkv, page_size, max_pages, lengths): as
# test_paged.py's DECODE_CASES — one token, one page, one block, one past
# a block, the full table; rep 4 and 6; tables not a whole number of blocks
@pytest.mark.parametrize("granularity,h,hkv,ps,maxp,lens", [
    pytest.param("token", 8, 2, 8, 5, [1, 11, 40], id="token-8-2"),
    pytest.param("token", 4, 4, 8, 5, [1, 11, 40], id="token-4-4"),
    pytest.param("page", 8, 2, 8, 5, [1, 11, 40], id="page-8-2"),
    pytest.param("token", 12, 2, 32, 5, [1, 32, 128, 129, 160],
                 id="token-rep6-page32-5"),
    pytest.param("page", 12, 2, 32, 17, [1, 32, 128, 129, 544],
                 id="page-rep6-page32-17"),
    pytest.param("token", 8, 2, 8, 17, [1, 8, 128, 129, 136],
                 id="token-rep4-page8-17"),
])
def test_paged_attention_int8_matches_ref(granularity, h, hkv, ps, maxp,
                                          lens):
    """The fused-dequant kernel path agrees with the (materializing) oracle
    at both scale granularities, and never reads the scales of a page past
    a row's length: nan there leaves the output unchanged."""
    rng = np.random.default_rng(0)
    b, d = len(lens), 64
    pages = b * maxp + 2
    q, kp, vp, bt, lens = _random_paged(rng, b, h, hkv, d, pages, ps, maxp,
                                        lens)
    axes = (-1,) if granularity == "token" else (2, 3)
    kq, ks = KQ.quantize(kp, axes=axes)
    vq, vs = KQ.quantize(vp, axes=axes)
    out = paged_attention(q, kq, vq, bt, lens, k_scales=ks, v_scales=vs)
    ref = paged_attention_ref(q, kq, vq, bt, lens, k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # and the quantized result stays near the fp oracle (int8 error only)
    base = paged_attention_ref(q, kp, vp, bt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base), atol=0.05)
    used = {int(bt[i, j]) for i in range(b)
            for j in range(-(-int(lens[i]) // ps))}
    dead = jnp.asarray([p for p in range(pages) if p not in used])
    out2 = paged_attention(q, kq, vq, bt, lens,
                           k_scales=ks.at[:, dead].set(jnp.nan),
                           v_scales=vs.at[:, dead].set(jnp.nan))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_paged_attention_int8_requires_both_scales():
    rng = np.random.default_rng(1)
    q, kp, vp, bt, lens = _random_paged(rng, 2, 4, 2, 32, 9, 4, 4, [7, 13])
    kq, ks = KQ.quantize(kp)
    with pytest.raises(ValueError, match="both"):
        paged_attention(q, kq, vp, bt, lens, k_scales=ks)
    with pytest.raises(ValueError, match="both"):
        paged_attention_ref(q, kq, vp, bt, lens, k_scales=ks)


def test_attend_fused_dequant_matches_flash_ref():
    """The slot-cache fused path (K scales folded into logits, V scales into
    probabilities) equals attention over the dequantized cache — decode
    (grouped) and prefill (non-grouped) branches, GQA and MHA."""
    rng = np.random.default_rng(2)
    for h, hkv, grouped, sq in [(8, 2, True, 1), (4, 4, True, 1),
                                (8, 2, False, 5)]:
        b, sk, d = 2, 12, 32
        q = jnp.asarray(rng.normal(size=(b, sq, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(b, sk, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(b, sk, hkv, d)), jnp.float32)
        kq, ks = KQ.quantize(k)
        vq, vs = KQ.quantize(v)
        qpos = jnp.full((b, sq), sk - sq, jnp.int32) + jnp.arange(sq)[None]
        out = attend(q, kq, vq, qpos=qpos, causal=True, grouped=grouped,
                     k_scale=ks, v_scale=vs)
        ref = flash_attention_ref(q, KQ.dequantize(kq, ks),
                                  KQ.dequantize(vq, vs), causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- PagedCache
def _quant_pc(granularity, **kw):
    kv = KVQuantConfig(dtype="int8", granularity=granularity)
    args = dict(num_pages=8, page_size=4, n_layers=2, kv_heads=2, head_dim=8,
                dtype=jnp.float32, kv_quant=kv)
    args.update(kw)
    return PagedCache(**args)


@pytest.mark.parametrize("granularity", ["token", "page"])
def test_paged_cache_quant_roundtrip_all_write_paths(granularity):
    """write_prefill + write_decode_token on an int8 pool agree with
    per-layer write_tokens, and gather_kv returns values within the int8
    round-trip bound of what was written."""
    L, n, hkv, d, ps = 2, 10, 2, 8, 4
    rng = np.random.default_rng(3)
    k = jnp.asarray(rng.normal(size=(L, n, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(L, n, hkv, d)), jnp.float32)
    kd = jnp.asarray(rng.normal(size=(L, hkv, d)), jnp.float32)
    vd = jnp.asarray(rng.normal(size=(L, hkv, d)), jnp.float32)

    pc = _quant_pc(granularity, n_layers=L, kv_heads=hkv, head_dim=d,
                   page_size=ps)
    assert pc.k_pages.dtype == jnp.int8 and pc.k_scales is not None
    assert pc.alloc_seq(0, n)
    pc.write_prefill(0, 0, k, v)
    assert pc.extend_seq(0, 1)
    pc.write_decode_token(0, kd, vd)

    ref = _quant_pc(granularity, n_layers=L, kv_heads=hkv, head_dim=d,
                    page_size=ps)
    assert ref.alloc_seq(0, n)
    for layer in range(L):
        ref.write_tokens(0, layer, 0, k[layer], v[layer])
    assert ref.extend_seq(0, 1)
    for layer in range(L):
        ref.write_tokens(0, layer, n, kd[layer][None], vd[layer][None])

    full_k = jnp.concatenate([k, kd[:, None]], axis=1)
    for layer in range(L):
        ka, va = pc.gather_kv(0, layer)
        kb, vb = ref.gather_kv(0, layer)
        assert ka.dtype == jnp.float32          # dequantized on read
        np.testing.assert_array_equal(np.asarray(ka), np.asarray(kb))
        np.testing.assert_array_equal(np.asarray(va), np.asarray(vb))
        # round-trip bound: per-token exact scale; per-page shares one scale
        # across the page (and requantizes on append), so bound it by the
        # page amax instead
        err = np.abs(np.asarray(ka) - np.asarray(full_k[layer]))
        if granularity == "token":
            amax = np.abs(np.asarray(full_k[layer])).max(axis=-1)
            assert (err <= amax[..., None] / 254.0 * (1 + 1e-6)).all()
        else:
            assert err.max() <= np.abs(np.asarray(full_k[layer])).max() / 64.0


@pytest.mark.parametrize("granularity", ["token", "page"])
def test_paged_cache_quant_cow_copies_scales(granularity):
    """COW must copy scale-pool rows with their pages: after a follower
    rewrites shared pages, the donor's dequantized gather is bit-identical
    and the follower reads back its own values."""
    pc = _quant_pc(granularity)
    rng = np.random.default_rng(4)
    kd = jnp.asarray(rng.normal(size=(10, 2, 8)), jnp.float32)
    assert pc.alloc_seq(0, 10)
    for layer in range(2):
        pc.write_tokens(0, layer, 0, kd, kd)
    donor_table = list(pc.tables[0])
    donor_read = [np.asarray(pc.gather_kv(0, layer)[0]) for layer in range(2)]

    assert pc.alloc_seq(1, 12, share_from=0)
    kf = jnp.asarray(rng.normal(size=(12, 2, 8)) * 3.0, jnp.float32)
    for layer in range(2):
        pc.write_tokens(1, layer, 0, kf, kf)    # very different scales

    assert pc.tables[0] == donor_table
    assert pc.tables[1] != donor_table
    for layer in range(2):
        np.testing.assert_array_equal(
            np.asarray(pc.gather_kv(0, layer)[0]), donor_read[layer])
        # follower's read is its own data (not donor payloads dequantized
        # against follower scales or vice versa)
        kf_read = np.asarray(pc.gather_kv(1, layer)[0])
        amax = np.abs(np.asarray(kf)).max()
        assert np.abs(kf_read - np.asarray(kf)).max() <= amax / 60.0


def test_paged_cache_quant_prefix_reuse_shares_scales(small_lm):
    """Prefix-cache hits on an int8 pool: the follower physically shares the
    donor's quantized pages AND their scales — its gather of the shared
    prefix is bit-identical to the donor's."""
    pc = _quant_pc("token", num_pages=12)
    rng = np.random.default_rng(5)
    tokens = list(range(100, 111))              # 2 full pages + partial
    k = jnp.asarray(rng.normal(size=(11, 2, 8)), jnp.float32)
    assert pc.alloc_seq(0, 11, tokens=tokens)
    for layer in range(2):
        pc.write_tokens(0, layer, 0, k, k)
    pc.register_prefix(0, tokens)

    assert pc.alloc_seq(1, 11, tokens=tokens)
    assert pc.prefix_hits[1] == 2
    assert pc.tables[1][:2] == pc.tables[0][:2]
    for layer in range(2):
        np.testing.assert_array_equal(
            np.asarray(pc.gather_kv(1, layer)[0][:8]),
            np.asarray(pc.gather_kv(0, layer)[0][:8]))


# -------------------------------------------------------------------- engine
def _mixed_prefix_prompts(cfg, rng):
    """The mixed-length multi-request workload with a prefix-sharing pair
    from tests/test_paged.py."""
    prompts = [rng.integers(2, cfg.vocab_size, size=n).tolist()
               for n in (7, 13, 3)]
    base = rng.integers(2, cfg.vocab_size, size=8).tolist()
    prompts.append(base + rng.integers(2, cfg.vocab_size, size=5).tolist())
    prompts.append(base + rng.integers(2, cfg.vocab_size, size=3).tolist())
    return prompts


def test_engine_int8_paged_matches_bf16_slot_greedy(small_lm):
    """Acceptance: int8-KV paged decode is token-identical (greedy) to the
    bf16 slot engine on the mixed-length prefix-sharing workload; the int8
    slot engine agrees too (slot-vs-paged parity under int8 KV)."""
    cfg, model, params = small_lm
    prompts = _mixed_prefix_prompts(cfg, np.random.default_rng(0))
    engines = {
        "slot/bf16": Engine(model, params, EngineConfig(
            batch_slots=3, max_len=64, eos_id=-1, kv_quant="bf16")),
        "slot/int8": Engine(model, params, EngineConfig(
            batch_slots=3, max_len=64, eos_id=-1, kv_quant="int8")),
        "paged/int8": Engine(model, params, EngineConfig(
            batch_slots=3, max_len=64, eos_id=-1, cache="paged", page_size=4,
            kv_quant="int8")),
    }
    outs = {}
    for name, eng in engines.items():
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        outs[name] = {f.rid: f.output for f in eng.run()}
    ref = outs["slot/bf16"]
    for name in ("slot/int8", "paged/int8"):
        assert outs[name] == ref, name
    # int8 caches really were in play
    assert engines["slot/int8"].cache_dtype == jnp.int8
    paged = engines["paged/int8"]
    assert paged.stats.prefix_hit_pages > 0      # prefix sharing exercised
    leaves = {p.dtype for p in jax.tree_util.tree_leaves(paged.cache)}
    assert jnp.dtype(jnp.int8) in leaves         # payload pools
    assert paged.pc.utilization == 0.0           # everything released


def test_engine_int8_kernel_on_hot_path(small_lm, monkeypatch):
    """The int8 paged decode hot path must run the Pallas kernel with scale
    pools attached (fused dequant), not a dequantize-then-attend fallback."""
    import repro.models.attention as attn_mod
    cfg, model, params = small_lm
    seen = {"n": 0, "with_scales": 0}
    real = attn_mod.PA.paged_attention

    def counting(*a, **kw):
        seen["n"] += 1
        if kw.get("k_scales") is not None:
            seen["with_scales"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod.PA, "paged_attention", counting)
    eng = Engine(model, params, EngineConfig(
        batch_slots=2, max_len=32, eos_id=-1, cache="paged", page_size=4,
        kv_quant="int8"))
    eng.submit([5, 6, 7, 8, 9], max_new_tokens=3)
    done = eng.run()
    assert len(done) == 1 and len(done[0].output) == 3
    assert seen["n"] > 0 and seen["with_scales"] == seen["n"]


def test_engine_budget_pool_int8_doubles_pages_and_batch(small_lm):
    """Same page-pool byte budget: the int8 engine derives ~2x the bf16
    page count and sustains a deeper concurrent batch on a workload that
    exhausts the bf16 pool (the BENCH_serving capacity experiment)."""
    cfg, model, params = small_lm
    ps = 16
    budget = 4 * KQ.page_bytes(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
                               ps, kv_quant=KVQuantConfig(dtype="bf16"))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(2, cfg.vocab_size, size=28).tolist()
               for _ in range(6)]
    peaks, pages, outs = {}, {}, {}
    for mode in ("bf16", "int8"):
        eng = Engine(model, params, EngineConfig(
            batch_slots=6, max_len=128, eos_id=-1, cache="paged",
            page_size=ps, kv_quant=mode, page_pool_bytes=budget))
        for p in prompts:
            eng.submit(p, max_new_tokens=4)
        outs[mode] = {f.rid: f.output for f in eng.run()}
        peaks[mode], pages[mode] = eng.stats.peak_active, eng.pc.num_pages
    assert pages["bf16"] == 4                   # 2 pages/request -> 2 live
    assert pages["int8"] >= 2 * pages["bf16"] * 0.85
    assert peaks["int8"] > peaks["bf16"]
    assert outs["int8"] == outs["bf16"]         # greedy parity survives
    # budget must be honored: derived pool fits under it
    assert KQ.page_bytes(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim, ps,
                         kv_quant=KVQuantConfig(dtype="int8")) \
        * pages["int8"] <= budget


def test_engine_rejects_budget_on_slot_layout(small_lm):
    cfg, model, params = small_lm
    with pytest.raises(ValueError, match="paged"):
        Engine(model, params, EngineConfig(
            batch_slots=2, max_len=32, cache="slot", page_pool_bytes=1 << 20))
    with pytest.raises(ValueError, match="zero pages"):
        Engine(model, params, EngineConfig(
            batch_slots=2, max_len=32, cache="paged", page_pool_bytes=8))


def test_quantized_kv_rejects_unsupported_families():
    cfg = smoke_config("falcon_mamba_7b")       # SSM: no KV to quantize
    model = build_model(cfg)
    with pytest.raises(ValueError, match="full-attention"):
        model.init_cache(2, 16, kv_quant=KVQuantConfig(dtype="int8"))
    swa = smoke_config("hymba_1p5b")            # ring buffers unsupported
    with pytest.raises(ValueError, match="full-attention|ring"):
        build_model(swa).init_cache(2, 16,
                                    kv_quant=KVQuantConfig(dtype="int8"))


# -------------------------------------------------------------- memory model
def test_kv_cache_report_capacity_factors(small_lm):
    cfg, _, _ = small_lm
    rows = MM.kv_cache_report(cfg, batch_slots=4, max_len=128, page_size=16)
    by = {(r["layout"], r["mode"]): r for r in rows}
    assert set(by) == {("slot", "fp32"), ("slot", "bf16"),
                       ("slot", "int8/token"),
                       ("paged", "fp32"), ("paged", "bf16"),
                       ("paged", "int8/token"), ("paged", "int8/page")}
    for layout in ("slot", "paged"):
        fp32 = by[(layout, "fp32")]["bytes"]
        bf16 = by[(layout, "bf16")]["bytes"]
        tok8 = by[(layout, "int8/token")]["bytes"]
        assert bf16 == fp32 / 2
        # int8+f32 per-token scales: payload/4 plus 1/head_dim overhead
        assert fp32 / 4 < tok8 < fp32 / 2
        assert by[(layout, "int8/token")]["capacity_x_vs_fp32"] > 3.0
    # per-page scales are cheaper than per-token
    assert (by[("paged", "int8/page")]["bytes"]
            < by[("paged", "int8/token")]["bytes"])
    # the report matches the byte-budget derivation the engine uses
    per_page = KQ.page_bytes(cfg.num_layers, cfg.num_kv_heads, cfg.head_dim,
                             16, kv_quant=KVQuantConfig(dtype="int8"))
    assert KQ.num_pages_for_budget(per_page * 5, cfg.num_layers,
                                   cfg.num_kv_heads, cfg.head_dim, 16,
                                   kv_quant=KVQuantConfig(dtype="int8")) == 5
