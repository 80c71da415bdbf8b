"""Attention variants: GQA (bias / qk-norm / sliding-window / M-RoPE) and
DeepSeek-style MLA (compressed KV cache, absorbed decode path).

All paths funnel into one primitive, ``attend``: grouped-GQA einsums (no
head-repetition materialization) with a position-based mask and **query
chunking** (`lax.map` + checkpoint) so (B, H, Sq, Sk) logits never exceed a
chunk — the jnp analogue of flash attention, mandatory for 32k prefill /
train_4k backward memory.

Cache layouts (DESIGN.md §2/§10):
  full attention : slot — k/v (B, max_len, Hkv, D), write at seq_lens via
                   scatter; or paged — head-major k/v pools (Hkv, pages,
                   page_size, D)
                   addressed through a per-sequence device block table
                   (decode AND prefill run kernels/paged_attention.py —
                   no gathered KV copy exists anywhere on the paged path)
  sliding window : ring buffers (B, window + num_sink, Hkv, D); the first
                   num_sink slots pin attention sinks (hymba meta tokens)
  MLA            : compressed (B, max_len, kv_lora + rope_dim)

Quantized KV (DESIGN.md §12): with ``kv_quant`` the full-attention caches
store int8 payloads plus parallel per-token symmetric scale arrays
(``k_scale``/``v_scale`` slot, ``k_scales``/``v_scales`` paged); writes
quantize in the same fused scatter and reads rescale inside the attention
math (``attend``'s grouped path folds K scales into the logits and V scales
into the probabilities; the paged decode kernel dequantizes in VMEM) — a
floating-point copy of the cache is never materialized on the hot path.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import paged_attention as PA
from repro.kernels import ref as KR
from repro.models import layers as L
from repro.serving import kv_quant as KQ

Q_CHUNK = 2048          # max query rows per logits block
NEG_INF = -1e30


def _mask(qpos, kpos, valid, *, causal: bool, window: int, num_sink: int):
    """qpos: (B, Sq); kpos: (B, Sk) absolute key positions; valid: (B, Sk) or
    None. Returns (B, Sq, Sk) boolean."""
    qp = qpos[:, :, None]
    kp = kpos[:, None, :]
    m = jnp.ones(qp.shape[:2] + (kpos.shape[1],), bool)
    if causal:
        m &= kp <= qp
    if window:
        m &= (kp > qp - window) | (kp < num_sink)
    if valid is not None:
        m &= valid[:, None, :]
    return m


def _attend_block(q, k, v, qpos, kpos, valid, *, causal, window, num_sink,
                  scale, grouped: bool = False, k_scale=None, v_scale=None):
    """q: (B, Sq, H, D); k, v: (B, Sk, Hkv, Dk/Dv).

    Train/prefill (``grouped=False``): K/V repeated to H heads so logits shard
    over the (padded) head axis — see layers.set_act_sharding.
    Decode (``grouped=True``): grouped-GQA einsum keeps the K/V cache in its
    native layout — no repeat, no cache resharding (§Perf cell B iteration 4).
    All einsums take bf16 operands with f32 accumulation — an f32 copy of the
    (large) K/V cache is never materialized (§Perf cell B iteration 2).

    Quantized KV (``k_scale``/``v_scale``: (B, Sk, Hkv) per-token symmetric
    scales over int8 k/v): the grouped path folds the K scales into the
    logits after the QK product and the V scales into the probabilities
    before the PV product — mathematically identical to dequantizing the
    cache, without ever building the fp copy."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    rep = h // hkv
    m = _mask(qpos, kpos, valid, causal=causal, window=window,
              num_sink=num_sink)
    # the grouped einsum also hosts the fused-dequant path at rep == 1
    if grouped and (rep > 1 or k_scale is not None):
        qg = q.reshape(b, sq, hkv, rep, d)
        kk = k if k_scale is None else k.astype(jnp.float32)
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kk,
                            preferred_element_type=jnp.float32) * scale
        if k_scale is not None:       # (B, Sk, Hkv) -> (B, Hkv, 1, 1, Sk)
            logits = logits * k_scale.astype(jnp.float32).transpose(
                0, 2, 1)[:, :, None, None, :]
        logits = jnp.where(m[:, None, None], logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1)
        if v_scale is not None:
            pv = p * v_scale.astype(jnp.float32).transpose(
                0, 2, 1)[:, :, None, None, :]
            out = jnp.einsum("bgrqk,bkgd->bqgrd", pv,
                             v.astype(jnp.float32),
                             preferred_element_type=jnp.float32)
        else:
            out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(v.dtype), v,
                             preferred_element_type=jnp.float32)
        return out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)
    if k_scale is not None:
        # prefill route: dequantize up front — the same order of extra fp
        # bytes this branch already spends on GQA head repetition
        k = KQ.dequantize(k, k_scale, dtype=q.dtype)
        v = KQ.dequantize(v, v_scale, dtype=q.dtype)
    if rep > 1:
        k = L.constrain_heads(jnp.repeat(k, rep, axis=2))
        v = L.constrain_heads(jnp.repeat(v, rep, axis=2))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = L.constrain_logits(logits)
    logits = jnp.where(m[:, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def attend(q, k, v, *, qpos, kpos=None, valid=None, causal=True, window=0,
           num_sink=0, scale=None, chunk=Q_CHUNK, grouped=False,
           k_scale=None, v_scale=None):
    """Unified masked attention with query chunking.

    q (B,Sq,H,D); k,v (B,Sk,Hkv,·); qpos (B,Sq) absolute query positions;
    kpos (B,Sk) absolute key positions (default arange); valid (B,Sk) marks
    live cache slots; k_scale/v_scale (B,Sk,Hkv) mark k/v as int8 payloads
    with per-token symmetric dequant scales (fused — see _attend_block)."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if kpos is None:
        kpos = jnp.broadcast_to(jnp.arange(sk, dtype=jnp.int32)[None], (b, sk))
    fn = functools.partial(_attend_block, causal=causal, window=window,
                           num_sink=num_sink, scale=scale, grouped=grouped,
                           k_scale=k_scale, v_scale=v_scale)
    if sq <= chunk or sq % chunk != 0:
        return fn(q, k, v, qpos, kpos, valid)
    nc = sq // chunk
    qs = jnp.moveaxis(q.reshape(b, nc, chunk, h, d), 1, 0)
    ps = jnp.moveaxis(qpos.reshape(b, nc, chunk), 1, 0)

    def one(args):
        qc, pc = args
        return fn(qc, k, v, pc, kpos, valid)

    outs = jax.lax.map(jax.checkpoint(one), (qs, ps))
    return jnp.moveaxis(outs, 0, 1).reshape(b, sq, h, v.shape[-1])


# ------------------------------------------------------------------------- GQA
def gqa_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    d, hd = cfg.d_model, cfg.head_dim
    ks = jax.random.split(rng, 4)
    p = {
        "wq": L.linear_init(ks[0], d, cfg.num_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": L.linear_init(ks[1], d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": L.linear_init(ks[2], d, cfg.num_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": L.linear_init(ks[3], cfg.num_heads * hd, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _resolve_q_chunk(kernels, chunk: int, s: int, cfg, page_size: int) -> int:
    """Paged-prefill query tile height (ISSUE 10 satellite).  ``None`` keeps
    the historical 128; a concrete int was lane-validated by ``KernelConfig``;
    ``"auto"`` consults the autotuner cache — shapes are static at trace
    time, so the lookup (which times concrete synthetic arrays) runs
    host-side even under an outer jit trace."""
    qc = getattr(kernels, "q_chunk", None)
    if qc == "auto":
        from repro.kernels import autotune as AT
        qc = AT.get_q_chunk(s, cfg.num_heads, cfg.num_kv_heads,
                            cfg.head_dim, page_size,
                            interpret=kernels.resolve().interpret)
    return min(chunk, qc or 128)


def gqa_apply(p, x, *, cfg: ModelConfig, kernels=L.DEFAULT_KERNELS,
              positions=None, cache=None, seq_lens=None, window: int = 0,
              causal: bool = True, num_sink: int = 0, block_tables=None,
              write_lens=None):
    """Returns (out, new_cache)."""
    b, s, d = x.shape
    hd = cfg.head_dim
    q = L.linear(p["wq"], x, name="wq", kernels=kernels).reshape(b, s, cfg.num_heads, hd)
    k = L.linear(p["wk"], x, name="wk", kernels=kernels).reshape(b, s, cfg.num_kv_heads, hd)
    v = L.linear(p["wv"], x, name="wv", kernels=kernels).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = L.rms_head_norm(p["q_norm"], q, cfg.norm_eps)
        k = L.rms_head_norm(p["k_norm"], k, cfg.norm_eps)
    if positions is None:
        positions = jnp.arange(s)[None, :].astype(jnp.int32) + jnp.zeros((b, 1), jnp.int32)
    q = L.constrain_heads(L.apply_rope(q, positions, cfg.rope_theta, cfg.mrope_sections))
    k = L.apply_rope(k, positions, cfg.rope_theta, cfg.mrope_sections)

    chunk = cfg.attn_q_chunk
    if cache is None:
        qpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        out = attend(q, k, v, qpos=qpos, causal=causal and not cfg.is_encoder,
                     window=window, num_sink=num_sink, chunk=chunk)
        new_cache = None
    elif "k_pages" in cache:
        # Paged layout (DESIGN.md §10/§13): K/V pages of a shared physical
        # pool addressed through the per-sequence device block table.  Decode
        # runs the Pallas paged-attention kernel; prefill runs the chunked
        # paged-prefill kernel directly over the pool — no path here ever
        # materializes a gathered KV copy (``kernels.paged_*_impl = "ref"``
        # routes to the jnp oracles in ``kernels/ref.py``, which do gather —
        # debugging only).  Right-padded (bucketed) prefill passes
        # ``write_lens`` — padded positions' writes are routed to the null
        # page so they never corrupt real pages; positions past the block
        # table (an overrunning sequence) are null-routed too instead of
        # aliasing into the last table column's live page.
        assert block_tables is not None, "paged cache requires block_tables"
        assert window == 0 and num_sink == 0, "paged layout is full-attn only"
        interpret = kernels.resolve().interpret        # the kernel lane
        kp, vp = cache["k_pages"], cache["v_pages"]   # (Hkv, P, ps, D)
        ksc, vsc = cache.get("k_scales"), cache.get("v_scales")
        ps = kp.shape[2]
        tpos = seq_lens[:, None] + jnp.arange(s)[None, :]          # (B, S) abs
        # out-of-range logical pages (an overrunning sequence) fill with the
        # null page instead of aliasing into the last table column
        pages = jnp.take_along_axis(block_tables, tpos // ps, axis=1,
                                    mode="fill", fill_value=0)
        if write_lens is not None:                                 # (B,) real
            pages = jnp.where(jnp.arange(s)[None, :] < write_lens[:, None],
                              pages, 0)                            # null page
        offs = tpos % ps
        # one scatter per pool per layer-call: every new token's KV lands in
        # its (page, offset) cell of every head in a single batched write —
        # quantize-on-write when the pool carries scale arrays (per-token
        # granularity).  The pools are head-major, so the written values
        # move their head axis to the front: (B, S, Hkv, ...) -> (Hkv, B, S,
        # ...)
        def head_major(a):
            return jnp.moveaxis(a, 2, 0)

        if ksc is not None:
            kq, kss = KQ.quantize(k, scale_dtype=ksc.dtype)
            vq, vss = KQ.quantize(v, scale_dtype=vsc.dtype)
            kp = kp.at[:, pages, offs].set(head_major(kq))
            vp = vp.at[:, pages, offs].set(head_major(vq))
            ksc = ksc.at[:, pages, offs].set(head_major(kss))
            vsc = vsc.at[:, pages, offs].set(head_major(vss))
        else:
            kp = kp.at[:, pages, offs].set(head_major(k).astype(kp.dtype))
            vp = vp.at[:, pages, offs].set(head_major(v).astype(vp.dtype))
        if s == 1:
            if kernels.paged_attention_impl == "kernel":
                out = PA.paged_attention(
                    q[:, 0], kp, vp, block_tables, seq_lens + 1,
                    k_scales=ksc, v_scales=vsc, interpret=interpret)
            else:
                out = KR.paged_attention_ref(
                    q[:, 0], kp, vp, block_tables, seq_lens + 1,
                    k_scales=ksc, v_scales=vsc)
            out = out[:, None]
        else:
            wl = (write_lens if write_lens is not None
                  else jnp.full((b,), s, jnp.int32))
            if kernels.paged_prefill_impl == "kernel":
                out = PA.paged_prefill(q, kp, vp, block_tables, seq_lens,
                                       seq_lens + wl, k_scales=ksc,
                                       v_scales=vsc,
                                       q_chunk=_resolve_q_chunk(
                                           kernels, chunk, s, cfg, ps),
                                       interpret=interpret)
            else:
                out = KR.paged_prefill_ref(q, kp, vp, block_tables, seq_lens,
                                           seq_lens + wl, k_scales=ksc,
                                           v_scales=vsc)
        new_cache = {"k_pages": kp, "v_pages": vp}
        if ksc is not None:
            new_cache.update(k_scales=ksc, v_scales=vsc)
    else:
        kc, vc = cache["k"], cache["v"]
        ksl, vsl = cache.get("k_scale"), cache.get("v_scale")
        cap = kc.shape[1]
        is_ring = bool(window) and cap == window + num_sink
        bidx = jnp.arange(b)[:, None]
        tpos = seq_lens[:, None] + jnp.arange(s)[None, :]          # (B, S) abs
        if is_ring:
            # attend over [old ring ; fresh block] jointly, THEN commit — a
            # write-first ring would let late block tokens overwrite early
            # tokens' window during chunked prefill.
            rw = cap - num_sink
            j = jnp.arange(cap)[None, :]
            jr = j - num_sink
            rlen_old = (seq_lens - num_sink)[:, None]
            p_ring = ((rlen_old - 1 - jr) // rw) * rw + jr + num_sink
            kpos_c = jnp.where(j < num_sink, j, p_ring)            # (B, cap)
            valid_c = jnp.where(j < num_sink, j < seq_lens[:, None],
                                (p_ring >= num_sink) & (p_ring < seq_lens[:, None]))
            k_all = jnp.concatenate([kc.astype(k.dtype), k], axis=1)
            v_all = jnp.concatenate([vc.astype(v.dtype), v], axis=1)
            kpos = jnp.concatenate([kpos_c, tpos], axis=1)
            valid = jnp.concatenate([valid_c, jnp.ones(tpos.shape, bool)], axis=1)
            out = attend(q, k_all, v_all, qpos=tpos, kpos=kpos, valid=valid,
                         causal=True, window=window, num_sink=num_sink,
                         chunk=chunk)
            slot = jnp.where(tpos < num_sink, tpos,
                             num_sink + (tpos - num_sink) % rw)
            kc = kc.at[bidx, slot].set(k.astype(kc.dtype))
            vc = vc.at[bidx, slot].set(v.astype(vc.dtype))
        else:
            # bucketed prefill: right-padded positions (>= write_lens) are
            # pointed past the cache and *dropped* — the old
            # ``minimum(tpos, cap - 1)`` clamp scattered pad garbage into
            # cell cap-1 whenever the bucket overhung the capacity.  Any
            # genuine position overrun drops the same way instead of
            # corrupting the last live cell.
            slot = tpos
            if write_lens is not None:
                slot = jnp.where(jnp.arange(s)[None, :] < write_lens[:, None],
                                 slot, cap)
            if ksl is not None:       # quantize-on-write, per-token scales
                kq, kss = KQ.quantize(k, scale_dtype=ksl.dtype)
                vq, vss = KQ.quantize(v, scale_dtype=vsl.dtype)
                kc = kc.at[bidx, slot].set(kq, mode="drop")
                vc = vc.at[bidx, slot].set(vq, mode="drop")
                ksl = ksl.at[bidx, slot].set(kss, mode="drop")
                vsl = vsl.at[bidx, slot].set(vss, mode="drop")
            else:
                kc = kc.at[bidx, slot].set(k.astype(kc.dtype), mode="drop")
                vc = vc.at[bidx, slot].set(v.astype(vc.dtype), mode="drop")
            out = attend(q, kc, vc, qpos=tpos, causal=True, window=window,
                         num_sink=num_sink, chunk=chunk, grouped=s <= 8,
                         k_scale=ksl, v_scale=vsl)
        new_cache = {"k": kc, "v": vc}
        if ksl is not None:
            new_cache.update(k_scale=ksl, v_scale=vsl)
    out = out.reshape(b, s, cfg.num_heads * hd)
    # row-parallel epilogue (DESIGN.md §17): under tensor-parallel serving
    # each device holds its head-slice of wo's K axis, so the projection is
    # a partial sum until the psum completes it; identity otherwise
    return L.tp_all_reduce(
        L.linear(p["wo"], out, name="wo", kernels=kernels)), new_cache


# ------------------------------------------------------------------------- MLA
def mla_init(rng, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    h = cfg.num_heads
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    ks = jax.random.split(rng, 4)
    return {
        "wq": L.linear_init(ks[0], d, h * qk, dtype=dtype),
        "wkv_a": L.linear_init(ks[1], d, cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                               dtype=dtype),
        "kv_norm": L.norm_init(cfg.kv_lora_rank, "rmsnorm", dtype),
        "wkv_b": L.linear_init(ks[2], cfg.kv_lora_rank,
                               h * (cfg.qk_nope_head_dim + cfg.v_head_dim), dtype=dtype),
        "wo": L.linear_init(ks[3], h * cfg.v_head_dim, d, dtype=dtype),
    }


def _mla_expand(p, c_kv, cfg, kernels, b, n, h):
    """Expand compressed kv: (B, N, dc) -> k_nope (B,N,H,dn), v (B,N,H,dv)."""
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    kv = L.linear(p["wkv_b"], c_kv, name="wkv_b", kernels=kernels)
    kv = kv.reshape(b, n, h, dn + dv)
    return kv[..., :dn], kv[..., dn:]


def mla_apply(p, x, *, cfg: ModelConfig, kernels=L.DEFAULT_KERNELS,
              positions=None, cache=None, seq_lens=None):
    b, s, d = x.shape
    h = cfg.num_heads
    dn, dr, dv, dc = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim, cfg.kv_lora_rank)
    if positions is None:
        positions = jnp.arange(s)[None, :].astype(jnp.int32) + jnp.zeros((b, 1), jnp.int32)

    q = L.linear(p["wq"], x, name="wq", kernels=kernels).reshape(b, s, h, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    q_rope = L.apply_rope(q_rope, positions, cfg.rope_theta)

    kv_a = L.linear(p["wkv_a"], x, name="wkv_a", kernels=kernels)
    c_kv, k_rope = kv_a[..., :dc], kv_a[..., dc:]
    c_kv = L.apply_norm(p["kv_norm"], c_kv, norm_type="rmsnorm", eps=cfg.norm_eps)
    k_rope = L.apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)  # (B,S,1,dr)

    scale = 1.0 / math.sqrt(dn + dr)
    if cache is None:
        # train / one-shot prefill: expanded attention over the block
        k_nope, v = _mla_expand(p, c_kv, cfg, kernels, b, s, h)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, s, h, dr))], -1)
        qq = jnp.concatenate([q_nope, q_rope], -1)
        qpos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        out = attend(qq, k, v, qpos=qpos, causal=True, scale=scale,
                     chunk=cfg.attn_q_chunk)
        new_cache = None
    else:
        cc = cache["c"]
        cap = cc.shape[1]
        bidx = jnp.arange(b)[:, None]
        tpos = seq_lens[:, None] + jnp.arange(s)[None, :]
        new_c = jnp.concatenate([c_kv, k_rope[:, :, 0, :]], -1)    # (B,S,dc+dr)
        cc = cc.at[bidx, jnp.minimum(tpos, cap - 1)].set(new_c.astype(cc.dtype))
        if s > 1:
            # prefill with cache: expand the (updated) compressed cache and run
            # chunked expanded attention (absorbed is decode-only)
            cached_c = cc[..., :dc].astype(x.dtype)
            cached_r = cc[..., dc:].astype(x.dtype)
            k_nope, v = _mla_expand(p, cached_c, cfg, kernels, b, cap, h)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(cached_r[:, :, None, :], (b, cap, h, dr))], -1)
            qq = jnp.concatenate([q_nope, q_rope], -1)
            out = attend(qq, k, v, qpos=tpos, causal=True, scale=scale,
                         chunk=cfg.attn_q_chunk)
        else:
            # decode: absorbed path — attend in compressed space (MLA's point:
            # the cache stores dc+dr per token instead of 2*H*D)
            from repro.core.gptq import QuantizedLinear, dequantize
            wb = p["wkv_b"]["w"]
            if isinstance(wb, QuantizedLinear):
                wb = dequantize(wb, x.dtype)
            wb = wb.reshape(dc, h, dn + dv)
            wb_k, wb_v = wb[..., :dn], wb[..., dn:]
            q_c = jnp.einsum("bshn,chn->bshc", q_nope, wb_k,
                             preferred_element_type=jnp.float32).astype(x.dtype)
            cached_c, cached_r = cc[..., :dc], cc[..., dc:]
            logits = (jnp.einsum("bshc,blc->bhsl", q_c, cached_c,
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bshr,blr->bhsl", q_rope, cached_r,
                                   preferred_element_type=jnp.float32)) * scale
            kpos = jnp.arange(cap)[None, None, None, :]
            mask = kpos <= tpos[:, None, :, None]
            logits = jnp.where(mask, logits, NEG_INF)
            pr = jax.nn.softmax(logits, axis=-1)
            o_c = jnp.einsum("bhsl,blc->bshc", pr.astype(cc.dtype), cached_c,
                             preferred_element_type=jnp.float32).astype(x.dtype)
            out = jnp.einsum("bshc,chv->bshv", o_c, wb_v,
                             preferred_element_type=jnp.float32).astype(x.dtype)
        new_cache = {"c": cc}
    out = out.reshape(b, s, h * dv)
    return L.linear(p["wo"], out, name="wo", kernels=kernels), new_cache


# ----------------------------------------------------------------- cache inits
def init_gqa_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                   window: int = 0, num_sink: int = 0, dtype=jnp.bfloat16,
                   kv_quant=None):
    cap = min(max_len, window + num_sink) if window else max_len
    shape = (batch, cap, cfg.num_kv_heads, cfg.head_dim)
    if kv_quant is not None and kv_quant.quantized:
        if window:
            raise ValueError(
                "quantized KV does not support sliding-window ring caches")
        if kv_quant.granularity != "token":
            raise ValueError(
                "the slot cache stores per-token scales; per-page scales "
                "exist only in the paged layout")
        sdt = kv_quant.scale_jnp_dtype
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:-1], sdt),
                "v_scale": jnp.zeros(shape[:-1], sdt)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def init_mla_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return {"c": jnp.zeros((batch, max_len,
                            cfg.kv_lora_rank + cfg.qk_rope_head_dim), dtype)}
