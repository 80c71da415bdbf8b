"""Pallas TPU paged-attention kernels: decode and chunked prefill.

Attention over a paged KV cache: each sequence's keys/values live in
non-contiguous fixed-size pages of a shared physical pool, addressed through a
per-sequence block table — the vLLM PagedAttention layout the paper's serving
substrate is built on, mapped to TPU idiom:

* **Head-major pools** ``(Hkv, P, page_size, D)``: one kv head's page is a
  ``(page_size, D)`` tile in the last two dimensions, which is what the TPU
  (8, 128) block rule needs (int8 pools: page_size a multiple of 32).
* **One kernel body walks only live pages** (``_paged_kernel``) — grid
  ``(B, kv-head groups, q-chunks)``, the block table, per-row start offsets
  and lengths scalar-prefetched, the pools left in HBM.  A program holds
  one query chunk of one row for a group of kv heads, and loops over the
  ``cdiv(n_keys, pages_per_block * page_size)`` blocks of about 128 tokens
  that the chunk can see: ``n_keys`` is the least of the row's length, the
  chunk's causal horizon and the block table's end, and is 0 for a chunk
  holding only pad queries (at or past the length), which fetches and
  computes nothing and writes zeros.  Each live page of a block is one DMA
  per pool that brings that page of the group's kv heads (a strided copy)
  into a double-buffered VMEM scratch (``_page_copies``), and the next
  block's DMAs are in flight while the current one is computed.  Pages at
  or past ``cdiv(n_keys, page_size)`` are never fetched; inside the last
  block their slots hold stale data, so both the logits and the value rows
  past ``n_keys`` are masked.  The gather happens as page-granular DMAs,
  never as a materialized (B, L, Hkv, D) copy.
* **Decode** (``paged_attention``) is one program per row over every kv
  head — one query position at ``lengths - 1``, so one DMA per page brings
  all heads.  **Chunked prefill** (``paged_prefill``) runs the whole suffix
  block of a (possibly prefix-hit) prompt: one program per (row, kv head,
  q-chunk), the query tile a (chunk × rep, D) block, the causal mask
  computed from the scalar-prefetched start offsets.  Pad query rows (at or
  past the row's length) come out as zeros.
* **Online softmax over blocks** — running max ``m``, normalizer ``l`` and
  an fp32 output accumulator per kv head live in VMEM scratch across the
  block loop (same scheme as ``flash_attention.py``); one writeback at the
  end of the program.  Q·Kᵀ takes bf16 operands as they are (their
  products are exact in fp32) and accumulates in fp32; softmax and P·V
  stay in fp32.
* **GQA without head repetition** — the query tile of a kv head is its
  ``rep = H // Hkv`` query heads (row r is chunk position r // rep); K/V
  are read once per kv head, never repeated.
* **Fused int8-KV dequantization** — with ``k_scales``/``v_scales`` the K/V
  pools hold int8 payloads, and each page DMA is joined by one per scale
  pool that brings the 128-lane row holding the page's scales (a DMA slice
  must be lane-aligned; ``_lane_rows``); the page's scales are lifted out
  of that row (``_scale_columns``) and the rescale happens inside the
  block loop: a floating-point copy of the KV cache never exists in HBM.
  Scale pools are parallel to the page pools — ``(Hkv, P, page_size)``
  per-token or ``(Hkv, P)`` per-page symmetric scales
  (``serving/kv_quant.py``).

``kernels/ref.py::paged_attention_ref`` / ``paged_prefill_ref`` are the jnp
oracles; ``interpret=None`` resolves the lane from the backend
(``kernels/lane.py``): the Pallas interpreter on CPU, compiled on a TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lane import resolve_interpret


def _sm_reset(m_ref, l_ref, acc_ref):
    """Reset the online-softmax VMEM scratch before the first block."""
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _sm_update(s, v, m_ref, l_ref, acc_ref):
    """One key block of the online softmax: ``s`` is the fully masked
    (rows, block) fp32 logit block, ``v`` the (block, D) fp32 value rows; the
    running max ``m``, normalizer ``l`` and fp32 output accumulator live in
    VMEM scratch across the block loop."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # fully-masked rows keep m == -inf: use a 0-based exp and zero correction
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
    pr = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m[:, None]), 0.0)
    l_ref[...] = corr * l_ref[...] + pr.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        pr, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _sm_flush(o_ref, m_ref, l_ref, acc_ref):
    """Write back the normalized accumulator (zero for all-masked rows)."""
    denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
    o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype).reshape(o_ref.shape)


def _dequant(x, s):
    """In-VMEM rescale of int8 KV: ``s`` holds the symmetric scales of
    ``x``'s rows, broadcasting over its last dimension.  Returns fp32."""
    return x.astype(jnp.float32) * s.astype(jnp.float32)


LANES = 128
# Keys one iteration of the block loop fetches and computes: a block of
# whole pages, about this many tokens.  On a v5e at qwen3-4b's decode
# shapes 64, 128 and 256 ran alike (PERF.md §6); 128 wastes less of a
# short row's last block than 256.
_BLOCK_TOKENS = 128


def _lane_rows(scales):
    """A scale pool as whole 128-lane rows: (Hkv, P, r) or (Hkv, P) ->
    (Hkv, R, 1, LANES), each head's scales flattened and zero-padded.  A
    DMA slice must be lane-aligned, and one page's r scales are narrower
    than a row: the kernel fetches the row holding them."""
    hkv = scales.shape[0]
    flat = scales.reshape(hkv, -1)
    flat = jnp.pad(flat, ((0, 0), (0, -flat.shape[1] % LANES)))
    return flat.reshape(hkv, -1, 1, LANES)


def _chunk_keys(q0, chunk, length, max_keys):
    """Keys a query chunk starting at position ``q0`` can see: the least of
    the row's ``length``, the chunk's causal horizon ``q0 + chunk`` and the
    block table's ``max_keys`` (keys past the table are not attended); 0
    for a chunk of pad queries only (``q0 >= length``)."""
    return jnp.where(q0 < length,
                     jnp.minimum(jnp.minimum(length, q0 + chunk), max_keys), 0)


def _page_id(bt_ref, b, i):
    """Physical page of row ``b``'s logical page ``i`` (clamped into the
    table: a stale slot past the live pages reads the last column)."""
    return bt_ref[b, jnp.minimum(i, bt_ref.shape[1] - 1)]


def _page_copies(bt_ref, b, heads, pools, sem, blk, slot, *, live_pages,
                 scale_rows, start):
    """Start (or wait for) the DMAs of block ``blk``'s live pages into
    buffer ``slot``: per page one DMA per pool that brings the kv heads
    ``heads`` (a ``pl.ds``) of it, and on int8 pools the 128-lane rows
    holding its scales.  One semaphore per slot counts all of them; no page
    at or past ``live_pages`` is fetched."""
    ppb = pools[0][1].shape[2]
    for j in range(ppb):
        @pl.when(blk * ppb + j < live_pages)
        def _():
            page = _page_id(bt_ref, b, blk * ppb + j)
            row = page * scale_rows // LANES         # of the scale pools
            for (hbm, buf), src in zip(pools, (page, page, row, row)):
                copy = pltpu.make_async_copy(
                    hbm.at[heads, src], buf.at[slot, :, j], sem.at[slot])
                if start:
                    copy.start()
                else:
                    copy.wait()


def _scale_columns(bt_ref, b, buf, slot, blk, i, *, page_size, scale_rows):
    """Head ``i``'s scales of each page of block ``blk`` as a (page_size, 1)
    column of its tokens', lifted out of the 128-lane row the DMA brought (a
    per-page scale repeats down the column): (pages_per_block, page_size,
    1)."""
    ppb = buf.shape[2]
    lane = jax.lax.broadcasted_iota(jnp.int32, (page_size, LANES), 1)
    tok = jax.lax.broadcasted_iota(jnp.int32, (page_size, LANES), 0)
    # the lane of token t's scale, past the page's first
    offset = tok * scale_rows // page_size
    cols = []
    for j in range(ppb):
        first = _page_id(bt_ref, b, blk * ppb + j) * scale_rows % LANES
        row = jnp.broadcast_to(buf[slot, i, j], (page_size, LANES))
        cols.append(jnp.where(lane == first + offset, row, 0.0)
                    .sum(axis=1, keepdims=True))
    return jnp.stack(cols)


def _paged_kernel(bt_ref, st_ref, len_ref, q_ref, *refs, rep, scale_rows,
                  scale):
    """One program: row ``b``'s query chunk ``qc`` for kv-head group ``g``,
    an online softmax per kv head over the live key blocks of that chunk
    only.  The pools stay in HBM; each block's live pages arrive as page
    DMAs into one half of a double-buffered VMEM scratch while the previous
    block is computed.  Query row r of a head's (chunk·rep, D) tile sits at
    position ``st_ref[b] + qc·chunk + r // rep`` and attends keys at or
    before it; a row at or past ``len_ref[b]`` is a pad query and attends
    none.  The slots of the last block past ``n_keys`` hold whatever an
    earlier block left there, so both the logits and the value rows past it
    are masked (a stale row may be non-finite, and 0 * nan is nan).
    ``scale_rows`` is the scales per page (page_size or 1), 0 on a
    floating-point pool."""
    quant = scale_rows > 0
    if quant:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf,
         sem, m_ref, l_ref, acc_ref) = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                 (vs_hbm, vs_buf))
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    b, g, qc = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    _, heads, ppb, page_size, d = k_buf.shape
    rows = q_ref.shape[2]
    chunk = rows // rep
    block = ppb * page_size
    length = len_ref[b]
    q0 = st_ref[b] + qc * chunk
    n_keys = _chunk_keys(q0, chunk, length, bt_ref.shape[1] * page_size)
    live_pages = pl.cdiv(n_keys, page_size)
    n_blocks = pl.cdiv(live_pages, ppb)
    # the last key each query row attends: its own position, none for a pad
    # query, and never a stale slot past n_keys
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // rep
    last = jnp.where(qpos < length, jnp.minimum(qpos, n_keys - 1), -1)
    copies = functools.partial(_page_copies, bt_ref, b,
                               pl.ds(g * heads, heads), pools, sem,
                               live_pages=live_pages, scale_rows=scale_rows)

    def columns(buf, slot, blk, i):
        return _scale_columns(bt_ref, b, buf, slot, blk, i,
                              page_size=page_size, scale_rows=scale_rows)

    for i in range(heads):
        _sm_reset(m_ref.at[i], l_ref.at[i], acc_ref.at[i])
    copies(0, 0, start=True)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            copies(blk + 1, 1 - slot, start=True)

        copies(blk, slot, start=False)
        kpos = blk * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        vpos = blk * block + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        for i in range(heads):
            q = q_ref[0, i]                              # (chunk*rep, D)
            if quant:
                k = _dequant(k_buf[slot, i], columns(ks_buf, slot, blk, i))
                v = _dequant(v_buf[slot, i], columns(vs_buf, slot, blk, i))
            else:
                k, v = k_buf[slot, i], v_buf[slot, i].astype(jnp.float32)
            k, v = k.reshape(block, d), v.reshape(block, d)
            if q.dtype != k.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            # bf16 x bf16 products are exact in fp32: Q.K^T takes the
            # operands as they are and accumulates in fp32
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(kpos <= last, s * scale, -jnp.inf)
            v = jnp.where(vpos < n_keys, v, 0.0)
            _sm_update(s, v, m_ref.at[i], l_ref.at[i], acc_ref.at[i])
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    for i in range(heads):
        _sm_flush(o_ref.at[0, i], m_ref.at[i], l_ref.at[i], acc_ref.at[i])


def _paged_call(qg, k_pages, v_pages, block_tables, seq_start, lengths,
                k_scales, v_scales, *, rows, heads, rep, scale, interpret,
                name):
    """Run ``_paged_kernel`` over query tiles ``qg`` (B, Hkv, nq·rows, D):
    kv head h's rows of query chunk c are ``qg[b, h, c*rows:(c+1)*rows]``,
    ``heads`` kv heads to a program.  Returns the output tiles, shaped like
    ``qg``."""
    b, hkv, n_rows, d = qg.shape
    page_size = k_pages.shape[2]
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    max_pages = block_tables.shape[1]
    ppb = max(1, min(max_pages, _BLOCK_TOKENS // page_size))
    tile = pl.BlockSpec((1, heads, rows, d),
                        lambda b, g, qc, bt, st, ln: (b, g, qc, 0))
    inputs = [qg, k_pages, v_pages]
    buffers = [pltpu.VMEM((2, heads, ppb, page_size, d), pool.dtype)
               for pool in (k_pages, v_pages)]
    scale_rows = 0
    if k_scales is not None:
        scale_rows = 1 if k_scales.ndim == 2 else page_size
        assert LANES % scale_rows == 0, (
            f"int8 pools need a page_size dividing {LANES}: {page_size}")
        inputs += [_lane_rows(k_scales), _lane_rows(v_scales)]
        buffers += [pltpu.VMEM((2, heads, ppb, 1, LANES), s.dtype)
                    for s in (k_scales, v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv // heads, n_rows // rows),
        in_specs=[tile] + [pl.BlockSpec(memory_space=pltpu.HBM)] * (
            len(inputs) - 1),
        out_specs=tile,
        scratch_shapes=buffers + [pltpu.SemaphoreType.DMA((2,)),
                                  pltpu.VMEM((heads, rows), jnp.float32),
                                  pltpu.VMEM((heads, rows), jnp.float32),
                                  pltpu.VMEM((heads, rows, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, rep=rep, scale_rows=scale_rows,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        interpret=resolve_interpret(interpret),
        name=name,
    )(block_tables.astype(jnp.int32), seq_start.astype(jnp.int32),
      lengths.astype(jnp.int32), *inputs)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attention(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                    block_tables: jnp.ndarray, lengths: jnp.ndarray, *,
                    k_scales: jnp.ndarray | None = None,
                    v_scales: jnp.ndarray | None = None,
                    scale: float | None = None,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Single-token decode attention over a paged KV pool.

    q            : (B, H, D) — one query token per sequence.
    k_pages/v_pages: (Hkv, P, page_size, D) head-major physical page pools
                   (int8 when ``k_scales``/``v_scales`` are given).
    block_tables : (B, max_pages) int32 — logical page i of sequence b lives
                   in physical page ``block_tables[b, i]``; entries past a
                   row's length are never read.
    lengths      : (B,) int32 — keys at logical positions < lengths[b] attend
                   (the just-written decode token included); only the
                   ``cdiv(lengths[b], page_size)`` pages holding them are
                   fetched.
    k_scales/v_scales: optional symmetric dequant scales parallel to the
                   pools — (Hkv, P, page_size) per-token or (Hkv, P)
                   per-page; dequantization is fused into the page loop.
    Returns (B, H, D).
    """
    b, h, d = q.shape
    hkv = k_pages.shape[0]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # one program per row over every kv head: the query sits at length - 1
    out = _paged_call(q.reshape(b, hkv, rep, d), k_pages, v_pages,
                      block_tables, lengths - 1, lengths, k_scales, v_scales,
                      rows=rep, heads=hkv, rep=rep, scale=scale,
                      interpret=interpret, name="paged_attention")
    return out.reshape(b, h, d)


@functools.partial(jax.jit,
                   static_argnames=("scale", "q_chunk", "interpret"))
def paged_prefill(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                  block_tables: jnp.ndarray, seq_start: jnp.ndarray,
                  lengths: jnp.ndarray, *,
                  k_scales: jnp.ndarray | None = None,
                  v_scales: jnp.ndarray | None = None,
                  scale: float | None = None, q_chunk: int = 128,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Chunked prefill attention over a paged KV pool.

    q            : (B, S, H, D) — the suffix query block; query i of row b
                   sits at absolute position ``seq_start[b] + i`` (its KV
                   must already be written to the pool).
    k_pages/v_pages: (Hkv, P, page_size, D) head-major physical page pools
                   (int8 when ``k_scales``/``v_scales`` are given).
    block_tables : (B, max_pages) int32 — logical page i of row b lives in
                   physical page ``block_tables[b, i]``; entries past the
                   keys a chunk can see are never read.
    seq_start    : (B,) int32 — prefix-hit length (0 on a cold prefill).
    lengths      : (B,) int32 — total valid keys per row (prefix + real
                   suffix tokens, i.e. ``seq_start + write_lens``); keys at
                   or past this are masked, so right-padded bucket positions
                   never leak into real rows' outputs, and queries at or
                   past it are pad queries whose outputs are zero.
    ``q_chunk`` bounds the query rows per program (the VMEM tile is
    (q_chunk·rep, D)); S is padded up to a chunk multiple internally and the
    pad rows' outputs are sliced off.  A chunk reads only the pages of the
    keys it can see (``_chunk_keys``).  Returns (B, S, H, D).
    """
    b, s, h, d = q.shape
    hkv = k_pages.shape[0]
    assert h % hkv == 0, (h, hkv)
    rep = h // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    chunk = max(1, min(q_chunk, s))
    nq = -(-s // chunk)
    pad = nq * chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (B, S, H, D) -> (B, Hkv, nq*chunk*rep, D): a program's query tile is
    # a kv head's rep query heads over one chunk of positions
    qg = q.reshape(b, nq, chunk, hkv, rep, d).transpose(0, 3, 1, 2, 4, 5)
    out = _paged_call(qg.reshape(b, hkv, nq * chunk * rep, d), k_pages,
                      v_pages, block_tables, seq_start, lengths, k_scales,
                      v_scales, rows=chunk * rep, heads=1, rep=rep,
                      scale=scale, interpret=interpret, name="paged_prefill")
    out = out.reshape(b, hkv, nq, chunk, rep, d).transpose(0, 2, 3, 1, 4, 5)
    return out.reshape(b, nq * chunk, h, d)[:, :s]
