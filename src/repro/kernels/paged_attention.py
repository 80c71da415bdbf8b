"""Pallas TPU paged-attention kernels: decode (ISSUE 2) and chunked prefill
(ISSUE 5).

Attention over a paged KV cache: each sequence's keys/values live in
non-contiguous fixed-size pages of a shared physical pool, addressed through a
per-sequence block table — the vLLM PagedAttention layout the paper's serving
substrate is built on, mapped to TPU idiom:

* **Head-major pools** ``(Hkv, P, page_size, D)``: one kv head's page is a
  ``(page_size, D)`` tile in the last two dimensions, which is what the TPU
  (8, 128) block rule needs (int8 pools: page_size a multiple of 32).
* **Decode walks only each row's live pages** — grid ``(B,)``, the block
  table and sequence lengths scalar-prefetched, the pools left in HBM.  A
  row's program loops over ``cdiv(lengths[b], pages_per_block *
  page_size)`` blocks of about 128 tokens; each live page of a block is one
  DMA per pool that brings that page of every kv head (a strided copy)
  into a double-buffered VMEM scratch, and the next block's DMAs are in
  flight while the current one is computed.  Pages at or past
  ``cdiv(lengths[b], page_size)`` are never fetched, and no block past the
  last live page is computed; inside the last block their slots hold stale
  data, so both the logits and the value rows past the length are masked.
  The gather happens as page-granular DMAs, never as a materialized
  (B, L, Hkv, D) copy.
* **Online softmax over blocks** — running max ``m``, normalizer ``l`` and
  an fp32 output accumulator per kv head live in VMEM scratch across the
  block loop (same scheme as ``flash_attention.py``); one writeback at the
  end of the row.
* **GQA without head repetition** — the query block for a kv head is its
  ``rep = H // Hkv`` query heads, shaped (rep, D); logits are (rep, block)
  so K/V are read once per kv head, never repeated.
* **Fused int8-KV dequantization** (ISSUE 4) — with ``k_scales``/``v_scales``
  the K/V pools hold int8 payloads and the kernel DMAs the page *plus its
  scales* into VMEM, rescaling inside the online-softmax loop: a floating-
  point copy of the KV cache never exists in HBM.  Scale pools are parallel
  to the page pools — ``(Hkv, P, page_size)`` per-token or ``(Hkv, P)``
  per-page symmetric scales (``serving/kv_quant.py``).  The prefill kernel
  views them with trailing unit dimensions so a page's scales are one
  legal block; the decode kernel reads them as 128-lane rows (a DMA slice
  must be lane-aligned) and lifts each page's scales out of its row.

* **Chunked paged prefill** (ISSUE 5) — ``paged_prefill`` runs the *whole
  suffix block* of a (possibly prefix-hit) prompt with online softmax
  directly over the physical pool: grid **(B, Hkv, q-chunks, pages)**, the
  query block a (chunk × rep, D) tile, the causal mask computed from the
  scalar-prefetched per-row start offsets.  This removes the serving
  stack's last materialized KV copy — the old prefill path gathered
  ``kp[block_tables]`` into a contiguous (B, max_pages·page_size, Hkv, D)
  view (and densely dequantized it when int8), doubling peak prefill
  memory.  Both int8 scale granularities dequantize in VMEM here too.

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
    """Reset the online-softmax VMEM scratch at the first page."""
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _sm_update(s, v, m_ref, l_ref, acc_ref):
    """One page of the online softmax, shared by the decode and prefill
    kernels: ``s`` is the fully masked (rows, page_size) fp32 logit block,
    ``v`` the (page_size, D) fp32 value page; the running max ``m``,
    normalizer ``l`` and fp32 output accumulator live in VMEM scratch across
    the page axis."""
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


def _dequant_page(k_ref, v_ref, ks_ref, vs_ref):
    """In-VMEM rescale of one int8 page: the page DMA brought the quantized
    payload plus its scales — a (page_size, 1) per-token column or a (1, 1)
    per-page scalar, either broadcasting over (page_size, D).  Returns fp32
    k, v; no fp KV is ever materialized in HBM."""
    return (_dequant(k_ref[0, 0], ks_ref[0, 0]),
            _dequant(v_ref[0, 0], vs_ref[0, 0]))


def _scale_spec_and_views(k_scales, v_scales, page_size, index_map):
    """BlockSpec + kernel views of the scale pools: (Hkv, P, page_size)
    per-token scales become (Hkv, P, page_size, 1), (Hkv, P) per-page ones
    (Hkv, P, 1, 1), so one page's scales are a block whose last two
    dimensions are full."""
    if k_scales.ndim == 2:                 # (Hkv, P): one scale per page
        rows = 1
        views = [k_scales[:, :, None, None], v_scales[:, :, None, None]]
    else:                                  # (Hkv, P, page_size): per token
        rows = page_size
        views = [k_scales[..., None], v_scales[..., None]]
    return pl.BlockSpec((1, 1, rows, 1), index_map), views


# ---------------------------------------------------------------------- decode
LANES = 128
# Keys one iteration of the decode kernel's page loop fetches and computes:
# a block of whole pages, about this many tokens.  On a v5e at qwen3-4b's
# decode shapes 64, 128 and 256 ran alike (PERF.md §6); 128 wastes
# less of a short row's last block than 256.
_DECODE_BLOCK_TOKENS = 128


def _lane_rows(scales):
    """A scale pool as whole 128-lane rows: (Hkv, P, r) or (Hkv, P) ->
    (Hkv, R, 1, LANES), each head's scales flattened and zero-padded.  A
    DMA slice must be lane-aligned, and one page's r scales are narrower
    than a row: the decode kernel fetches the row holding them."""
    hkv = scales.shape[0]
    flat = scales.reshape(hkv, -1)
    flat = jnp.pad(flat, ((0, 0), (0, -flat.shape[1] % LANES)))
    return flat.reshape(hkv, -1, 1, LANES)


def _decode_kernel(bt_ref, len_ref, q_ref, *refs, pages_per_block,
                   scale_rows, scale):
    """One row: an online softmax per kv head over the row's live page
    blocks only.  The pools stay in HBM.  Each live page of a block is one
    DMA per pool that brings the page of every kv head (and on int8 pools
    one more per scale pool, the 128-lane rows holding its scales) into one
    half of a double-buffered VMEM scratch; the next block's DMAs are in
    flight while the current block is computed.  Pages at or past
    ``cdiv(length, page_size)`` are never fetched; their slots in the last
    block hold whatever an earlier block left there, so both the logits and
    the value rows past the length are masked (a stale row may be
    non-finite, and 0 * nan is nan).  ``scale_rows`` is the scales per page
    (page_size or 1), 0 on a floating-point pool."""
    quant = scale_rows > 0
    if quant:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf,
         sem, m_ref, l_ref, acc_ref) = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf), (ks_hbm, ks_buf),
                 (vs_hbm, vs_buf))
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref = refs
        pools = ((k_hbm, k_buf), (v_hbm, v_buf))
    b = pl.program_id(0)
    max_pages = bt_ref.shape[1]
    _, hkv, _, page_size, d = k_buf.shape
    block = pages_per_block * page_size
    # keys past the block table (an overrunning row) are not attended
    length = jnp.minimum(len_ref[b], max_pages * page_size)
    live_pages = pl.cdiv(length, page_size)
    n_blocks = pl.cdiv(live_pages, pages_per_block)

    def page_id(blk, j):
        return bt_ref[b, jnp.minimum(blk * pages_per_block + j, max_pages - 1)]

    def page_copies(blk, slot, start):
        """Start (or wait for) the DMAs of block ``blk``'s live pages into
        buffer ``slot``; one semaphore per slot counts all of them."""
        for j in range(pages_per_block):
            @pl.when(blk * pages_per_block + j < live_pages)
            def _():
                page = page_id(blk, j)
                row = page * scale_rows // LANES     # of the scale pools
                for (hbm, buf), src in zip(pools, (page, page, row, row)):
                    copy = pltpu.make_async_copy(
                        hbm.at[:, src], buf.at[slot, :, j], sem.at[slot])
                    if start:
                        copy.start()
                    else:
                        copy.wait()

    def scale_columns(buf, slot, blk, h):
        """Head ``h``'s scales of each page as a (page_size, 1) column of its
        tokens', lifted out of the 128-lane row the DMA brought (a per-page
        scale repeats down the column): (pages_per_block, page_size, 1)."""
        lane = jax.lax.broadcasted_iota(jnp.int32, (page_size, LANES), 1)
        tok = jax.lax.broadcasted_iota(jnp.int32, (page_size, LANES), 0)
        # the lane of token t's scale, past the page's first
        offset = tok * scale_rows // page_size
        cols = []
        for j in range(pages_per_block):
            first = page_id(blk, j) * scale_rows % LANES
            row = jnp.broadcast_to(buf[slot, h, j], (page_size, LANES))
            cols.append(jnp.where(lane == first + offset, row, 0.0)
                        .sum(axis=1, keepdims=True))
        return jnp.stack(cols)

    for h in range(hkv):
        _sm_reset(m_ref.at[h], l_ref.at[h], acc_ref.at[h])
    page_copies(0, 0, start=True)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            page_copies(blk + 1, 1 - slot, start=True)

        page_copies(blk, slot, start=False)
        kpos = blk * block + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)
        vpos = blk * block + jax.lax.broadcasted_iota(jnp.int32, (block, d), 0)
        for h in range(hkv):
            q = q_ref[0, h]                              # (rep, D)
            if quant:
                k = _dequant(k_buf[slot, h], scale_columns(ks_buf, slot, blk, h))
                v = _dequant(v_buf[slot, h], scale_columns(vs_buf, slot, blk, h))
            else:
                k, v = k_buf[slot, h], v_buf[slot, h].astype(jnp.float32)
            k, v = k.reshape(block, d), v.reshape(block, d)
            if q.dtype != k.dtype:
                q, k = q.astype(jnp.float32), k.astype(jnp.float32)
            # bf16 x bf16 products are exact in fp32: Q.K^T takes the
            # operands as they are and accumulates in fp32
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(kpos < length, s * scale, -jnp.inf)
            v = jnp.where(vpos < length, v, 0.0)
            _sm_update(s, v, m_ref.at[h], l_ref.at[h], acc_ref.at[h])
        return carry

    jax.lax.fori_loop(0, n_blocks, body, 0)
    for h in range(hkv):
        _sm_flush(o_ref.at[0, h], m_ref.at[h], l_ref.at[h], acc_ref.at[h])


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
    interpret = resolve_interpret(interpret)
    b, h, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    assert h % hkv == 0, (h, hkv)
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    rep = h // hkv
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    ppb = max(1, min(max_pages, _DECODE_BLOCK_TOKENS // page_size))

    qg = q.reshape(b, hkv, rep, d)
    row_spec = pl.BlockSpec((1, hkv, rep, d), lambda b, bt, ln: (b, 0, 0, 0))
    inputs = [qg, k_pages, v_pages]
    buffers = [pltpu.VMEM((2, hkv, ppb, page_size, d), pool.dtype)
               for pool in (k_pages, v_pages)]
    scale_rows = 0
    if k_scales is not None:
        scale_rows = 1 if k_scales.ndim == 2 else page_size
        assert LANES % scale_rows == 0, (
            f"int8 decode needs a page_size dividing {LANES}: {page_size}")
        inputs += [_lane_rows(k_scales), _lane_rows(v_scales)]
        buffers += [pltpu.VMEM((2, hkv, ppb, 1, LANES), s.dtype)
                    for s in (k_scales, v_scales)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[row_spec] + [pl.BlockSpec(memory_space=pltpu.HBM)] * (
            len(inputs) - 1),
        out_specs=row_spec,
        scratch_shapes=buffers + [pltpu.SemaphoreType.DMA((2,)),
                                  pltpu.VMEM((hkv, rep), jnp.float32),
                                  pltpu.VMEM((hkv, rep), jnp.float32),
                                  pltpu.VMEM((hkv, rep, d), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, pages_per_block=ppb,
                          scale_rows=scale_rows, scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rep, d), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32), *inputs)
    return out.reshape(b, h, d)


# --------------------------------------------------------------------- prefill
def _prefill_update(q, k, v, b, o_ref, m_ref, l_ref, acc_ref, st_ref, len_ref,
                    *, page_size, rep, scale):
    """One prefill page for one query chunk: q (chunk*rep, D) — ``rep`` query
    heads per chunk row, row r is chunk position r // rep; k, v
    (page_size, D) fp32 in VMEM.  The causal mask is computed from the
    scalar-prefetched per-row absolute start offset ``st_ref[b]``; keys past
    ``len_ref[b]`` (right-padded bucket positions, unwritten reserve pages)
    are masked like the decode kernel masks pages past the length.  Pages
    entirely above the chunk's causal horizon or past the row length are
    skipped outright — roughly the upper triangle of the (chunk, page)
    grid, where every logit would mask to -inf."""
    p = pl.program_id(3)

    @pl.when(p == 0)
    def _():
        _sm_reset(m_ref, l_ref, acc_ref)

    # program ids / scalar prefetch reads stay outside the pl.when body
    # (program_id does not lower inside the predicated branch on interpret)
    chunk = q.shape[0] // rep
    q0 = st_ref[b] + pl.program_id(2) * chunk       # tile's first qpos
    kbase = p * page_size
    length = len_ref[b]
    live = (kbase <= q0 + chunk - 1) & (kbase < length)

    @pl.when(live)
    def _():
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        qpos = q0 + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=0) // rep
        kpos = kbase + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, dimension=1)
        s = jnp.where((kpos <= qpos) & (kpos < length), s, -jnp.inf)
        _sm_update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(p == pl.num_programs(3) - 1)
    def _():
        _sm_flush(o_ref, m_ref, l_ref, acc_ref)


def _prefill_kernel(bt_ref, st_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                    m_ref, l_ref, acc_ref, *, page_size, rep, scale):
    b = pl.program_id(0)
    q = q_ref[0, 0, 0].astype(jnp.float32)               # (chunk*rep, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (page_size, D)
    v = v_ref[0, 0].astype(jnp.float32)
    _prefill_update(q, k, v, b, o_ref, m_ref, l_ref, acc_ref, st_ref, len_ref,
                    page_size=page_size, rep=rep, scale=scale)


def _prefill_kernel_quant(bt_ref, st_ref, len_ref, q_ref, k_ref, v_ref,
                          ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref, *,
                          page_size, rep, scale):
    """Int8-KV prefill variant: page payload + scales arrive in one DMA and
    the rescale happens inside the online-softmax page loop."""
    b = pl.program_id(0)
    q = q_ref[0, 0, 0].astype(jnp.float32)               # (chunk*rep, D)
    k, v = _dequant_page(k_ref, v_ref, ks_ref, vs_ref)
    _prefill_update(q, k, v, b, o_ref, m_ref, l_ref, acc_ref, st_ref, len_ref,
                    page_size=page_size, rep=rep, scale=scale)


@functools.partial(jax.jit,
                   static_argnames=("scale", "q_chunk", "interpret"))
def paged_prefill(q: jnp.ndarray, k_pages: jnp.ndarray, v_pages: jnp.ndarray,
                  block_tables: jnp.ndarray, seq_start: jnp.ndarray,
                  lengths: jnp.ndarray, *,
                  k_scales: jnp.ndarray | None = None,
                  v_scales: jnp.ndarray | None = None,
                  scale: float | None = None, q_chunk: int = 128,
                  interpret: bool | None = None) -> jnp.ndarray:
    """Chunked prefill attention over a paged KV pool (ISSUE 5 tentpole).

    q            : (B, S, H, D) — the suffix query block; query i of row b
                   sits at absolute position ``seq_start[b] + i`` (its KV
                   must already be written to the pool).
    k_pages/v_pages: (Hkv, P, page_size, D) head-major physical page pools
                   (int8 when ``k_scales``/``v_scales`` are given).
    block_tables : (B, max_pages) int32 — padding entries must point at a
                   valid (e.g. null) page.
    seq_start    : (B,) int32 — prefix-hit length (0 on a cold prefill).
    lengths      : (B,) int32 — total valid keys per row (prefix + real
                   suffix tokens, i.e. ``seq_start + write_lens``); keys at
                   or past this are masked, so right-padded bucket positions
                   never leak into real rows' outputs.
    ``q_chunk`` bounds the query rows per grid step (the VMEM tile is
    (q_chunk·rep, D)); S is padded up to a chunk multiple internally and the
    pad rows' outputs are sliced off.  Returns (B, S, H, D).
    """
    interpret = resolve_interpret(interpret)
    b, s, h, d = q.shape
    hkv, _, page_size, _ = k_pages.shape
    assert h % hkv == 0, (h, hkv)
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    rep = h // hkv
    max_pages = block_tables.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    chunk = max(1, min(q_chunk, s))
    nq = -(-s // chunk)
    pad = nq * chunk - s
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (B, S, H, D) -> (B, Hkv, nq, chunk*rep, D): one grid step's query tile
    # is a kv head's rep query heads over one chunk of positions
    qg = q.reshape(b, nq, chunk, hkv, rep, d).transpose(0, 3, 1, 2, 4, 5)
    qg = qg.reshape(b, hkv, nq, chunk * rep, d)
    def page_map(b, h, qc, p, bt, st, ln):
        return (h, bt[b, p], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, 1, chunk * rep, d),
                     lambda b, h, qc, p, bt, st, ln: (b, h, qc, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d), page_map),
        pl.BlockSpec((1, 1, page_size, d), page_map),
    ]
    inputs = [qg, k_pages, v_pages]
    if k_scales is None:
        kernel = functools.partial(_prefill_kernel, page_size=page_size,
                                   rep=rep, scale=scale)
    else:
        scale_spec, views = _scale_spec_and_views(k_scales, v_scales,
                                                  page_size, page_map)
        in_specs += [scale_spec, scale_spec]
        inputs += views
        kernel = functools.partial(_prefill_kernel_quant, page_size=page_size,
                                   rep=rep, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, nq, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, 1, chunk * rep, d),
                               lambda b, h, qc, p, bt, st, ln: (b, h, qc, 0, 0)),
        scratch_shapes=[pltpu.VMEM((chunk * rep,), jnp.float32),
                        pltpu.VMEM((chunk * rep,), jnp.float32),
                        pltpu.VMEM((chunk * rep, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, nq, chunk * rep, d), q.dtype),
        interpret=interpret,
        name="paged_prefill",
    )(block_tables.astype(jnp.int32), seq_start.astype(jnp.int32),
      lengths.astype(jnp.int32), *inputs)
    out = out.reshape(b, hkv, nq, chunk, rep, d).transpose(0, 2, 3, 1, 4, 5)
    return out.reshape(b, nq * chunk, h, d)[:, :s]
