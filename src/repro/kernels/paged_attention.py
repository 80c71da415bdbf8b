"""Pallas TPU paged-attention kernels: decode (ISSUE 2) and chunked prefill
(ISSUE 5).

Attention over a paged KV cache: each sequence's keys/values live in
non-contiguous fixed-size pages of a shared physical pool, addressed through a
per-sequence block table — the vLLM PagedAttention layout the paper's serving
substrate is built on, mapped to TPU idiom:

* **Head-major pools** ``(Hkv, P, page_size, D)``: one kv head's page is a
  ``(page_size, D)`` tile in the last two dimensions, which is what the TPU
  (8, 128) block rule needs (int8 pools: page_size a multiple of 32).
* **Grid (B, Hkv, n_pages)** with the block table and sequence lengths as
  *scalar-prefetch* operands: the K/V ``BlockSpec`` index maps read
  ``block_tables[b, p]`` so each program DMAs exactly one physical page into
  VMEM — the gather happens in the memory system, never as a materialized
  (B, L, Hkv, D) copy.
* **Online softmax over pages** — running max ``m``, normalizer ``l`` and an
  fp32 output accumulator live in VMEM scratch across the page loop (same
  scheme as ``flash_attention.py``); one writeback on the last page.
* **GQA without head repetition** — the query block for a kv head is its
  ``rep = H // Hkv`` query heads, shaped (rep, D); logits are (rep, page_size)
  so K/V are read once per kv head, never repeated.
* Pages past a sequence's length (block-table padding points at the null
  page) still execute structurally but are fully masked, mirroring the
  flash kernel's masked-tile convention.
* **Fused int8-KV dequantization** (ISSUE 4) — with ``k_scales``/``v_scales``
  the K/V pools hold int8 payloads and the kernel DMAs the page *plus its
  scales* into VMEM, rescaling inside the online-softmax loop: a floating-
  point copy of the KV cache never exists in HBM.  Scale pools are parallel
  to the page pools — ``(Hkv, P, page_size)`` per-token or ``(Hkv, P)``
  per-page symmetric scales (``serving/kv_quant.py``); the kernels view
  them with trailing unit dimensions so a page's scales are one legal
  block.

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


def _page_update(q, k, v, b, o_ref, m_ref, l_ref, acc_ref, len_ref, *,
                 page_size, scale):
    """One decode page: q (rep, D); k, v (page_size, D) fp32 in VMEM (already
    dequantized on the int8 path)."""
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _():
        _sm_reset(m_ref, l_ref, acc_ref)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    kpos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, dimension=1)
    s = jnp.where(kpos < len_ref[b], s, -jnp.inf)
    _sm_update(s, v, m_ref, l_ref, acc_ref)

    @pl.when(p == pl.num_programs(2) - 1)
    def _():
        _sm_flush(o_ref, m_ref, l_ref, acc_ref)


def _kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, page_size, scale):
    b = pl.program_id(0)
    q = q_ref[0, 0].astype(jnp.float32)                  # (rep, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (page_size, D)
    v = v_ref[0, 0].astype(jnp.float32)                  # (page_size, D)
    _page_update(q, k, v, b, o_ref, m_ref, l_ref, acc_ref, len_ref,
                 page_size=page_size, scale=scale)


def _dequant_page(k_ref, v_ref, ks_ref, vs_ref):
    """In-VMEM rescale of one int8 page: the page DMA brought the quantized
    payload plus its scales — a (page_size, 1) per-token column or a (1, 1)
    per-page scalar, either broadcasting over (page_size, D).  Returns fp32
    k, v; no fp KV is ever materialized in HBM."""
    k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0].astype(jnp.float32)
    return k, v


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


def _kernel_quant(bt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, page_size, scale):
    """Int8-KV decode variant: dequantization happens inside the online-
    softmax page loop (see ``_dequant_page``)."""
    b = pl.program_id(0)
    q = q_ref[0, 0].astype(jnp.float32)                  # (rep, D)
    k, v = _dequant_page(k_ref, v_ref, ks_ref, vs_ref)
    _page_update(q, k, v, b, o_ref, m_ref, l_ref, acc_ref, len_ref,
                 page_size=page_size, scale=scale)


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
                   in physical page ``block_tables[b, i]``; padding entries
                   must point at a valid (e.g. null) page.
    lengths      : (B,) int32 — keys at logical positions < lengths[b] attend
                   (the just-written decode token included).
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

    qg = q.reshape(b, hkv, rep, d)

    def page_map(b, h, p, bt, ln):
        return (h, bt[b, p], 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, rep, d), lambda b, h, p, bt, ln: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, page_size, d), page_map),
        pl.BlockSpec((1, 1, page_size, d), page_map),
    ]
    inputs = [qg, k_pages, v_pages]
    if k_scales is None:
        kernel = functools.partial(_kernel, page_size=page_size, scale=scale)
    else:
        scale_spec, views = _scale_spec_and_views(k_scales, v_scales,
                                                  page_size, page_map)
        in_specs += [scale_spec, scale_spec]
        inputs += views
        kernel = functools.partial(_kernel_quant, page_size=page_size,
                                   scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, rep, d),
                               lambda b, h, p, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[pltpu.VMEM((rep,), jnp.float32),
                        pltpu.VMEM((rep,), jnp.float32),
                        pltpu.VMEM((rep, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel,
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
