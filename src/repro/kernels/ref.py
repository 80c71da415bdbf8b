"""Pure-jnp oracles for every Pallas kernel in this package.

These are the numerical ground truth for kernel tests AND the lowering path
used by the production dry-run (the math — packed-int4 reads, group dequant,
matmul — is identical, so `cost_analysis()` sees the same HBM traffic the TPU
kernel would generate).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import packing
from repro.serving import kv_quant


def gptq_matmul_ref(x: jnp.ndarray, qweight: jnp.ndarray, scales: jnp.ndarray,
                    qzeros: jnp.ndarray, *, group_size: int,
                    perm: jnp.ndarray | None = None,
                    out_dtype=None) -> jnp.ndarray:
    """y = x @ dequant(qweight)  —  x: (..., K); qweight: (K//8, N) int32.

    scales: (G, N); qzeros: (G, N//8) int32 (col-packed).  ``perm`` is the
    act-order permutation (paper's ``b_q_perm``): qweight rows are in permuted
    order, so activations are gathered first.
    """
    out_dtype = out_dtype or x.dtype
    k = qweight.shape[0] * packing.NIBBLES_PER_WORD
    n = scales.shape[1]
    if perm is not None:
        x = jnp.take(x, perm, axis=-1)
    q = packing.unpack_int4_rows(qweight, k)                    # (K, N) int8
    z = packing.unpack_int4_cols(qzeros, n)                     # (G, N) int8
    g = group_size if group_size > 0 else k
    w = (q.reshape(k // g, g, n).astype(scales.dtype)
         - z[:, None, :].astype(scales.dtype)) * scales[:, None, :]
    w = w.reshape(k, n)
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                preferred_element_type=jnp.float32)
    return y.astype(out_dtype)


def dequant_ref(qweight: jnp.ndarray, scales: jnp.ndarray, qzeros: jnp.ndarray,
                *, group_size: int, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Standalone dequantization (the first pass of the 'naive' strategy)."""
    k = qweight.shape[0] * packing.NIBBLES_PER_WORD
    n = scales.shape[1]
    q = packing.unpack_int4_rows(qweight, k)
    z = packing.unpack_int4_cols(qzeros, n)
    g = group_size if group_size > 0 else k
    w = (q.reshape(k // g, g, n).astype(jnp.float32)
         - z[:, None, :].astype(jnp.float32)) * scales[:, None, :].astype(jnp.float32)
    return w.reshape(k, n).astype(dtype)


def flash_attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True, window: int | None = None,
                        scale: float | None = None) -> jnp.ndarray:
    """Reference attention. q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D). GQA via
    head repetition. Optional causal + sliding-window masking."""
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    assert h % hkv == 0
    rep = h // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(sq)[:, None] + (sk - sq)   # right-aligned decode support
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    logits = jnp.where(mask[None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _token_major_pools(k_pages, v_pages, k_scales, v_scales):
    """Head-major (Hkv, P, ps, D) pools -> dequantized (P, ps, Hkv, D)."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    if k_scales is not None:
        k_pages = kv_quant.dequantize(k_pages, k_scales, dtype=jnp.float32)
        v_pages = kv_quant.dequantize(v_pages, v_scales, dtype=jnp.float32)
    return k_pages.transpose(1, 2, 0, 3), v_pages.transpose(1, 2, 0, 3)


def paged_attention_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                        v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                        lengths: jnp.ndarray, *,
                        k_scales: jnp.ndarray | None = None,
                        v_scales: jnp.ndarray | None = None,
                        scale: float | None = None) -> jnp.ndarray:
    """Oracle for ``kernels/paged_attention.py``: gather every sequence's
    pages into a contiguous (B, max_pages*page_size, Hkv, D) view, then run
    masked grouped attention.  q: (B, H, D); k/v_pages: (Hkv, P, ps, D)
    head-major; block_tables: (B, max_pages) int32; lengths: (B,) int32.
    -> (B, H, D).

    ``k_scales``/``v_scales`` — (Hkv, P, ps) per-token or (Hkv, P) per-page
    symmetric scales for int8 pools (``serving/kv_quant.py``): the oracle
    simply materializes the dequantized pools, which the kernel never does."""
    k_pages, v_pages = _token_major_pools(k_pages, v_pages, k_scales,
                                          v_scales)
    b, h, d = q.shape
    _, ps, hkv, _ = k_pages.shape
    rep = h // hkv
    k = k_pages[block_tables].reshape(b, -1, hkv, d)    # (B, maxp*ps, Hkv, D)
    v = v_pages[block_tables].reshape(b, -1, hkv, d)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qg = q.reshape(b, hkv, rep, d)
    logits = jnp.einsum("bgrd,bkgd->bgrk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = kpos < lengths[:, None]                      # (B, maxp*ps)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    # exact-zero masked keys: a lengths[b] == 0 row yields 0 output, matching
    # the kernel's fully-masked-page convention
    p = p * mask[:, None, None]
    out = jnp.einsum("bgrk,bkgd->bgrd", p, v.astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_prefill_ref(q: jnp.ndarray, k_pages: jnp.ndarray,
                      v_pages: jnp.ndarray, block_tables: jnp.ndarray,
                      seq_start: jnp.ndarray, lengths: jnp.ndarray, *,
                      k_scales: jnp.ndarray | None = None,
                      v_scales: jnp.ndarray | None = None,
                      scale: float | None = None) -> jnp.ndarray:
    """Oracle for ``kernels/paged_attention.py::paged_prefill``: gather every
    sequence's pages into a contiguous (B, max_pages*page_size, Hkv, D) view
    (densely dequantized when int8 — exactly the materialization the kernel
    exists to avoid), then run causally masked grouped attention over the
    whole suffix block.  Pools are head-major (Hkv, P, ps, D), as for the
    kernel.

    q: (B, S, H, D) — query i of row b sits at absolute position
    ``seq_start[b] + i``; ``lengths``: (B,) total valid keys per row
    (``seq_start + write_lens``), masking right-padded bucket positions and
    unwritten reserve pages.  Fully-masked query rows yield exact zeros,
    matching the kernel's zero-normalizer convention.  -> (B, S, H, D).
    """
    k_pages, v_pages = _token_major_pools(k_pages, v_pages, k_scales,
                                          v_scales)
    b, s, h, d = q.shape
    _, ps, hkv, _ = k_pages.shape
    rep = h // hkv
    k = k_pages[block_tables].reshape(b, -1, hkv, d)    # (B, maxp*ps, Hkv, D)
    v = v_pages[block_tables].reshape(b, -1, hkv, d)
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qg = q.reshape(b, s, hkv, rep, d)
    logits = jnp.einsum("bqgrd,bkgd->bgrqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = seq_start[:, None] + jnp.arange(s)[None, :]           # (B, S)
    kpos = jnp.arange(k.shape[1])                                # (K,)
    mask = ((kpos[None, None, :] <= qpos[:, :, None])
            & (kpos[None, None, :] < lengths[:, None, None]))    # (B, S, K)
    logits = jnp.where(mask[:, None, None], logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    p = p * mask[:, None, None]
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


def selective_scan_ref(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                       b: jnp.ndarray, c: jnp.ndarray, d: jnp.ndarray,
                       h0: jnp.ndarray | None = None):
    """Mamba-1 selective scan oracle.

    x, dt: (B, L, Di); a: (Di, S); b, c: (B, L, S); d: (Di,)
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * B_t * x_t ;  y_t = C_t . h_t + D*x_t
    Returns (y: (B, L, Di), h_last: (B, Di, S)).
    """
    bsz, length, di = x.shape
    s = a.shape[1]
    xf = x.astype(jnp.float32)
    dtf = jax.nn.softplus(dt.astype(jnp.float32))

    def step(h, inp):
        # da/dbx computed per timestep — materializing the full (B, L, Di, S)
        # discretization costs 16x the activation bytes (550 TB at train_4k
        # production shape; see EXPERIMENTS.md §Roofline notes)
        x_t, dt_t, b_t, c_t = inp                                # (B,Di),(B,Di),(B,S),(B,S)
        da_t = jnp.exp(dt_t[..., None] * a[None])                # (B, Di, S)
        h = da_t * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bds,bs->bd", h, c_t)
        return h, y

    h0 = jnp.zeros((bsz, di, s), jnp.float32) if h0 is None else h0.astype(jnp.float32)
    hl, ys = jax.lax.scan(step, h0,
                          (xf.transpose(1, 0, 2), dtf.transpose(1, 0, 2),
                           b.transpose(1, 0, 2), c.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2) + xf * d[None, None, :]
    return y.astype(x.dtype), hl
