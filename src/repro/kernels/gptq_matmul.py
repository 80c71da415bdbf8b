"""Pallas TPU kernel for fused GPTQ W4A16 matmul — the paper's kernel.

y[M, N] = x[M, K] @ dequant(qweight[K//8, N], scales[G, N], qzeros[G, N//8])

The served strategy (all three optimizations on, ``OPT4GPTQ``) runs the
**full-K kernel** (``fused_full_k``), shared with the decode GEMV lane:

* grid ``(M // bm, N // bn)``; each program holds the whole K extent of its
  weight column block in VMEM and reduces it in a static loop over chunks
  of 128 packed rows, so every block satisfies the TPU (8, 128) tiling rule
  at any K (K=2560 is 2.5 lane tiles of packed rows);
* the nibble unpack never reshapes across sublanes: the *activations* are
  de-interleaved instead (``x[:, 8r + j]`` becomes plane ``j``), so nibble
  ``j`` of packed row ``r`` multiplies plane ``j`` — eight MXU dots per chunk;
* zero points are unpacked to (G, N) int8 outside the kernel (G = K/group
  rows: about 1/64 of the weight bytes), so their block is lane-aligned
  where the packed (G, N//8) layout would need bn to be a multiple of 1024.

The other strategies are the paper's ablation variants, kept on the
K-blocked grid below; they are validated in interpret mode only.

Strategy flags (core/opt_strategies.py) select the paper's ablation variants:

* SMB  (``accum_vmem``): fp32 VMEM scratch accumulator, K-innermost grid,
  single writeback on the last K step — vs. K-OUTERMOST grid where every K
  step revisits the HBM-backed output block (read-modify-write), the TPU
  analogue of the DCU baseline's per-thread global atomicAdd traffic.
* VML  (``packed_loads``): weights arrive as packed int32 (8 nibbles/word,
  K/8 rows) and are unpacked with vector shifts in VREGs — vs. a pre-expanded
  int8 array with 2x the HBM footprint.
* ILA  (``mxu``): the dequantized (bk, bn) tile feeds the MXU via ``jnp.dot``
  (f32 accumulation) — vs. a VPU fori-loop of broadcast multiply+add
  (the compiler-generated-scalar-code analogue).

Tiling: blocks are (8,128)-aligned; defaults bm=128, bn=256, bk=512 give a
~0.33 MB working set (see DESIGN.md §6).  Requested block sizes are legalized
for the actual shape (``resolve_block_sizes``: bk shrinks to divide K and
align with the group size; N is zero-padded up to a multiple of bn) — a
``ValueError`` is raised only for shapes the packed layout cannot serve.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import packing
from repro.core.opt_strategies import KernelStrategy, OPT4GPTQ
from repro.kernels.lane import resolve_interpret

NIB = packing.NIBBLES_PER_WORD
LANE = 128
CHUNK_ROWS = 128           # packed rows per K step of the full-K kernel
VMEM_LIMIT = 64 * 1024 * 1024


def nibble(q, j: int):
    """Nibble ``j`` of every int32 word of ``q``, as int32 in [0, 15].

    A logical shift on int32 words: Mosaic has no uint32 -> float cast, so
    the unpack never leaves int32 (the sign bit of nibble 7 is shifted out,
    not extended)."""
    return jax.lax.shift_right_logical(q, jnp.int32(4 * j)) & 0xF


def _unpack_rows_block(qw, bk):
    """(bk//8, bn) int32 -> (bk, bn) f32 nibble values, vector shift/mask."""
    nib = jnp.stack([nibble(qw, j) for j in range(NIB)], axis=1)
    return nib.reshape(bk, qw.shape[-1]).astype(jnp.float32)


def _unpack_cols_block(qz, bn):
    """(gk, bn//8) int32 -> (gk, bn) f32 zero points."""
    nib = jnp.stack([nibble(qz, j) for j in range(NIB)], axis=2)
    return nib.reshape(qz.shape[0], bn).astype(jnp.float32)


def _dequant_tile(w_nib, s, z, bk, group_size):
    """(bk, bn) nibbles + (gk, bn) scales/zeros -> (bk, bn) dequantized f32."""
    gk = s.shape[0]
    if gk == 1:
        return (w_nib - z) * s                       # broadcast over rows
    reps = bk // gk
    s_rep = jnp.repeat(s, reps, axis=0)
    z_rep = jnp.repeat(z, reps, axis=0)
    return (w_nib - z_rep) * s_rep


def _compute_tile(x_tile, w_tile, mxu: bool):
    """x:(bm,bk) f32  w:(bk,bn) f32 -> (bm,bn) f32 partial product."""
    if mxu:
        return jnp.dot(x_tile, w_tile, preferred_element_type=jnp.float32)
    # ILA-off: VPU broadcast multiply + add, one K row per step.
    bm, bk = x_tile.shape
    bn = w_tile.shape[1]

    def body(j, acc):
        xj = jax.lax.dynamic_slice_in_dim(x_tile, j, 1, axis=1)       # (bm, 1)
        wj = jax.lax.dynamic_slice_in_dim(w_tile, j, 1, axis=0)       # (1, bn)
        return acc + xj * wj

    return jax.lax.fori_loop(0, bk, body, jnp.zeros((bm, bn), jnp.float32))


# --------------------------------------------------------------------- kernels
def _kernel_vmem(x_ref, qw_ref, s_ref, qz_ref, o_ref, acc_ref, *,
                 bk, group_size, strategy: KernelStrategy):
    """K-innermost grid; fp32 VMEM accumulator; single writeback (SMB on)."""
    knum = pl.num_programs(2)
    kidx = pl.program_id(2)

    @pl.when(kidx == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if strategy.packed_loads:
        w_nib = _unpack_rows_block(qw_ref[...], bk)
    else:
        w_nib = qw_ref[...].astype(jnp.float32)
    z = _unpack_cols_block(qz_ref[...], s_ref.shape[1])
    w = _dequant_tile(w_nib, s_ref[...].astype(jnp.float32), z, bk, group_size)
    acc_ref[...] += _compute_tile(x_ref[...].astype(jnp.float32), w, strategy.mxu)

    @pl.when(kidx == knum - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _kernel_hbm(x_ref, qw_ref, s_ref, qz_ref, o_ref, *,
                bk, group_size, strategy: KernelStrategy):
    """K-OUTERMOST grid; output block revisited (evict+reload through HBM each
    K sweep) — the global-memory atomic-accumulation analogue (SMB off)."""
    kidx = pl.program_id(0)

    @pl.when(kidx == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    if strategy.packed_loads:
        w_nib = _unpack_rows_block(qw_ref[...], bk)
    else:
        w_nib = qw_ref[...].astype(jnp.float32)
    z = _unpack_cols_block(qz_ref[...], s_ref.shape[1])
    w = _dequant_tile(w_nib, s_ref[...].astype(jnp.float32), z, bk, group_size)
    part = _compute_tile(x_ref[...].astype(jnp.float32), w, strategy.mxu)
    o_ref[...] += part.astype(o_ref.dtype)


def _kernel_dequant(qw_ref, s_ref, qz_ref, w_ref, *, bk, group_size, packed):
    """Pass 1 of the 'naive' strategy: materialize bf16 weights to HBM."""
    if packed:
        w_nib = _unpack_rows_block(qw_ref[...], bk)
    else:
        w_nib = qw_ref[...].astype(jnp.float32)
    z = _unpack_cols_block(qz_ref[...], s_ref.shape[1])
    w = _dequant_tile(w_nib, s_ref[...].astype(jnp.float32), z, bk, group_size)
    w_ref[...] = w.astype(w_ref.dtype)


def _kernel_matmul(x_ref, w_ref, o_ref, acc_ref):
    """Pass 2 of the 'naive' strategy: plain bf16 matmul (re-reads W from HBM)."""
    kidx = pl.program_id(2)

    @pl.when(kidx == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...].astype(jnp.float32),
                            w_ref[...].astype(jnp.float32),
                            preferred_element_type=jnp.float32)

    @pl.when(kidx == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def full_k_strategy(strategy: KernelStrategy) -> bool:
    """The served strategy: fused, VMEM accumulation, packed loads, MXU."""
    return (strategy.fused and strategy.accum_vmem and strategy.packed_loads
            and strategy.mxu)


def _kernel_full_k(x_ref, qw_ref, s_ref, z_ref, b_ref, o_ref, *, chunks,
                   rows_per_group):
    """One (bm, bn) output block over the whole K extent.

    ``x_ref`` is (8, bm, K//8): plane ``j`` holds the activations of K rows
    ``8r + j``, so it pairs with nibble ``j`` of packed weight row ``r``.
    ``chunks`` are static (start, end) packed-row spans; each lies inside
    one quantization group or starts on a group boundary, so its scales and
    zeros are group rows repeated ``rows_per_group`` times."""
    bm, bn = o_ref.shape
    cdt = jnp.bfloat16 if x_ref.dtype == jnp.bfloat16 else jnp.float32
    s_all = s_ref[...].astype(jnp.float32)
    z_all = z_ref[...].astype(jnp.float32)
    acc = jnp.zeros((bm, bn), jnp.float32)
    for r0, r1 in chunks:
        g0, g1 = r0 // rows_per_group, -(-r1 // rows_per_group)
        s, z = s_all[g0:g1], z_all[g0:g1]
        if g1 - g0 > 1:
            rows = (g1 - g0, rows_per_group, bn)
            s = jnp.broadcast_to(s[:, None], rows).reshape(-1, bn)
            z = jnp.broadcast_to(z[:, None], rows).reshape(-1, bn)
        q = qw_ref[r0:r1, :]
        for j in range(NIB):
            w = ((nibble(q, j).astype(jnp.float32) - z) * s).astype(cdt)
            acc += jnp.dot(x_ref[j, :, r0:r1].astype(cdt), w,
                           preferred_element_type=jnp.float32)
    o_ref[...] = (acc + b_ref[...]).astype(o_ref.dtype)


def _lane_block(n: int, bn: int) -> tuple[int, int]:
    """(bn, n_pad): a lane-aligned column block dividing the padded N.  N
    below one lane tile is one full-width block; other N pad to a lane
    multiple (only odd test shapes do — model widths are lane multiples)."""
    if n < LANE:
        return n, n
    n_pad = _round_up(n, LANE)
    bn = max(LANE, min(bn, n_pad) // LANE * LANE)
    while n_pad % bn:
        bn -= LANE
    return bn, n_pad


def _k_chunks(kw: int, rows_per_group: int, bk: int) -> tuple:
    """Static packed-row spans of the full-K loop: ``bk // 8`` rows rounded
    to lane tiles (x plane slices start on a lane boundary), and never a
    span that straddles a group boundary without starting on one."""
    step = max(CHUNK_ROWS, bk // NIB // CHUNK_ROWS * CHUNK_ROWS)
    if step % rows_per_group and rows_per_group % step:
        step = rows_per_group
    return tuple((r, min(r + step, kw)) for r in range(0, kw, step))


def fused_full_k(x: jnp.ndarray, qweight: jnp.ndarray, scales: jnp.ndarray,
                 qzeros: jnp.ndarray, bias: jnp.ndarray | None, *,
                 group_size: int, bm: int, bn: int, bk: int, out_dtype,
                 interpret: bool, name: str) -> jnp.ndarray:
    """The served W4A16 kernel (decode GEMV and prefill matmul alike).
    x: (M, K); qweight: (K//8, N) int32; returns (M, N) + bias.  ``name``
    is the device op's name in a profile (``gptq_gemv`` / ``gptq_matmul``),
    kept stable whatever function encloses the call."""
    m, k = x.shape
    n = scales.shape[1]
    if k % NIB:
        raise ValueError(f"K={k} not divisible by {NIB}: unservable by int4 "
                         f"row packing (shape M={m}, K={k}, N={n})")
    g = group_size if group_size > 0 else k
    kw = k // NIB
    bn, n_pad = _lane_block(n, bn)
    z = packing.unpack_int4_cols(qzeros, n)        # (G, N) int8, lane-aligned
    b = (jnp.zeros((1, n), jnp.float32) if bias is None
         else bias.reshape(1, n).astype(jnp.float32))
    if n_pad != n:
        dn = n_pad - n
        qweight = jnp.pad(qweight, ((0, 0), (0, dn)))
        scales = jnp.pad(scales, ((0, 0), (0, dn)), constant_values=1.0)
        z = jnp.pad(z, ((0, 0), (0, dn)))
        b = jnp.pad(b, ((0, 0), (0, dn)))
    bm = max(8, min(_round_up(bm, 8), _round_up(m, 8)))
    m_pad = _round_up(m, bm)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    xd = x.reshape(m_pad, kw, NIB).transpose(2, 0, 1)     # (8, M, K//8)
    gtot = scales.shape[0]
    y = pl.pallas_call(
        functools.partial(_kernel_full_k,
                          chunks=_k_chunks(kw, g // NIB, bk),
                          rows_per_group=g // NIB),
        grid=(m_pad // bm, n_pad // bn),
        in_specs=[
            pl.BlockSpec((NIB, bm, kw), lambda mi, ni: (0, mi, 0)),
            pl.BlockSpec((kw, bn), lambda mi, ni: (0, ni)),
            pl.BlockSpec((gtot, bn), lambda mi, ni: (0, ni)),
            pl.BlockSpec((gtot, bn), lambda mi, ni: (0, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name=name,
    )(xd, qweight, scales, z, b)
    return y[:m, :n]


# ------------------------------------------------------------------ dispatcher
def _scale_block(bk, group_size):
    """Rows of the scales/zeros block covering bk K-rows."""
    return max(bk // group_size, 1)


def resolve_block_sizes(m: int, k: int, n: int, group_size: int,
                        bm: int, bn: int, bk: int) -> tuple[int, int, int]:
    """Shrink requested blocks to legal sizes for this shape.

    Legal means: bm/bn/bk multiples of 8 (packed rows come in 8-nibble words),
    bk divides K and aligns with the quantization group (bk % g == 0 or
    g % bk == 0).  N never constrains bn — the caller pads N up to a multiple
    of bn (see ``pad_cols``).  Raises ``ValueError`` only when no legal K
    block exists (K not servable by the packed layout).
    """
    g = group_size if group_size > 0 else k
    if k % NIB != 0:
        raise ValueError(
            f"K={k} not divisible by {NIB}: unservable by int4 row packing "
            f"(shape M={m}, K={k}, N={n}, group_size={group_size})")
    bm = max(min(_round_up(bm, 8), _round_up(m, 8)), 8)
    bn = max(min(_round_up(bn, 8), _round_up(n, 8)), 8)
    bk_req = max(min(bk, k) // NIB * NIB, NIB)
    bk = None
    for cand in range(bk_req, 0, -NIB):
        if k % cand == 0 and (cand % g == 0 or g % cand == 0):
            bk = cand
            break
    if bk is None:
        raise ValueError(
            f"no legal K block for M={m}, K={k}, N={n}, "
            f"group_size={group_size}: need a multiple of {NIB} that divides "
            f"K and aligns with the group size")
    return bm, bn, bk


def pad_cols(qweight: jnp.ndarray, scales: jnp.ndarray, qzeros: jnp.ndarray,
             n: int, bn: int):
    """Zero-pad the N axis up to a multiple of bn so any (8,128)-aligned bn is
    servable (e.g. N=1000 with bn=256 pads to 1024; output is sliced back).
    Padded columns dequantize to (0 - 0) * 1 = 0 and never reach the caller."""
    if n % NIB != 0:
        raise ValueError(f"N={n} not divisible by {NIB}: unservable by int4 "
                         f"column packing of qzeros")
    n_pad = _round_up(n, bn)
    if n_pad == n:
        return qweight, scales, qzeros, n
    dn = n_pad - n
    qweight = jnp.pad(qweight, ((0, 0), (0, dn)))
    scales = jnp.pad(scales, ((0, 0), (0, dn)), constant_values=1.0)
    qzeros = jnp.pad(qzeros, ((0, 0), (0, dn // NIB)))
    return qweight, scales, qzeros, n_pad


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "strategy", "bm", "bn", "bk", "out_dtype",
                     "interpret"))
def gptq_matmul(x: jnp.ndarray, qweight: jnp.ndarray, scales: jnp.ndarray,
                qzeros: jnp.ndarray, *, group_size: int,
                strategy: KernelStrategy = OPT4GPTQ,
                bm: int = 128, bn: int = 256, bk: int = 512,
                out_dtype=None, interpret: bool | None = None) -> jnp.ndarray:
    """Fused GPTQ matmul. x: (M, K). qweight: (K//8, N) int32 when
    ``strategy.packed_loads`` else (K, N) int8 (pre-expanded). Caller applies
    the act-order permutation to x (see ops.gptq_linear)."""
    interpret = resolve_interpret(interpret)
    m, k = x.shape
    n = scales.shape[1]
    if full_k_strategy(strategy):
        return fused_full_k(x, qweight, scales, qzeros, None,
                            group_size=group_size, bm=bm, bn=bn, bk=bk,
                            out_dtype=out_dtype or x.dtype,
                            interpret=interpret, name="gptq_matmul")
    g = group_size if group_size > 0 else k
    bm, bn, bk = resolve_block_sizes(m, k, n, group_size, bm, bn, bk)
    qweight, scales, qzeros, n_pad = pad_cols(qweight, scales, qzeros, n, bn)
    gk = _scale_block(bk, g)
    # scales/qzeros row *block* index for K-step ki: BlockSpec index maps
    # count in blocks of gk rows, so the group-row element offset ki*bk//g
    # must be divided by the block height — ki when bk >= g (each K block
    # owns its own gk group rows), ki*bk//g when bk < g (several K blocks
    # share one group row).  The previous ki*bk//g element-offset form read
    # the wrong group rows whenever gk > 1 and K spanned > 2 blocks
    # (interpret-mode index clamping masked it at 2).
    sdiv = g * gk

    def _s_inner(mi, ni, ki):
        return (ki * bk // sdiv, ni)

    def _s_outer(ki, mi, ni):
        return (ki * bk // sdiv, ni)

    m_pad = _round_up(m, bm)
    if m_pad != m:
        x = jnp.pad(x, ((0, m_pad - m), (0, 0)))
    nm, nn, nk = m_pad // bm, n_pad // bn, k // bk
    out_dtype = out_dtype or x.dtype
    out_shape = jax.ShapeDtypeStruct((m_pad, n_pad), out_dtype)

    if strategy.packed_loads:
        qw_spec_inner = pl.BlockSpec((bk // NIB, bn), lambda mi, ni, ki: (ki, ni))
        qw_spec_outer = pl.BlockSpec((bk // NIB, bn), lambda ki, mi, ni: (ki, ni))
    else:
        qw_spec_inner = pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni))
        qw_spec_outer = pl.BlockSpec((bk, bn), lambda ki, mi, ni: (ki, ni))

    if not strategy.fused:
        # naive two-pass: dequant whole W to HBM, then matmul re-reads it.
        w_bf16 = pl.pallas_call(
            functools.partial(_kernel_dequant, bk=bk, group_size=g,
                              packed=strategy.packed_loads),
            grid=(nk, nn),
            in_specs=[
                pl.BlockSpec((bk // NIB, bn) if strategy.packed_loads else (bk, bn),
                             lambda ki, ni: (ki, ni)),
                pl.BlockSpec((gk, bn), lambda ki, ni: (ki * bk // sdiv, ni)),
                pl.BlockSpec((gk, bn // NIB),
                             lambda ki, ni: (ki * bk // sdiv, ni)),
            ],
            out_specs=pl.BlockSpec((bk, bn), lambda ki, ni: (ki, ni)),
            out_shape=jax.ShapeDtypeStruct((k, n_pad), jnp.bfloat16),
            interpret=interpret,
        )(qweight, scales, qzeros)
        y = pl.pallas_call(
            _kernel_matmul,
            grid=(nm, nn, nk),
            in_specs=[
                pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
                pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
        )(x, w_bf16)
        return y[:m, :n]

    if strategy.accum_vmem:
        y = pl.pallas_call(
            functools.partial(_kernel_vmem, bk=bk, group_size=g,
                              strategy=strategy),
            grid=(nm, nn, nk),                      # K innermost
            in_specs=[
                pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
                qw_spec_inner,
                pl.BlockSpec((gk, bn), _s_inner),
                pl.BlockSpec((gk, bn // NIB), _s_inner),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
            out_shape=out_shape,
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
            interpret=interpret,
        )(x, qweight, scales, qzeros)
    else:
        y = pl.pallas_call(
            functools.partial(_kernel_hbm, bk=bk, group_size=g,
                              strategy=strategy),
            grid=(nk, nm, nn),                      # K OUTERMOST: HBM revisits
            in_specs=[
                pl.BlockSpec((bm, bk), lambda ki, mi, ni: (mi, ki)),
                qw_spec_outer,
                pl.BlockSpec((gk, bn), _s_outer),
                pl.BlockSpec((gk, bn // NIB), _s_outer),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda ki, mi, ni: (mi, ni)),
            out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), jnp.float32),
            interpret=interpret,
        )(x, qweight, scales, qzeros)
        y = y.astype(out_dtype)
    return y[:m, :n]


def _round_up(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult
