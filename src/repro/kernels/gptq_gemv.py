"""Pallas TPU kernel for the decode fast lane: fused W4A16 GEMV.

Decode is a batch of single-token matvecs — M <= the slot batch (typically 8,
one sublane tile) while K and N are model-sized — so the general
``gptq_matmul`` grid wastes its M tiling and pays one program launch per
(mi, ni, ki) cell.  This kernel is specialized for that shape:

* **N-major grid** ``(N // bn,)`` — one program per output column block; the
  full K reduction happens inside the program (a static loop over K
  chunks), so there is no K grid dimension at all.  The served strategy
  runs ``gptq_matmul.fused_full_k`` with one 8-row sublane tile of M; the
  kernel below serves the ablation strategies (interpret mode only).
* **SMB** — the fp32 accumulator lives in VMEM scratch for the whole
  reduction and is written back exactly once.  This grid *is* the SMB
  optimization: strategies with ``accum_vmem=False`` intentionally keep the
  general kernel's K-outermost grid (output block revisited through HBM each
  sweep — the paper's atomicAdd-traffic baseline) and are delegated.
* **VML** — weights stream as packed int32 words (8 nibbles each) and are
  unpacked with vector shifts in-register; ``packed_loads=False`` takes the
  pre-expanded int8 array at 2x the HBM bytes.
* **ILA** — the dequantized (bk, bn) chunk feeds the MXU via ``jnp.dot``
  (decode M pads to a full sublane, so the MXU still helps); ``mxu=False``
  runs the VPU broadcast multiply-add loop.
* **Fused bias** — the bias column block is added during the single
  writeback instead of a separate elementwise pass over (M, N).

Dispatch policy lives in ``kernels/ops.py::gptq_linear`` (M-threshold route:
decode -> here, prefill -> ``gptq_matmul``).  Block sizes come from the
caller or from ``kernels/autotune.py``.  See DESIGN.md §7.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.opt_strategies import KernelStrategy, OPT4GPTQ
from repro.kernels import gptq_matmul as _gm
from repro.kernels.gptq_matmul import (NIB, _compute_tile, _dequant_tile,
                                       _round_up, _unpack_cols_block,
                                       _unpack_rows_block, full_k_strategy,
                                       fused_full_k, pad_cols,
                                       resolve_block_sizes)
from repro.kernels.lane import resolve_interpret

# M at or below this routes to the GEMV lane (ops.gptq_linear dispatcher):
# one padded sublane tile, the paper's decode regime.
GEMV_M_MAX = 8


def _kernel_gemv(x_ref, qw_ref, s_ref, qz_ref, b_ref, o_ref, acc_ref, *,
                 bk, nk, group_size, strategy: KernelStrategy):
    """One output column block: full-K reduction in VMEM, single writeback.

    The K loop is a *static* Python unroll (nk = K/bk is a trace-time
    constant, small by construction): every ref slice is static, so nothing
    lowers to while-loops or dynamic slices — the chunking only bounds the
    live dequant tile at (bk, bn) instead of (K, bn)."""
    bn = o_ref.shape[1]
    g = group_size
    gk = max(bk // g, 1)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    for j in range(nk):
        if strategy.packed_loads:
            kw = bk // NIB
            w_nib = _unpack_rows_block(qw_ref[j * kw:(j + 1) * kw, :], bk)
        else:
            w_nib = qw_ref[j * bk:(j + 1) * bk, :].astype(jnp.float32)
        goff = (j * bk) // g
        s = s_ref[goff:goff + gk, :].astype(jnp.float32)
        z = _unpack_cols_block(qz_ref[goff:goff + gk, :], bn)
        w = _dequant_tile(w_nib, s, z, bk, g)
        x_chunk = x_ref[:, j * bk:(j + 1) * bk].astype(jnp.float32)
        acc_ref[...] += _compute_tile(x_chunk, w, strategy.mxu)
    o_ref[...] = (acc_ref[...] + b_ref[...].astype(jnp.float32)
                  ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("group_size", "strategy", "bn", "bk", "out_dtype",
                     "interpret"))
def gptq_gemv(x: jnp.ndarray, qweight: jnp.ndarray, scales: jnp.ndarray,
              qzeros: jnp.ndarray, bias: jnp.ndarray | None = None, *,
              group_size: int, strategy: KernelStrategy = OPT4GPTQ,
              bn: int = 256, bk: int = 512, out_dtype=None,
              interpret: bool | None = None) -> jnp.ndarray:
    """Fused GPTQ GEMV: y = x @ dequant(qweight) + bias for small-M decode.

    x: (M, K) with M <= GEMV_M_MAX (padded to a sublane tile).  qweight is
    (K//8, N) int32 when ``strategy.packed_loads`` else (K, N) int8.  Caller
    applies the act-order permutation to x (see ops.gptq_linear).  Strategies
    without the fused+VMEM-accumulator structure delegate to ``gptq_matmul``
    (their ablation semantics are grid-level, which this lane removes).
    """
    interpret = resolve_interpret(interpret)
    m, k = x.shape
    n = scales.shape[1]
    out_dtype = out_dtype or x.dtype
    if full_k_strategy(strategy):
        return fused_full_k(x, qweight, scales, qzeros, bias,
                            group_size=group_size, bm=8, bn=bn, bk=bk,
                            out_dtype=out_dtype, interpret=interpret,
                            name="gptq_gemv")
    if not (strategy.fused and strategy.accum_vmem):
        y = _gm.gptq_matmul(x, qweight, scales, qzeros,
                            group_size=group_size, strategy=strategy,
                            bm=8, bn=bn, bk=bk, out_dtype=out_dtype,
                            interpret=interpret)
        if bias is not None:
            y = y + bias.astype(y.dtype)
        return y

    g = group_size if group_size > 0 else k
    _, bn, bk = resolve_block_sizes(m, k, n, group_size, 8, bn, bk)
    qweight, scales, qzeros, n_pad = pad_cols(qweight, scales, qzeros, n, bn)
    bm = _round_up(m, 8)
    if bm != m:
        x = jnp.pad(x, ((0, bm - m), (0, 0)))
    if bias is None:
        b = jnp.zeros((1, n_pad), jnp.float32)
    else:
        b = bias.reshape(1, n).astype(jnp.float32)
        if n_pad != n:
            b = jnp.pad(b, ((0, 0), (0, n_pad - n)))

    nn, nk = n_pad // bn, k // bk
    gtot = scales.shape[0]
    if strategy.packed_loads:
        qw_spec = pl.BlockSpec((k // NIB, bn), lambda ni: (0, ni))
    else:
        qw_spec = pl.BlockSpec((k, bn), lambda ni: (0, ni))

    y = pl.pallas_call(
        functools.partial(_kernel_gemv, bk=bk, nk=nk, group_size=g,
                          strategy=strategy),
        grid=(nn,),                                  # N-major, no K dimension
        in_specs=[
            pl.BlockSpec((bm, k), lambda ni: (0, 0)),
            qw_spec,
            pl.BlockSpec((gtot, bn), lambda ni: (0, ni)),
            pl.BlockSpec((gtot, bn // NIB), lambda ni: (0, ni)),
            pl.BlockSpec((1, bn), lambda ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda ni: (0, ni)),
        out_shape=jax.ShapeDtypeStruct((bm, n_pad), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="gptq_gemv",
    )(x, qweight, scales, qzeros, b)
    return y[:m, :n]
