"""Pallas TPU flash attention (prefill): online-softmax tiling so the (Sq, Sk)
logits never leave VMEM — the attention-side complement to the paper's
memory-centric kernel work (DESIGN.md §6 tiling conventions).

Grid (B*H, Sq/bq, Sk/bk), K innermost (arbitrary); VMEM carries the running
max m, normalizer l, and output accumulator per (bq, d) block. Causal blocks
above the diagonal are masked; fully-masked tiles still execute (structural
grid) — block-level early-exit is a TPU-side optimization left to the
compiler's dimension semantics.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lane import resolve_interpret


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, bq, bk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                     # (bq, d)
    k = k_ref[0].astype(jnp.float32)                     # (bk, d)
    v = v_ref[0].astype(jnp.float32)                     # (bk, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(kpos <= qpos, s, -jnp.inf)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    # guard fully-masked rows (m == -inf): exp(-inf - -inf) -> use 0 correction
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
    p = jnp.exp(jnp.where(jnp.isfinite(s), s - safe_m[:, None], -jnp.inf))
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l_new = corr * l_ref[...] + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        denom = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, bq: int = 128, bk: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """q, k, v: (B, S, H, D) with H == Hkv (repeat GQA outside). -> (B,S,H,D)."""
    interpret = resolve_interpret(interpret)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    scale = 1.0 / math.sqrt(d)

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, sk, d)

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq, bk=bk),
        grid=(b * h, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, qi, ki: (g, qi, 0)),
            pl.BlockSpec((1, bk, d), lambda g, qi, ki: (g, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda g, qi, ki: (g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, qi, ki: (g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
