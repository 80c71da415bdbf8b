"""Pallas TPU selective-scan (Mamba-1) kernel — the deployment answer to
EXPERIMENTS.md §Perf cell D: the SSM state (bd, S) lives in a VMEM scratch
across all timesteps, so HBM traffic is just the x/dt/B/C streams + one final
state writeback, instead of the jnp scan's per-step (B, Di, S) state
round-trip (4096x/layer at train_4k).

Grid (B, Di/bd, L/bl), L innermost (arbitrary semantics). Discretization is
computed per timestep in-register (never materializing (B, L, Di, S) — the
cell-D lesson applied in-kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lane import resolve_interpret


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, h0_ref,
            y_ref, hout_ref, h_ref, *, bl):
    li = pl.program_id(2)

    @pl.when(li == 0)
    def _():
        h_ref[...] = h0_ref[...][0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)                     # (bd, S)
    dvec = d_ref[...].astype(jnp.float32)                  # (bd,)

    # NB: slice-based ref indexing throughout — integer indices in ref
    # load/store tuples break the interpret-mode discharge rules on some
    # jax versions (`'int' object has no attribute 'shape'`).
    def step(j, h):
        row = (slice(0, 1), pl.ds(j, 1), slice(None))
        xt = x_ref[row][0, 0].astype(jnp.float32)                    # (bd,)
        dt = jax.nn.softplus(dt_ref[row][0, 0].astype(jnp.float32))
        bt = b_ref[row][0, 0].astype(jnp.float32)                    # (S,)
        ct = c_ref[row][0, 0].astype(jnp.float32)
        da = jnp.exp(dt[:, None] * a)                      # (bd, S)
        h = da * h + (dt * xt)[:, None] * bt[None, :]
        y = jnp.sum(h * ct[None, :], axis=1) + dvec * xt
        y_ref[row] = y[None, None, :].astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, bl, step, h_ref[...])

    @pl.when(li == pl.num_programs(2) - 1)
    def _():
        hout_ref[...] = h_ref[...][None].astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "bl", "interpret"))
def selective_scan(x, dt, a, b, c, d, h0=None, *, bd: int = 512,
                   bl: int = 128, interpret: bool | None = None):
    """x, dt: (B, L, Di); a: (Di, S); b, c: (B, L, S); d: (Di,);
    h0: (B, Di, S) or None. Returns (y (B, L, Di), h_last (B, Di, S))."""
    interpret = resolve_interpret(interpret)
    bsz, length, di = x.shape
    s = a.shape[1]
    bd = min(bd, di)
    bl = min(bl, length)
    assert di % bd == 0 and length % bl == 0, (di, length, bd, bl)
    if h0 is None:
        h0 = jnp.zeros((bsz, di, s), jnp.float32)

    grid = (bsz, di // bd, length // bl)
    y, h_last = pl.pallas_call(
        functools.partial(_kernel, bl=bl),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bl, bd), lambda bi, di_, li: (bi, li, di_)),  # x
            pl.BlockSpec((1, bl, bd), lambda bi, di_, li: (bi, li, di_)),  # dt
            pl.BlockSpec((bd, s), lambda bi, di_, li: (di_, 0)),           # a
            pl.BlockSpec((1, bl, s), lambda bi, di_, li: (bi, li, 0)),     # b
            pl.BlockSpec((1, bl, s), lambda bi, di_, li: (bi, li, 0)),     # c
            pl.BlockSpec((bd,), lambda bi, di_, li: (di_,)),               # d
            pl.BlockSpec((1, bd, s), lambda bi, di_, li: (bi, di_, 0)),    # h0
        ],
        out_specs=[
            pl.BlockSpec((1, bl, bd), lambda bi, di_, li: (bi, li, di_)),
            pl.BlockSpec((1, bd, s), lambda bi, di_, li: (bi, di_, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, length, di), x.dtype),
            jax.ShapeDtypeStruct((bsz, di, s), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, s), jnp.float32)],
        interpret=interpret,
    )(x, dt, a, b, c, d, h0)
    return y, h_last
