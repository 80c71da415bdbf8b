"""Public jit'd wrappers dispatching Pallas kernels vs pure-jnp references.

``use_pallas=False`` routes to the ref oracle — identical math and HBM
traffic; ``use_pallas=True`` invokes the Pallas kernels, compiled or
interpreted as ``interpret`` says (``None``: from the backend, see
``kernels/lane.py``; ``KernelConfig.resolve()`` makes the choice for the
model).

The Pallas path has two lanes (DESIGN.md §7):

* **decode** (M <= ``gptq_gemv.GEMV_M_MAX``): the fused W4A16 GEMV kernel —
  N-major grid, full-K VMEM reduction, fused bias add.
* **prefill/train** (larger M): the general tiled ``gptq_matmul``.

``block_sizes`` may be a concrete (bm, bn, bk) tuple, ``None`` (kernel
defaults), or the string ``"auto"`` — the per-shape autotuner cache
(``kernels/autotune.py``).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import packing
from repro.core.gptq import QuantizedLinear
from repro.core.opt_strategies import KernelStrategy, OPT4GPTQ
from repro.kernels import gptq_gemv as _gemv
from repro.kernels import gptq_matmul as _gm
from repro.kernels import ref as _ref
from repro.kernels.gptq_gemv import GEMV_M_MAX
from repro.kernels.lane import resolve_interpret


def gptq_linear(ql: QuantizedLinear, x: jnp.ndarray, *,
                strategy: KernelStrategy = OPT4GPTQ,
                use_pallas: bool = False, interpret: bool | None = None,
                block_sizes: tuple[int, int, int] | str | None = None
                ) -> jnp.ndarray:
    """y = x @ dequant(W) + bias  for x of shape (..., K)."""
    k, n = ql.shape
    lead = x.shape[:-1]
    x2 = x.reshape(-1, k)
    if ql.perm is not None:
        x2 = jnp.take(x2, ql.perm, axis=-1)         # exllama-style b_q_perm
    m = x2.shape[0]

    if use_pallas:
        interpret = resolve_interpret(interpret)
        qw = (ql.qweight if strategy.packed_loads
              else packing.unpack_int4_rows(ql.qweight, k))   # VML-off: int8 2x
        if block_sizes == "auto":
            from repro.kernels import autotune                # lazy: optional
            block_sizes = autotune.get_block_sizes(
                m, k, n, ql.group_size, strategy, interpret=interpret)
        if m <= GEMV_M_MAX:
            # decode fast lane: fused GEMV with bias folded into writeback
            kwargs = {}
            if block_sizes is not None:
                kwargs = dict(zip(("bn", "bk"), block_sizes[1:]))
            y = _gemv.gptq_gemv(x2, qw, ql.scales, ql.qzeros, ql.bias,
                                group_size=ql.group_size, strategy=strategy,
                                out_dtype=x.dtype, interpret=interpret,
                                **kwargs)
            return y.reshape(*lead, n)
        kwargs = {}
        if block_sizes is not None:
            kwargs = dict(zip(("bm", "bn", "bk"), block_sizes))
        y = _gm.gptq_matmul(x2, qw, ql.scales, ql.qzeros,
                            group_size=ql.group_size, strategy=strategy,
                            out_dtype=x.dtype, interpret=interpret, **kwargs)
    else:
        y = _ref.gptq_matmul_ref(x2, ql.qweight, ql.scales, ql.qzeros,
                                 group_size=ql.group_size, perm=None,
                                 out_dtype=x.dtype)
    if ql.bias is not None:
        y = y + ql.bias.astype(y.dtype)
    return y.reshape(*lead, n)
