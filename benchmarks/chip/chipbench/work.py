"""Operations and bytes of the served path, computed from shapes.

These are the yardstick of the roofline shares and of ``step_mfu``: the
work a call needs, whatever kernel implements it.  A step is described as
the engine scheduled it (``run.py`` records it before each traced step):
``{"batch": B, "width": C, "rows": [[start, n, emit], ...]}`` — each live
row computes ``n`` tokens at positions ``start .. start + n - 1`` of its
sequence, and ``emit`` rows take the logits of their last position.  A
family (``families/``) counts a whole step from these calls:
``step_calls(cfg, step)``, ``{kernel class: [(flops, bytes), ...]}``, and
``useful_flops(cfg, step)``.
"""
from __future__ import annotations

BF16 = 2


def gptq_call(m: int, k: int, n: int, group: int) -> tuple[float, float]:
    """``y (m, n) = x (m, k) @ dequant(W)`` with int4 ``W`` (k, n): 2mkn
    operations; bytes of the packed weights (k n / 2), bf16 scales and
    packed zeros per group, bf16 activations in and out."""
    groups = k // group
    flops = 2.0 * m * k * n
    nbytes = (k * n / 2 + groups * n * BF16 + groups * n / 2
              + m * k * BF16 + m * n * BF16)
    return flops, nbytes


def attention_rows(rows, heads: int, kv_heads: int, head_dim: int,
                   kv_bytes: int = BF16) -> tuple[float, float]:
    """Causal attention of each row's ``n`` queries over its real context
    (position ``start + j`` sees ``start + j + 1`` keys): Q K^T and P V
    operations; K and V read once per row over its final context, Q in and
    O out."""
    flops = nbytes = 0.0
    for start, n, _ in rows:
        keys = n * start + n * (n + 1) / 2
        flops += 4.0 * heads * head_dim * keys
        nbytes += (2 * kv_heads * (start + n) * head_dim * kv_bytes
                   + 2 * n * heads * head_dim * BF16)
    return flops, nbytes


def roofline_seconds(calls, peak_flops: float, peak_bytes_s: float) -> float:
    """The least time the chip could take for ``calls``: per call the
    larger of operations over peak and bytes over bandwidth."""
    return sum(max(f / peak_flops, b / peak_bytes_s) for f, b in calls)
