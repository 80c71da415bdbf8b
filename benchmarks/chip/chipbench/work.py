"""Operations and bytes of the served path, computed from shapes.

These are the yardstick of the roofline shares and of ``step_mfu``: the
work a call needs, whatever kernel implements it.  A step is described as
the engine scheduled it (``run.py`` records it before each traced step):
``{"batch": B, "width": C, "rows": [[start, n, emit], ...]}`` — each live
row computes ``n`` tokens at positions ``start .. start + n - 1`` of its
sequence, and ``emit`` rows take the logits of their last position.
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


def step_gptq(cfg: dict, step: dict, projections: dict) -> list:
    """Every GPTQ call of one step as ``(flops, bytes)``: each projection
    of each layer at M = batch x width rows, as the fused step runs it."""
    m = step["batch"] * step["width"]
    g = cfg["quantization"]["group_size"]
    calls = [gptq_call(m, k, n, g) for k, n in projections.values()]
    return calls * cfg["num_hidden_layers"]


def step_attention(cfg: dict, step: dict) -> list:
    """Every paged attention call of one step (one per layer)."""
    call = attention_rows(step["rows"], cfg["num_attention_heads"],
                          cfg["num_key_value_heads"], cfg["head_dim"])
    return [call] * cfg["num_hidden_layers"]


def useful_flops(cfg: dict, step: dict, projections: dict) -> float:
    """Model operations of the tokens a step really computes: projections
    once per real token, attention at each token's real context, and the
    output head once per emitting row.  Padding rows and widths do not
    count."""
    tokens = sum(n for _, n, _ in step["rows"])
    per_token = 2.0 * sum(k * n for k, n in projections.values())
    attn, _ = attention_rows(step["rows"], cfg["num_attention_heads"],
                             cfg["num_key_value_heads"], cfg["head_dim"])
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * sum(
        1 for _, _, emit in step["rows"] if emit)
    return cfg["num_hidden_layers"] * (tokens * per_token + attn) + head


def roofline_seconds(calls, peak_flops: float, peak_bytes_s: float) -> float:
    """The least time the chip could take for ``calls``: per call the
    larger of operations over peak and bytes over bandwidth."""
    return sum(max(f / peak_flops, b / peak_bytes_s) for f, b in calls)
