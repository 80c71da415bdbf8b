"""Quantized KV-cache subsystem (DESIGN.md §12).

At serving scale the KV cache, not the 4-bit weights, dominates memory:
QServe (W4A8KV4) and COMET (W4A4KV4) both show that quantizing it — with
dequantization fused into the attention kernel — multiplies effective cache
capacity, and therefore batch depth and throughput, at negligible accuracy
cost.  This module is the single source of that machinery:

* ``KVQuantConfig`` — what the cache stores: ``fp32``/``bf16`` passthrough
  or ``int8`` payloads with symmetric scales at ``token`` (one scale per
  written token per kv head) or ``page`` (one scale per physical page per
  kv head — the ``(Hkv, P)`` pool) granularity.
* ``quantize`` / ``dequantize`` — the symmetric round-to-nearest transform
  shared by every write/read fusion point (model cache tree, ``PagedCache``
  data path, kernel oracles).
* Byte accounting — ``page_bytes``/``slot_bytes``/``num_pages_for_budget``:
  with ``EngineConfig.page_pool_bytes`` the page pool is derived from a byte
  budget, so int8 KV roughly doubles (vs bf16) or quadruples (vs fp32) the
  pool — which the paged engine converts directly into deeper continuous
  batching.

Scale-pool layouts (parallel to the head-major ``(..., Hkv, P + 1,
page_size, D)`` ``k_pages``/``v_pages`` payload pools, one pool per K and V):

  token granularity : ``(..., Hkv, P + 1, page_size)``  — exact per write
  page granularity  : ``(..., Hkv, P + 1)``             — cheapest storage;
                      appends requantize the touched page (PagedCache data
                      path only — the engine's fused path is per-token)

Slot layout stores per-token scales as ``(B, max_len, Hkv)`` next to the
``(B, max_len, Hkv, D)`` int8 ``k``/``v``.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

QMAX = 127.0                 # symmetric int8 range [-127, 127]
_SCALE_FLOOR = 1e-8          # an all-zero vector quantizes to zeros, not NaN

_KV_DTYPES = {
    "fp32": jnp.float32, "float32": jnp.float32,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "int8": jnp.int8,
}
_CANONICAL = {"float32": "fp32", "bfloat16": "bf16"}
_SCALE_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
GRANULARITIES = ("token", "page")


@dataclasses.dataclass(frozen=True)
class KVQuantConfig:
    """How the serving KV cache stores keys and values.

    ``dtype``: ``"fp32"``/``"bf16"`` are passthrough (no quantization —
    equivalent to setting the cache dtype); ``"int8"`` stores symmetric
    8-bit payloads plus a parallel scale pool.  ``granularity`` picks the
    scale resolution (``"token"`` or ``"page"``); ``scale_dtype`` the scale
    pool's storage dtype.
    """
    dtype: str = "int8"
    granularity: str = "token"
    scale_dtype: str = "float32"

    def __post_init__(self):
        if self.dtype not in _KV_DTYPES:
            raise ValueError(
                f"unknown KV-quant dtype {self.dtype!r}; expected one of "
                f"{sorted(set(_CANONICAL) | set(_CANONICAL.values()) | {'int8'})}")
        object.__setattr__(self, "dtype",
                           _CANONICAL.get(self.dtype, self.dtype))
        if self.granularity not in GRANULARITIES:
            raise ValueError(
                f"unknown KV-quant granularity {self.granularity!r}; "
                f"expected one of {GRANULARITIES}")
        if self.scale_dtype not in _SCALE_DTYPES:
            raise ValueError(
                f"KV-quant scale_dtype must be a float dtype "
                f"{sorted(_SCALE_DTYPES)}, got {self.scale_dtype!r}")

    @property
    def quantized(self) -> bool:
        return self.dtype == "int8"

    @property
    def jnp_dtype(self):
        """Payload storage dtype (int8 when quantized)."""
        return jnp.dtype(_KV_DTYPES[self.dtype])

    @property
    def scale_jnp_dtype(self):
        return jnp.dtype(_SCALE_DTYPES[self.scale_dtype])


# ------------------------------------------------------------- the transform
def quantize(x: jnp.ndarray, *, axes=(-1,), scale_dtype=jnp.float32):
    """Symmetric int8 quantization over ``axes``.

    Returns ``(q, scales)``: ``q`` is int8 with ``x``'s shape; ``scales`` has
    ``axes`` removed.  Per-token-per-head KV uses ``axes=(-1,)`` (reduce D);
    per-page uses ``axes=(position, D)``.  Scales are computed in fp32
    (``amax / 127``) then cast, so the round-trip error of one write is
    bounded by ``scale / 2``.
    """
    axes = tuple(a % x.ndim for a in axes)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axes, keepdims=True)
    scale = jnp.maximum(amax, _SCALE_FLOOR) / QMAX
    q = jnp.clip(jnp.round(xf / scale), -QMAX, QMAX).astype(jnp.int8)
    scales = jnp.squeeze(scale, axis=axes).astype(scale_dtype)
    return q, scales


def dequantize(q: jnp.ndarray, scales: jnp.ndarray, *, dtype=jnp.float32):
    """Inverse of ``quantize``; granularity is inferred from the rank gap.

    ``q.ndim - scales.ndim == 1`` — per-token scales over payloads with one
    more (D) axis; ``== 2`` — per-page ``(..., Hkv, P)`` scales over
    head-major ``(..., Hkv, P, page_size, D)`` payloads.
    """
    gap = q.ndim - scales.ndim
    if gap == 1:                       # token: broadcast over D
        s = scales[..., None]
    elif gap == 2:                     # page: broadcast over (position, D)
        s = scales[..., None, None]
    else:
        raise ValueError(
            f"scale rank {scales.ndim} does not match payload rank {q.ndim} "
            f"at token (gap 1) or page (gap 2) granularity")
    return (q.astype(jnp.float32) * s.astype(jnp.float32)).astype(dtype)


# --------------------------------------------------------------- scale shapes
def paged_scale_shape(num_pages: int, page_size: int, kv_heads: int,
                      granularity: str) -> tuple[int, ...]:
    """Per-layer scale-pool shape parallel to a head-major ``(Hkv,
    num_pages + 1, page_size, D)`` payload pool (null page included)."""
    if granularity == "token":
        return (kv_heads, num_pages + 1, page_size)
    if granularity == "page":
        return (kv_heads, num_pages + 1)
    raise ValueError(f"unknown granularity {granularity!r}")


# ------------------------------------------------------------ byte accounting
def default_num_pages(batch_slots: int, max_len: int, page_size: int) -> int:
    """The engine's capacity-equivalent page-pool default: the slot cache's
    worst-case token budget, shared across rows at page granularity."""
    return batch_slots * -(-max_len // page_size)
def _payload_itemsize(dtype, kv_quant: KVQuantConfig | None) -> int:
    if kv_quant is not None and kv_quant.quantized:
        return 1
    if kv_quant is not None:
        return kv_quant.jnp_dtype.itemsize
    return jnp.dtype(dtype).itemsize


def page_bytes(n_layers: int, kv_heads: int, head_dim: int, page_size: int, *,
               dtype=jnp.float32, kv_quant: KVQuantConfig | None = None) -> int:
    """Bytes of one *allocatable* page across all layers, K + V pools,
    scale pools included."""
    payload = (n_layers * 2 * page_size * kv_heads * head_dim
               * _payload_itemsize(dtype, kv_quant))
    if kv_quant is None or not kv_quant.quantized:
        return payload
    per_page = kv_heads if kv_quant.granularity == "page" \
        else page_size * kv_heads
    return payload + n_layers * 2 * per_page * kv_quant.scale_jnp_dtype.itemsize


def slot_bytes(n_layers: int, kv_heads: int, head_dim: int, batch_slots: int,
               max_len: int, *, dtype=jnp.float32,
               kv_quant: KVQuantConfig | None = None) -> int:
    """Bytes of the slot-layout cache (per-token scales when quantized)."""
    payload = (n_layers * 2 * batch_slots * max_len * kv_heads * head_dim
               * _payload_itemsize(dtype, kv_quant))
    if kv_quant is None or not kv_quant.quantized:
        return payload
    return payload + (n_layers * 2 * batch_slots * max_len * kv_heads
                      * kv_quant.scale_jnp_dtype.itemsize)


def num_pages_for_budget(budget_bytes: int, n_layers: int, kv_heads: int,
                         head_dim: int, page_size: int, *,
                         dtype=jnp.float32,
                         kv_quant: KVQuantConfig | None = None) -> int:
    """Allocatable pages a byte budget buys (the +1 null page is excluded —
    it exists in every configuration alike)."""
    per_page = page_bytes(n_layers, kv_heads, head_dim, page_size,
                          dtype=dtype, kv_quant=kv_quant)
    pages = int(budget_bytes) // per_page
    if pages <= 0:
        raise ValueError(
            f"page-pool byte budget {budget_bytes} buys zero pages "
            f"({per_page} bytes/page at page_size={page_size})")
    return pages
