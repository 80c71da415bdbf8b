"""Shared layer primitives: linear (fp + GPTQ dispatch + calibration capture),
norms, RoPE / M-RoPE, embeddings.

Params are plain nested dicts of jnp arrays; every function is pure. The only
impurity is the module-level calibration capture context, used exclusively by
the (unjitted, unrolled) GPTQ calibration pass.
"""
from __future__ import annotations

import dataclasses
import enum

import jax
import jax.numpy as jnp

from repro.core.gptq import QuantizedLinear, accumulate_hessian
from repro.core.opt_strategies import KernelStrategy, OPT4GPTQ
from repro.kernels import lane
from repro.kernels import ops as kops


# --------------------------------------------------------- calibration capture
@dataclasses.dataclass
class CaptureContext:
    hessians: dict[str, jnp.ndarray] = dataclasses.field(default_factory=dict)
    counts: dict[str, int] = dataclasses.field(default_factory=dict)
    active: bool = False

    def add(self, name: str, x: jnp.ndarray):
        self.hessians[name] = accumulate_hessian(self.hessians.get(name), x)
        self.counts[name] = self.counts.get(name, 0) + int(
            x.reshape(-1, x.shape[-1]).shape[0])


_CAPTURE = CaptureContext()
_NAME_STACK: list[str] = []


class name_scope:
    """Qualifies capture names per layer (calibration runs unscanned/unjitted)."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        _NAME_STACK.append(self.name)
        return self

    def __exit__(self, *exc):
        _NAME_STACK.pop()
        return False


def qualified(name: str) -> str:
    return ".".join(_NAME_STACK + [name]) if _NAME_STACK else name


def capture_context() -> CaptureContext:
    return _CAPTURE


class capture_hessians:
    """with capture_hessians() as ctx: model.apply(...)  (unjitted only)."""

    def __enter__(self):
        global _CAPTURE
        _CAPTURE = CaptureContext(active=True)
        return _CAPTURE

    def __exit__(self, *exc):
        _CAPTURE.active = False
        return False


# -------------------------------------------------- activation sharding hooks
# Set by launch code (trace-time static): constrains (B, S, D) activations so
# GSPMD shards scan carries / saved residuals instead of replicating them, and
# (B, S, H, D) / (B, H, Sq, Sk) attention tensors so logits shard over heads
# (GSPMD pads when H doesn't divide the axis — e.g. hymba's 25 heads / 16).
_ACT_SPEC = None      # (B, S, D)
_HEADS_SPEC = None    # (B, S, H, D)
_LOGITS_SPEC = None   # (B, H, Sq, Sk)
_MOE_SPEC = None      # (E, C, d/f) dispatch buffers


def set_act_sharding(spec, heads_spec=None, logits_spec=None, moe_spec=None):
    """specs: jax.sharding.PartitionSpec or None."""
    global _ACT_SPEC, _HEADS_SPEC, _LOGITS_SPEC, _MOE_SPEC
    _ACT_SPEC = spec
    _HEADS_SPEC = heads_spec
    _LOGITS_SPEC = logits_spec
    _MOE_SPEC = moe_spec


def constrain_moe(x):
    if _MOE_SPEC is None or x.ndim != 4:
        return x
    return jax.lax.with_sharding_constraint(x, _MOE_SPEC)


# shard_map expert-parallel context: (mesh, fsdp_axis, model_axis, batch_axes)
_MOE_EP = None


def set_moe_ep(mesh, fsdp_axis: str, model_axis: str, batch_axes):
    global _MOE_EP
    _MOE_EP = None if mesh is None else (mesh, fsdp_axis, model_axis,
                                         batch_axes)


def moe_ep_context():
    return _MOE_EP


# shard_map tensor-parallel epilogue (serving/parallel.py, DESIGN.md §17):
# armed at trace time inside the shard_map body.  When set, row-parallel
# linears (wo / w_down) hold K-shards, so their partial matmul outputs are
# completed with a psum over the named TP axis; unset (the default), the
# epilogue is the identity and single-device traces are untouched.
_TP_AXIS = None


class tp_epilogue:
    """``with L.tp_epilogue(axis): model.apply(...)`` — inside a shard_map
    body only; nests/restores like a dynamic scope."""

    def __init__(self, axis: str):
        self.axis = axis

    def __enter__(self):
        global _TP_AXIS
        self._prev = _TP_AXIS
        _TP_AXIS = self.axis
        return self

    def __exit__(self, *exc):
        global _TP_AXIS
        _TP_AXIS = self._prev
        return False


def tp_all_reduce(y):
    """Row-parallel all-reduce epilogue: psum when a TP axis is armed."""
    if _TP_AXIS is None:
        return y
    return jax.lax.psum(y, _TP_AXIS)


def constrain_act(x):
    if _ACT_SPEC is None or x.ndim != 3:
        return x
    return jax.lax.with_sharding_constraint(x, _ACT_SPEC)


def constrain_heads(x):
    if _HEADS_SPEC is None or x.ndim != 4:
        return x
    return jax.lax.with_sharding_constraint(x, _HEADS_SPEC)


def constrain_logits(x):
    if _LOGITS_SPEC is None or x.ndim != 4:
        return x
    return jax.lax.with_sharding_constraint(x, _LOGITS_SPEC)


# ------------------------------------------------------------------ kernel cfg
LANE = 128   # TPU lane width: last-dim tiling unit for VMEM tiles


class CacheLayout(str, enum.Enum):
    """Serving KV-cache layout (DESIGN.md §2/§10).

    SLOT  : contiguous (B, max_len, ...) per-slot cache — the TPU-idiomatic
            default; shape-stable jitted decode.
    PAGED : block-table pages over a shared physical pool — the vLLM
            PagedAttention layout; decode runs the Pallas paged-attention
            kernel (``kernels/paged_attention.py``).
    """
    SLOT = "slot"
    PAGED = "paged"


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """How quantized linears and the serving cache execute (threaded through
    model apply fns).

    ``block_sizes`` is a concrete (bm, bn, bk) tuple, ``None`` for the kernel
    defaults, or ``"auto"`` to consult the per-shape autotuner cache
    (``kernels/autotune.py`` — tuned once per (M, K, N, group, strategy) key,
    persisted to JSON).  ``cache_layout`` selects the serving cache layout
    (``Engine(cache=...)`` defaults to it); ``paged_attention_impl`` /
    ``paged_prefill_impl`` pick the paged decode / prefill hot paths —
    ``"kernel"`` (the Pallas kernels) or ``"ref"`` (the jnp gather oracles
    in ``kernels/ref.py``, which materialize a contiguous KV copy —
    debugging and the bench's gather-vs-kernel comparison only).

    The kernel lane is one decision, made from the backend by ``resolve()``
    (``kernels/lane.py``), which the engine calls once when it is built:
    ``interpret`` is True only on the CPU backend, and ``use_pallas`` left
    at ``None`` means the Pallas GPTQ kernels everywhere but the CPU, where
    the jnp reference keeps the test suite fast.  ``use_pallas=False`` is
    the reference lane on purpose (the oracle a chip run is checked
    against), never a fallback.

    ``q_chunk`` bounds the query rows per grid step of the chunked
    ``paged_prefill`` kernel (the VMEM query tile is (q_chunk·rep, D)).
    ``None`` keeps the historical 128; ``"auto"`` consults the autotuner
    cache (co-tuned with the engine's step token budget); a concrete value
    must be a positive multiple of the 128-wide TPU lane."""
    strategy: KernelStrategy = OPT4GPTQ
    use_pallas: bool | None = None    # None: Pallas off the CPU (resolve())
    interpret: bool | None = None     # None: interpret on the CPU only
    block_sizes: tuple[int, int, int] | str | None = None
    cache_layout: str = CacheLayout.SLOT
    paged_attention_impl: str = "kernel"
    paged_prefill_impl: str = "kernel"
    q_chunk: int | str | None = None

    def __post_init__(self):
        qc = self.q_chunk
        if qc is None or qc == "auto":
            return
        if not isinstance(qc, int) or isinstance(qc, bool) or qc <= 0 \
                or qc % LANE != 0:
            raise ValueError(
                f"q_chunk must be a positive multiple of the {LANE}-wide "
                f"lane (or 'auto'), got {qc!r}")

    def resolve(self) -> "KernelConfig":
        """This config with the kernel lane fixed from the backend.  A TPU
        never runs a kernel interpreted, so ``interpret=True`` off the CPU
        is an error."""
        cpu = lane.default_interpret()
        interpret = cpu if self.interpret is None else self.interpret
        if interpret and not cpu:
            raise ValueError(
                f"interpret=True on the {jax.default_backend()!r} backend: "
                f"Pallas kernels run interpreted on the CPU only")
        use_pallas = (not interpret if self.use_pallas is None
                      else self.use_pallas)
        return dataclasses.replace(self, interpret=interpret,
                                   use_pallas=use_pallas)


DEFAULT_KERNELS = KernelConfig()


# ---------------------------------------------------------------------- linear
def linear_init(rng, d_in: int, d_out: int, *, bias: bool = False,
                dtype=jnp.float32, scale: float | None = None):
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": jax.random.normal(rng, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x: jnp.ndarray, *, name: str = "",
           kernels: KernelConfig = DEFAULT_KERNELS) -> jnp.ndarray:
    """Apply a linear layer; dispatches on param type (fp vs GPTQ-quantized)."""
    if _CAPTURE.active and name:
        _CAPTURE.add(qualified(name), x)
    w = p["w"]
    if isinstance(w, QuantizedLinear):
        kernels = kernels.resolve()      # idempotent; the engine's is resolved
        y = kops.gptq_linear(w, x, strategy=kernels.strategy,
                             use_pallas=kernels.use_pallas,
                             interpret=kernels.interpret,
                             block_sizes=kernels.block_sizes)
    else:
        y = jnp.dot(x, w.astype(x.dtype))
    if "b" in p and p["b"] is not None:
        y = y + p["b"].astype(y.dtype)
    return y


# ----------------------------------------------------------------------- norms
def norm_init(d: int, norm_type: str = "rmsnorm", dtype=jnp.float32):
    p = {"scale": jnp.ones((d,), dtype)}
    if norm_type == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p, x: jnp.ndarray, *, norm_type: str = "rmsnorm",
               eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    if norm_type == "layernorm":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_head_norm(scale: jnp.ndarray, x: jnp.ndarray, eps: float = 1e-6):
    """qk-norm: RMSNorm over the head_dim of (..., H, D)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ------------------------------------------------------------------------ RoPE
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               mrope_sections: tuple[int, ...] | None = None) -> jnp.ndarray:
    """x: (B, S, H, D). positions: (B, S) int32, or (3, B, S) for M-RoPE
    (temporal/height/width sections, qwen2-vl)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                                    # (D/2,)
    if mrope_sections is not None and positions.ndim == 3:
        # split the D/2 frequencies into t/h/w sections, each using its own pos
        secs = mrope_sections
        assert sum(secs) == d // 2, (secs, d)
        pos_parts = []
        start = 0
        for i, s in enumerate(secs):
            pos_parts.append(jnp.broadcast_to(positions[i][..., None],
                                              positions.shape[1:] + (s,)))
            start += s
        pos = jnp.concatenate(pos_parts, axis=-1)                 # (B, S, D/2)
        ang = pos.astype(jnp.float32) * inv[None, None, :]
    else:
        if positions.ndim == 3:
            positions = positions[0]
        ang = positions[..., None].astype(jnp.float32) * inv[None, None, :]
    cos = jnp.cos(ang)[:, :, None, :]                             # (B, S, 1, D/2)
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return y.astype(x.dtype)


# ------------------------------------------------------------------ embeddings
def embed_init(rng, vocab: int, d: int, dtype=jnp.float32):
    return {"embedding": jax.random.normal(rng, (vocab, d), dtype) * 0.02}


def embed_lookup(p, ids: jnp.ndarray, dtype) -> jnp.ndarray:
    return jnp.take(p["embedding"], ids, axis=0).astype(dtype)


def embed_logits(p, x: jnp.ndarray) -> jnp.ndarray:
    """Tied output head: logits = x @ E^T (f32 for stability)."""
    return jnp.dot(x.astype(jnp.float32),
                   p["embedding"].astype(jnp.float32).T)


# ------------------------------------------------------------------ activations
def swiglu(gate: jnp.ndarray, up: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def squared_relu(x: jnp.ndarray) -> jnp.ndarray:
    r = jax.nn.relu(x)
    return r * r
