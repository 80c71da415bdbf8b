"""Public serving API types: the request lifecycle contract (DESIGN.md §11).

One place defines what a serving request *is* — everything the engine, the
HTTP front-end, the examples and the benchmarks previously re-derived from
positional kwargs:

* ``EngineConfig``     — the engine's construction surface (was 10 kwargs
                         duplicated across launch/examples/benchmarks).
* ``RequestState``     — QUEUED → PREFILL → RUNNING → FINISHED | ABORTED.
* ``FinishReason``     — why generation ended (OpenAI-compatible values).
* ``StreamEvent``      — one generated token of one request, as yielded by
                         ``Engine.stream()``; terminal events carry the
                         ``RequestOutput``.
* ``RequestOutput``    — a completed (or aborted) request with per-request
                         latency metrics (ttft / tpot / e2e latency).

``RequestOutput`` is the same record the pre-redesign engine returned as
``scheduler.Finished`` (kept as an alias there), extended with
``finish_reason``/``state`` — old callers keep working unchanged.
"""
from __future__ import annotations

import dataclasses
import enum

import jax.numpy as jnp

from repro.models import layers as L
from repro.serving.kv_quant import KVQuantConfig
from repro.serving.spec_decode import SpecConfig


class RequestState(str, enum.Enum):
    """Lifecycle of one serving request inside the engine."""
    QUEUED = "queued"        # submitted, waiting for slot/page admission
    PREFILL = "prefill"      # admitted; prompt KV being written
    RUNNING = "running"      # decoding, first token already produced
    PREEMPTED = "preempted"  # evicted by a higher-priority request; its KV
                             # pages are offloaded to host memory and it is
                             # back in the queue awaiting restore
    FINISHED = "finished"    # completed via stop token / eos / length
    ABORTED = "aborted"      # cancelled via Engine.abort()


class FinishReason(str, enum.Enum):
    """Why a request stopped — values match the OpenAI completions API
    where one exists (stop/length); shed/stall are overload outcomes
    (DESIGN.md §14)."""
    STOP = "stop"            # eos (unless ignore_eos) or a stop_token_id
    LENGTH = "length"        # hit max_new_tokens
    ABORT = "abort"          # Engine.abort() mid-flight or while queued
    SHED = "shed"            # queue deadline expired before admission
                             # (graceful overload shedding -> HTTP 503)
    STALL = "stall"          # engine worker watchdog fired: a step exceeded
                             # the stall timeout; in-flight requests fail
                             # instead of hanging their clients


class QueueFullError(RuntimeError):
    """``Engine.submit`` under bounded admission: the wait queue is at
    ``EngineConfig.max_queued``.  The HTTP front-end maps this to 429 with
    a ``Retry-After`` header (``retry_after_s``)."""

    def __init__(self, msg: str, retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Single-sourced engine construction config.

    Every field previously travelled as an ``Engine.__init__`` kwarg,
    re-spelled independently by ``launch/serve.py``, both serving examples
    and the benchmarks.  ``Engine(model, params, EngineConfig(...))`` is the
    supported spelling; the old kwargs remain as a deprecated shim.
    """
    batch_slots: int = 8
    max_len: int = 512
    kernels: L.KernelConfig = L.DEFAULT_KERNELS
    eos_id: int = 1
    cache: str | None = None          # None -> kernels.cache_layout
    page_size: int = 16
    num_pages: int | None = None      # None -> batch_slots * ceil(max_len/page)
    cache_dtype: object = None        # None -> the model's dtype
    seed: int = 0
    # KV quantization (DESIGN.md §12): None, a KVQuantConfig, or a dtype
    # string shorthand ("int8" / "bf16" / "fp32" — normalized to a config)
    kv_quant: object = None
    # paged layout: derive num_pages from a byte budget (payload + scale
    # pools) instead of the capacity-equivalent default — the lever that
    # turns int8 KV into a ~2x (vs bf16) / ~4x (vs fp32) deeper page pool
    page_pool_bytes: int | None = None
    # ---- overload resilience (DESIGN.md §14) ----
    # bounded admission: submit() raises QueueFullError once this many
    # requests are waiting (None = unbounded, the pre-§14 behaviour)
    max_queued: int | None = None
    # default per-request queue deadline: a request not admitted within
    # this many seconds of submit is shed (FinishReason.SHED); per-request
    # ``queue_timeout_s`` on submit() overrides it
    default_queue_timeout_s: float | None = None
    # paged layout: allow a higher-priority request that cannot reserve
    # pages to preempt a lower-priority victim (offload its pages to host
    # memory and re-queue it) instead of deferring behind it
    preemption: bool = True
    # injectable clock (serving/clock.py) — every serving deadline and
    # timestamp reads through it; None -> the real SystemClock
    clock: object = None
    # serving fault injector (serving/faults.py::FaultInjector) consulted
    # at the top of every Engine.step(); None in production
    faults: object = None
    # ---- observability (DESIGN.md §15) ----
    # metrics=False swaps the engine's registry for the no-op NullRegistry
    # (the zero-cost opt-out); EngineStats then reads all-zero
    metrics: bool = True
    # step-span tracer (serving/tracing.py::Tracer) recording per-request
    # lifecycle + per-step spans for Perfetto export; None = tracing off
    tracer: object = None
    # ---- speculative decoding (DESIGN.md §16) ----
    # a SpecConfig turns the decode loop into propose-k / batched-verify
    # steps emitting up to k+1 tokens each; None = plain one-token decode
    speculation: SpecConfig | None = None
    # paged layout: directory to persist/restore the hashed prefix-cache
    # index + page payloads across engine restarts (DESIGN.md §16); the
    # engine loads it at construction when the directory exists and
    # ``Engine.save_prefix_cache()`` writes it
    prefix_cache_path: str | None = None
    # ---- tensor parallelism (DESIGN.md §17) ----
    # device mesh this engine spans (serving/parallel.py): None or (1,) is
    # today's single-device engine, (N,) shards GPTQ weights head-/N-major
    # and the KV page pools per device with shard_map around the paged
    # kernels.  Paged layout only; page budgets (num_pages /
    # page_pool_bytes) are interpreted *per device* — each device's pool
    # holds its head-slice of the same global page ids
    mesh_shape: tuple | None = None
    # mesh axis name the row-parallel all-reduce epilogue psums over
    tp_axis: str = "model"
    # ---- fused-step execution (ISSUE 10, DESIGN.md §18) ----
    # token budget of one fused engine step: decode/verify rows claim their
    # tokens first, the remainder is handed to waiting prompts as prefill
    # chunks riding the same jitted program.  None = no budget — a whole
    # remaining prompt prefills in one chunk (still via the fused program)
    max_step_tokens: int | None = None

    def __post_init__(self):
        if self.batch_slots <= 0:
            raise ValueError(f"batch_slots must be > 0, got {self.batch_slots}")
        if self.max_len <= 0:
            raise ValueError(f"max_len must be > 0, got {self.max_len}")
        if self.page_size <= 0:
            raise ValueError(f"page_size must be > 0, got {self.page_size}")
        if self.num_pages is not None and self.num_pages <= 0:
            raise ValueError(
                f"num_pages must be > 0 (or None for the capacity-equivalent "
                f"default), got {self.num_pages}")
        layout = getattr(self.cache, "value", self.cache)
        if layout is not None and layout not in ("slot", "paged"):
            raise ValueError(f"unknown cache layout {self.cache!r}")
        if isinstance(self.kv_quant, str):
            # shorthand; KVQuantConfig rejects unknown dtype strings
            object.__setattr__(self, "kv_quant",
                               KVQuantConfig(dtype=self.kv_quant))
        if self.kv_quant is not None:
            if not isinstance(self.kv_quant, KVQuantConfig):
                raise ValueError(
                    f"kv_quant must be a KVQuantConfig or a dtype string, "
                    f"got {self.kv_quant!r}")
            if self.kv_quant.quantized:
                if self.cache_dtype is not None:
                    raise ValueError(
                        f"kv_quant='int8' stores int8 payloads — "
                        f"cache_dtype={self.cache_dtype!r} would be ignored; "
                        f"pass one or the other")
                if self.kv_quant.granularity != "token":
                    raise ValueError(
                        "the engine's fused write path uses per-token "
                        "scales; per-page granularity is served by the "
                        "PagedCache data-path API only")
            elif (self.cache_dtype is not None
                  and jnp.dtype(self.cache_dtype) != self.kv_quant.jnp_dtype):
                raise ValueError(
                    f"kv_quant passthrough dtype {self.kv_quant.dtype!r} "
                    f"conflicts with cache_dtype={self.cache_dtype!r}")
        if self.page_pool_bytes is not None:
            if self.page_pool_bytes <= 0:
                raise ValueError(
                    f"page_pool_bytes must be > 0, got {self.page_pool_bytes}")
            if self.num_pages is not None:
                raise ValueError(
                    "pass either num_pages or page_pool_bytes, not both")
        if self.max_queued is not None and self.max_queued <= 0:
            raise ValueError(
                f"max_queued must be > 0 (or None for unbounded), got "
                f"{self.max_queued}")
        if (self.default_queue_timeout_s is not None
                and self.default_queue_timeout_s <= 0):
            raise ValueError(
                f"default_queue_timeout_s must be > 0, got "
                f"{self.default_queue_timeout_s}")
        if self.speculation is not None:
            if not isinstance(self.speculation, SpecConfig):
                raise ValueError(
                    f"speculation must be a SpecConfig, got "
                    f"{self.speculation!r}")
            if self.speculation.k >= self.max_len:
                raise ValueError(
                    f"speculation k={self.speculation.k} must be < "
                    f"max_len={self.max_len}")
        if (self.prefix_cache_path is not None
                and not isinstance(self.prefix_cache_path, str)):
            raise ValueError(
                f"prefix_cache_path must be a directory path string, got "
                f"{self.prefix_cache_path!r}")
        if self.mesh_shape is not None:
            dims = tuple(self.mesh_shape)
            if not dims or any(not isinstance(d, int) or d <= 0
                               for d in dims):
                raise ValueError(
                    f"mesh_shape must be a non-empty tuple of positive "
                    f"ints, got {self.mesh_shape!r}")
            object.__setattr__(self, "mesh_shape", dims)
            tp = 1
            for d in dims:
                tp *= d
            if tp > 1:
                if layout == "slot" or (layout is None and getattr(
                        self.kernels.cache_layout, "value",
                        self.kernels.cache_layout) == "slot"):
                    raise ValueError(
                        "tensor-parallel serving shards the KV page pools "
                        "— cache='paged' required with mesh_shape "
                        f"{dims}")
        if self.max_step_tokens is not None and self.max_step_tokens <= 0:
            raise ValueError(
                f"max_step_tokens must be > 0 (or None for unbudgeted "
                f"prefill chunks), got {self.max_step_tokens}")
        if not self.tp_axis or not isinstance(self.tp_axis, str):
            raise ValueError(
                f"tp_axis must be a non-empty axis name, got "
                f"{self.tp_axis!r}")


@dataclasses.dataclass
class RequestOutput:
    """A completed or aborted request, with request-level latency metrics.

    ``output`` holds the generated token ids (stop/eos token included when it
    caused the stop).  Aborted-while-queued requests have empty ``output``
    and ``t_first_token == 0.0``.
    """
    rid: int
    prompt_len: int
    output: list[int]
    arrival: float
    t_first_token: float
    t_done: float
    finish_reason: FinishReason | None = None
    # speculative decoding (DESIGN.md §16): draft tokens this request was
    # offered / kept across its verify steps (both 0 with speculation off)
    spec_proposed: int = 0
    spec_accepted: int = 0

    @property
    def state(self) -> RequestState:
        return (RequestState.ABORTED
                if self.finish_reason is FinishReason.ABORT
                else RequestState.FINISHED)

    @property
    def ttft(self) -> float:
        """Time to first token, from submission (0.0 when no token was ever
        produced — e.g. aborted while still queued)."""
        if not self.t_first_token:
            return 0.0
        return self.t_first_token - self.arrival

    @property
    def tpot(self) -> float:
        """Time per output token over the decode phase (post-first-token).

        Deliberately normalized by *emitted tokens*, not engine steps — a
        speculative verify step that lands k+1 tokens reads as k+1 cheap
        tokens here, keeping tpot comparable between spec-on and spec-off
        runs (DESIGN.md §16)."""
        n = len(self.output)
        if n <= 1 or not self.t_first_token:
            return 0.0
        return (self.t_done - self.t_first_token) / (n - 1)

    @property
    def acceptance_rate(self) -> float:
        """Fraction of proposed draft tokens this request accepted (0.0
        when it never saw a speculative step)."""
        if not self.spec_proposed:
            return 0.0
        return self.spec_accepted / self.spec_proposed

    @property
    def latency(self) -> float:
        """End-to-end latency, submission to completion."""
        return self.t_done - self.arrival


@dataclasses.dataclass(frozen=True)
class StreamEvent:
    """One token of one request, yielded by ``Engine.stream()``.

    ``index`` is the token's position in the request's output.  Terminal
    events set ``finish_reason`` and carry the full ``RequestOutput``; an
    abort's terminal event has ``token is None`` (nothing was sampled).
    """
    rid: int
    token: int | None
    index: int
    finish_reason: FinishReason | None = None
    output: RequestOutput | None = None
