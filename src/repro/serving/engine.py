"""Serving engine: continuous batching through ONE token-budgeted jitted
program per step — the vLLM role in the paper's stack (DESIGN.md §2, §10,
§11, §18).

The public surface is the request lifecycle API (``serving/api.py``):

* ``Engine(model, params, EngineConfig(...))`` — construction is
  single-sourced through ``EngineConfig``; the old 10-kwarg constructor
  survives as a deprecated shim (gated by ``tests/test_lint.py``).
* ``submit()`` validates at admission time (slot/page capacity,
  ``SamplingParams`` domains) and takes per-request stop criteria
  (``stop_token_ids``, ``ignore_eos``, ``max_new_tokens``).
* ``generate(prompts)`` — blocking convenience, returns ``RequestOutput``s
  with per-request ``ttft``/``tpot``/``finish_reason``.
* ``stream()`` — an iterator that pumps ``step()`` and yields per-token
  ``StreamEvent``s across *all* in-flight requests (continuous batching
  preserved); terminal events carry the ``RequestOutput``.
* ``abort(rid)`` — cancels a queued or in-flight request, freeing its slot
  or paged reservation (including prefix-cache refcounts) immediately.
* Requests move ``QUEUED → PREFILL → RUNNING → FINISHED | ABORTED`` — plus
  ``PREEMPTED`` and back under overload (``RequestState``);
  ``launch/serve.py --serve`` exposes the whole thing as an OpenAI-style
  ``/v1/completions`` HTTP endpoint with SSE streaming
  (``serving/http_api.py``).

Overload resilience (DESIGN.md §14): requests carry a ``priority`` class —
on the paged layout a higher class that cannot reserve pages preempts the
lowest/most-recent victim (its private pages are checkpointed to host
memory via ``PagedCache.offload`` and restored later, greedy
token-identical); ``EngineConfig.max_queued`` bounds the wait queue
(``QueueFullError`` → HTTP 429) and per-request queue deadlines shed
unadmitted requests (``FinishReason.SHED`` → HTTP 503).  All deadline
logic reads an injectable clock (``serving/clock.py``) and a
``FaultInjector`` (``serving/faults.py``) can deterministically inject
page exhaustion, stalls and aborts at chosen steps.

Two cache layouts, selected by ``EngineConfig.cache`` (default: the
``KernelConfig.cache_layout`` enum):

* ``"slot"`` — the model's native contiguous cache, fixed ``max_len`` per
  decode slot; bucketed prefill lengths (bounded jit recompiles).
* ``"paged"`` — the PagedAttention layout: fixed-size KV pages of a shared
  physical pool addressed through a device block table
  (``serving/kv_cache.py::PagedCache``), page-budget admission that reserves
  the full prompt+decode footprint up front (generation can never hit pool
  exhaustion mid-flight), a hashed-prefix cache (prefix-hit requests prefill
  only their suffix against the reused pages), and the Pallas paged
  kernels on *both* hot paths — decode and the chunked paged-prefill
  kernel, so no gathered KV copy is ever materialized.  Prefill is
  bucketed like the slot path — padded positions' page writes are routed
  to the null page (``write_lens``), so recompiles stay bounded by the
  bucket set.

Fused-step execution (ISSUE 10, DESIGN.md §18): every engine step is one
invocation of ``_fused_step_impl`` over a token-budgeted batch in which each
row is a ``(seq, chunk_start=seq_lens, chunk_len)`` span of its sequence —
plain decode is a 1-token chunk, chunked prefill a budget-sized chunk, and
speculative verify a (k+1)-token chunk.  Admission only *reserves* cache
space (pages / a slot); the prompt then streams into the cache as chunks
dealt by ``Scheduler.plan_chunks`` under ``EngineConfig.max_step_tokens``,
so a long prompt can no longer stall concurrent decodes for its whole
prefill — the bounded-TTFT-under-load payoff BENCH_serving.json's
``chunked_prefill`` section measures.  The step width is bucketed
(1, k+1, then the prefill buckets) so jit recompiles stay bounded under
mixed traffic.

The hot loop is sync-free in both layouts: per-request sampling parameters
are lowered to per-row device arrays (greedy flag, temperature, top-k/top-p,
one PRNG key per row), empty rows are masked on device, and the whole
model-step + accept/sample runs inside one ``jit``.  Exactly one
device->host transfer happens per step — the packed (B, K+2) int32 matrix
``[n_emitted | emitted tokens...]`` (K=0 without speculation).
"""
from __future__ import annotations

import functools
import json
import os
import warnings
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import LM
from repro.serving import clock as CLK
from repro.serving import kv_cache as KV
from repro.serving import kv_quant as KQ
from repro.serving import parallel as PL
from repro.serving import spec_decode as SD
from repro.serving import tracing as TR
from repro.serving.api import (EngineConfig, FinishReason, QueueFullError,
                               RequestOutput, RequestState, StreamEvent)
from repro.serving.metrics import EngineMetrics, make_engine_metrics
from repro.serving.sampler import (SamplingParams, accept_speculative,
                                   sample)
from repro.serving.scheduler import (PREFILL_BUCKETS, Active, Request,
                                     Scheduler, bucket_len)


class EngineStats:
    """Read-view over the engine's metrics registry (DESIGN.md §15).

    The attribute surface predates the registry (ad-hoc dataclass counting)
    and is kept verbatim so existing callers and the BENCH_serving.json
    schema don't move; every value now reads straight out of the same
    counters ``GET /metrics`` exposes — the two can never disagree.  With
    ``EngineConfig(metrics=False)`` all values read 0.
    """

    def __init__(self, metrics: EngineMetrics):
        self._m = metrics

    # counters ---------------------------------------------------------------
    @property
    def tokens_generated(self) -> int:
        return int(self._m.tokens_generated.value)

    @property
    def prefill_tokens(self) -> int:
        return int(self._m.prefill_tokens.value)

    @property
    def steps(self) -> int:
        return int(self._m.steps.value)

    @property
    def wall_s(self) -> float:
        """Clock seconds spent inside ``Engine.step`` (the injectable
        clock) — accumulated per step, so direct ``step()`` pumps (the HTTP
        worker, the overload bench) are accounted exactly like ``run()``."""
        return float(self._m.wall_seconds.value)

    # paged layout: pages/tokens served from the hashed-prefix cache instead
    # of being re-prefilled
    @property
    def prefix_hit_pages(self) -> int:
        return int(self._m.prefix_hit_pages.value)

    @property
    def prefix_hit_tokens(self) -> int:
        return int(self._m.prefix_hit_tokens.value)

    # deepest concurrent batch ever admitted — the number int8 KV moves by
    # widening the page pool under a fixed byte budget (DESIGN.md §12)
    @property
    def peak_active(self) -> int:
        return int(self._m.peak_active.value)

    # ---- overload resilience (DESIGN.md §14) ----
    @property
    def preemptions(self) -> int:
        return int(self._m.preemptions.value)

    @property
    def offloaded_pages(self) -> int:
        return int(self._m.offloaded_pages.value)

    @property
    def offloaded_bytes(self) -> int:
        return int(self._m.offloaded_bytes.value)

    @property
    def restored_pages(self) -> int:
        return int(self._m.restored_pages.value)

    @property
    def rejected_submits(self) -> int:
        return int(self._m.rejected_submits.value)

    @property
    def deferred_admissions(self) -> int:
        return int(self._m.deferred_admissions.value)

    @property
    def shed_requests(self) -> int:
        return int(self._m.shed_requests.value)

    # ---- speculative decoding (DESIGN.md §16) ----
    @property
    def spec_proposed(self) -> int:
        return int(self._m.spec_proposed.value)

    @property
    def spec_accepted(self) -> int:
        return int(self._m.spec_accepted.value)

    @property
    def spec_verify_steps(self) -> int:
        return int(self._m.spec_verify_steps.value)

    @property
    def acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens (0.0 before any proposal)."""
        p = self.spec_proposed
        return self.spec_accepted / p if p else 0.0

    @property
    def tokens_per_step(self) -> float:
        """Decode tokens emitted per engine step — 1.0 for plain decode,
        up to k+1 under speculation.  The multi-token-step-aware
        denominator for throughput accounting: tpot and tok/s derive from
        *emitted tokens* (see ``RequestOutput.tpot``), never from step
        counts, so BENCH_serving.json stays comparable across spec
        on/off."""
        s = self.steps
        return self.tokens_generated / s if s else 0.0

    def __repr__(self) -> str:
        fields = ("tokens_generated", "prefill_tokens", "steps", "wall_s",
                  "prefix_hit_pages", "prefix_hit_tokens", "peak_active",
                  "preemptions", "offloaded_pages", "offloaded_bytes",
                  "restored_pages", "rejected_submits",
                  "deferred_admissions", "shed_requests", "spec_proposed",
                  "spec_accepted")
        inner = ", ".join(f"{f}={getattr(self, f)!r}" for f in fields)
        return f"EngineStats({inner})"


_UNSET = object()


class Engine:
    def __init__(self, model: LM, params,
                 config: Optional[EngineConfig] = None, *,
                 batch_slots=_UNSET, max_len=_UNSET, kernels=_UNSET,
                 eos_id=_UNSET, cache_dtype=_UNSET, seed=_UNSET,
                 cache=_UNSET, page_size=_UNSET, num_pages=_UNSET):
        legacy = {k: v for k, v in dict(
            batch_slots=batch_slots, max_len=max_len, kernels=kernels,
            eos_id=eos_id, cache_dtype=cache_dtype, seed=seed, cache=cache,
            page_size=page_size, num_pages=num_pages).items()
            if v is not _UNSET}
        if config is not None and legacy:
            raise TypeError(
                f"pass either an EngineConfig or legacy kwargs, not both "
                f"(got config and {sorted(legacy)})")
        if config is None:
            # deprecated shim: the pre-EngineConfig kwarg constructor.
            # tests/test_lint.py gates in-repo (non-test) callers off it.
            if legacy:
                warnings.warn(
                    "Engine(**kwargs) is deprecated; pass "
                    "Engine(model, params, EngineConfig(...))",
                    DeprecationWarning, stacklevel=2)
            config = EngineConfig(**legacy)
        self.config = config
        self.model = model
        self.params = params
        # the kernel lane (compiled / interpreted, Pallas / reference) is
        # decided here, once, from the backend — kernels/lane.py
        self.kernels = config.kernels.resolve()
        self.eos_id = config.eos_id
        self.sched = Scheduler()
        self.rng = jax.random.key(config.seed)
        self.clock = config.clock if config.clock is not None \
            else CLK.SYSTEM_CLOCK
        self.faults = config.faults
        self.tracer = config.tracer
        if self.tracer is not None:
            # the tracer's spans read the engine's clock (one tracer serves
            # one engine)
            self.tracer.clock = self.clock
        TR.install_process_hooks()
        # what the last step ran (``_step_record``); None before the first
        # step and after a step with no live row
        self.last_step: Optional[dict] = None
        self._step_no = 0
        self._next_rid = 0
        self._requests: dict[int, Request] = {}
        self._events: list[StreamEvent] = []
        # rid -> RestoredSeq for restores committed by _reserve_paged but
        # not yet resumed by _admit_paged (one admission pass apart)
        self._pending_restores: dict[int, KV.RestoredSeq] = {}
        self._admit_round: list[Request] = []
        kvq = config.kv_quant            # normalized by EngineConfig
        if kvq is not None and not kvq.quantized:
            # fp passthrough is just another way to spell the cache dtype
            cache_dtype = kvq.jnp_dtype
            kvq = None
        elif config.cache_dtype is not None:
            cache_dtype = config.cache_dtype
        else:
            # the KV pools store what the model computes in (bf16 for a
            # served model; smoke configs compute in fp32)
            cache_dtype = jnp.dtype(model.cfg.dtype)
        self.kv_quant = kvq
        # what the cache payloads are stored as (int8 when quantized)
        self.cache_dtype = jnp.dtype(jnp.int8) if kvq is not None \
            else jnp.dtype(cache_dtype)
        batch_slots, max_len = config.batch_slots, config.max_len
        page_size, num_pages = config.page_size, config.num_pages

        layout = config.cache if config.cache is not None \
            else config.kernels.cache_layout
        self.layout = getattr(layout, "value", layout)
        if self.layout not in ("slot", "paged"):
            raise ValueError(f"unknown cache layout {layout!r}")
        if config.page_pool_bytes is not None and self.layout != "paged":
            raise ValueError(
                "page_pool_bytes applies to the paged cache layout only")

        # ---- tensor parallelism (DESIGN.md §17) ----
        self.tp = PL.mesh_size(config.mesh_shape)
        self._tp_ctx = None
        if self.tp > 1:
            if self.layout != "paged":
                raise ValueError(
                    "tensor-parallel serving shards the KV page pools — "
                    "the slot layout is single-device (cache='paged')")
            # validates head divisibility / GQA-only / act-order and builds
            # the mesh + local model + parameter PartitionSpecs
            self._tp_ctx = PL.build_tp_context(model, params, self.tp,
                                               config.tp_axis)

        # observability (DESIGN.md §15): one registry per engine, stamped
        # with the cache layout + kv-quant mode as constant labels;
        # EngineStats is a thin read-view over the same counters /metrics
        # exposes, so the two can never disagree
        kv_mode = kvq.dtype if kvq is not None \
            else jnp.dtype(cache_dtype).name
        self.metrics = make_engine_metrics(self.layout, kv_mode,
                                           enabled=config.metrics)
        self.stats = EngineStats(self.metrics)

        if self.layout == "paged":
            cfg = model.cfg
            max_pages = -(-max_len // page_size)
            if config.page_pool_bytes is not None:
                # byte-budget-derived pool: int8 KV buys ~2x (vs bf16) / ~4x
                # (vs fp32) the pages — i.e. deeper continuous batching.
                # Under tensor parallelism the budget is *per device*: each
                # device's pool holds its num_kv_heads/tp head-slice, so the
                # same byte budget buys tp× the pages (capacity scales with
                # devices, the whole point of DESIGN.md §17)
                num_pages = KQ.num_pages_for_budget(
                    config.page_pool_bytes, cfg.num_layers,
                    cfg.num_kv_heads // self.tp,
                    cfg.head_dim, page_size, dtype=cache_dtype, kv_quant=kvq)
            elif num_pages is None:
                num_pages = KQ.default_num_pages(batch_slots, max_len,
                                                 page_size)
            # bookkeeping-only manager: page payloads live in the model cache
            # tree below; the manager owns the device block table + free lists
            self.pc = KV.PagedCache(
                num_pages=num_pages, page_size=page_size,
                n_layers=cfg.num_layers, kv_heads=cfg.num_kv_heads,
                head_dim=cfg.head_dim, dtype=cache_dtype,
                max_seqs=batch_slots, max_pages=max_pages, alloc_pools=False,
                kv_quant=kvq)
            # raises for stacks paging can't serve (SSM/SWA/MLA/meta tokens);
            # under TP the pools are born head-sharded and the GPTQ weights
            # are sharded here; page *ids* stay global, so the PagedCache
            # bookkeeping above (free lists, refcounts, COW, prefix index)
            # is unchanged
            self.cache = self.paged_cache_like(num_pages)
            if self._tp_ctx is not None:
                self.params = PL.shard_params(self._tp_ctx, self.params)
            # per-device pool accounting for the device-labeled gauges
            self.metrics.configure_devices(
                self.tp,
                KQ.page_bytes(cfg.num_layers, cfg.num_kv_heads // self.tp,
                              cfg.head_dim, page_size, dtype=cache_dtype,
                              kv_quant=kvq) * (num_pages + 1))
            self.slots = None
        else:
            self.slots = KV.SlotCache(model, batch_slots, max_len,
                                      dtype=cache_dtype, kv_quant=kvq)
            self.pc = None
        self.batch_rows = batch_slots
        self.max_len = max_len

        # ---- speculative decoding (DESIGN.md §16) ----
        self._spec: Optional[SD.Speculator] = None
        if config.speculation is not None:
            cfg = model.cfg
            # rollback-by-not-advancing-seq_lens needs positional KV that
            # rejected writes can be abandoned in; recurrent (SSM) state and
            # ring (SWA) caches are mutated destructively by every token
            if cfg.family in ("ssm", "hybrid") or cfg.sliding_window \
                    or cfg.meta_tokens or cfg.attn_type != "gqa":
                raise ValueError(
                    "speculative decoding requires a full-attention GQA "
                    "stack with positional KV (no SSM/sliding-window/MLA/"
                    f"meta tokens), got family={cfg.family!r} "
                    f"attn_type={cfg.attn_type!r}")
            self._spec = SD.make_speculator(config.speculation, model,
                                            config, kernels=self.kernels)

        # Chunked prefill rides the same write-masked multi-token path as
        # spec-verify, so it carries the same family restriction; the other
        # slot-layout families (SSM/SWA/hybrid/meta — paging already rejects
        # them) keep the legacy inline whole-prompt prefill at admission and
        # run their decodes as 1-token chunks of the fused step.
        cfg = model.cfg
        self._chunked = (cfg.family not in ("ssm", "hybrid")
                         and not cfg.sliding_window and not cfg.meta_tokens
                         and cfg.attn_type == "gqa")
        # fused-step width buckets: 1 (pure decode), k+1 (verify), then the
        # prefill buckets — bounds recompiles under mixed traffic
        k1 = self._spec.k + 1 if self._spec is not None else 1
        self._width_buckets = tuple(sorted({1, k1, *PREFILL_BUCKETS}))

        # donate the cache tree (and seq_lens) so XLA updates the KV pools
        # in place instead of copying the whole pool every step — the
        # engine reassigns them from the jit results and keeps no other
        # reference.  CPU has no donation support (it would only warn), so
        # gate on the backend.
        cpu = jax.default_backend() == "cpu"
        tol = (config.speculation.greedy_accept_tol
               if config.speculation is not None else None)
        # the ONE jitted program every step runs (ISSUE 10): decode,
        # chunked prefill and spec-verify are all chunk rows of it
        impl = functools.partial(self._fused_step_impl, greedy_tol=tol)
        if self._tp_ctx is not None:
            # shard_map entry point (serving/parallel.py): same impl, same
            # operand positions, traced against the per-device local model
            fused = PL.tp_wrap_fused(self._tp_ctx, self.kernels, impl)
        else:
            fused = functools.partial(impl, self.model, self.kernels)
        self._fused = jax.jit(
            fused, static_argnames=("all_greedy",),
            donate_argnums=() if cpu else (6, 7))       # cache, seq_lens
        self._prefill = jax.jit(
            functools.partial(self._prefill_impl, self.model, self.kernels),
            donate_argnums=() if cpu else (3,))         # slot sub-cache
        self._read_slot = jax.jit(self._read_slot_impl)
        self._write_slot = jax.jit(self._write_slot_impl,
                                   donate_argnums=() if cpu else (0,))

        # ---- prefix-cache persistence (DESIGN.md §16) ----
        if config.prefix_cache_path is not None:
            if self.layout != "paged":
                raise ValueError(
                    "prefix_cache_path persists the hashed prefix cache — "
                    "a paged-layout feature (cache='paged')")
            self._load_prefix_cache(config.prefix_cache_path)

    # ------------------------------------------------------------ jitted fns
    @staticmethod
    def _fused_step_impl(model, kernels, params, tokens, chunk_lens, drafts,
                         draft_lens, emit, cache, seq_lens, block_tables,
                         live, greedy, temps, top_ks, top_ps, keys,
                         draft_probs, *, all_greedy: bool = False,
                         greedy_tol: float | None = None):
        """THE engine program (ISSUE 10, DESIGN.md §18): one forward over a
        token-budgeted batch of per-row chunks, then accept/sample.

        Every row is a ``(chunk_start=seq_lens[i], chunk_len=chunk_lens[i])``
        span of its sequence, right-padded to the bucketed step width C:

        * plain decode       — 1-token chunk, ``draft_lens=0``, ``emit``
        * chunked prefill    — budget-sized chunk; ``emit`` only on the
          chunk that completes the prompt (its last-position logits yield
          the first generated token)
        * speculative verify — (draft_lens+1)-token chunk ``[anchor |
          drafts]``; drafts are spliced in on device so device-resident
          draft-model proposals never round-trip through the host
        * unscheduled rows   — ``live=False``: writes masked, seq_lens kept

        ``accept_speculative`` degenerates to plain greedy/sampled decode at
        ``draft_lens=0`` (window width 1 → bonus token only), so ONE program
        serves every mix.  Cache writes cover ``chunk_lens`` positions
        (write_lens masking: null page on the paged layout, dropped on the
        slot layout); rejected-draft KV is dead weight the next chunk
        overwrites before anything can attend it (rollback by not advancing
        seq_lens).  Returns the packed (B, K+2) int32 transfer
        ``[n_emit | emitted...]``, the cache, and advanced seq_lens.
        """
        b, c = tokens.shape
        k = drafts.shape[1]
        if k and c > k:
            # splice drafts behind each row's anchor token (positions 1..k);
            # rows without drafts (prefill chunks, plain decode) keep their
            # staged tokens
            dmask = jnp.arange(k, dtype=jnp.int32)[None, :] \
                < draft_lens[:, None]
            span = jax.lax.dynamic_slice_in_dim(tokens, 1, k, axis=1)
            tokens = jax.lax.dynamic_update_slice(
                tokens, jnp.where(dmask, drafts, span), (0, 1))
        wl = jnp.where(live, chunk_lens, 0)
        # verify window: positions [start, start+k] score the k drafts + the
        # bonus.  start = chunk_lens-1 for draft-free rows (the last real
        # position — its argmax/sample is the next token), 0 for verify rows
        start = jnp.clip(chunk_lens - 1 - draft_lens, 0, None)
        idx = jnp.clip(
            start[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None, :],
            0, c - 1)
        # only the window's logits are computed: (B, k+1, V), never (B, C, V)
        window, cache = model.forward_chunks(
            params, tokens, wl, cache, seq_lens, kernels=kernels,
            block_tables=block_tables, logit_idx=idx)
        n_acc, emitted = accept_speculative(
            window, drafts, draft_lens, keys, greedy=greedy, temps=temps,
            top_ks=top_ks, top_ps=top_ps, draft_probs=draft_probs,
            all_greedy=all_greedy, greedy_tol=greedy_tol)
        n_acc = jnp.where(live & (draft_lens > 0), n_acc, 0)
        emit_live = emit & live
        n_emit = jnp.where(emit_live, n_acc + 1, 0)
        emitted = jnp.where(emit_live[:, None], emitted, 0)
        # advance by the accepted span (verify) or the whole chunk; the
        # emitted bonus token is never cache-written — it is the next step's
        # decode input (dead rows: wl=0 and draft_lens=0 keep seq_lens)
        adv = jnp.where(draft_lens > 0, n_acc + 1, wl)
        seq_lens = seq_lens + jnp.where(live, adv, 0)
        packed = jnp.concatenate([n_emit[:, None], emitted],
                                 axis=1).astype(jnp.int32)
        return packed, cache, seq_lens

    # ------------------------------------------------- cache trees, probes
    def paged_cache_like(self, num_pages: int):
        """A fresh paged cache tree laid out like this engine's (dtype, KV
        quantization, tensor-parallel sharding) with ``num_pages`` pages:
        the engine's own pool, or scratch space for ``forward_probe``."""
        init = functools.partial(
            self.model.init_paged_cache, num_pages, self.pc.page_size,
            dtype=self.cache_dtype, kv_quant=self.kv_quant)
        if self._tp_ctx is not None:
            return PL.init_cache(self._tp_ctx, init)
        return init()

    def forward_probe(self, kernels=None):
        """Jitted ``model.forward_chunks`` over this engine's model and
        parameter layout (shard_map'd under tensor parallelism), with
        ``kernels`` as the lane (default: the engine's) — what parity checks
        run a prefill and a decode through.  Call it as ``fn(params, tokens,
        chunk_lens, cache, seq_lens, block_tables, logit_idx)``; it returns
        ``(logits, cache)``."""
        kernels = (self.kernels if kernels is None else kernels).resolve()
        if self._tp_ctx is not None:
            return jax.jit(PL.tp_wrap_forward(self._tp_ctx, kernels))

        def fwd(params, tokens, chunk_lens, cache, seq_lens, block_tables,
                logit_idx):
            return self.model.forward_chunks(
                params, tokens, chunk_lens, cache, seq_lens,
                kernels=kernels, block_tables=block_tables,
                logit_idx=logit_idx)

        return jax.jit(fwd)

    @staticmethod
    def _prefill_impl(model, kernels, params, tokens, length, cache, seq_lens):
        # tokens right-padded to a bucket; `length` is the true prompt length.
        # Legacy inline-prefill path: slot-layout families whose caches the
        # write-masked chunked path cannot serve (SSM/SWA/hybrid/meta).
        lengths = jnp.full((tokens.shape[0],), length, jnp.int32)
        logits, cache, seq_lens = model.prefill(
            params, {"tokens": tokens}, cache, seq_lens, kernels=kernels,
            true_lengths=lengths)   # index within the text block
        return logits, cache, seq_lens - (tokens.shape[1] - length)

    @staticmethod
    def _read_slot_impl(cache, slot):
        """Slice one slot's cache sub-tree (batch axis 1; traced slot index,
        so every slot shares a single compiled program)."""
        return jax.tree_util.tree_map(
            lambda x: jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=1)
            if x.ndim >= 2 else x, cache)

    @staticmethod
    def _write_slot_impl(cache, sub, slot):
        """Write a prefilled sub-tree back into the slot via
        ``dynamic_update_slice`` — no whole-cache-tree rebuild per admit."""
        return jax.tree_util.tree_map(
            lambda full, s: jax.lax.dynamic_update_slice_in_dim(
                full, s.astype(full.dtype), slot, axis=1)
            if full.ndim >= 2 else s, cache, sub)

    # -------------------------------------------------------------- lifecycle
    def submit(self, tokens: list[int], max_new_tokens: int = 32,
               sampling: SamplingParams = SamplingParams(greedy=True), *,
               stop_token_ids: Sequence[int] = (),
               ignore_eos: bool = False, priority: int = 0,
               queue_timeout_s: Optional[float] = None) -> int:
        """Queue one request; returns its rid.

        Validates everything a bad request could break later — prompt+decode
        capacity on *both* cache layouts and the ``SamplingParams`` domains —
        so failures surface here with a clear message instead of inside the
        jitted decode step.  ``stop_token_ids`` stop generation like eos
        does; ``ignore_eos=True`` disables the eos stop (fixed-length
        benchmark decoding).

        Overload behaviour (DESIGN.md §14): ``priority`` picks the admission
        class (higher admitted first; on the paged layout a class may
        preempt strictly lower ones under page pressure).  Raises
        ``QueueFullError`` when ``EngineConfig.max_queued`` requests are
        already waiting.  ``queue_timeout_s`` (default
        ``EngineConfig.default_queue_timeout_s``) sheds the request with
        ``FinishReason.SHED`` if it is still unadmitted that many seconds
        after submit.
        """
        tokens = list(tokens)
        if not tokens:
            raise ValueError("empty prompt")
        if max_new_tokens <= 0:
            raise ValueError(
                f"max_new_tokens must be > 0, got {max_new_tokens}")
        if queue_timeout_s is not None and queue_timeout_s <= 0:
            raise ValueError(
                f"queue_timeout_s must be > 0, got {queue_timeout_s}")
        sampling.validate(self.model.cfg.vocab_size)
        mq = self.config.max_queued
        if mq is not None and len(self.sched.waiting) >= mq:
            self.metrics.rejected_submits.inc()
            # crude Retry-After: one in-flight generation's worth of steps
            per_step = (self.stats.wall_s / self.stats.steps
                        if self.stats.steps else 0.1)
            raise QueueFullError(
                f"wait queue is full ({mq} requests queued); retry later",
                retry_after_s=max(1.0, per_step * max_new_tokens))
        if self.layout == "paged":
            need = self.pc.pages_needed(len(tokens) + max_new_tokens)
            if need > min(self.pc.max_pages, self.pc.num_pages):
                raise ValueError(
                    f"request needs {need} pages "
                    f"(prompt {len(tokens)} + max_new {max_new_tokens} "
                    f"tokens) but the pool can never provide more than "
                    f"{min(self.pc.max_pages, self.pc.num_pages)}")
        elif len(tokens) + max_new_tokens > self.max_len:
            raise ValueError(
                f"request needs {len(tokens) + max_new_tokens} cache "
                f"positions (prompt {len(tokens)} + max_new "
                f"{max_new_tokens} tokens) but slot capacity max_len is "
                f"{self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        now = self.clock.now()
        timeout = (queue_timeout_s if queue_timeout_s is not None
                   else self.config.default_queue_timeout_s)
        req = Request(rid=rid, tokens=tokens,
                      max_new_tokens=max_new_tokens, sampling=sampling,
                      arrival=now,
                      stop_token_ids=tuple(stop_token_ids),
                      ignore_eos=ignore_eos, priority=priority,
                      queue_deadline=(now + timeout
                                      if timeout is not None else None))
        self._requests[rid] = req
        self.sched.submit(req)
        if self.tracer is not None:
            self.tracer.request_state(rid, "QUEUED", now,
                                      prompt_len=len(tokens),
                                      max_new_tokens=max_new_tokens,
                                      priority=priority)
        return rid

    def state_of(self, rid: int) -> RequestState:
        """Current lifecycle state of a submitted request."""
        return self._requests[rid].state

    def abort(self, rid: int) -> Optional[RequestOutput]:
        """Cancel a queued or in-flight request.

        Frees its decode slot or paged reservation immediately (page
        refcounts — including prefix-cache-shared pages — return to their
        pre-request values; the block-table row and free list are restored).
        Returns the partial ``RequestOutput`` with
        ``finish_reason=FinishReason.ABORT``, or None when the rid is
        unknown or already finished.  A terminal ``StreamEvent`` is emitted
        so ``stream()`` consumers observe the abort.
        """
        req = self.sched.cancel(rid)
        if req is not None:     # queued (or preempted): no device resources
            if self.layout == "paged":
                self.pc.drop_offloaded(rid)   # free any host checkpoint
            req.state = RequestState.ABORTED
            out = RequestOutput(
                rid=rid, prompt_len=len(req.tokens),
                output=list(req.saved_output),
                arrival=req.arrival, t_first_token=req.saved_t_first,
                t_done=self.clock.now(), finish_reason=FinishReason.ABORT)
            self.metrics.requests_finished.labels(reason="abort").inc()
            if self.tracer is not None:
                self.tracer.request_end(rid, "abort", out.t_done,
                                        tokens=len(out.output))
            self._events.append(StreamEvent(
                rid=rid, token=None, index=len(out.output),
                finish_reason=FinishReason.ABORT, output=out))
            return out
        hit = self.sched.find_active(rid)
        if hit is None:
            return None
        row, a = hit
        out = self._finish(row, [], FinishReason.ABORT)
        self._events.append(StreamEvent(
            rid=rid, token=None, index=len(out.output),
            finish_reason=FinishReason.ABORT, output=out))
        return out

    def _stop_reason(self, a: Active) -> Optional[FinishReason]:
        """Per-request stop criteria, checked after each generated token."""
        tok, req = a.output[-1], a.req
        if tok in req.stop_token_ids:
            return FinishReason.STOP
        if not req.ignore_eos and tok == self.eos_id:
            return FinishReason.STOP
        if len(a.output) >= req.max_new_tokens:
            return FinishReason.LENGTH
        return None

    def _emit_token(self, a: Active, row: int, tok: int,
                    finished: list[RequestOutput]):
        """Record one generated token: stop-criteria check, terminal
        bookkeeping, and the StreamEvent for ``stream()`` consumers."""
        reason = self._stop_reason(a)
        out = self._finish(row, finished, reason) if reason else None
        self._events.append(StreamEvent(
            rid=a.req.rid, token=tok, index=len(a.output) - 1,
            finish_reason=reason, output=out))

    def _sample_first(self, logits, req: Request) -> int:
        """Sample the first generated token from the prefill logits."""
        self.rng, k = jax.random.split(self.rng)
        return int(sample(logits, k, req.sampling)[0])

    def _shed_expired(self, finished: list[RequestOutput]):
        """Graceful shedding (DESIGN.md §14): drop queued requests whose
        queue deadline passed before admission.  They hold no resources;
        clients observe ``FinishReason.SHED`` (HTTP 503 + Retry-After)."""
        now = self.clock.now()
        for req in self.sched.pop_expired(now):
            req.state = RequestState.FINISHED
            out = RequestOutput(
                rid=req.rid, prompt_len=len(req.tokens), output=[],
                arrival=req.arrival, t_first_token=0.0, t_done=now,
                finish_reason=FinishReason.SHED)
            self.metrics.shed_requests.inc()
            self.metrics.requests_finished.labels(reason="shed").inc()
            if self.tracer is not None:
                self.tracer.request_end(req.rid, "shed", now,
                                        queued_s=now - req.arrival)
            finished.append(out)
            self._events.append(StreamEvent(
                rid=req.rid, token=None, index=0,
                finish_reason=FinishReason.SHED, output=out))

    def _admit(self, finished: list[RequestOutput]):
        self._shed_expired(finished)
        if self.layout == "paged":
            self._admit_paged(finished)
        else:
            self._admit_slot(finished)

    def _admit_slot(self, finished: list[RequestOutput]):
        for req in self.sched.admit(self.slots.num_free):
            slot = self.slots.alloc()
            assert slot is not None
            a = self.sched.activate(req, slot)
            a.t_admit = self.clock.now()
            self.metrics.queue_wait.observe(a.t_admit - req.arrival)
            if not self._chunked:
                self._prefill_slot_inline(req, a, slot, finished)
                continue
            # reservation only: the prompt streams into the slot as fused-
            # step chunks (write_lens drops each chunk's padded positions)
            a.prefill_ctx = req.tokens
            a.prefill_pos = 0
            a.prefill_end = len(req.tokens)
            self.slots.seq_lens = self.slots.seq_lens.at[slot].set(0)
            if self.tracer is not None:
                self.tracer.request_state(req.rid, "PREFILL", a.t_admit,
                                          prompt_len=len(req.tokens),
                                          slot=slot)

    def _prefill_slot_inline(self, req: Request, a: Active, slot: int,
                             finished: list[RequestOutput]):
        """Legacy whole-prompt prefill at admission, for slot-layout
        families the write-masked chunked path cannot serve: recurrent
        state (SSM) and ring caches (SWA) are polluted by padded tokens ->
        exact-length prefill for those families (one compile per length)."""
        t_admit = a.t_admit
        cfg = self.model.cfg
        paddable = cfg.family not in ("ssm", "hybrid") \
            and not cfg.sliding_window
        blen = bucket_len(len(req.tokens)) if paddable else len(req.tokens)
        if self.tracer is not None:
            self.tracer.request_state(req.rid, "PREFILL", t_admit,
                                      prompt_len=len(req.tokens),
                                      prefill_chunk=blen, slot=slot)
        toks = np.zeros((1, blen), np.int32)
        toks[0, :len(req.tokens)] = req.tokens
        slot_idx = jnp.asarray(slot, jnp.int32)
        sub_cache = self._read_slot(self.slots.cache, slot_idx)
        sub_lens = jnp.zeros((1,), jnp.int32)
        logits, sub_cache, sub_lens = self._prefill(
            self.params, jnp.asarray(toks), len(req.tokens), sub_cache,
            sub_lens)
        self.slots.cache = self._write_slot(self.slots.cache, sub_cache,
                                            slot_idx)
        self.slots.seq_lens = self.slots.seq_lens.at[slot].set(sub_lens[0])
        self.metrics.prefill_tokens.inc(len(req.tokens))
        tok = self._sample_first(logits, req)
        a.t_first_token = self.clock.now()
        self.metrics.ttft.labels(priority=req.priority).observe(
            a.t_first_token - req.arrival)
        a.output.append(tok)
        req.state = RequestState.RUNNING
        if self.tracer is not None:
            self.tracer.prefill_span(req.rid, t_admit, a.t_first_token,
                                     prefill_chunk=blen,
                                     prefill_tokens=len(req.tokens))
            self.tracer.request_state(req.rid, "RUNNING", a.t_first_token)
        self._emit_token(a, slot, tok, finished)

    # --------------------------------------------- paged admission/preemption
    def _gather_pages(self, page_ids: list[int]):
        """Host copies of the named physical pages from the engine's model
        cache tree (``KV.PAGE_AXIS`` in every pool/scale leaf) — the payload
        mover ``PagedCache.offload`` uses under ``alloc_pools=False``."""
        idx = np.asarray(page_ids, np.int32)
        return jax.tree_util.tree_map(
            lambda a: np.asarray(jnp.take(a, idx, axis=KV.PAGE_AXIS)),
            self.cache)

    def _scatter_pages(self, page_ids: list[int], payload):
        """Write host pages back into the model cache tree at freshly
        allocated physical page ids (restore counterpart)."""
        idx = jnp.asarray(page_ids, jnp.int32)
        self.cache = jax.tree_util.tree_map(
            lambda a, h: a.at[:, :, idx].set(jnp.asarray(h, a.dtype)),
            self.cache, payload)

    # ----------------------------------- prefix-cache persistence (§16)
    def save_prefix_cache(self, path: Optional[str] = None) -> int:
        """Serialize the hashed prefix-cache index + its page payloads to a
        directory (``index.json`` + ``pages.npz``) so a future engine with
        the same model/quant config starts warm.  Safe because the hash
        chain is deterministic across processes (sha256 seed keyed by the
        kv-quant mode + page size — ``kv_cache.prefix_hash_seed``), so the
        persisted keys mean the same token prefixes to the loader.
        Returns the number of pages written."""
        if self.layout != "paged":
            raise ValueError("prefix-cache persistence is paged-layout only")
        path = path if path is not None else self.config.prefix_cache_path
        if path is None:
            raise ValueError("no prefix_cache_path configured or passed")
        pc = self.pc
        keys, pages = pc.export_prefix_index()
        payload = self._gather_pages(pages) if pages else None
        leaves = jax.tree_util.tree_leaves(payload) if pages else []
        os.makedirs(path, exist_ok=True)
        index = {"version": 1, "seed": int(pc._hash_seed),
                 "page_size": pc.page_size,
                 "keys": [str(k) for k in keys], "n_leaves": len(leaves)}
        if leaves:
            np.savez(os.path.join(path, "pages.npz"),
                     **{f"leaf_{i}": leaf for i, leaf in enumerate(leaves)})
        with open(os.path.join(path, "index.json"), "w") as f:
            json.dump(index, f)
        return len(pages)

    def _load_prefix_cache(self, path: str) -> int:
        """Warm-start the prefix cache from ``save_prefix_cache`` output.
        Missing directory/index is a cold start (returns 0); an index saved
        under a different quant mode or page size raises — its page bytes
        would be silently wrong for this cache.  Adopted pages are pinned
        (refcount 1, no owning sequence) so the warm set is never evicted;
        pool pressure permitting, a prefix subset is adopted."""
        pc = self.pc
        index_path = os.path.join(path, "index.json")
        if not os.path.exists(index_path):
            return 0
        with open(index_path) as f:
            index = json.load(f)
        if (index.get("seed") != int(pc._hash_seed)
                or index.get("page_size") != pc.page_size):
            raise ValueError(
                f"prefix cache at {path!r} was saved under a different "
                f"kv-quant mode or page size (seed/page_size mismatch) — "
                f"its page payloads are not valid for this engine")
        keys = [int(k) for k in index["keys"]]
        if not keys:
            return 0
        if index["n_leaves"] != len(jax.tree_util.tree_leaves(self.cache)):
            raise ValueError(
                f"prefix cache at {path!r} was saved from a different model "
                f"cache shape ({index['n_leaves']} leaves)")
        data = np.load(os.path.join(path, "pages.npz"))
        leaves = [data[f"leaf_{i}"] for i in range(index["n_leaves"])]
        treedef = jax.tree_util.tree_structure(self.cache)
        payload = jax.tree_util.tree_unflatten(treedef, leaves)
        adopted = pc.adopt_prefix_pages(keys)
        if not adopted:
            return 0
        col = {k: i for i, k in enumerate(keys)}
        cols = [col[k] for k, _ in adopted]
        dest = [p for _, p in adopted]
        sub = jax.tree_util.tree_map(
            lambda a: np.take(a, cols, axis=KV.PAGE_AXIS), payload)
        self._scatter_pages(dest, sub)
        return len(adopted)

    def _ctx_tokens(self, req: Request) -> list[int]:
        """The token span a preempted request's KV checkpoint covers:
        prompt plus every generated token already *written* to the cache —
        the last sampled token is the next decode input, not yet written."""
        return req.tokens + req.saved_output[:-1]

    def _preempt_victim(self, min_priority: int) -> bool:
        """Evict the best victim below ``min_priority``: retire it from the
        batch, checkpoint its private pages to host memory, release its
        reservation, and re-queue it (PREEMPTED, original queue order, its
        generated tokens saved for the restore)."""
        row = self.sched.preemption_victim(min_priority)
        if row is None:
            return False
        if self._spec is not None:
            self._spec.invalidate(row)
        a = self.sched.retire(row)
        req = a.req
        rec = self.pc.offload(req.rid, gather=self._gather_pages)
        req.saved_output = a.output
        req.saved_t_first = a.t_first_token
        req.state = RequestState.PREEMPTED
        self.sched.requeue(req)
        m = self.metrics
        m.preemptions.inc()
        m.offloaded_pages.inc(rec.n_payload_pages)
        m.offloaded_bytes.inc(rec.nbytes)
        if self.tracer is not None:
            now = self.clock.now()
            self.tracer.request_instant(
                req.rid, "offload", now, pages=rec.n_payload_pages,
                shared_pages=rec.shared_pages, bytes=rec.nbytes)
            self.tracer.request_state(req.rid, "PREEMPTED", now,
                                      offloaded_pages=rec.n_payload_pages)
        return True

    def _try_reserve(self, req: Request) -> bool:
        """One reservation attempt: restore an offloaded victim, or a fresh
        prompt+decode footprint reservation with prefix registration."""
        if req.rid in self.pc.offloaded:
            info = self.pc.restore(
                req.rid, self._ctx_tokens(req),
                reserve=req.max_new_tokens - len(req.saved_output) + 1,
                scatter=self._scatter_pages)
            if info is None:
                return False
            self._pending_restores[req.rid] = info
            return True
        if not self.pc.alloc_seq(req.rid, len(req.tokens), tokens=req.tokens,
                                 reserve=req.max_new_tokens):
            return False
        # prefix registration is deferred to prompt completion (``step``):
        # the prompt KV now streams in over several fused-step chunks, so
        # registering here would let a follower share still-unwritten pages
        return True

    def _prefix_pending(self, req: Request) -> bool:
        """True when an active mid-prefill row's context shares at least one
        full page with ``req``'s prompt: that leader will publish those pages
        to the prefix cache once its last chunk lands, so admitting ``req``
        now would forfeit the share (the registry only lists written pages).
        Deferring one round costs at most the leader's remaining prefill."""
        ps = self.pc.page_size
        ctxs = [a.prefill_ctx for a in self.sched.active.values()
                if a.pending_prefill]
        # leaders reserved earlier in this same admission round are not in
        # ``active`` yet (activation happens once the round closes)
        ctxs += [r.tokens for r in self._admit_round]
        for ctx in ctxs:
            n = min(len(req.tokens), len(ctx)) // ps * ps
            lcp = next((i for i in range(n) if req.tokens[i] != ctx[i]), n)
            if lcp >= ps:
                return True
        return False

    def _reserve_paged(self, req: Request) -> bool:
        """Admission policy for ``Scheduler.admit``: reserve the request's
        whole prompt+decode page footprint (minus prefix-cache hits) and a
        block-table row, or defer.  The request's prompt pages enter the
        prefix cache only once its last prefill chunk has written them
        (``step``) — a follower can never share still-unwritten pages;
        instead its admission waits until the leader's prefix is published.

        When the reservation fails and preemption is enabled, victims
        strictly below this request's priority are evicted (lowest class
        first, most-recently-admitted within it) until the reservation fits
        or no eligible victim remains (DESIGN.md §14)."""
        if req.rid not in self.pc.offloaded and self._prefix_pending(req):
            self.metrics.deferred_admissions.inc()
            return False
        ok = self._try_reserve(req)
        while (not ok and self.config.preemption
               and self._preempt_victim(req.priority)):
            ok = self._try_reserve(req)
        if not ok:
            self.metrics.deferred_admissions.inc()
        elif req.rid not in self._pending_restores:
            self._admit_round.append(req)
        return ok

    def _resume_restored(self, req: Request, a: Active, row: int,
                         info: KV.RestoredSeq):
        """Re-activate a preempted request after its pages came back
        on-device: re-attach its generated tokens and schedule any prefix
        span whose donor evicted while it was offloaded (``[hit_pages,
        snap_start_page)`` — restore left those pages empty) as fused-step
        chunks.  No token is sampled for the gap (``prefill_sample=False``):
        the next token comes from the next decode step, fed the last
        generated token — which makes the round trip token-identical under
        greedy."""
        pc = self.pc
        ctx = self._ctx_tokens(req)
        a.output = req.saved_output
        a.t_first_token = req.saved_t_first
        req.saved_output = []
        gap_start = info.hit_pages * pc.page_size
        gap_end = info.snap_start_page * pc.page_size
        m = self.metrics
        m.restored_pages.inc(info.restored_pages)
        m.prefix_hit_pages.inc(info.hit_pages)
        m.prefix_hit_tokens.inc(gap_start)
        if self.tracer is not None:
            now = self.clock.now()
            self.tracer.request_instant(
                req.rid, "restore", now, restored_pages=info.restored_pages,
                hit_pages=info.hit_pages,
                gap_recompute_tokens=max(0, gap_end - gap_start))
        if gap_start < gap_end:
            # stream the donor-evicted span back through budget-sized
            # chunks; the row decodes again once they land (its snapshot
            # pages past the gap already hold KV — ``resume_len`` is
            # published then)
            a.prefill_ctx = ctx
            a.prefill_pos = gap_start
            a.prefill_end = gap_end
            a.prefill_sample = False
            a.resume_len = info.length
            pc.seq_lens = pc.seq_lens.at[row].set(gap_start)
            return
        pc.seq_lens = pc.seq_lens.at[row].set(info.length)
        pc.register_prefix(req.rid, ctx)
        req.state = RequestState.RUNNING
        if self.tracer is not None:
            self.tracer.request_state(req.rid, "RUNNING", self.clock.now(),
                                      restored=True)

    def _admit_paged(self, finished: list[RequestOutput]):
        pc = self.pc
        self._admit_round = []
        for req in self.sched.admit(self._reserve_paged):
            row = pc.row_of(req.rid)
            a = self.sched.activate(req, row)
            a.t_admit = self.clock.now()
            self.metrics.queue_wait.observe(a.t_admit - req.arrival)
            info = self._pending_restores.pop(req.rid, None)
            if info is not None:
                # preemption restore: pages are back (host scatter already
                # done by _try_reserve); no first-token sample — decode
                # continues where it left off, possibly after gap chunks
                self._resume_restored(req, a, row, info)
                continue
            hit_pages = pc.prefix_hits.get(req.rid, 0)
            if hit_pages * pc.page_size >= len(req.tokens):
                # Full-prefix hit (ISSUE 5): a zero-token suffix chunk would
                # leave no position to sample the first token from.  Back
                # off so at least the last prompt token is recomputed; the
                # backed-off pages are swapped private first so a donor's
                # live pages are never rewritten.  Unreachable via
                # ``alloc_seq``'s own hit cap — this guards any future
                # admission path that shares more aggressively.
                hit_pages = (len(req.tokens) - 1) // pc.page_size
                pc.release_prefix(req.rid, hit_pages)
                pc.prefix_hits[req.rid] = hit_pages
            hit_tokens = hit_pages * pc.page_size
            # reservation only: the prompt suffix streams into the reserved
            # pages as fused-step chunks (Scheduler.plan_chunks deals them
            # under the token budget); the device row starts at the hit
            a.prefill_ctx = req.tokens
            a.prefill_pos = hit_tokens
            a.prefill_end = len(req.tokens)
            pc.seq_lens = pc.seq_lens.at[row].set(hit_tokens)
            pc.lengths[req.rid] = hit_tokens
            m = self.metrics
            m.prefix_hit_pages.inc(hit_pages)
            m.prefix_hit_tokens.inc(hit_tokens)
            if self.tracer is not None:
                self.tracer.request_state(
                    req.rid, "PREFILL", a.t_admit,
                    prompt_len=len(req.tokens), prefix_hit_pages=hit_pages,
                    pages_reserved=len(pc.tables[req.rid]), row=row)

    def _finish(self, row: int, finished: list[RequestOutput],
                reason: FinishReason = FinishReason.STOP) -> RequestOutput:
        a = self.sched.retire(row)
        if self.layout == "paged":
            self.pc.free_seq(a.req.rid)
        else:
            self.slots.free(row)
        a.req.state = (RequestState.ABORTED if reason is FinishReason.ABORT
                       else RequestState.FINISHED)
        if self._spec is not None:
            self._spec.invalidate(row)
        out = RequestOutput(
            rid=a.req.rid, prompt_len=len(a.req.tokens), output=a.output,
            arrival=a.req.arrival, t_first_token=a.t_first_token,
            t_done=self.clock.now(), finish_reason=reason,
            spec_proposed=a.req.spec_proposed,
            spec_accepted=a.req.spec_accepted)
        m = self.metrics
        m.requests_finished.labels(reason=reason.value).inc()
        if out.t_first_token:
            m.request_latency.observe(out.latency)
        if out.tpot > 0.0:
            m.tpot.observe(out.tpot)
        if self.tracer is not None:
            self.tracer.request_end(out.rid, reason.value, out.t_done,
                                    tokens=len(out.output))
        finished.append(out)
        return out

    # a legacy `while True: eng.step()` loop never drains the event buffer;
    # cap it (drop-oldest) so such callers don't grow memory unboundedly
    _MAX_PENDING_EVENTS = 65_536

    def _step_width(self, need: int) -> int:
        """Bucketed fused-step width: smallest of ``_width_buckets`` (1,
        k+1, then the prefill buckets) holding ``need`` tokens; multiples
        of 4096 past the table.  Bounds jit recompiles under mixed
        traffic."""
        for b in self._width_buckets:
            if need <= b:
                return b
        return -(-need // 4096) * 4096

    def step(self) -> list[RequestOutput]:
        """One engine iteration: admissions + ONE fused-step invocation
        (DESIGN.md §18) covering every live row's chunk — decode, chunked
        prefill and spec-verify together.

        Wall-clock accounting happens *here* (one clock read at entry, one
        at exit) so every pump — ``run``/``generate``/``stream`` wrappers,
        the HTTP worker thread, or a bare ``while: eng.step()`` loop —
        accounts identically into ``engine_wall_seconds_total``.

        Each phase is a span (``serving/tracing.py``): ``engine.step``
        around the whole and, in order inside it, ``engine.admit``,
        ``engine.plan``, ``engine.stage`` (holding ``engine.propose`` under
        speculation), ``engine.dispatch``, ``engine.wait`` (the blocking
        transfer, also counted into ``engine_device_wait_seconds_total``),
        ``engine.emit`` and ``engine.end_step``.  ``last_step`` records
        what the step ran.

        Rollback is implicit under speculation: ``seq_lens`` (and the host
        page-length mirror) advance only to the accepted position;
        rejected positions' KV is dead weight that the next chunk
        overwrites before anything can attend it.  Per-row draft budgets
        are capped at ``max_new - emitted - 1`` so a full acceptance plus
        the bonus token lands exactly on the reserved page/slot footprint,
        never past it.
        """
        with TR.span("engine.step", self.tracer) as step_span:
            # jit cache misses inside the step count against this engine
            prev, TR.RUNNING.engine = TR.RUNNING.engine, self
            try:
                return self._step(step_span)
            finally:
                TR.RUNNING.engine = prev

    def _step(self, step_span: TR.Span) -> list[RequestOutput]:
        t_step0 = self.clock.now()
        tr = self.tracer
        if self.faults is not None:
            # deterministic fault injection (serving/faults.py): scheduled
            # page seizures, stalls and aborts fire before admissions
            self.faults.on_step(self)
        if len(self._events) > self._MAX_PENDING_EVENTS:
            del self._events[:len(self._events) - self._MAX_PENDING_EVENTS]
        finished: list[RequestOutput] = []
        self.last_step = None
        with TR.span("engine.admit", tr):
            self._admit(finished)
        self.metrics.peak_active.set_max(len(self.sched.active))
        if not self.sched.active:
            with TR.span("engine.end_step", tr):
                self._end_step(t_step0, finished, 0, step_span)
            return finished
        bs = self.batch_rows
        spec = self._spec
        k = spec.k if spec is not None else 0
        # token-budget packing: every decode row claims its reserve (1
        # plain, k+1 under speculation), the remaining budget is dealt to
        # mid-prefill rows as prompt chunks; budget-starved prefill rows
        # sit this step out (live=False, writes masked, seq_lens kept)
        with TR.span("engine.plan", tr):
            plan = self.sched.plan_chunks(self.config.max_step_tokens,
                                          reserve=k + 1)
        with TR.span("engine.stage", tr):
            decode_rows = {row: a for row, a in self.sched.active.items()
                           if not a.pending_prefill}
            # host-side staging: per-row sampling arrays + chunk spans
            # (numpy, no device round-trips)
            live = np.zeros((bs,), np.bool_)
            emit = np.zeros((bs,), np.bool_)
            chunk_lens = np.zeros((bs,), np.int32)
            greedy = np.ones((bs,), np.bool_)
            temps = np.ones((bs,), np.float32)
            top_ks = np.zeros((bs,), np.int32)
            top_ps = np.ones((bs,), np.float32)
            for row, a in self.sched.active.items():
                sp = a.req.sampling
                greedy[row] = sp.greedy or sp.temperature == 0.0
                temps[row] = sp.temperature if sp.temperature > 0.0 else 1.0
                top_ks[row] = sp.top_k
                top_ps[row] = sp.top_p
            all_greedy = bool(greedy.all())

            # ---- speculative proposal (decode rows only) ----
            lens = np.zeros((bs,), np.int32)
            drafts_dev = jnp.zeros((bs, k), jnp.int32)
            probs = None
            if spec is not None and decode_rows:
                with TR.span("engine.propose", tr, step=self._step_no,
                             batch=len(decode_rows)) as propose_span:
                    rows: dict[int, tuple[int, list[int], int]] = {}
                    caps = np.zeros((bs,), np.int32)
                    for row, a in decode_rows.items():
                        cap = max(0, min(spec.k, a.req.max_new_tokens
                                         - len(a.output) - 1))
                        rows[row] = (a.req.rid, a.req.tokens + a.output, cap)
                        caps[row] = cap
                    samp_host = None if all_greedy \
                        else (greedy, temps, top_ks, top_ps)
                    prop = spec.propose(rows, all_greedy=all_greedy,
                                        samp=samp_host)
                    lens = np.minimum(np.asarray(prop.draft_lens, np.int32),
                                      caps)
                    drafts_dev = prop.drafts \
                        if not isinstance(prop.drafts, np.ndarray) \
                        else jnp.asarray(prop.drafts)
                    probs = prop.probs
                    proposed = int(lens.sum())
                    self.metrics.spec_proposed.inc(proposed)
                    for row, a in decode_rows.items():
                        a.req.spec_proposed += int(lens[row])
                    propose_span.set(proposed=proposed)

            # ---- chunk staging: decode anchors (+drafts on device), prompt
            # chunks from the plan ----
            need = k + 1 if (spec is not None and decode_rows) else 1
            if plan:
                need = max(need, max(plan.values()))
            width = self._step_width(need)
            tokens = np.zeros((bs, width), np.int32)
            for row, a in decode_rows.items():
                live[row] = True
                emit[row] = True
                chunk_lens[row] = int(lens[row]) + 1
                tokens[row, 0] = a.output[-1] if a.output \
                    else a.req.tokens[-1]
            for row, c in plan.items():
                a = self.sched.active[row]
                live[row] = True
                chunk_lens[row] = c
                tokens[row, :c] = \
                    a.prefill_ctx[a.prefill_pos:a.prefill_pos + c]
                # the chunk that completes the prompt emits the first token
                # (restore-gap chunks never sample — the next token is
                # already in the request's output)
                emit[row] = (a.prefill_pos + c >= a.prefill_end
                             and a.prefill_sample)
            if all_greedy:
                # argmax-only trace: no rng consumption, no sampling operands
                samp = (None, None, None, None, None)
            else:
                self.rng, sub = jax.random.split(self.rng)
                samp = (jnp.asarray(greedy), jnp.asarray(temps),
                        jnp.asarray(top_ks), jnp.asarray(top_ps),
                        jax.random.split(sub, bs))
            head = (self.params, jnp.asarray(tokens), jnp.asarray(chunk_lens),
                    drafts_dev, jnp.asarray(lens), jnp.asarray(emit))
            live_dev = jnp.asarray(live)
            self.last_step = self._step_record(plan, chunk_lens, emit, width)
            step_span.set(rows=len(self.last_step["rows"]), width=width,
                          tokens=int(chunk_lens.sum()),
                          prefill_tokens=int(sum(plan.values())))
        with TR.span("engine.dispatch", tr):
            if self.layout == "paged":
                pc = self.pc
                packed_dev, self.cache, pc.seq_lens = self._fused(
                    *head, self.cache, pc.seq_lens, pc.block_tables,
                    live_dev, *samp, probs, all_greedy=all_greedy)
            else:
                packed_dev, self.slots.cache, self.slots.seq_lens = \
                    self._fused(*head, self.slots.cache, self.slots.seq_lens,
                                None, live_dev, *samp, probs,
                                all_greedy=all_greedy)
        with TR.span("engine.wait", tr):
            t_wait = self.clock.now()
            # the single device->host transfer of the step
            packed = np.asarray(jax.device_get(packed_dev))
            self.metrics.device_wait_seconds.inc(self.clock.now() - t_wait)
        with TR.span("engine.emit", tr):
            decoded = 0
            accepted_total = 0
            for row in sorted(self.sched.active):
                a = self.sched.active[row]
                rid = a.req.rid
                if not live[row]:
                    continue
                if row in plan:
                    self._advance_prefill(a, row, plan[row], packed,
                                          finished)
                    continue
                n_emit = int(packed[row, 0])
                n_acc = n_emit - 1
                if self.layout == "paged":
                    self.pc.lengths[rid] += n_emit   # host seq_lens mirror
                if spec is not None:
                    a.req.spec_accepted += n_acc
                    accepted_total += n_acc
                    self.metrics.spec_accepted.inc(n_acc)
                    self.metrics.spec_accept_len.observe(n_acc)
                for tok in packed[row, 1:1 + n_emit].tolist():
                    decoded += 1
                    a.output.append(int(tok))
                    self._emit_token(a, row, int(tok), finished)
                    if row not in self.sched.active:
                        # retired mid-span (stop token / length / abort):
                        # the retirement already freed the row's device
                        # state, so later emitted tokens are dropped with it
                        break
                else:
                    if spec is not None:
                        spec.observe(row, rid, n_acc)
            m = self.metrics
            m.tokens_generated.inc(decoded)
            m.steps.inc()
            if spec is not None and decode_rows:
                m.spec_verify_steps.inc()
                step_span.set(accepted=accepted_total)
        with TR.span("engine.end_step", tr):
            self._end_step(t_step0, finished, decoded, step_span)
        return finished

    def _step_record(self, plan: dict, chunk_lens, emit,
                     width: int) -> dict:
        """``last_step``: the step's batch rows and width, and for each
        live row ``[start, chunk, emits]`` in admission order — a prefill
        chunk starts at the row's prefill position, a decode row at its
        context (the tokens already in its cache)."""
        rows = []
        for row, a in self.sched.active.items():
            if row in plan:
                rows.append([a.prefill_pos, plan[row], bool(emit[row])])
            elif not a.pending_prefill:
                ctx = (self.pc.lengths[a.req.rid] if self.layout == "paged"
                       else len(a.req.tokens) + len(a.output) - 1)
                rows.append([ctx, int(chunk_lens[row]), True])
        return {"batch": self.batch_rows, "width": width, "rows": rows}

    def _advance_prefill(self, a: Active, row: int, c: int, packed,
                         finished: list[RequestOutput]) -> None:
        """Bookkeeping for one landed prefill chunk: advance the span; on
        the chunk that completes the prompt, register the now-written
        prefix pages and surface the first generated token (or, for a
        restore gap, publish the resumed length — its next token is
        already in the request's output)."""
        req = a.req
        a.prefill_pos += c
        self.metrics.prefill_tokens.inc(c)
        pc = self.pc if self.layout == "paged" else None
        if pc is not None and a.prefill_sample:
            pc.lengths[req.rid] += c   # host seq_lens mirror
        if a.pending_prefill:
            return
        if not a.prefill_sample:
            # restore gap recomputed: the snapshot pages past the gap
            # already hold KV — publish the full resumed length
            if pc is not None:
                pc.seq_lens = pc.seq_lens.at[row].set(a.resume_len)
                pc.register_prefix(req.rid, a.prefill_ctx)
            a.prefill_sample = True
            a.resume_len = 0
            req.state = RequestState.RUNNING
            if self.tracer is not None:
                self.tracer.request_state(req.rid, "RUNNING",
                                          self.clock.now(), restored=True)
            return
        if pc is not None:
            pc.register_prefix(req.rid, a.prefill_ctx)
        tok = int(packed[row, 1])
        a.t_first_token = self.clock.now()
        self.metrics.ttft.labels(priority=req.priority).observe(
            a.t_first_token - req.arrival)
        a.output.append(tok)
        req.state = RequestState.RUNNING
        if self.tracer is not None:
            self.tracer.prefill_span(req.rid, a.t_admit, a.t_first_token,
                                     prefill_chunk=c,
                                     prefill_tokens=a.prefill_end)
            self.tracer.request_state(req.rid, "RUNNING", a.t_first_token)
        self._emit_token(a, row, tok, finished)

    def _end_step(self, t0: float, finished: list[RequestOutput],
                  decoded: int, step_span: TR.Span) -> None:
        """Close out one ``step()``: wall/duration accounting, occupancy
        gauges, and the step span's annotations for the Perfetto export.
        Host-side bookkeeping only — nothing here touches a device
        value."""
        t1 = self.clock.now()
        m = self.metrics
        m.wall_seconds.inc(t1 - t0)
        m.step_duration.observe(t1 - t0)
        m.active_requests.set(len(self.sched.active))
        m.waiting_requests.set(len(self.sched.waiting))
        if self.layout == "paged":
            m.sync_pool(self.pc)
        if self.tracer is not None:
            args = {"step": self._step_no, "batch": len(self.sched.active),
                    "waiting": len(self.sched.waiting), "decoded": decoded,
                    "finished": len(finished)}
            if self.layout == "paged":
                occ = self.pc.occupancy()
                args["free_pages"] = occ["free_pages"]
                args["pool_utilization"] = round(occ["utilization"], 6)
            step_span.set(**args)
        self._step_no += 1

    def drain_events(self) -> list[StreamEvent]:
        """Take ownership of the pending ``StreamEvent``s (per-token events
        from ``step()`` and terminal abort events) without stepping."""
        events, self._events = self._events, []
        return events

    def step_events(self) -> list[StreamEvent]:
        """One engine iteration, returning the per-token ``StreamEvent``s it
        produced (plus any pending abort events) instead of just the
        finished requests."""
        self.step()
        return self.drain_events()

    def run(self, *, max_steps: int = 10_000) -> list[RequestOutput]:
        """Drain the queue; returns finished requests with latency stats.

        Wall time is accounted inside ``step()`` — there is no extra
        accounting here, so driving ``step()`` directly reads the same."""
        out: list[RequestOutput] = []
        steps = 0
        while not self.sched.idle and steps < max_steps:
            out.extend(self.step())
            self._events.clear()       # run() consumers read outputs, not events
            steps += 1
        return out

    def generate(self, prompts, *, max_new_tokens: int = 32,
                 sampling: SamplingParams = SamplingParams(greedy=True),
                 stop_token_ids: Sequence[int] = (),
                 ignore_eos: bool = False,
                 max_steps: int = 10_000) -> list[RequestOutput]:
        """Blocking convenience: submit ``prompts`` (one token-id list, or a
        list of them) and pump ``step()`` until they all finish.  Returns
        their ``RequestOutput``s in submission order.  ``sampling`` may be a
        single ``SamplingParams`` or one per prompt."""
        if prompts and isinstance(prompts[0], int):
            prompts = [prompts]
        samplings = (list(sampling) if isinstance(sampling, (list, tuple))
                     else [sampling] * len(prompts))
        if len(samplings) != len(prompts):
            raise ValueError(
                f"{len(prompts)} prompts but {len(samplings)} SamplingParams")
        rids = [self.submit(p, max_new_tokens=max_new_tokens, sampling=sp,
                            stop_token_ids=stop_token_ids,
                            ignore_eos=ignore_eos)
                for p, sp in zip(prompts, samplings)]
        want = set(rids)
        outs: dict[int, RequestOutput] = {}
        steps = 0
        while want and not self.sched.idle and steps < max_steps:
            for out in self.step():
                if out.rid in want:
                    outs[out.rid] = out
                    want.discard(out.rid)
            self._events.clear()
            steps += 1
        return [outs[r] for r in rids if r in outs]

    def stream(self, *, max_steps: int = 10_000) -> Iterator[StreamEvent]:
        """Pump ``step()`` until the engine is idle, yielding one
        ``StreamEvent`` per generated token across all in-flight requests —
        continuous batching preserved (new submissions made while iterating
        are admitted and interleaved).  Terminal events carry the request's
        ``RequestOutput``; aborts surface as terminal events too."""
        steps = 0
        while not self.sched.idle and steps < max_steps:
            yield from self.step_events()
            steps += 1
        # e.g. an abort() that idled the engine mid-iteration
        yield from self.drain_events()
