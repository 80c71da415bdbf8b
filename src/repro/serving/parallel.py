"""Tensor-parallel paged serving (DESIGN.md §17): one ``Engine`` spanning a
device mesh.

The engine's single fused-step program (ISSUE 10) is wrapped in
``shard_map`` over a 1-D mesh so the GPTQ weights and the KV page pools are
*partitioned* across devices while the scheduler, block tables and sampling
state stay replicated.  Layout (Megatron col->row inside every block,
reusing the parameter role sets from ``sharding/partition.py``):

* **col-parallel** (``wq``/``wk``/``wv``/``w_gate``/``w_up``): output (N)
  axis sharded — each device computes its own head / d_ff slice from
  replicated activations.  For GPTQ leaves that means ``qweight``/
  ``scales``/``qzeros`` columns (the qzeros nibble packing needs the
  per-device N to stay a multiple of 8).
* **row-parallel** (``wo``/``w_down``): input (K) axis sharded — each
  device already holds the matching slice of the upstream activations
  (its heads / its d_ff lanes) and produces a *partial* matmul that
  ``layers.tp_all_reduce`` (a psum over the TP axis, armed by
  ``layers.tp_epilogue`` at trace time) completes.  Act-order ``perm``
  permutes the full K axis and cannot cross shards — rejected.
* **KV page pools**: the ``num_kv_heads`` axis of ``k_pages``/``v_pages``
  (and the int8 ``k_scales``/``v_scales`` pools) is sharded.  Page *ids*
  stay global — every device owns the head-slice of every page — so the
  host-side ``PagedCache`` bookkeeping (free lists, refcounts, COW, the
  hashed prefix index, offload/restore) is byte-for-byte the single-device
  code, and block tables are replicated operands.
* everything else (embedding, norms, tied head, q/k-norm scales, sampling
  state, PRNG keys) is replicated, so the post-psum activations — and
  therefore logits, argmax and samples — are identical on every device and
  the replicated out-specs are sound by construction.

The shard_map body runs the *same* ``Engine._fused_step_impl`` code (one
program for decode, chunked prefill and spec-verify — ISSUE 10) against a
local model whose config carries the per-device head counts (``gqa_apply``
reshapes with ``cfg.num_heads`` / ``cfg.num_kv_heads``), which keeps the
Pallas ``paged_attention`` / ``paged_prefill`` / GPTQ GEMV kernels entirely
unchanged: they see a smaller model.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.gptq import QuantizedLinear
from repro.models import layers as L
from repro.sharding.partition import COL_PARALLEL, ROW_PARALLEL


def _shard_map(body, mesh, in_specs, out_specs):
    """shard_map with the replication-check compat shim (same dance as
    ``models/ffn.py``'s expert-parallel path): the out-specs are replicated
    by construction (see module docstring), which the checker cannot
    prove through psum-free branches."""
    sm = getattr(jax, "shard_map", None)
    if sm is None:                              # pragma: no cover - old jax
        from jax.experimental.shard_map import shard_map as sm
    try:
        return sm(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_vma=False)
    except TypeError:                           # pragma: no cover - old jax
        return sm(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                  check_rep=False)


def _entry_name(entry) -> str:
    """Dict key / dataclass field name of one tree-path entry."""
    for attr in ("key", "name", "idx"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


@dataclasses.dataclass(frozen=True)
class TPContext:
    """Everything the engine needs to run one model tensor-parallel."""
    mesh: Mesh
    axis: str
    tp: int
    local_model: object          # LM with per-device head counts
    param_specs: object          # PartitionSpec tree matching the params


# ------------------------------------------------------------- spec building
def _matrix_spec(ndim: int, shard_axis: int, axis: str) -> P:
    """P over an ndim-array sharding exactly ``shard_axis`` (negative,
    counted from the end so group-stacked leading dims stay replicated)."""
    dims: list = [None] * ndim
    dims[ndim + shard_axis] = axis
    return P(*dims)


def param_specs(params, axis: str, tp: int):
    """PartitionSpec tree for a (possibly GPTQ-quantized, possibly
    group-stacked) parameter tree.  Raises ``ValueError`` naming the
    offending leaf when a shard axis does not divide by ``tp`` or an
    act-order permutation sits on a row-parallel projection."""

    def spec(path, leaf):
        names = [_entry_name(e) for e in path]
        role = next((n for n in reversed(names)
                     if n in COL_PARALLEL or n in ROW_PARALLEL), None)
        if role is None:
            return P()
        where = "/".join(names)
        leafname = names[-1]
        if leafname == "perm":
            if role in ROW_PARALLEL:
                raise ValueError(
                    f"{where}: act-order perm permutes the full K axis and "
                    f"cannot be sharded row-parallel; quantize with "
                    f"act_order=False for tensor-parallel serving")
            return P()                      # col-parallel: K replicated
        # dense {w, b} and quantized {qweight, scales, qzeros, bias} leaves:
        # col-parallel shards the last (N) axis, row-parallel the K axis
        # (second-to-last for matrices).  A row-parallel bias would be
        # added once per shard and then psum-multiplied by tp — reject it
        # (wo / w_down carry no bias in this codebase).
        if role in ROW_PARALLEL and leafname in ("b", "bias"):
            raise ValueError(
                f"{where}: bias on a row-parallel projection would be "
                f"summed tp={tp} times by the all-reduce epilogue")
        shard_axis = -1 if role in COL_PARALLEL else -2
        if leaf.ndim < -shard_axis:
            return P()
        dim = leaf.shape[shard_axis]
        if dim % tp:
            raise ValueError(
                f"{where}: axis of size {dim} does not divide tp={tp} "
                f"(shape {tuple(leaf.shape)})")
        return _matrix_spec(leaf.ndim, shard_axis, axis)

    return jax.tree_util.tree_map_with_path(spec, params)


def cache_specs(cache, axis: str, tp: int = 1):
    """PartitionSpec tree for a paged cache tree: the ``num_kv_heads`` axis
    of the head-major page pools (``k_pages``/``v_pages``: ``(layers, Hkv,
    pages, page_size, D)``) and scale pools (``k_scales``/``v_scales``:
    ``(layers, Hkv, pages, page_size)``) is sharded — axis 1 of every leaf,
    so each device's pool is a whole head-slice of every page; page ids
    stay global."""

    def spec(path, leaf):
        name = _entry_name(path[-1]) if path else ""
        if not name.endswith(("_pages", "_scales")):
            raise ValueError(
                f"unrecognized paged-cache leaf {name!r} — tensor-parallel "
                f"serving knows k/v_pages and k/v_scales pools only")
        if leaf.shape[1] % tp:
            raise ValueError(
                f"{name}: num_kv_heads={leaf.shape[1]} does not "
                f"divide tp={tp}")
        return _matrix_spec(leaf.ndim, 1 - leaf.ndim, axis)

    return jax.tree_util.tree_map_with_path(spec, cache)


# ------------------------------------------------------------------ building
def build_tp_context(model, params, tp: int, axis: str = "model") -> TPContext:
    """Validate the (model, params) pair for ``tp``-way tensor parallelism
    and return the mesh + local model + parameter specs the engine wires
    into its jitted programs.  Pure host-side: nothing is device_put here."""
    if tp <= 0:
        raise ValueError(f"tp must be >= 1, got {tp}")
    avail = len(jax.devices())
    if tp > avail:
        raise ValueError(
            f"tensor parallelism tp={tp} needs {tp} devices but only "
            f"{avail} are available (CPU runs: set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=N before "
            f"jax initializes)")
    cfg = model.cfg
    if cfg.attn_type != "gqa" or cfg.family in ("ssm", "hybrid") \
            or getattr(cfg, "num_experts", 0):
        raise ValueError(
            "tensor-parallel serving supports full-attention GQA stacks "
            f"only, got family={cfg.family!r} attn_type={cfg.attn_type!r}")
    for field in ("num_heads", "num_kv_heads"):
        n = getattr(cfg, field)
        if n % tp:
            raise ValueError(
                f"{field}={n} does not divide tp={tp} — heads are the "
                f"tensor-parallel unit")
    local_cfg = dataclasses.replace(cfg, num_heads=cfg.num_heads // tp,
                                    num_kv_heads=cfg.num_kv_heads // tp)
    local_model = type(model)(local_cfg)
    mesh = Mesh(np.asarray(jax.devices()[:tp]), (axis,))
    return TPContext(mesh=mesh, axis=axis, tp=tp, local_model=local_model,
                     param_specs=param_specs(params, axis, tp))


def _device_put_tree(mesh, tree, specs):
    return jax.tree_util.tree_map(
        lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
        tree, specs)


def shard_params(ctx: TPContext, params):
    """Commit the parameter tree to its TP sharding (slices land on their
    owning device; replicated leaves are broadcast)."""
    return _device_put_tree(ctx.mesh, params, ctx.param_specs)


def init_cache(ctx: TPContext, init):
    """Build the paged cache tree ``init()`` returns straight into its
    head-sharded layout: each device allocates only its own head-slice (the
    global pool is ``tp`` times a device's share and need not fit on one)."""
    specs = cache_specs(jax.eval_shape(init), ctx.axis, ctx.tp)
    return jax.jit(init, out_shardings=jax.tree_util.tree_map(
        lambda s: NamedSharding(ctx.mesh, s), specs))()


def localize_quantized(params):
    """Rewrite ``QuantizedLinear.shape`` metadata to the *local* (K, N)
    implied by each shard's ``qweight``: the logical shape is static
    metadata, so shard_map hands the body global numbers over local arrays
    and ``kops.gptq_linear``'s ``k, n = ql.shape`` reshape would be wrong
    without this.  ``shape[-2] * 8`` survives group-stacked leaves (the
    leading count dim slices off before the kernel sees it)."""

    def fix(ql):
        if not isinstance(ql, QuantizedLinear):
            return ql
        return dataclasses.replace(
            ql, shape=(ql.qweight.shape[-2] * 8, ql.qweight.shape[-1]))

    return jax.tree_util.tree_map(
        fix, params, is_leaf=lambda x: isinstance(x, QuantizedLinear))


# ------------------------------------------------------------- engine entry
def tp_wrap_fused(ctx: TPContext, kernels, impl):
    """shard_map wrapper for ``Engine._fused_step_impl`` — the *one* jitted
    program tensor-parallel serving wraps (ISSUE 10; the old
    decode/prefill-paged wrapper pair collapsed into this, which is also
    what lifted the spec-under-TP config rejection: verify is just another
    chunk row now).  Params/cache arrive sharded; every host-side operand
    (tokens, chunk/draft lens, masks, sampling state, PRNG keys) is
    replicated; the packed token matrix and seq_lens leave replicated so
    the engine's one device->host transfer per step is unchanged.  Meant to
    be wrapped in ``jax.jit(..., static_argnames=("all_greedy",))`` exactly
    like the single-device partial it replaces."""
    rep = P()

    def wrapped(params, tokens, chunk_lens, drafts, draft_lens, emit, cache,
                seq_lens, block_tables, live, greedy, temps, top_ks, top_ps,
                keys, draft_probs, *, all_greedy: bool = False):
        def body(params, tokens, chunk_lens, drafts, draft_lens, emit,
                 cache, seq_lens, block_tables, live, greedy, temps,
                 top_ks, top_ps, keys, draft_probs):
            params = localize_quantized(params)
            with L.tp_epilogue(ctx.axis):
                return impl(ctx.local_model, kernels, params, tokens,
                            chunk_lens, drafts, draft_lens, emit, cache,
                            seq_lens, block_tables, live, greedy, temps,
                            top_ks, top_ps, keys, draft_probs,
                            all_greedy=all_greedy)

        cspecs = cache_specs(cache, ctx.axis, ctx.tp)
        fn = _shard_map(
            body, ctx.mesh,
            in_specs=(ctx.param_specs, rep, rep, rep, rep, rep, cspecs,
                      rep, rep, rep, rep, rep, rep, rep, rep, rep),
            out_specs=(rep, cspecs, rep))
        return fn(params, tokens, chunk_lens, drafts, draft_lens, emit,
                  cache, seq_lens, block_tables, live, greedy, temps,
                  top_ks, top_ps, keys, draft_probs)

    return wrapped


def tp_wrap_forward(ctx: TPContext, kernels):
    """shard_map wrapper for ``model.forward_chunks`` with ``logit_idx`` —
    the probe ``Engine.forward_probe`` checks a sharded engine's logits
    with.  Same placement as ``tp_wrap_fused``: params and cache sharded,
    everything else and the logits replicated."""
    rep = P()

    def wrapped(params, tokens, chunk_lens, cache, seq_lens, block_tables,
                logit_idx):
        def body(params, tokens, chunk_lens, cache, seq_lens, block_tables,
                 logit_idx):
            params = localize_quantized(params)
            with L.tp_epilogue(ctx.axis):
                return ctx.local_model.forward_chunks(
                    params, tokens, chunk_lens, cache, seq_lens,
                    kernels=kernels, block_tables=block_tables,
                    logit_idx=logit_idx)

        cspecs = cache_specs(cache, ctx.axis, ctx.tp)
        fn = _shard_map(body, ctx.mesh,
                        in_specs=(ctx.param_specs, rep, rep, cspecs, rep,
                                  rep, rep),
                        out_specs=(rep, cspecs))
        return fn(params, tokens, chunk_lens, cache, seq_lens, block_tables,
                  logit_idx)

    return wrapped


def mesh_size(mesh_shape) -> int:
    """Total device count of an ``EngineConfig.mesh_shape`` (1 for None)."""
    if mesh_shape is None:
        return 1
    return math.prod(int(d) for d in mesh_shape)
