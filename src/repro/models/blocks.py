"""Transformer block assembly.

A model is a sequence of *layer groups*: (count, BlockKind) with parameters
stacked over the count dimension and applied with ``lax.scan`` (O(1) HLO size
— mandatory for 64-80 layer dry-run compiles). Groups exist because layers can
be heterogeneous: hymba interleaves global-attention layers among SWA layers,
deepseek-v2 has a leading dense-FFN layer before the MoE stack.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import ffn as F
from repro.models import layers as L
from repro.models import mamba as M


@dataclasses.dataclass(frozen=True)
class BlockKind:
    attn: str = "full"        # full | swa | mla | none
    ffn: str = "dense"        # dense | moe | none
    ssm: bool = False


def layer_groups(cfg: ModelConfig) -> list[tuple[int, BlockKind]]:
    """Derive the group structure from the config."""
    if cfg.family == "ssm":
        return [(cfg.num_layers, BlockKind(attn="none", ffn="none", ssm=True))]
    if cfg.family == "hybrid":
        groups: list[tuple[int, BlockKind]] = []
        glob = set(cfg.global_attn_layers)
        i = 0
        while i < cfg.num_layers:
            if i in glob:
                groups.append((1, BlockKind(attn="full", ffn="dense", ssm=True)))
                i += 1
            else:
                j = i
                while j < cfg.num_layers and j not in glob:
                    j += 1
                groups.append((j - i, BlockKind(attn="swa", ffn="dense", ssm=True)))
                i = j
        return groups
    attn = "mla" if cfg.attn_type == "mla" else "full"
    if cfg.num_experts:
        groups = []
        if cfg.first_dense_layers:
            groups.append((cfg.first_dense_layers, BlockKind(attn=attn, ffn="dense")))
        groups.append((cfg.num_layers - cfg.first_dense_layers,
                       BlockKind(attn=attn, ffn="moe")))
        return groups
    if cfg.sliding_window and cfg.global_attn_layers:
        # generic SWA/global interleave (same mechanism as hybrid)
        groups = []
        glob = set(cfg.global_attn_layers)
        i = 0
        while i < cfg.num_layers:
            if i in glob:
                groups.append((1, BlockKind(attn="full", ffn="dense")))
                i += 1
            else:
                j = i
                while j < cfg.num_layers and j not in glob:
                    j += 1
                groups.append((j - i, BlockKind(attn="swa", ffn="dense")))
                i = j
        return groups
    return [(cfg.num_layers, BlockKind(attn=attn, ffn="dense"))]


# ------------------------------------------------------------------ block init
def block_init(rng, cfg: ModelConfig, kind: BlockKind, dtype=jnp.float32):
    ks = jax.random.split(rng, 4)
    p: dict = {}
    if kind.attn != "none":
        p["norm1"] = L.norm_init(cfg.d_model, cfg.norm_type, dtype)
        if kind.attn == "mla":
            p["attn"] = A.mla_init(ks[0], cfg, dtype)
        else:
            p["attn"] = A.gqa_init(ks[0], cfg, dtype)
    if kind.ssm:
        if "norm1" not in p:
            p["norm1"] = L.norm_init(cfg.d_model, cfg.norm_type, dtype)
        p["ssm"] = M.mamba_init(ks[1], cfg, dtype)
        if kind.attn != "none":   # hybrid: per-branch output norms (hymba)
            p["attn_out_norm"] = L.norm_init(cfg.d_model, cfg.norm_type, dtype)
            p["ssm_out_norm"] = L.norm_init(cfg.d_model, cfg.norm_type, dtype)
    if kind.ffn != "none":
        p["norm2"] = L.norm_init(cfg.d_model, cfg.norm_type, dtype)
        if kind.ffn == "moe":
            p["ffn"] = F.moe_init(ks[2], cfg, dtype)
        else:
            p["ffn"] = F.ffn_init(ks[2], cfg, dtype=dtype)
    return p


def block_cache_init(cfg: ModelConfig, kind: BlockKind, batch: int,
                     max_len: int, dtype=jnp.bfloat16, kv_quant=None):
    quantized = kv_quant is not None and kv_quant.quantized
    if quantized and (kind.attn != "full" or kind.ssm):
        raise ValueError(
            f"quantized KV (kv_quant) supports full-attention GQA blocks "
            f"only, got attn={kind.attn!r} ssm={kind.ssm}")
    c: dict = {}
    if kind.attn == "mla":
        c["attn"] = A.init_mla_cache(cfg, batch, max_len, dtype)
    elif kind.attn == "swa":
        c["attn"] = A.init_gqa_cache(cfg, batch, max_len,
                                     window=cfg.sliding_window,
                                     num_sink=cfg.meta_tokens, dtype=dtype)
    elif kind.attn == "full":
        c["attn"] = A.init_gqa_cache(cfg, batch, max_len, dtype=dtype,
                                     kv_quant=kv_quant)
    if kind.ssm:
        c["ssm"] = M.init_mamba_cache(cfg, batch, dtype)
    return c


def block_paged_cache_init(cfg: ModelConfig, kind: BlockKind, num_pages: int,
                           page_size: int, dtype=jnp.bfloat16, kv_quant=None):
    """Paged-layout cache for one block: head-major (Hkv, num_pages + 1,
    page_size, D) physical pools (page 0 is the null page — see
    serving/kv_cache.py), plus (Hkv, num_pages + 1, page_size) per-token
    scale pools when ``kv_quant`` stores int8.  Only homogeneous
    full-attention stacks support paging."""
    if kind.attn != "full" or kind.ssm:
        raise ValueError(
            f"paged cache layout supports full-attention blocks only, got "
            f"attn={kind.attn!r} ssm={kind.ssm}")
    shape = (cfg.num_kv_heads, num_pages + 1, page_size, cfg.head_dim)
    if kv_quant is not None and kv_quant.quantized:
        if kv_quant.granularity != "token":
            raise ValueError(
                "the fused paged write path is per-token; per-page scales "
                "are served by the PagedCache data-path API only")
        sdt = kv_quant.scale_jnp_dtype
        return {"attn": {"k_pages": jnp.zeros(shape, jnp.int8),
                         "v_pages": jnp.zeros(shape, jnp.int8),
                         "k_scales": jnp.zeros(shape[:-1], sdt),
                         "v_scales": jnp.zeros(shape[:-1], sdt)}}
    return {"attn": {"k_pages": jnp.zeros(shape, dtype),
                     "v_pages": jnp.zeros(shape, dtype)}}


def group_paged_cache_init(cfg, kind, count, num_pages, page_size,
                           dtype=jnp.bfloat16, kv_quant=None):
    one = block_paged_cache_init(cfg, kind, num_pages, page_size, dtype,
                                 kv_quant)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (count,) + a.shape), one)


# ----------------------------------------------------------------- block apply
def block_apply(p, x, *, cfg: ModelConfig, kind: BlockKind,
                kernels=L.DEFAULT_KERNELS, positions=None, cache=None,
                seq_lens=None, num_sink: int = 0, block_tables=None,
                write_lens=None):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache: dict = {}

    if kind.attn != "none" or kind.ssm:
        h = L.apply_norm(p["norm1"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        branch_out = None
        if kind.attn == "mla":
            ao, ac = A.mla_apply(p["attn"], h, cfg=cfg, kernels=kernels,
                                 positions=positions,
                                 cache=cache.get("attn") if cache else None,
                                 seq_lens=seq_lens)
            branch_out = ao
            if ac is not None:
                new_cache["attn"] = ac
        elif kind.attn in ("full", "swa"):
            window = cfg.sliding_window if kind.attn == "swa" else 0
            ao, ac = A.gqa_apply(p["attn"], h, cfg=cfg, kernels=kernels,
                                 positions=positions,
                                 cache=cache.get("attn") if cache else None,
                                 seq_lens=seq_lens, window=window,
                                 causal=not cfg.is_encoder, num_sink=num_sink,
                                 block_tables=block_tables,
                                 write_lens=write_lens)
            branch_out = ao
            if ac is not None:
                new_cache["attn"] = ac
        if kind.ssm:
            so, sc = M.mamba_apply(p["ssm"], h, cfg=cfg, kernels=kernels,
                                   cache=cache.get("ssm") if cache else None)
            if sc is not None:
                new_cache["ssm"] = sc
            if branch_out is not None:   # hybrid: mean of normalized branches
                branch_out = 0.5 * (
                    L.apply_norm(p["attn_out_norm"], branch_out,
                                 norm_type=cfg.norm_type, eps=cfg.norm_eps)
                    + L.apply_norm(p["ssm_out_norm"], so,
                                   norm_type=cfg.norm_type, eps=cfg.norm_eps))
            else:
                branch_out = so
        x = x + branch_out

    if kind.ffn != "none":
        h = L.apply_norm(p["norm2"], x, norm_type=cfg.norm_type, eps=cfg.norm_eps)
        if kind.ffn == "moe":
            moe_fn = F.moe_apply_ep if cfg.moe_impl == "ep" else F.moe_apply
            fo, a = moe_fn(p["ffn"], h, cfg=cfg, kernels=kernels)
            aux = aux + a
        else:
            fo = F.ffn_apply(p["ffn"], h, cfg=cfg, kernels=kernels)
        x = x + fo
    return x, new_cache, aux


# ----------------------------------------------------------------- group level
def group_init(rng, cfg: ModelConfig, count: int, kind: BlockKind,
               dtype=jnp.float32):
    rngs = jax.random.split(rng, count)
    return jax.vmap(lambda r: block_init(r, cfg, kind, dtype))(rngs)


def group_cache_init(cfg, kind, count, batch, max_len, dtype=jnp.bfloat16,
                     kv_quant=None):
    one = block_cache_init(cfg, kind, batch, max_len, dtype, kv_quant)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (count,) + a.shape), one)


def group_apply(stack, x, *, cfg: ModelConfig, kind: BlockKind, count: int,
                kernels=L.DEFAULT_KERNELS, positions=None, cache=None,
                seq_lens=None, num_sink: int = 0, remat: str | None = None,
                block_tables=None, write_lens=None):
    """Scan a homogeneous group of ``count`` blocks. Returns (x, new_cache, aux)."""
    remat = remat if remat is not None else cfg.remat

    def body_fn(p, x, c):
        x = L.constrain_act(x)   # keep scan carry / saved residuals sharded
        return block_apply(p, x, cfg=cfg, kind=kind, kernels=kernels,
                           positions=positions, cache=c, seq_lens=seq_lens,
                           num_sink=num_sink, block_tables=block_tables,
                           write_lens=write_lens)

    if remat == "full":
        body_fn = jax.checkpoint(body_fn)
    elif remat == "dots":
        body_fn = jax.checkpoint(
            body_fn, policy=jax.checkpoint_policies.checkpoint_dots)

    if not cfg.scan_layers:
        caches, auxes = [], jnp.zeros((), jnp.float32)
        for i in range(count):
            p_i = jax.tree_util.tree_map(lambda a: a[i], stack)
            c_i = (jax.tree_util.tree_map(lambda a: a[i], cache)
                   if cache is not None else None)
            with L.name_scope(f"layer{i}"):
                x, nc, a = body_fn(p_i, x, c_i)
            caches.append(nc)
            auxes = auxes + a
        new_cache = (jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *caches)
                     if cache is not None else None)
        return x, new_cache, auxes

    def scan_body(carry, xs):
        x, aux = carry
        p, c = xs
        x, nc, a = body_fn(p, x, c)
        return (x, aux + a), nc

    (x, aux), new_cache = jax.lax.scan(
        scan_body, (x, jnp.zeros((), jnp.float32)), (stack, cache))
    return x, new_cache, aux
