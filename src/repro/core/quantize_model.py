"""Whole-model GPTQ quantization: walk a parameter tree and replace every
eligible projection weight with a ``QuantizedLinear`` (concrete arrays, via
the GPTQ algorithm + captured Hessians) or with abstract ShapeDtypeStructs
(for the dry-run's serving memory/roofline analysis).

``random_quantized_params`` draws a serving-ready tree straight from a seed
at the quantized shapes (random weights at published widths, no dense copy).

Eligible = transformer projection matrices (attention, FFN, SSM, per-expert
tensors). Embeddings, output head, norms, routers, conv and SSM scan tensors
stay fp (matching AutoGPTQ / the paper's vLLM setup).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gptq import GPTQConfig, QuantizedLinear, gptq_quantize

PROJ_PARENTS = {
    "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down", "in_proj", "out_proj",
    "x_proj", "dt_proj", "wkv_a", "wkv_b", "head_proj",
}
EXPERT_NAMES = {"w_gate", "w_up", "w_down"}


def _path_parts(path):
    out = []
    for e in path:
        if hasattr(e, "key"):
            out.append(str(e.key))
        elif hasattr(e, "name"):
            out.append(str(e.name))
        else:
            out.append(str(e))
    return out


def _eligible(parts: list[str], leaf) -> str | None:
    """Returns 'proj' | 'expert' | None. Operates on logical (trailing) dims."""
    last = parts[-1]
    if last == "w" and len(parts) >= 2 and parts[-2] in PROJ_PARENTS:
        return "proj"
    if last in EXPERT_NAMES and "experts" in parts:
        return "expert"
    return None


def _quant_group(k: int, group_size: int) -> int | None:
    """Largest usable group size for a K dim (None -> not quantizable)."""
    if k % 8 != 0:
        return None
    if group_size > 0 and k % group_size == 0:
        return group_size
    return k                                  # single whole-K group


def abstract_quantized_params(abstract_params, cfg_gptq: GPTQConfig,
                              scale_dtype=jnp.bfloat16):
    """ShapeDtypeStruct tree with QuantizedLinear stand-ins (dry-run serving)."""

    def f(path, leaf):
        parts = _path_parts(path)
        kind = _eligible(parts, leaf)
        if kind is None:
            return leaf
        *lead, k, n = leaf.shape
        g = _quant_group(k, cfg_gptq.group_size)
        if g is None or n % 8 != 0:
            return leaf
        ngroups = k // g
        sds = jax.ShapeDtypeStruct
        return QuantizedLinear(
            qweight=sds((*lead, k // 8, n), jnp.int32),
            scales=sds((*lead, ngroups, n), scale_dtype),
            qzeros=sds((*lead, ngroups, n // 8), jnp.int32),
            perm=None, bias=None, shape=(k, n), group_size=g)

    return jax.tree_util.tree_map_with_path(f, abstract_params)


def _is_ql(x) -> bool:
    return isinstance(x, QuantizedLinear)


# a packed int32 word whose eight nibbles are all the mid-grid zero point 8
_MID_ZEROS = int(np.array(0x88888888, np.uint32).view(np.int32))
# std of (q - 8) for q uniform on [0, 15]: sqrt((16**2 - 1) / 12)
_NIBBLE_STD = float(np.sqrt((16 ** 2 - 1) / 12))


def _random_quantized(key, ql: QuantizedLinear) -> QuantizedLinear:
    """Random int4 weights at ``ql``'s shapes: uniform nibbles around the
    mid zero point, group scales sized so the dequantized weight has the
    dense init's std (K ** -0.5)."""
    kq, ks = jax.random.split(key)
    qweight = jax.lax.bitcast_convert_type(
        jax.random.bits(kq, ql.qweight.shape, jnp.uint32), jnp.int32)
    base = ql.shape[0] ** -0.5 / _NIBBLE_STD
    scales = (base * jax.random.uniform(ks, ql.scales.shape, jnp.float32,
                                        0.75, 1.25)).astype(ql.scales.dtype)
    qzeros = jnp.full(ql.qzeros.shape, _MID_ZEROS, jnp.int32)
    return dataclasses.replace(ql, qweight=qweight, scales=scales,
                               qzeros=qzeros)


def random_quantized_params(model, cfg_gptq: GPTQConfig, seed: int,
                            scale_dtype=jnp.bfloat16):
    """Serving parameters drawn straight from ``seed``: every eligible
    projection is random int4 at the shapes of
    ``abstract_quantized_params``; embeddings and norms come from
    ``model.init``.  One jitted program builds the tree, and the dense
    projection weights ``model.init`` would draw are dead code there, so
    the device never holds a bf16 copy of them (a published-width model
    fits beside its KV pool)."""
    abstract = abstract_quantized_params(model.abstract_params(), cfg_gptq,
                                         scale_dtype)

    def build(key):
        k_dense, k_quant = jax.random.split(key)
        dense = model.init(k_dense)
        leaves, treedef = jax.tree_util.tree_flatten(abstract,
                                                     is_leaf=_is_ql)
        keys = jax.random.split(k_quant, len(leaves))
        quant = jax.tree_util.tree_unflatten(
            treedef, [_random_quantized(k, leaf) if _is_ql(leaf) else None
                      for k, leaf in zip(keys, leaves)])
        return jax.tree_util.tree_map(
            lambda a, d, q: q if _is_ql(a) else d, abstract, dense, quant,
            is_leaf=_is_ql)

    return jax.jit(build)(jax.random.key(seed))


def quantize_params(params, hessians: dict[str, Any] | None,
                    cfg_gptq: GPTQConfig, scale_dtype=jnp.bfloat16):
    """Concrete whole-model quantization. ``hessians`` maps qualified names
    ("layer3.wq" style, from layers.capture_hessians) to (K, K) arrays; missing
    entries quantize with H=I (RTN + error feedback).

    Stacked leading dims (scan groups L, experts E) are quantized slice-wise
    and restacked."""
    hessians = hessians or {}

    def lookup_h(parts, idx):
        # capture names are "layer{i}.{proj}" within a group; fall back to None
        for key in (".".join(parts), f"layer{idx}.{parts[-2] if len(parts) > 1 else parts[-1]}"):
            if key in hessians:
                return hessians[key]
        return None

    def quant_one(w, h):
        return gptq_quantize(
            w, h, dataclasses.replace(cfg_gptq, scale_dtype=scale_dtype))

    def f(path, leaf):
        parts = _path_parts(path)
        kind = _eligible(parts, leaf)
        if kind is None:
            return leaf
        *lead, k, n = leaf.shape
        g = _quant_group(k, cfg_gptq.group_size)
        if g is None or n % 8 != 0:
            return leaf
        cfg_local = dataclasses.replace(cfg_gptq, group_size=g)
        if not lead:
            return gptq_quantize(leaf, lookup_h(parts, 0), cfg_local)
        flat = leaf.reshape(-1, k, n)
        quants = [gptq_quantize(flat[i], lookup_h(parts, i), cfg_local)
                  for i in range(flat.shape[0])]
        stack = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs).reshape(
            *lead, *xs[0].shape), *quants)
        return stack

    return jax.tree_util.tree_map_with_path(f, params)


def dequantize_tree(params, dtype=jnp.bfloat16):
    """Inverse walk (testing): QuantizedLinear leaves -> dense arrays."""
    from repro.core.gptq import dequantize

    def is_ql(x):
        return isinstance(x, QuantizedLinear)

    def f(leaf):
        if not is_ql(leaf):
            return leaf
        if leaf.qweight.ndim == 2:
            return dequantize(leaf, dtype)
        *lead, kw, n = leaf.qweight.shape
        k = leaf.shape[0]
        flat_q = leaf.qweight.reshape(-1, kw, n)
        flat_s = leaf.scales.reshape(-1, leaf.scales.shape[-2], n)
        flat_z = leaf.qzeros.reshape(-1, leaf.qzeros.shape[-2], leaf.qzeros.shape[-1])
        outs = [dequantize(QuantizedLinear(flat_q[i], flat_s[i], flat_z[i],
                                           None, None, leaf.shape,
                                           leaf.group_size), dtype)
                for i in range(flat_q.shape[0])]
        return jnp.stack(outs).reshape(*lead, k, n)

    return jax.tree_util.tree_map(f, params, is_leaf=is_ql)
