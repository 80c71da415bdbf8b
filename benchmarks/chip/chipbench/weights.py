"""Random int4 model weights drawn from a seed, in the benchmark's own naming.

The benchmark makes the weights, hands them to the program, and lets the
plain reference draw the very same values again, one layer at a time, so
the reference takes nothing that the program has made.

The family draws the leaves.  Every leaf has its own key: ``fold_in(seed
key, crc32(name))`` and, for a per-layer leaf, ``fold_in(that, layer)``
(``layer`` counted over the whole model).  The stacked draw vmaps the
per-layer draw over the layer index, which gives bit-identical values.

Projections are GPTQ int4 in the AutoGPTQ layout (group ``g`` along K):

* ``qweight`` (K//8, N) int32 — nibble ``j`` of word ``[r, n]`` is row
  ``8r + j`` of column ``n`` (row ``8r`` in the least significant nibble);
* ``scales``  (K//g, N) bf16;
* ``qzeros``  (K//g, N//8) int32 — nibble ``j`` of word ``[G, c]`` is the
  zero point of column ``8c + j``;
* dequantized ``w[k, n] = (q[k, n] - z[k // g, n]) * s[k // g, n]``.

Nibbles are uniform on [0, 15], zero points 7 or 8, and scales are sized so
that the dequantized weight has standard deviation about ``K ** -0.5``.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

import chipbench

NIBBLE_STD = math.sqrt((16 ** 2 - 1) / 12)      # std of q - 7.5, q ~ U{0..15}
_SHIFTS = tuple(4 * j for j in range(8))


def seed_key(seed: int):
    """A threefry key holding all 64 bits of ``seed``."""
    s = int(seed) % 2 ** 64
    return jax.random.wrap_key_data(
        jnp.array([s >> 32, s & 0xFFFFFFFF], jnp.uint32))


def leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def pack_cols(z):
    """(G, N) nibbles -> (G, N // 8) int32 words, column 8c in the low
    nibble."""
    g, n = z.shape
    z = z.astype(jnp.uint32).reshape(g, n // 8, 8)
    word = sum(z[:, :, j] << _SHIFTS[j] for j in range(8))
    return jax.lax.bitcast_convert_type(word, jnp.int32)


def draw_int4(key, k: int, n: int, group: int) -> dict:
    kq, kz, ks = jax.random.split(key, 3)
    groups = k // group
    qweight = jax.lax.bitcast_convert_type(
        jax.random.bits(kq, (k // 8, n), jnp.uint32), jnp.int32)
    zeros = 7 + jax.random.bernoulli(kz, 0.5, (groups, n)).astype(jnp.int32)
    scales = (k ** -0.5 / NIBBLE_STD * jax.random.uniform(
        ks, (groups, n), jnp.float32, 0.75, 1.25)).astype(jnp.bfloat16)
    return {"qweight": qweight, "scales": scales, "qzeros": pack_cols(zeros)}


def draw_norm(key, d: int, with_bias: bool) -> dict:
    ks, kb = jax.random.split(key)
    out = {"scale": jax.random.uniform(ks, (d,), jnp.float32, 0.9, 1.1)
           .astype(jnp.bfloat16)}
    if with_bias:
        out["bias"] = (0.02 * jax.random.normal(kb, (d,), jnp.float32)
                       ).astype(jnp.bfloat16)
    return out


def draw_model(cfg: dict, seed: int) -> dict:
    """Every weight of the model, drawn on the default device in one jitted
    call, each layer group stacked on a leading axis as ``group<i>``."""
    fam = chipbench.family(cfg)

    def build(key):
        out = fam.draw_top(cfg, key)
        for g, (first, count) in enumerate(fam.groups(cfg)):
            out[f"group{g}"] = jax.vmap(
                lambda i: fam.draw_layer(cfg, key, i, g))(
                    jnp.arange(first, first + count))
        return out

    return jax.jit(build)(seed_key(seed))


def layer_drawer(cfg: dict, seed: int):
    """``fn(i)``: layer ``i``'s weights, the same values ``draw_model``
    stacks, whatever group holds it — what the reference reads one layer
    at a time.  The key is an argument, not a constant of the program: a
    constant key would let the compiler fold the whole draw at compile
    time, and give every seed a program of its own."""
    fam = chipbench.family(cfg)
    group_of = [g for g, (_, count) in enumerate(fam.groups(cfg))
                for _ in range(count)]
    key = seed_key(seed)
    fn = jax.jit(lambda k, i, g: fam.draw_layer(cfg, k, i, g),
                 static_argnums=2)
    return lambda i: fn(key, jnp.int32(i), group_of[i])


def top_drawer(cfg: dict, seed: int) -> dict:
    return jax.jit(lambda k: chipbench.family(cfg).draw_top(cfg, k))(
        seed_key(seed))
