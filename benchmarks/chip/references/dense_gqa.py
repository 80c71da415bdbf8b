"""Plain float32 reference of a dense decoder with grouped-query attention.

Written from the architecture's equations, not from the program: it reads
the weights that ``chipbench/weights.py`` draws (AutoGPTQ int4 layout) and
the configuration file's keys, and runs every product in float32 at
``Precision.HIGHEST``.  No kernel, no cache, no batching of steps: one full
causal forward over each whole sequence.

Per layer, with ``N`` the configured norm (RMSNorm, or LayerNorm with bias)::

    h = N_attn(x)
    q, k, v = h Wq, h Wk, h Wv            (heads of head_dim)
    q, k = RMSNorm_head(q), RMSNorm_head(k)   if qk_norm
    q, k = RoPE(q), RoPE(k)               (rotate-half, all of head_dim)
    x = x + softmax(q k^T / sqrt(head_dim) + causal) v  Wo
    h = N_mlp(x)
    x = x + (silu(h Wg) * (h Wu)) Wd      if hidden_act == "silu"
    x = x + relu(h Wu)^2 Wd               if hidden_act == "relu2"

then ``N_final`` and logits against the tied embedding or the untied head.
Each key/value head serves ``num_attention_heads // num_key_value_heads``
query heads.

``act_quant`` computes the reference one precision below the
configuration's bf16, as the control: every projection's input row, and
each token's key and value head as a quantized KV cache would hold them,
is first rounded to symmetric per-row ``"int8"`` or to per-row scaled
``"fp8"`` (e4m3) — the W4A8 and 8-bit KV steps a later change might take.
The control must fail the check that the program passes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256            # query rows per attention block
VOCAB_BLOCK = 16384      # vocabulary rows per logits block


def dequant(w: dict) -> jnp.ndarray:
    """AutoGPTQ int4 -> (K, N) float32."""
    qw, s, qz = w["qweight"], w["scales"], w["qzeros"]
    kw, n = qw.shape
    groups = s.shape[0]
    u = jax.lax.bitcast_convert_type(qw, jnp.uint32)
    q = jnp.stack([(u >> (4 * j)) & 0xF for j in range(8)], axis=1)
    q = q.reshape(kw * 8, n).astype(jnp.float32)                  # row 8r+j
    uz = jax.lax.bitcast_convert_type(qz, jnp.uint32)
    z = jnp.stack([(uz >> (4 * j)) & 0xF for j in range(8)], axis=2)
    z = z.reshape(groups, n).astype(jnp.float32)                   # col 8c+j
    g = kw * 8 // groups
    w = (q.reshape(groups, g, n) - z[:, None]) * s.astype(jnp.float32)[:, None]
    return w.reshape(kw * 8, n)


def _norm(x, p, cfg):
    eps = cfg["norm_eps"]
    scale = p["scale"].astype(jnp.float32)
    if cfg["norm_type"] == "layernorm":
        mu = x.mean(-1, keepdims=True)
        var = jnp.square(x - mu).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + eps) * scale \
            + p["bias"].astype(jnp.float32)
    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * scale


def _act_quant(x, kind):
    """Round each row of ``x`` to ``kind`` with a per-row scale."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    if kind == "int8":
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if kind == "fp8":
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown act_quant {kind!r}")


def _proj(x, w, act_quant):
    if act_quant is not None:
        x = _act_quant(x, act_quant)
    return jnp.matmul(x, dequant(w), precision=HI)


def _rope(x, theta):
    """x (n, S, heads, D) at positions 0..S-1; pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal attention; q (n, S, H, D), k/v (n, S, Hkv, D), S a multiple
    of Q_BLOCK.  Query blocks run one after another to bound the logits."""
    n, s, h, d = q.shape
    hkv = k.shape[2]
    qg = q.reshape(n, s // Q_BLOCK, Q_BLOCK, hkv, h // hkv, d)
    kpos = jnp.arange(s)

    def block(i):
        qb = qg[:, i]                                         # (n,Q,g,r,D)
        logits = jnp.einsum("nqgrd,nkgd->ngrqk", qb, k,
                            precision=HI) / np.sqrt(d)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        logits = jnp.where(kpos[None, :] <= qpos[:, None], logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("ngrqk,nkgd->nqgrd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(s // Q_BLOCK))      # (B,n,Q,g,r,D)
    return jnp.moveaxis(out, 0, 1).reshape(n, s, h * d)


@functools.partial(jax.jit, static_argnames=("cfg_items", "act_quant"))
def _layer(x, lw, *, cfg_items, act_quant):
    cfg = dict(cfg_items)
    n, s, _ = x.shape
    h, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    a = _norm(x, lw["attn_norm"], cfg)
    q = _proj(a, lw["wq"], act_quant).reshape(n, s, h, hd)
    k = _proj(a, lw["wk"], act_quant).reshape(n, s, hkv, hd)
    v = _proj(a, lw["wv"], act_quant).reshape(n, s, hkv, hd)
    if cfg["qk_norm"]:
        q = _norm(q, {"scale": lw["q_norm"]}, {**cfg, "norm_type": "rmsnorm"})
        k = _norm(k, {"scale": lw["k_norm"]}, {**cfg, "norm_type": "rmsnorm"})
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    if act_quant is not None:
        k, v = _act_quant(k, act_quant), _act_quant(v, act_quant)
    x = x + _proj(_attention(q, k, v), lw["wo"], act_quant)
    m = _norm(x, lw["mlp_norm"], cfg)
    if cfg["hidden_act"] == "silu":
        f = jax.nn.silu(_proj(m, lw["w_gate"], act_quant)) \
            * _proj(m, lw["w_up"], act_quant)
    else:
        f = jnp.square(jax.nn.relu(_proj(m, lw["w_up"], act_quant)))
    return x + _proj(f, lw["w_down"], act_quant)


def _items(cfg: dict) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "norm_type", "norm_eps", "qk_norm", "rope_theta", "hidden_act")
    return tuple((k, cfg[k]) for k in keys)


def final_hidden(cfg: dict, top: dict, layer_fn, tokens: np.ndarray, *,
                 act_quant: str | None = None) -> jnp.ndarray:
    """Final-norm hidden states (n, S, d) float32 of right-padded token rows
    ``tokens`` (n, S); ``layer_fn(i)`` gives layer ``i``'s weights."""
    n, s = tokens.shape
    s_pad = -(-s // Q_BLOCK) * Q_BLOCK
    toks = np.zeros((n, s_pad), np.int32)
    toks[:, :s] = tokens
    x = jnp.take(top["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    items = _items(cfg)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_fn(i), cfg_items=items, act_quant=act_quant)
    return _norm(x, top["final_norm"], cfg)[:, :s]


@functools.partial(jax.jit, static_argnames=("valid",))
def _block_stats(h, w_blk, start, targets, best, arg, at, *, valid):
    """Fold one vocabulary block (its first ``valid`` rows are real) into
    the running best logit, its token, and the targets' logits."""
    logits = jnp.matmul(h, w_blk.astype(jnp.float32).T, precision=HI)
    logits = jnp.where(jnp.arange(w_blk.shape[0]) < valid, logits, -jnp.inf)
    ids = start + jnp.arange(w_blk.shape[0])
    blk_best = logits.max(-1)
    blk_arg = ids[jnp.argmax(logits, -1)]
    take = blk_best > best
    hit = (targets >= start) & (targets < start + valid)
    val = jnp.take_along_axis(
        logits, jnp.clip(targets - start, 0, valid - 1)[:, None],
        axis=1)[:, 0]
    return (jnp.where(take, blk_best, best), jnp.where(take, blk_arg, arg),
            jnp.where(hit, val, at))


def logit_stats(cfg: dict, top: dict, hidden: jnp.ndarray,
                targets: np.ndarray):
    """For rows of final hidden states (P, d): the best logit, its token,
    and the logit of ``targets`` (P,), over the whole vocabulary in
    blocks (the (P, V) logits never exist at once)."""
    head = top["embed"] if cfg["tie_word_embeddings"] else top["head"].T
    v = head.shape[0]
    p = hidden.shape[0]
    best = jnp.full((p,), -jnp.inf, jnp.float32)
    arg = jnp.zeros((p,), jnp.int32)
    at = jnp.full((p,), jnp.nan, jnp.float32)
    tg = jnp.asarray(targets, jnp.int32)
    for start in range(0, v, VOCAB_BLOCK):
        blk = jax.lax.slice_in_dim(head, start, min(start + VOCAB_BLOCK, v))
        valid = blk.shape[0]
        if valid < VOCAB_BLOCK:              # one shape for every block
            blk = jnp.pad(blk, ((0, VOCAB_BLOCK - valid), (0, 0)))
        best, arg, at = _block_stats(hidden, blk, start, tg, best, arg, at,
                                     valid=valid)
    return best, arg, at
