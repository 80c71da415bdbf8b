"""Top-level language model: embeddings -> layer groups -> norm -> logits.

One class covers all 10 assigned families; behaviour is driven entirely by
``ModelConfig`` (see ``blocks.layer_groups``).  The ``batch`` dict protocol:

  train   : {"tokens": (B,S) i32, "labels": (B,S) i32, "loss_mask": (B,S) f32?}
  prefill : {"tokens": (B,S)} (+ cache, seq_lens)
  decode  : {"tokens": (B,1)} (+ cache, seq_lens)
  frontends (audio/vlm stubs): "input_embeds" (B,S,d), "embed_mask" (B,S) bool
  qwen2-vl M-RoPE: "positions" (3,B,S) i32
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as B
from repro.models import layers as L


@dataclasses.dataclass(frozen=True)
class LM:
    cfg: ModelConfig

    # ------------------------------------------------------------------ params
    def init(self, rng) -> dict:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        groups = B.layer_groups(cfg)
        ks = jax.random.split(rng, len(groups) + 3)
        params: dict = {"embed": L.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype)}
        for i, (count, kind) in enumerate(groups):
            params[f"group{i}"] = B.group_init(ks[i + 1], cfg, count, kind, dtype)
        params["final_norm"] = L.norm_init(cfg.d_model, cfg.norm_type, dtype)
        if not cfg.tie_embeddings:
            params["head"] = L.linear_init(ks[-1], cfg.d_model, cfg.vocab_size,
                                           dtype=dtype)
        if cfg.meta_tokens:
            params["meta"] = (jax.random.normal(ks[-2], (cfg.meta_tokens, cfg.d_model),
                                                dtype) * 0.02)
        return params

    def abstract_params(self, rng=None) -> Any:
        return jax.eval_shape(self.init, jax.random.key(0))

    # ------------------------------------------------------------------ embed
    def _embed(self, params, batch, dtype):
        tokens = batch["tokens"]
        x = L.embed_lookup(params["embed"], tokens, dtype)
        if "input_embeds" in batch:
            emb = batch["input_embeds"].astype(dtype)
            if "embed_mask" in batch:     # vlm: splice vision embeds into text
                x = jnp.where(batch["embed_mask"][..., None], emb, x)
            else:                         # audio: frontend output replaces embed
                x = emb
        return x

    def _positions(self, batch, b, s, offset):
        if "positions" in batch:
            return batch["positions"]
        pos = jnp.arange(s, dtype=jnp.int32)[None, :] + jnp.zeros((b, 1), jnp.int32)
        if isinstance(offset, jnp.ndarray):
            pos = pos + offset[:, None]
        else:
            pos = pos + offset
        return pos

    # ----------------------------------------------------------------- forward
    def hidden(self, params, batch, *, kernels=L.DEFAULT_KERNELS,
               cache=None, seq_lens=None, mode: str = "train",
               block_tables=None, write_lens=None):
        """Backbone forward -> (final-norm hidden states, new_cache, aux)."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        x = self._embed(params, batch, dtype)
        b, s = x.shape[:2]
        nmeta = cfg.meta_tokens

        if nmeta and cache is None:       # prepend learned meta tokens (hymba)
            meta = jnp.broadcast_to(params["meta"][None], (b, nmeta, cfg.d_model))
            x = jnp.concatenate([meta.astype(dtype), x], axis=1)
            s = s + nmeta

        offset = seq_lens if (cache is not None and seq_lens is not None) else 0
        positions = self._positions(batch, b, s, offset)

        aux_total = jnp.zeros((), jnp.float32)
        new_cache: dict | None = {} if cache is not None else None
        remat = cfg.remat if mode == "train" else "none"
        x = L.constrain_act(x)
        for i, (count, kind) in enumerate(B.layer_groups(cfg)):
            c = cache.get(f"group{i}") if cache is not None else None
            x, nc, aux = B.group_apply(
                params[f"group{i}"], x, cfg=cfg, kind=kind, count=count,
                kernels=kernels, positions=positions, cache=c,
                seq_lens=seq_lens, num_sink=nmeta, remat=remat,
                block_tables=block_tables, write_lens=write_lens)
            if new_cache is not None:
                new_cache[f"group{i}"] = nc
            aux_total = aux_total + aux

        x = L.apply_norm(params["final_norm"], x, norm_type=cfg.norm_type,
                         eps=cfg.norm_eps)
        if nmeta and cache is None:
            x = x[:, nmeta:]
        return x, new_cache, aux_total

    def _logits(self, params, x):
        if self.cfg.tie_embeddings:
            return L.embed_logits(params["embed"], x)
        return L.linear(params["head"], x.astype(jnp.float32),
                        name="head").astype(jnp.float32)

    def apply(self, params, batch, *, kernels=L.DEFAULT_KERNELS,
              cache=None, seq_lens=None, mode: str = "train",
              block_tables=None, write_lens=None):
        """Returns (logits, new_cache, aux). Full-sequence (train/prefill) when
        cache is None or decode-with-cache otherwise."""
        x, new_cache, aux_total = self.hidden(
            params, batch, kernels=kernels, cache=cache, seq_lens=seq_lens,
            mode=mode, block_tables=block_tables, write_lens=write_lens)
        return self._logits(params, x), new_cache, aux_total

    # ------------------------------------------------------------------- cache
    def init_cache(self, batch_size: int, max_len: int, dtype=jnp.bfloat16,
                   kv_quant=None):
        """Slot-layout cache tree.  ``kv_quant`` (a quantized
        ``serving/kv_quant.py::KVQuantConfig``) switches the attention
        payloads to int8 with parallel per-token scale arrays — full-attention
        GQA stacks only (DESIGN.md §12)."""
        cfg = self.cfg
        cache = {}
        total = max_len + cfg.meta_tokens
        for i, (count, kind) in enumerate(B.layer_groups(cfg)):
            cache[f"group{i}"] = B.group_cache_init(cfg, kind, count, batch_size,
                                                    total, dtype, kv_quant)
        return cache

    def init_paged_cache(self, num_pages: int, page_size: int,
                         dtype=jnp.bfloat16, kv_quant=None):
        """Paged-layout cache tree (DESIGN.md §10): per-group physical page
        pools addressed by a shared block table.  Requires a homogeneous
        full-attention stack with no meta tokens.  ``kv_quant`` adds int8
        pools with parallel per-token scale pools (DESIGN.md §12).  The
        dtype default mirrors ``init_cache``; the serving engine passes
        its cache dtype (the model's dtype unless configured) explicitly."""
        cfg = self.cfg
        if cfg.meta_tokens:
            raise ValueError("paged cache layout does not support meta tokens")
        cache = {}
        for i, (count, kind) in enumerate(B.layer_groups(cfg)):
            cache[f"group{i}"] = B.group_paged_cache_init(
                cfg, kind, count, num_pages, page_size, dtype, kv_quant)
        return cache

    # -------------------------------------------------------------------- loss
    LOSS_CHUNK = 1024   # sequence rows per logits block (memory-bounded CE)

    def loss_fn(self, params, batch, *, kernels=L.DEFAULT_KERNELS):
        x, _, aux = self.hidden(params, batch, kernels=kernels, mode="train")
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        if mask is None:
            mask = jnp.ones(labels.shape, jnp.float32)
        b, s, d = x.shape

        def ce(xc, lc, mc):
            logits = self._logits(params, xc)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll = -jnp.take_along_axis(logp, lc[..., None], axis=-1)[..., 0]
            return jnp.sum(nll * mc)

        c = self.LOSS_CHUNK
        if s > c and s % c == 0:
            # chunked cross-entropy: the (B, S, V) fp32 logits tensor never
            # materializes; backward recomputes each chunk (jax.checkpoint)
            nc = s // c
            xs = (jnp.moveaxis(x.reshape(b, nc, c, d), 1, 0),
                  jnp.moveaxis(labels.reshape(b, nc, c), 1, 0),
                  jnp.moveaxis(mask.reshape(b, nc, c), 1, 0))
            nll_chunks = jax.lax.map(
                jax.checkpoint(lambda args: ce(*args)), xs)
            nll_sum = jnp.sum(nll_chunks)
        else:
            nll_sum = ce(x, labels, mask)
        loss = nll_sum / jnp.maximum(jnp.sum(mask), 1.0)
        return loss + aux, {"loss": loss, "aux": aux}

    # ----------------------------------------------------------- serving steps
    def forward_chunks(self, params, tokens, chunk_lens, cache, seq_lens, *,
                       kernels=L.DEFAULT_KERNELS, block_tables=None,
                       extra=None, logit_idx=None):
        """Unified serving forward (ISSUE 10, DESIGN.md §18): every row is
        one (chunk_start=seq_lens, chunk_len) span of its sequence — decode
        is a 1-token chunk, chunked prefill a budget-sized chunk, spec-verify
        a (k+1)-token chunk — all through the same cached multi-token path.

        tokens     : (B, C) int32, right-padded past ``chunk_lens``.
        chunk_lens : (B,) int32 real tokens per row; padded (and dead-row)
                     positions' cache writes are null-routed (paged) or
                     dropped (slot), and their keys are masked out of every
                     row's attention window.
        logit_idx  : optional (B, W) int32 chunk positions whose logits are
                     wanted — the head then runs on W rows per sequence, not
                     C (a (B, C, V) fp32 block at a 150k vocab is gigabytes).
        Row positions start at the absolute offset ``seq_lens``.  Returns
        (logits (B, C or W, V) fp32, new_cache); the caller advances
        seq_lens.
        """
        batch = {"tokens": tokens}
        if extra:
            batch.update(extra)
        x, cache, _ = self.hidden(
            params, batch, kernels=kernels, cache=cache, seq_lens=seq_lens,
            mode="decode", block_tables=block_tables, write_lens=chunk_lens)
        if logit_idx is not None:
            x = jnp.take_along_axis(x, logit_idx[:, :, None], axis=1)
        return self._logits(params, x), cache

    def prefill(self, params, batch, cache, seq_lens, *,
                kernels=L.DEFAULT_KERNELS, true_lengths=None,
                block_tables=None):
        """Whole-prompt convenience wrapper over ``forward_chunks``; returns
        logits of the last *real* position (``true_lengths`` handles
        right-padded bucketed prompts), new cache, new seq_lens."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        if cfg.meta_tokens:
            # engine guarantees seq_lens==0 at prefill; meta tokens fill the
            # cache prefix first
            meta_batch = {"tokens": jnp.zeros((b, cfg.meta_tokens), jnp.int32),
                          "input_embeds": jnp.broadcast_to(
                              params["meta"][None],
                              (b, cfg.meta_tokens, cfg.d_model))}
            _, cache, _ = self.apply(params, meta_batch, kernels=kernels,
                                     cache=cache, seq_lens=seq_lens,
                                     mode="prefill")
            seq_lens = seq_lens + cfg.meta_tokens
        # bucketed prompts: padded positions' cache writes are masked on
        # every layout — routed to the null page (paged) or dropped (slot);
        # real writes cover true_lengths tokens of the block
        if true_lengths is None:
            chunk_lens = jnp.full((b,), s, jnp.int32)
        else:
            chunk_lens = true_lengths.astype(jnp.int32)
        extra = {k: v for k, v in batch.items() if k != "tokens"}
        logits, cache = self.forward_chunks(
            params, tokens, chunk_lens, cache, seq_lens, kernels=kernels,
            block_tables=block_tables, extra=extra)
        if true_lengths is None:
            last = logits[:, -1]
        else:
            idx = (true_lengths - 1).astype(jnp.int32)
            last = jnp.take_along_axis(
                logits, idx[:, None, None].clip(0), axis=1)[:, 0]
        return last, cache, seq_lens + s

    def decode_step(self, params, tokens, cache, seq_lens, *,
                    kernels=L.DEFAULT_KERNELS, extra=None, block_tables=None):
        """tokens: (B, 1). Returns (logits (B, V), cache, seq_lens+1).
        One-token-chunk wrapper over ``forward_chunks``."""
        b, s = tokens.shape
        logits, cache = self.forward_chunks(
            params, tokens, jnp.full((b,), s, jnp.int32), cache, seq_lens,
            kernels=kernels, block_tables=block_tables, extra=extra)
        return logits[:, -1], cache, seq_lens + 1


def build_model(cfg: ModelConfig) -> LM:
    return LM(cfg)
