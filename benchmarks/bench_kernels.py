"""Kernel microbench: per-strategy interpret-mode wall time (harness check)
plus the modeled v5e bytes/time per strategy for the paper's canonical GEMM
shapes — the decode fast lane (ISSUE 1): for the decode-GEMV shape every
strategy is timed on the seed's fixed-block general-matmul path AND on the
GEMV lane with autotuned blocks, so the speedup is tracked per PR — and the
paged-KV decode attention (ISSUE 2): the Pallas paged-attention kernel vs the
jnp block-table gather reference vs the slot layout's contiguous grouped
attend, at the same batch/context shape.

Emits CSV lines through benchmarks/run.py and writes the structured record
to BENCH_kernels.json at the repo root (the perf trajectory for later PRs).
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gptq, packing
from repro.core.opt_strategies import STRATEGIES
from repro.core.perf_model import gptq_matmul_cost
from repro.kernels import autotune, ops
from repro.kernels import gptq_matmul as _gm
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ref import paged_attention_ref

SHAPES = [
    ("decode_gemv", 8, 1024, 1024, 128),
    ("prefill_gemm", 128, 1024, 512, 128),
]
SEED_BLOCKS = (8, 256, 256)       # the seed's fixed decode path
REPS = 3
JSON_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         os.pardir, "BENCH_kernels.json")


def _time(fn, reps=REPS):
    """us per call, best-of-reps — same timer the autotuner selects with
    (autotune._time_call), so benchmark numbers and tuning decisions agree."""
    return autotune._time_call(fn, reps=reps) * 1e6


# decode attention shape: batch rows x GQA heads over a paged 128-token context
PAGED_SHAPE = dict(b=4, h=8, hkv=2, d=64, page_size=16, max_pages=8)


def _bench_paged_decode(lines, records):
    """Paged-vs-slot decode attention (ISSUE 2): the serving-side complement
    of the GEMV lane.  Slot baseline is the contiguous grouped-GQA attend the
    slot engine decodes with; the paged rows pay the block-table gather."""
    from repro.models.attention import attend

    p = PAGED_SHAPE
    b, h, hkv, d = p["b"], p["h"], p["hkv"], p["d"]
    ps, maxp = p["page_size"], p["max_pages"]
    ctx = ps * maxp
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(hkv, b * maxp + 1, ps, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(hkv, b * maxp + 1, ps, d)), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(b * maxp).reshape(b, maxp), jnp.int32)
    lens = jnp.full((b,), ctx, jnp.int32)
    kc = jnp.asarray(rng.normal(size=(b, ctx, hkv, d)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(b, ctx, hkv, d)), jnp.float32)

    @jax.jit
    def slot_decode(q, kc, vc, lens):
        return attend(q[:, None], kc, vc, qpos=(lens - 1)[:, None],
                      causal=True, grouped=True)

    us_slot = _time(lambda: slot_decode(q, kc, vc, lens))
    us_kernel = _time(lambda: paged_attention(q, kp, vp, bt, lens))
    ref = jax.jit(paged_attention_ref)
    us_ref = _time(lambda: ref(q, kp, vp, bt, lens))
    rec = {"shape": "paged_decode", **p, "context": ctx,
           "us_slot_attend": us_slot, "us_paged_kernel": us_kernel,
           "us_paged_ref": us_ref,
           "paged_vs_slot": us_kernel / us_slot if us_slot else 0.0}
    records.append(rec)
    lines.append(
        f"kernel/paged_decode,{us_kernel:.0f},"
        f"slot_us={us_slot:.0f}|ref_us={us_ref:.0f}|"
        f"ctx={ctx}|ratio_vs_slot={rec['paged_vs_slot']:.2f}")


def run():
    lines = []
    records = []
    rng = np.random.default_rng(0)
    for name, m, k, n, g in SHAPES:
        w = jnp.asarray(rng.normal(0, 0.5, (k, n)).astype(np.float32))
        ql = gptq.gptq_quantize(w, None, gptq.GPTQConfig(group_size=g))
        x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
        decode = name.startswith("decode")
        for s, strat in STRATEGIES.items():
            cost = gptq_matmul_cost(m, k, n, group_size=g, strategy=strat)
            rec = {"shape": name, "m": m, "k": k, "n": n, "group_size": g,
                   "strategy": s, "model_us": cost.time_s * 1e6,
                   "hbm_kb": cost.hbm_bytes / 1e3}
            if decode:
                # seed path: fixed blocks through the general tiled matmul
                qw = (ql.qweight if strat.packed_loads
                      else packing.unpack_int4_rows(ql.qweight, k))
                bm, bn, bk = SEED_BLOCKS
                us_seed = _time(lambda: _gm.gptq_matmul(
                    x, qw, ql.scales, ql.qzeros, group_size=g, strategy=strat,
                    bm=bm, bn=bn, bk=bk))
                # fast lane: GEMV dispatch, fixed blocks vs autotuned blocks
                us_fixed = _time(lambda: ops.gptq_linear(
                    ql, x, strategy=strat, use_pallas=True,
                    block_sizes=SEED_BLOCKS))
                tuned = autotune.get_block_sizes(m, k, n, g, strat)
                us_auto = _time(lambda: ops.gptq_linear(
                    ql, x, strategy=strat, use_pallas=True,
                    block_sizes="auto"))
                rec.update(us_seed_matmul=us_seed, us_gemv_fixed=us_fixed,
                           us_gemv_auto=us_auto, auto_blocks=list(tuned),
                           speedup_vs_seed=us_seed / us_auto if us_auto else 0)
                lines.append(
                    f"kernel/{name}/{s},{us_auto:.0f},"
                    f"seed_us={us_seed:.0f}|gemv_fixed_us={us_fixed:.0f}|"
                    f"auto_blocks={'x'.join(map(str, tuned))}|"
                    f"speedup={rec['speedup_vs_seed']:.2f}|"
                    f"model_us={cost.time_s * 1e6:.2f}|"
                    f"hbm_kb={cost.hbm_bytes / 1e3:.0f}")
            else:
                us = _time(lambda: ops.gptq_linear(
                    ql, x, strategy=strat, use_pallas=True,
                    block_sizes=SEED_BLOCKS))
                rec["us"] = us
                lines.append(
                    f"kernel/{name}/{s},{us:.0f},"
                    f"model_us={cost.time_s * 1e6:.2f}|"
                    f"hbm_kb={cost.hbm_bytes / 1e3:.0f}")
            records.append(rec)
    _bench_paged_decode(lines, records)
    try:
        with open(JSON_PATH, "w") as f:
            json.dump(records, f, indent=1)
        lines.append(f"kernel/json,0,written={os.path.abspath(JSON_PATH)}")
    except OSError as e:
        lines.append(f"kernel/json,0,ERROR={e!r}")
    return lines


if __name__ == "__main__":
    for line in run():
        print(line)
