"""Block-size autotuner for the GPTQ Pallas kernels.

Three stages (DESIGN.md §8):

1. **Enumerate** (8,128)-aligned (bm, bn, bk) candidates legal for the shape
   (bk divides K and aligns with the quantization group; bn divides N when it
   can, else falls back to the padded-N block).
2. **Prune** with the analytic v5e cost model (``core/perf_model``): only
   candidates within ``PRUNE_FACTOR`` of the best modeled time are timed —
   the model ranks bk (HBM sweep count); timing resolves bm/bn ties.
3. **Time** the survivors on synthetic data (packed int32 weights, the real
   kernel entry points) and persist the winner to a JSON cache keyed by
   ``(M, K, N, group_size, strategy, lane)`` where lane is "gemv"
   (M <= GEMV_M_MAX -> ``gptq_gemv``) or "matmul" (-> ``gptq_matmul``).

The cache file defaults to ``~/.cache/repro/autotune.json`` and is overridden
by ``$REPRO_AUTOTUNE_CACHE``.  Lookups go memory -> file -> tune; a repeated
key never re-times (the test suite asserts this via ``timed_keys``).
Surface: ``KernelConfig(block_sizes="auto")`` in ``models/layers.py`` routes
``ops.gptq_linear`` through ``get_block_sizes``.  Timing uses concrete
synthetic arrays, so it executes (not traces) even when the lookup happens
while an outer ``jit`` is tracing the model.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.core.opt_strategies import KernelStrategy, OPT4GPTQ
from repro.core.perf_model import gptq_matmul_cost
from repro.kernels import gptq_gemv as _gemv
from repro.kernels import gptq_matmul as _gm
from repro.kernels.gptq_gemv import GEMV_M_MAX
from repro.kernels.lane import resolve_interpret

ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
DEFAULT_CACHE = os.path.join(os.path.expanduser("~"), ".cache", "repro",
                             "autotune.json")
PRUNE_FACTOR = 1.5        # modeled-time ratio beyond which candidates drop
MAX_TIMED = 6             # hard cap on survivors that get wall-clock timed
TIMING_REPS = 2

BM_CANDIDATES = (8, 16, 32, 64, 128)
BN_CANDIDATES = (64, 128, 256, 512, 1024)
BK_CANDIDATES = (64, 128, 256, 512, 1024)

_MEM: dict[str, tuple[int, int, int]] = {}
timed_keys: list[str] = []      # every key that ran wall-clock timing (tests)


def cache_path() -> str:
    return os.environ.get(ENV_CACHE, DEFAULT_CACHE)


def clear_memory_cache() -> None:
    _MEM.clear()


def _lane(m: int) -> str:
    return "gemv" if m <= GEMV_M_MAX else "matmul"


def cache_key(m: int, k: int, n: int, group_size: int,
              strategy: KernelStrategy, *, interpret: bool | None = None) -> str:
    """Includes the execution mode: interpreter-mode timings (CPU dev box)
    must never be reused for compiled-TPU runs — the two wall-clock signals
    are uncorrelated, so each mode tunes and caches independently."""
    mode = "interp" if resolve_interpret(interpret) else "compiled"
    return f"{m}x{k}x{n}:g{group_size}:{strategy.name}:{_lane(m)}:{mode}"


# ----------------------------------------------------------------- candidates
def candidate_blocks(m: int, k: int, n: int,
                     group_size: int) -> list[tuple[int, int, int]]:
    """Legal (8,128)-aligned blocks for the shape.  The GEMV lane pins bm to
    the padded sublane tile; bk must divide K and align with the group."""
    g = group_size if group_size > 0 else k
    m_pad = _gm._round_up(m, 8)
    if m <= GEMV_M_MAX:
        bms = [m_pad]
    else:
        bms = sorted({min(b, m_pad) for b in BM_CANDIDATES})
    bns = [b for b in BN_CANDIDATES if b <= n and n % b == 0]
    if not bns:
        bns = [min(_gm._round_up(n, 8), 256)]     # padded-N fallback block
    bks = [b for b in BK_CANDIDATES
           if b <= k and k % b == 0 and (b % g == 0 or g % b == 0)]
    if not bks:
        bks = [_gm.resolve_block_sizes(m, k, n, group_size, 8, 256, 512)[2]]
    return [(bm, bn, bk) for bm in bms for bn in bns for bk in bks]


def prune_candidates(cands: list[tuple[int, int, int]], m: int, k: int,
                     n: int, group_size: int, strategy: KernelStrategy,
                     *, max_timed: int = MAX_TIMED
                     ) -> list[tuple[int, int, int]]:
    """Rank by the analytic cost model and keep the near-optimal front.

    The model only sees bk (HBM sweep count), so many (bm, bn) variants tie;
    ties break toward larger tiles — fewer program launches — so the timed
    set spans the configs that actually differ at runtime."""
    scored = sorted(
        ((gptq_matmul_cost(m, k, n, group_size=group_size, strategy=strategy,
                           bk=bk).time_s, (bm, bn, bk))
         for bm, bn, bk in cands),
        key=lambda e: (e[0], -e[1][1] * e[1][2], -e[1][0]))
    best = scored[0][0]
    return [c for t, c in scored if t <= best * PRUNE_FACTOR][:max_timed]


# --------------------------------------------------------------------- timing
def _synthetic(m: int, k: int, n: int, group_size: int,
               strategy: KernelStrategy):
    rng = np.random.default_rng(0)
    g = group_size if group_size > 0 else k
    qweight = jnp.asarray(
        rng.integers(0, 1 << 32, size=(k // packing.NIBBLES_PER_WORD, n),
                     dtype=np.uint64).astype(np.uint32).view(np.int32))
    if not strategy.packed_loads:
        qweight = packing.unpack_int4_rows(qweight, k)
    scales = jnp.asarray(rng.uniform(0.005, 0.02, (k // g, n)).astype(np.float32))
    qzeros = jnp.asarray(
        rng.integers(0, 1 << 32, size=(k // g, n // packing.NIBBLES_PER_WORD),
                     dtype=np.uint64).astype(np.uint32).view(np.int32))
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    return x, qweight, scales, qzeros


def _time_call(fn, reps: int = TIMING_REPS) -> float:
    jax.block_until_ready(fn())                      # compile + warm
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_block_sizes(m: int, k: int, n: int, group_size: int,
                         strategy: KernelStrategy = OPT4GPTQ, *,
                         interpret: bool | None = None,
                         max_timed: int = MAX_TIMED
                         ) -> tuple[int, int, int]:
    """Enumerate -> prune -> time; returns the fastest (bm, bn, bk)."""
    survivors = prune_candidates(
        candidate_blocks(m, k, n, group_size), m, k, n, group_size, strategy,
        max_timed=max_timed)
    timed_keys.append(cache_key(m, k, n, group_size, strategy,
                                interpret=interpret))
    if len(survivors) == 1:
        return survivors[0]
    x, qw, scales, qzeros = _synthetic(m, k, n, group_size, strategy)
    lane = _lane(m)
    best_t, best_c = float("inf"), survivors[0]
    for bm, bn, bk in survivors:
        if lane == "gemv":
            fn = lambda: _gemv.gptq_gemv(
                x, qw, scales, qzeros, None, group_size=group_size,
                strategy=strategy, bn=bn, bk=bk, interpret=interpret)
        else:
            fn = lambda: _gm.gptq_matmul(
                x, qw, scales, qzeros, group_size=group_size,
                strategy=strategy, bm=bm, bn=bn, bk=bk, interpret=interpret)
        t = _time_call(fn)
        if t < best_t:
            best_t, best_c = t, (bm, bn, bk)
    return best_c


# ---------------------------------------------------------------- persistence
def _load_file(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


def _save_file(path: str, data: dict) -> None:
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(data, f, indent=1, sort_keys=True)
    os.replace(tmp, path)


def _q_chunk_lookup(key: str, path: str | None, tune) -> int:
    """Shared memory -> JSON file -> tune cache walk for scalar entries."""
    path = path or cache_path()
    mem_key = f"{path}|{key}"
    hit = _MEM.get(mem_key)
    if hit is not None:
        return int(hit)
    data = _load_file(path)
    if key in data:
        val = int(data[key])
    else:
        val = int(tune())
        data = _load_file(path)                  # re-read: concurrent writers
        data[key] = val
        try:
            _save_file(path, data)
        except OSError:
            pass                                 # read-only FS: memory only
    _MEM[mem_key] = val
    return val


def get_block_sizes(m: int, k: int, n: int, group_size: int,
                    strategy: KernelStrategy = OPT4GPTQ, *,
                    interpret: bool | None = None,
                    path: str | None = None) -> tuple[int, int, int]:
    """Cached autotune lookup: memory -> JSON file -> tune (and persist).

    The memory cache is scoped per cache file, so an explicit ``path`` (e.g.
    a pinned per-deployment config) is never shadowed by an earlier lookup of
    the same shape against a different file."""
    key = cache_key(m, k, n, group_size, strategy, interpret=interpret)
    path = path or cache_path()
    mem_key = f"{path}|{key}"
    hit = _MEM.get(mem_key)
    if hit is not None:
        return hit
    data = _load_file(path)
    if key in data:
        cfg = tuple(int(v) for v in data[key])
    else:
        cfg = autotune_block_sizes(m, k, n, group_size, strategy,
                                   interpret=interpret)
        data = _load_file(path)                  # re-read: concurrent writers
        data[key] = list(cfg)
        try:
            _save_file(path, data)
        except OSError:
            pass                                 # read-only FS: memory only
    _MEM[mem_key] = cfg
    return cfg


# ------------------------------------------------------- paged-prefill q_chunk
# ISSUE 10 satellite: the chunked-prefill query tile height used to be a
# fixed 128; ``KernelConfig(q_chunk="auto")`` co-tunes it with the engine's
# step token budget.  Candidates stay lane-aligned (multiples of 128) and
# never exceed the suffix length's bucket — a taller tile than the block is
# pure pad work.
Q_CHUNK_CANDIDATES = (128, 256, 512)


def q_chunk_cache_key(s: int, h: int, hkv: int, d: int, page_size: int, *,
                      interpret: bool | None = None) -> str:
    mode = "interp" if resolve_interpret(interpret) else "compiled"
    return f"qchunk:s{s}:h{h}:kv{hkv}:d{d}:ps{page_size}:{mode}"


def q_chunk_candidates(s: int) -> list[int]:
    cands = [c for c in Q_CHUNK_CANDIDATES if c <= max(s, Q_CHUNK_CANDIDATES[0])]
    return cands or [Q_CHUNK_CANDIDATES[0]]


def autotune_q_chunk(s: int, h: int, hkv: int, d: int, page_size: int, *,
                     interpret: bool | None = None) -> int:
    """Wall-clock the chunked paged-prefill kernel per candidate tile height
    on synthetic pools and return the fastest ``q_chunk``."""
    from repro.kernels import paged_attention as PA
    cands = q_chunk_candidates(s)
    timed_keys.append(q_chunk_cache_key(s, h, hkv, d, page_size,
                                        interpret=interpret))
    if len(cands) == 1:
        return cands[0]
    rng = np.random.default_rng(0)
    n_pages = -(-s // page_size)
    q = jnp.asarray(rng.normal(size=(1, s, h, d)).astype(np.float32))
    kp = jnp.asarray(
        rng.normal(size=(hkv, n_pages + 1, page_size, d)).astype(np.float32))
    vp = jnp.asarray(
        rng.normal(size=(hkv, n_pages + 1, page_size, d)).astype(np.float32))
    bt = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
    start = jnp.zeros((1,), jnp.int32)
    lengths = jnp.full((1,), s, jnp.int32)
    best_t, best_c = float("inf"), cands[0]
    for qc in cands:
        fn = lambda: PA.paged_prefill(q, kp, vp, bt, start, lengths,
                                      q_chunk=qc, interpret=interpret)
        t = _time_call(fn)
        if t < best_t:
            best_t, best_c = t, qc
    return best_c


def get_q_chunk(s: int, h: int, hkv: int, d: int, page_size: int, *,
                interpret: bool | None = None, path: str | None = None) -> int:
    """Cached ``q_chunk`` lookup: memory -> JSON file -> tune (and persist)."""
    key = q_chunk_cache_key(s, h, hkv, d, page_size, interpret=interpret)
    return _q_chunk_lookup(
        key, path,
        lambda: autotune_q_chunk(s, h, hkv, d, page_size, interpret=interpret))
