#!/usr/bin/env python3
"""Chip smoke run: serve qwen3-4b at its published widths on a TPU.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # four chips: tensor parallelism only

One chip.  The model is ``configs/qwen3_4b.py`` at full width (36 layers,
d_model 2560, 32/8 heads x 128, d_ff 9728, vocab 151936) with random int4
weights (group 128) drawn from ``--seed``, a paged bf16 KV pool of
``--pool-gib`` GiB, built by ``repro.launch.serve.build_engine`` — the
entry point ``python -m repro.launch.serve`` uses.  Two phases:

* parity — one prefill and one decode step of two prompts through the
  compiled Pallas lane (GPTQ GEMV/matmul, paged attention/prefill kernels)
  and through the jnp reference lane (``kernels/ref.py``), on the chip; the
  logits must be finite and agree within ``PARITY_RTOL``;
* serve — ``http_api.make_server`` on an ephemeral localhost port answers
  eight greedy requests (prompts of 128 to 1024 tokens, 32 new tokens
  each, two of them streamed with ``"stream": true``); every request must
  return 32 token ids inside the vocabulary.

``--tp 4`` runs only the tensor-parallel phase, in this one process: the
same model sharded over four chips (``mesh_shape=(4,)``) serves the same
prompts, then, once it is freed, an unsharded engine on device 0 does; the
prefill and decode logits must agree within ``TP_RTOL``, and every
projection weight and KV pool must sit a quarter on each device.

What the script prints before its last line is a smoke run on one device
kind, not a benchmark: times are host wall clock and include compilation.
The last line is one JSON object, ``{"ok": true, "device": {...}}``.  With
no TPU the script exits non-zero and prints no result; any failed check
raises, so the exit code is never 0 after a failure.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# Logit agreement bounds, as relative L2 error ||a - b|| / ||b|| over the
# (prompts, vocab) logits block.  Both lanes dequantize the same int4
# weights to the same bf16 values; they differ in f32 accumulation order
# (K chunks and eight nibble planes vs one dot; online vs two-pass softmax),
# and every such difference is rounded into the bf16 activations that feed
# the next of 36 layers.
PARITY_RTOL = 2e-2
# tp=4 against tp=1: the row-parallel projections' partial sums are rounded
# to bf16 on each device before the all-reduce, once per wo / w_down.
TP_RTOL = 3e-2

N_REQUESTS = 8
NEW_TOKENS = 32
PROMPT_MIN, PROMPT_MAX = 128, 1024
PARITY_PROMPT = 256


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def make_prompts(seed: int, vocab: int) -> list[list[int]]:
    import numpy as np
    rng = np.random.default_rng(seed)
    lengths = [PROMPT_MIN, PROMPT_MAX] + rng.integers(
        PROMPT_MIN, PROMPT_MAX + 1, size=N_REQUESTS - 2).tolist()
    return [rng.integers(2, vocab, size=n).tolist() for n in lengths]


def engine_args(seed: int, pool_gib: float, tp: int):
    from repro.launch import serve
    return serve.make_parser().parse_args([
        "--arch", "qwen3_4b", "--cache", "paged", "--slots", str(N_REQUESTS),
        "--max-len", "2048", "--page-size", "16", "--group-size", "128",
        "--page-pool-gib", str(pool_gib), "--seed", str(seed),
        "--tp", str(tp)])


def build(seed: int, pool_gib: float, tp: int):
    import jax
    from repro.launch.serve import build_engine
    t0 = time.perf_counter()
    cfg, eng = build_engine(engine_args(seed, pool_gib, tp))
    jax.block_until_ready((eng.params, eng.cache))
    log(f"engine tp={tp} built in {time.perf_counter() - t0:.1f} s: "
        f"{eng.pc.num_pages} pages of {eng.pc.page_size} tokens, KV "
        f"{eng.cache_dtype}, lane use_pallas={eng.kernels.use_pallas} "
        f"interpret={eng.kernels.interpret}")
    return cfg, eng


def probe_logits(eng, prompts, kernels=None):
    """Prefill each prompt from empty, then decode one token (the prompt's
    first token, the same input on every lane), through
    ``Engine.forward_probe`` on a scratch page pool.  Returns the
    last-position prefill logits and the decode logits, (B, V) each."""
    import jax.numpy as jnp
    import numpy as np
    ps = eng.pc.page_size
    b, s = len(prompts), max(len(p) for p in prompts)
    per_row = -(-(s + 1) // ps)
    cache = eng.paged_cache_like(b * per_row)
    tables = jnp.asarray(1 + np.arange(b * per_row).reshape(b, per_row),
                         jnp.int32)
    tokens = np.zeros((b, s), np.int32)
    for i, p in enumerate(prompts):
        tokens[i, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    fwd = eng.forward_probe(kernels)
    pre, cache = fwd(eng.params, jnp.asarray(tokens), lens, cache,
                     jnp.zeros((b,), jnp.int32), tables, (lens - 1)[:, None])
    first = jnp.asarray([[p[0]] for p in prompts], jnp.int32)
    dec, _ = fwd(eng.params, first, jnp.ones((b,), jnp.int32), cache, lens,
                 tables, jnp.zeros((b, 1), jnp.int32))
    return np.asarray(pre[:, 0]), np.asarray(dec[:, 0])


def compare(name: str, got, want, rtol: float) -> None:
    import numpy as np
    for label, a, b in (("prefill", got[0], want[0]),
                        ("decode", got[1], want[1])):
        if not (np.isfinite(a).all() and np.isfinite(b).all()):
            raise AssertionError(f"{name} {label}: non-finite logits")
        rel = float(np.linalg.norm(a - b) / np.linalg.norm(b))
        same = float((a.argmax(-1) == b.argmax(-1)).mean())
        log(f"{name} {label}: relative L2 error {rel:.3e} "
            f"(bound {rtol:.0e}), argmax agreement {same:.2f}")
        if not rel <= rtol:
            raise AssertionError(f"{name} {label}: relative L2 error "
                                 f"{rel:.3e} exceeds {rtol:.0e}")


def parity_phase(eng, prompts) -> None:
    """Compiled Pallas lane against the jnp reference lane, on the chip."""
    ref_lane = dataclasses.replace(eng.kernels, use_pallas=False,
                                   paged_attention_impl="ref",
                                   paged_prefill_impl="ref")
    t0 = time.perf_counter()
    pallas = probe_logits(eng, prompts)
    ref = probe_logits(eng, prompts, ref_lane)
    log(f"parity probes ran in {time.perf_counter() - t0:.1f} s "
        f"(compilation included)")
    compare("pallas vs reference", pallas, ref, PARITY_RTOL)


def _post(port: int, body: dict, timeout: float):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _complete(port: int, prompt: list[int], stream: bool, out: dict) -> None:
    body = {"prompt": prompt, "max_tokens": NEW_TOKENS, "temperature": 0.0,
            "ignore_eos": True, "stream": stream}
    t0 = time.perf_counter()
    with _post(port, body, timeout=1200) as resp:
        if not stream:
            data = json.loads(resp.read())
            out["tokens"] = data["choices"][0]["token_ids"]
            out["ttft_s"] = data["metrics"]["ttft_s"]
            return
        toks = []
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                break
            chunk = json.loads(line[len("data: "):])
            got = chunk["choices"][0]["token_ids"]
            if got and not toks:
                out["ttft_s"] = time.perf_counter() - t0
            toks.extend(got)
        out["tokens"] = toks


def serve_phase(eng, cfg, prompts) -> list[list[int]]:
    """Eight concurrent greedy requests through the HTTP front end."""
    from repro.serving.http_api import make_server
    server = make_server(eng, port=0, model_name=cfg.name)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    results = [{} for _ in prompts]
    errors: list[BaseException] = []

    def run(i):
        try:
            _complete(server.port, prompts[i], stream=i % 4 == 1,
                      out=results[i])
        except BaseException as e:             # re-raised below
            errors.append(e)

    t0 = time.perf_counter()
    clients = [threading.Thread(target=run, args=(i,))
               for i in range(len(prompts))]
    try:
        for c in clients:
            c.start()
        for c in clients:
            c.join(timeout=1500)
    finally:
        server.shutdown()
        thread.join(timeout=60)
    wall = time.perf_counter() - t0
    if errors:
        raise errors[0]
    if any(c.is_alive() for c in clients):
        raise AssertionError("a client did not finish")
    outs = []
    for i, r in enumerate(results):
        toks = r.get("tokens")
        if toks is None or len(toks) != NEW_TOKENS:
            raise AssertionError(f"request {i} returned {toks!r}")
        if not all(isinstance(t, int) and 0 <= t < cfg.vocab_size
                   for t in toks):
            raise AssertionError(f"request {i}: token id outside the vocab")
        outs.append(toks)
    ttfts = ", ".join(f"{r['ttft_s']:.2f}" for r in results)
    log(f"served {len(prompts)} requests over HTTP ({sum(map(len, prompts))} "
        f"prompt tokens, {NEW_TOKENS * len(prompts)} generated) in "
        f"{wall:.1f} s host wall clock, compilation included; TTFT s per "
        f"request: {ttfts}")
    return outs


def _device_bytes(tree) -> dict:
    """Bytes of ``tree``'s shards held by each device."""
    import jax
    per: dict = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in leaf.addressable_shards:
            per[shard.device.id] = per.get(shard.device.id, 0) \
                + shard.data.nbytes
    return per


def check_placement(eng, tp: int) -> None:
    """Every projection weight and KV pool sits 1/tp on each device."""
    import jax
    from repro.core.gptq import QuantizedLinear
    qls = [leaf for leaf in jax.tree_util.tree_leaves(
        eng.params, is_leaf=lambda x: isinstance(x, QuantizedLinear))
        if isinstance(leaf, QuantizedLinear)]
    for name, tree in (("int4 projections", qls), ("KV pools", eng.cache)):
        for leaf in jax.tree_util.tree_leaves(tree):
            full = 1
            for d in leaf.shape:
                full *= d
            shard = 1
            for d in leaf.sharding.shard_shape(leaf.shape):
                shard *= d
            if shard * tp != full:
                raise AssertionError(f"{name}: a {leaf.shape} leaf has "
                                     f"shard shape {leaf.sharding.shard_shape(leaf.shape)}")
        per = _device_bytes(tree)
        total = sum(per.values())
        if len(per) != tp or any(abs(v * tp - total) > total * 1e-6
                                 for v in per.values()):
            raise AssertionError(f"{name}: per-device bytes {per}")
        log(f"{name}: {total / 2**30:.3f} GiB, per device "
            + ", ".join(f"{d}: {v / 2**30:.3f} GiB"
                        for d, v in sorted(per.items())))
    for d in jax.devices()[:tp]:
        stats = d.memory_stats() or {}
        log(f"device {d.id} bytes_in_use "
            f"{stats.get('bytes_in_use', 0) / 2**30:.2f} GiB")


def tp_phase(seed: int, pool_gib: float, tp: int) -> None:
    import jax
    if len(jax.devices()) < tp:
        raise SystemExit(f"--tp {tp} needs {tp} devices, found "
                         f"{len(jax.devices())}")
    cfg, eng = build(seed, pool_gib, tp)
    prompts = make_prompts(seed, cfg.vocab_size)
    check_placement(eng, tp)
    sharded = probe_logits(eng, prompts[:2])
    t0 = time.perf_counter()
    outs_tp = [o.output for o in eng.generate(prompts,
                                              max_new_tokens=NEW_TOKENS)]
    log(f"tp={tp}: generated {sum(map(len, outs_tp))} tokens in "
        f"{time.perf_counter() - t0:.1f} s host wall clock")
    del eng
    gc.collect()
    cfg, eng = build(seed, pool_gib, 1)
    single = probe_logits(eng, prompts[:2])
    outs_1 = [o.output for o in eng.generate(prompts,
                                             max_new_tokens=NEW_TOKENS)]
    compare(f"tp={tp} vs tp=1", sharded, single, TP_RTOL)
    same = sum(a == b for x, y in zip(outs_tp, outs_1) for a, b in zip(x, y))
    whole = sum(x == y for x, y in zip(outs_tp, outs_1))
    log(f"greedy tokens tp={tp} vs tp=1: {same}/{NEW_TOKENS * len(prompts)} "
        f"equal, {whole}/{len(prompts)} requests identical")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=1,
                    help="run only the tensor-parallel phase on this many "
                         "chips (4 on a v5e 2x2 host)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pool-gib", type=float, default=4.0,
                    help="paged KV pool per device")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "src", "repro")):
        print("chip_smoke.py: run it from a checkout of the repository "
              "(src/repro is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(HERE, "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py: no TPU (JAX found {devices[0].platform}); "
              f"this script measures nothing elsewhere", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import setup_compile_cache
    log(f"compile cache: {setup_compile_cache()}")
    log(f"devices: {len(devices)} x {devices[0].device_kind}")

    if args.tp > 1:
        tp_phase(args.seed, args.pool_gib, args.tp)
    else:
        cfg, eng = build(args.seed, args.pool_gib, 1)
        prompts = make_prompts(args.seed, cfg.vocab_size)
        parity_phase(eng, [p[:PARITY_PROMPT] for p in prompts[:2]])
        serve_phase(eng, cfg, prompts)
    stats = devices[0].memory_stats() or {}
    log(f"smoke run on {devices[0].device_kind}, not a benchmark: "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 0) / 2**30:.2f}"
        f" GiB of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
