"""Serving driver: build a 4-bit GPTQ model from a seed and either run a
synthetic request stream through the continuous-batching engine (offline
mode, default) or expose it as an OpenAI-style HTTP service (``--serve``).

The weights are random: every projection is drawn as int4 at its quantized
shape (``core/quantize_model.py::random_quantized_params``), so a
published-width model loads without ever holding its dense copy.  The
kernel lane follows the backend: compiled Pallas on a TPU, the Pallas
interpreter on the CPU (``kernels/lane.py``).

  # offline throughput run
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b --smoke \
      --requests 8 --strategy opt4gptq [--no-pallas] [--cache paged]

  # HTTP service: POST /v1/completions (token-id prompts, SSE streaming),
  # GET /metrics (Prometheus text) and GET /healthz (watchdog freshness)
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3_4b --smoke \
      --serve --port 8000

Observability (DESIGN.md §15): every phase of a step (``engine.admit``,
``engine.plan``, ``engine.stage``, ``engine.dispatch``, ``engine.wait``,
``engine.emit``, ``engine.end_step`` inside ``engine.step``) and of the
HTTP worker (``worker.inbox``, ``worker.fan_out``) is one span with two
sinks: a ``jax.profiler.TraceAnnotation`` on the device trace's clock,
seen by any profiler session, and -- with ``--trace-out trace.json``,
which attaches a ``Tracer`` -- a slice of the same name in the
Chrome/Perfetto ``trace_event`` file written on exit, beside the
per-request lifecycle tracks.  ``--log-json`` switches this script's
own progress lines to one JSON object per line (machine-parseable event
log); ``--no-metrics`` swaps the engine's registry for the zero-cost null
one.
"""
import argparse
import json
import sys
import time


def log_event(args, event: str, **fields):
    """One structured driver event: human line by default, one JSON object
    per line under ``--log-json`` (``{"event": ..., **fields}``)."""
    if getattr(args, "log_json", False):
        print(json.dumps({"event": event, **fields}, sort_keys=True),
              flush=True)
    else:
        kv = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"[serve] {event}: {kv}" if kv else f"[serve] {event}",
              flush=True)


def build_engine(args):
    """Model + quantization + engine from CLI args — shared by both modes."""
    import jax

    from repro.configs import get_config, smoke_config
    from repro.core.gptq import GPTQConfig
    from repro.core.opt_strategies import get_strategy
    from repro.core.quantize_model import random_quantized_params
    from repro.models import build_model, layers as L
    from repro.serving.api import EngineConfig
    from repro.serving.engine import Engine
    from repro.serving.spec_decode import SpecConfig
    from repro.serving.tracing import Tracer

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    qparams = random_quantized_params(
        model, GPTQConfig(group_size=args.group_size), seed=args.seed)
    kern = L.KernelConfig(strategy=get_strategy(args.strategy),
                          use_pallas=not args.no_pallas)
    tracer = Tracer() if args.trace_out else None
    spec = None
    if args.speculate != "off":
        spec = SpecConfig(method=args.speculate, k=args.spec_k,
                          draft_arch=args.draft_arch,
                          draft_smoke=args.smoke)
    tp = getattr(args, "tp", 1) or 1
    eng = Engine(model, qparams, EngineConfig(
        batch_slots=args.slots, max_len=args.max_len, kernels=kern,
        eos_id=-1, cache=args.cache, page_size=args.page_size,
        kv_quant=args.kv_quant, max_queued=args.max_queued,
        default_queue_timeout_s=args.queue_timeout,
        metrics=not args.no_metrics, tracer=tracer,
        speculation=spec, prefix_cache_path=args.prefix_cache,
        mesh_shape=(tp,) if tp > 1 else None, seed=args.seed,
        page_pool_bytes=(None if args.page_pool_gib is None
                         else int(args.page_pool_gib * 2 ** 30))))
    if tp > 1:
        log_event(args, "tensor_parallel", tp=tp,
                  devices=len(jax.devices()))
    return cfg, eng


def export_trace(args, eng):
    """Flush still-open request spans and write the Perfetto trace file."""
    if eng.tracer is None:
        return
    eng.tracer.flush_open(eng.clock.now())
    path = eng.tracer.export(args.trace_out)
    log_event(args, "trace_exported", path=path,
              events=len(eng.tracer.events))


def run_offline(args, cfg, eng):
    import jax

    from repro.data.pipeline import sharegpt_stream

    stream = sharegpt_stream(args.requests, vocab_size=cfg.vocab_size,
                             seed=0, mean_prompt=10, mean_output=args.max_new,
                             max_prompt=args.max_len // 2)
    t0 = time.time()
    for r in stream:
        eng.submit(r.prompt, max_new_tokens=min(r.output_len, args.max_new))
    persist = args.prefix_cache and args.cache == "paged"
    if persist:
        # drain drops every published prefix entry (refcount reaches zero),
        # so the warm set must be captured while requests are still live —
        # pump manually and snapshot once about halfway through the stream
        done, saved = [], None
        while not eng.sched.idle:
            done.extend(eng.step())
            if saved is None and len(done) >= max(1, args.requests // 2):
                saved = eng.save_prefix_cache(args.prefix_cache)
        if saved is None:
            saved = eng.save_prefix_cache(args.prefix_cache)
    else:
        done = eng.run()
    dt = time.time() - t0
    toks = sum(len(f.output) for f in done)
    lat = sorted(f.latency for f in done)
    s = eng.stats
    if args.log_json:
        log_event(args, "offline_done", arch=cfg.name,
                  strategy=args.strategy, cache=args.cache,
                  requests=len(done), tokens=toks,
                  tok_per_s=round(toks / dt, 2),
                  p50_latency_s=round(lat[len(lat) // 2], 4),
                  wall_s=round(s.wall_s, 4), steps=s.steps,
                  tokens_per_step=round(s.tokens_per_step, 3),
                  prefix_hit_pages=s.prefix_hit_pages,
                  prefix_hit_tokens=s.prefix_hit_tokens,
                  spec_proposed=s.spec_proposed,
                  spec_accepted=s.spec_accepted,
                  acceptance_rate=round(s.acceptance_rate, 4))
    else:
        extra = ""
        if args.cache == "paged":
            extra = (f", prefix-hit pages {s.prefix_hit_pages}"
                     f" ({s.prefix_hit_tokens} tokens)")
        if args.speculate != "off":
            extra += (f", spec accept {s.spec_accepted}/{s.spec_proposed}"
                      f" ({s.acceptance_rate:.0%},"
                      f" {s.tokens_per_step:.2f} tok/step)")
        print(f"[serve] {cfg.name} x {args.strategy} [{args.cache}] on "
              f"{jax.devices()[0].platform}: {len(done)} reqs, {toks} "
              f"tokens, {toks / dt:.2f} tok/s host wall clock, p50 "
              f"{lat[len(lat) // 2]:.2f}s{extra}")
    if persist:
        log_event(args, "prefix_cache_saved", path=args.prefix_cache,
                  pages=saved)
    export_trace(args, eng)


def run_http(args, cfg, eng):
    from repro.serving.http_api import make_server

    server = make_server(eng, host=args.host, port=args.port,
                         model_name=cfg.name,
                         stall_timeout_s=args.stall_timeout)
    log_event(args, "listening", arch=cfg.name, cache=args.cache,
              url=f"http://{args.host}:{server.port}/v1/completions",
              metrics=f"http://{args.host}:{server.port}/metrics",
              healthz=f"http://{args.host}:{server.port}/healthz")
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        # a second Ctrl-C during shutdown (worker join) must not lose the
        # trace — export runs no matter how shutdown ends
        try:
            server.shutdown()
        except KeyboardInterrupt:
            pass
        finally:
            export_trace(args, eng)


def make_parser() -> argparse.ArgumentParser:
    """The driver's command line — also how ``chip_smoke.py`` configures
    ``build_engine``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_4b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--strategy", default="opt4gptq")
    ap.add_argument("--no-pallas", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--cache", choices=("slot", "paged"), default="slot",
                    help="KV layout: fixed slots or PagedAttention block "
                         "tables (DESIGN.md §10)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--page-pool-gib", type=float, default=None,
                    help="size the paged KV pool from this many GiB per "
                         "device instead of slots x max_len")
    ap.add_argument("--group-size", type=int, default=128,
                    help="GPTQ quantization group along K")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random int4 weights and of sampling")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (DESIGN.md §17): shard "
                         "GPTQ weights and the KV page pools across this "
                         "many devices (paged cache only; page budget is "
                         "per device)")
    ap.add_argument("--kv-quant", choices=("fp32", "bf16", "int8"),
                    default=None, dest="kv_quant",
                    help="KV-cache storage: fp passthrough or int8 with "
                         "fused per-token scales (DESIGN.md §12)")
    ap.add_argument("--speculate", choices=("off", "ngram", "draft"),
                    default="off",
                    help="speculative decoding (DESIGN.md §16): model-free "
                         "n-gram prompt lookup or a smaller draft model, "
                         "verified in one batched forward per step")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per verify step")
    ap.add_argument("--draft-arch", default=None, metavar="ARCH",
                    help="registry config for --speculate draft (honours "
                         "--smoke); must share the target vocab")
    ap.add_argument("--prefix-cache", default=None, metavar="DIR",
                    help="persisted prefix-cache directory: warm pages are "
                         "loaded at startup if present (paged cache only); "
                         "offline mode snapshots the live index there "
                         "mid-run (drain evicts published entries)")
    ap.add_argument("--serve", action="store_true",
                    help="run the OpenAI-style /v1/completions HTTP "
                         "front-end instead of the offline request stream")
    ap.add_argument("--max-queued", type=int, default=None,
                    help="bounded admission: reject submits past this many "
                         "queued requests with HTTP 429 (DESIGN.md §14)")
    ap.add_argument("--queue-timeout", type=float, default=None,
                    help="shed requests not admitted within this many "
                         "seconds (HTTP 503, DESIGN.md §14)")
    ap.add_argument("--stall-timeout", type=float, default=None,
                    help="arm the engine-worker watchdog: fail in-flight "
                         "requests if a step stalls past this (§14)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8000,
                    help="HTTP port for --serve (0 = ephemeral)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record per-request/step spans and write a "
                         "Chrome/Perfetto trace_event JSON file on exit "
                         "(DESIGN.md §15)")
    ap.add_argument("--log-json", action="store_true",
                    help="emit driver progress as one JSON object per line "
                         "instead of human-readable text")
    ap.add_argument("--no-metrics", action="store_true",
                    help="disable the metrics registry (NullRegistry: "
                         "/metrics exposes nothing, EngineStats reads zero)")
    return ap


def main(argv=None):
    args = make_parser().parse_args(argv)

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    cfg, eng = build_engine(args)
    if args.serve:
        run_http(args, cfg, eng)
    else:
        run_offline(args, cfg, eng)
    return 0


if __name__ == "__main__":
    sys.exit(main())
