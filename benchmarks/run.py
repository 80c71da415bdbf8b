"""Benchmark driver — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines.

  figs 2-3  : bench_paper_figs  (throughput/latency per model x strategy)
  tables1-2 : bench_accuracy    (ppl fp16 vs GPTQ vs RTN; strategy agreement)
  kernels   : bench_kernels     (per-strategy micro costs + decode fast lane;
                                 writes BENCH_kernels.json for the perf
                                 trajectory across PRs)
  serving   : bench_serving     (request-level ttft/tpot/throughput
                                 percentiles, slot vs paged; writes
                                 BENCH_serving.json)
  roofline  : roofline_table    (dry-run derived roofline per cell)

``--sections kernels,roofline`` runs a subset (default: all).
``--trace-out trace.json`` is forwarded to sections that accept it (today:
serving — exports a Perfetto trace of the preemption overload run).
"""
import argparse
import inspect
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--sections", default="all",
                    help="comma-separated subset of "
                         "kernels,paper_figs,accuracy,serving,roofline "
                         "(default all)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace_event JSON from "
                         "sections that support tracing (serving)")
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    print("name,us_per_call,derived")
    from benchmarks import bench_kernels, bench_paper_figs, bench_accuracy, \
        bench_serving, roofline_table
    sections = [
        ("kernels", bench_kernels.run),
        ("paper_figs", bench_paper_figs.run),
        ("accuracy", bench_accuracy.run),
        ("serving", bench_serving.run),
        ("roofline", roofline_table.run),
    ]
    if args.sections != "all":
        wanted = {s.strip() for s in args.sections.split(",")}
        unknown = wanted - {name for name, _ in sections}
        if unknown:
            sys.exit(f"unknown sections: {sorted(unknown)}")
        sections = [(n, f) for n, f in sections if n in wanted]
    failed = 0
    for name, fn in sections:
        try:
            kwargs = {}
            if (args.trace_out
                    and "trace_out" in inspect.signature(fn).parameters):
                kwargs["trace_out"] = args.trace_out
            for line in fn(**kwargs):
                print(line, flush=True)
        except Exception as e:
            failed += 1
            print(f"{name}/ERROR,0,{e!r}", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
