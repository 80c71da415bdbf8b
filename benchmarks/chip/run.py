#!/usr/bin/env python3
"""Chip benchmark of the served 4-bit GPTQ path: one run of one cell.

    python3 benchmarks/chip/run.py --workload qwen3-4b.decode-long \\
        --seed 1234 --seconds 45 --trace 0

Run it from the root of a checkout, on a machine whose JAX finds a TPU with
at least as many chips as the cell asks for; elsewhere it exits non-zero
and prints no result.  A cell is a workload of ``BENCHMARK.json``: a
configuration file (``configs/``), a traffic file (``traffic/``) and its
check limits (``cells/``).  ``--trace 0`` prints the cell's end-to-end
metrics; ``--trace 1`` takes a profiler trace of a steady part of the
window and prints the cell's per-layer metrics.  Either way the last line
of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``: each compared number beside its limit), and the last
lines of standard error repeat the checks.

``--control fp8`` (or ``int8``) runs the control: the reference at fp8
(int8) activations and K/V stands in for the program, its own first tokens
at every position of the sampled answers are what ``logit_gap`` compares,
and the run comes out not correct.  The benchmark's own runs leave it off.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("int8", "fp8"), default=None,
                    help="compare the reference at this lower precision in "
                         "the program's place (the control run)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"no program to measure: {ROOT}/src/repro is missing")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)
    # the compile cache sits at a fixed path inside the checkout, whatever
    # the environment names, so only a checkout's first run compiles (JAX
    # reads the variable when it is imported)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    from chipbench import harness
    try:
        cell = harness.Cell.find(args.workload)
    except (KeyError, FileNotFoundError) as e:
        return fail(str(e))
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX found {devices[0].platform}; this "
                    f"benchmark measures nothing elsewhere")
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, found "
                    f"{len(devices)}")
    try:
        peaks = harness.device_peaks(devices[0].device_kind)
    except KeyError as e:
        return fail(str(e))
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_start=T_START, peaks=peaks,
                           control=args.control)
    info = out.pop("info")
    print(f"run.py: {json.dumps(info)}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
