"""One benchmark run of one cell: build, warm up, serve, measure, check.

The parent process holds the chip: it draws the weights, builds the engine
(``chipbench.program``), warms up every step width the cell's traffic can
use, and serves ``/v1/completions`` on an ephemeral localhost port.  The
load generator is a child process that never imports JAX
(``chipbench.loadgen``).  When the window has closed the parent reads the
chip's peak memory, frees the engine, and checks the served tokens against
the plain reference (``chipbench.check``).  Every metric is read by a
reader of its own, ``metrics/<name>.py`` (or ``metrics/<prefix>.py`` for a
name ``<prefix>.<cell kind>``), from the ``Run`` this module collects.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

import chipbench
from chipbench import check as C
from chipbench import program as P
from chipbench import tracereduce as TR
from chipbench import weights as W

BENCH_DIR = chipbench.BENCH_DIR
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
CHILD_SLACK_S = 240.0


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A workload of ``BENCHMARK.json`` with its files read."""
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @classmethod
    def find(cls, name: str, root: str = ROOT) -> "Cell":
        """The workload ``name`` of ``<root>/BENCHMARK.json``; its traffic
        and cell files are found by name under ``<root>/benchmarks/chip``."""
        bench = load_json(root, "BENCHMARK.json")
        here = os.path.join(root, os.path.relpath(BENCH_DIR, ROOT))
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                           f"(have {sorted(cells)})")
        w = cells[name]
        conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

        def mine(metrics):
            return [m for m in metrics
                    if name in m.get("workloads", [name])]

        return cls(name=name, chips=w["chips"],
                   config=load_json(root, conf["file"]),
                   traffic=load_json(here, "traffic", f"{w['traffic']}.json"),
                   limits=load_json(here, "cells", f"{name}.json")["limits"],
                   end_to_end=mine(bench["end_to_end"]),
                   per_layer=mine(bench["per_layer"]))


@dataclasses.dataclass
class Run:
    """What a run collected; the metric readers read this."""
    config: dict
    traffic: dict
    seconds: float
    records: list
    window: tuple
    setup_s: float
    counters_open: dict
    counters_close: dict
    peaks: dict
    chips: int
    trace: dict | None = None
    steps: list = dataclasses.field(default_factory=list)

    @property
    def family(self):
        return chipbench.family(self.config)

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t <= self.window[1]


def reader(name: str, bench_dir: str = BENCH_DIR):
    """``read(run)`` of ``metrics/<name>.py``, else of the file named by
    the part of ``name`` before its first dot."""
    for stem in (name, name.split(".")[0]):
        if os.path.exists(os.path.join(bench_dir, "metrics", f"{stem}.py")):
            return chipbench.load("metrics", stem, bench_dir).read
    raise FileNotFoundError(f"no reader for metric {name!r} under metrics/")


def counters(engine) -> dict:
    """The engine registry's unlabelled counters, by name."""
    return {f.name: f.value for f in engine.metrics.registry.families()
            if f.kind == "counter" and not f.label_names}


class StepRecorder:
    """Host spans (``jax.profiler.TraceAnnotation``) around the engine's
    calls, the profiler's start and stop between steps, and the
    composition of every traced step (``chipbench.work``'s step form, and
    what ``describe``, a family's, adds).  Installed on the instances only,
    in traced runs only."""

    def __init__(self, engine, trace_dir: str, describe=None):
        import jax
        self.eng = engine
        self.trace_dir = trace_dir
        self.steps: list[dict] = []
        self.t_from = self.t_to = None
        self.state = "idle"
        self._ann = jax.profiler.TraceAnnotation
        step, plan = engine.step, engine.sched.plan_chunks

        def traced_step():
            self._maybe_start()
            with self._ann("engine.step"):
                out = step()
            self._maybe_stop()
            return out

        def traced_plan(budget, *, reserve=1):
            with self._ann("engine.plan"):
                chunks = plan(budget, reserve=reserve)
            if self.state == "tracing":
                self.steps.append(self._describe(chunks))
                if describe is not None:
                    describe(self.eng, self.steps[-1])
            return chunks

        engine.step = traced_step
        engine.sched.plan_chunks = traced_plan
        for attr, span in (("_admit", "engine.admit"),
                           ("_fused", "engine.dispatch"),
                           ("_end_step", "engine.end_step")):
            setattr(engine, attr, self.annotate(span, getattr(engine, attr)))

    def annotate(self, span: str, fn):
        def wrapped(*a, **k):
            with self._ann(span):
                return fn(*a, **k)
        return wrapped

    def arm(self, t_from: float, t_to: float) -> None:
        self.t_from, self.t_to = t_from, t_to
        self.state = "armed"

    def _maybe_start(self):
        if self.state == "armed" and time.monotonic() >= self.t_from:
            import jax
            # no Python function tracing: it would slow the host it traces
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.state = "tracing"

    def _maybe_stop(self):
        if self.state == "tracing" and time.monotonic() >= self.t_to:
            import jax
            jax.profiler.stop_trace()
            self.state = "done"

    def _describe(self, chunks: dict) -> dict:
        eng = self.eng
        rows = []
        need = 1
        for row, a in eng.sched.active.items():
            if row in chunks:
                n = chunks[row]
                need = max(need, n)
                rows.append([a.prefill_pos, n,
                             a.prefill_pos + n >= a.prefill_end
                             and a.prefill_sample])
            elif not a.pending_prefill:
                rows.append([eng.pc.lengths[a.req.rid], 1, True])
        return {"batch": eng.batch_rows, "width": eng._step_width(need),
                "rows": rows}


def warm_up(engine, vocab: int, seed: int) -> list[int]:
    """Run one request through every step width the token budget allows
    (decode width 1 and each prefill bucket up to the budget), so that
    nothing compiles inside the window."""
    rng = np.random.default_rng([int(seed) % 2 ** 64, 9])
    budget = engine.config.max_step_tokens
    widths = [w for w in engine._width_buckets if w <= budget]
    for i, w in enumerate(widths[1:]):
        # the first request also takes one decode step (width 1)
        engine.generate([rng.integers(2, vocab, size=w).tolist()],
                        max_new_tokens=2 if i == 0 else 1, ignore_eos=True)
    return widths


def _drive(port: int, cell: Cell, seed: int, seconds: float, on_line,
           workdir: str) -> dict:
    """Run the load generator child to its end; ``on_line(word, t)`` for
    each ``OPEN``/``CLOSE`` it prints."""
    spec_path = os.path.join(workdir, "loadgen_spec.json")
    out_path = os.path.join(workdir, "loadgen_result.json")
    with open(spec_path, "w") as f:
        json.dump({"port": port, "traffic": cell.traffic, "seed": seed,
                   "vocab": cell.config["vocab_size"],
                   "seconds": seconds}, f)
    child = subprocess.Popen(
        [sys.executable, "-m", "chipbench.loadgen", spec_path, out_path],
        cwd=BENCH_DIR, stdout=subprocess.PIPE, text=True)
    try:
        for line in child.stdout:
            word, _, t = line.partition(" ")
            if word in ("OPEN", "CLOSE"):
                on_line(word, float(t))
        child.wait(timeout=CHILD_SLACK_S)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    with open(out_path) as f:
        result = json.load(f)
    if result["error"] is not None:
        raise RuntimeError(f"load generator: {result['error']}")
    return result


def _settle(server, timeout_s: float = 30.0) -> None:
    """Abort every request still in the engine once the load generator has
    hung up (a queued one would otherwise wait for its first token to find
    its client gone), and wait until the engine is idle, so that no handler
    is left waiting on it when the server shuts down."""
    worker = server.worker
    for rid in list(worker._subs):
        worker.abort(rid)
    end = time.monotonic() + timeout_s
    while not worker.eng.sched.idle and time.monotonic() < end:
        time.sleep(0.05)


def device_peaks(device_kind: str) -> dict:
    """The chip's published peaks (``peaks.json``); a device missing from
    the table is an error, never a default."""
    table = load_json(BENCH_DIR, "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (have {sorted(table)})")
    return table[device_kind]


def live_tokens(records: list, t: float) -> int:
    """Tokens in the KV cache of the requests streaming at ``t``: each
    one's prompt and the tokens it had received by then."""
    return sum(len(r["prompt"]) + sum(1 for x in r["times"] if x <= t)
               for r in records
               if r["times"] and r["times"][0] <= t
               and (r["status"] != "ok" or r["times"][-1] > t))


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, peaks: dict | None = None,
             control: str | None = None, fault=None) -> dict:
    """One run; returns the result line's object (without printing).
    ``peaks`` (the device's row of ``peaks.json``) is needed by the traced
    readers.  With ``control`` (a lower precision of the reference, such as
    ``"fp8"``) the reference at that precision stands in for the program:
    the tokens it puts first, at every position of the sampled answers,
    take the served tokens' place in the comparison that decides
    ``correct``.  ``fault(engine)``, for tests, breaks the engine before it
    serves."""
    import jax
    from repro.serving.http_api import make_server

    cfg = cell.config
    devices = jax.local_devices()[:cell.chips]
    stages = {"imports_s": time.monotonic() - t_start}
    t = time.monotonic()
    weights = W.draw_model(cfg, seed)
    jax.block_until_ready(weights)
    stages["weights_s"] = time.monotonic() - t
    t = time.monotonic()
    _, engine = P.build_engine(cfg, weights, seed)
    del weights
    stages["engine_s"] = time.monotonic() - t
    t = time.monotonic()
    widths = warm_up(engine, cfg["vocab_size"], seed)
    stages["warm_up_s"] = time.monotonic() - t
    if fault is not None:
        fault(engine)
    workdir = tempfile.mkdtemp(prefix="chipbench-")
    fam = chipbench.family(cfg)
    recorder = (StepRecorder(engine, os.path.join(workdir, "trace"),
                             getattr(fam, "describe", None))
                if trace else None)
    server = make_server(engine, port=0, model_name=cfg["name"])
    if recorder is not None:
        server.worker._fan_out = recorder.annotate("worker.fan_out",
                                                   server.worker._fan_out)
    serving = threading.Thread(target=server.serve_forever, daemon=True)
    serving.start()
    marks: dict = {}

    def on_line(word, t):
        marks[word] = (t, counters(engine))
        if word == "OPEN" and recorder is not None:
            span = min(cell.traffic["trace_s"], seconds)
            t0 = t + (seconds - span) / 2
            recorder.arm(t0, t0 + span)

    try:
        result = _drive(server.port, cell, seed, seconds, on_line, workdir)
        _settle(server)
    finally:
        server.shutdown()
        serving.join(timeout=60)
    if recorder is not None and recorder.state == "tracing":
        jax.profiler.stop_trace()
        recorder.state = "done"
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    t_open, c_open = marks["OPEN"]
    t_close, c_close = marks["CLOSE"]
    stages["after_close_s"] = time.monotonic() - t_close
    run = Run(config=cfg, traffic=cell.traffic, seconds=t_close - t_open,
              records=result["records"], window=(t_open, t_close),
              setup_s=t_open - t_start, counters_open=c_open,
              counters_close=c_close,
              peaks=peaks, chips=cell.chips)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out_extra = {}
    if recorder is not None:
        events = TR.load(recorder.trace_dir) if recorder.state == "done" \
            else []
        run.trace = TR.reduce(events, TR.classes(fam))
        run.steps = recorder.steps
        if run.trace is not None:
            device.update(busy_s=run.trace["busy_s"],
                          window_s=run.trace["window_s"])
            out_extra["breakdown"] = TR.breakdown(run.trace)
        recorder.eng = None
    server.worker.eng = None
    del engine, server, recorder
    gc.collect()
    shutil.rmtree(workdir, ignore_errors=True)

    t_check = time.monotonic()
    vocab = cfg["vocab_size"]
    malformed = C.malformed(run.records, vocab)
    picked = C.sample(run.records, vocab, seed, **cell.traffic["check"])
    gaps = (C.logit_gaps(cfg, seed, picked,
                         controls=(control,) if control else ())
            if picked else {"logit_gap": float("inf"), "tokens": 0,
                            "requests": 0})
    gap = gaps[f"control_gap.{control}"] if control and picked \
        else gaps["logit_gap"]
    metrics = {}
    wanted = cell.per_layer if trace else cell.end_to_end
    for m in wanted:
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {"logit_gap": {"value": gap, "limit": cell.limits["logit_gap"]},
              "malformed": {"value": malformed, "limit": 0}}
    correct = (malformed == 0 and gaps["tokens"] > 0
               and gap <= cell.limits["logit_gap"])
    stages["check_s"] = time.monotonic() - t_check
    statuses: dict = {}
    for r in run.records:
        key = r["status"][:60]
        statuses[key] = statuses.get(key, 0) + 1
    info = {"widths": widths, "setup": stages, "statuses": statuses,
            "lateness": result.get("lateness", {}), "compared": gaps,
            "window_s": run.seconds,
            "live_tokens": {"open": live_tokens(run.records, t_open),
                            "close": live_tokens(run.records, t_close)}}
    if control:
        info["control"] = control
    return {"correct": correct, "attempted": len(run.records),
            "failed": failed(run), "metrics": metrics, "device": device,
            **out_extra, "info": info, "checks": checks}


def failed(run: Run) -> int:
    """Requests that errored, and in an open loop those due inside the
    window that never had their first token."""
    n = 0
    for r in run.records:
        if r["status"] not in ("ok", "hung up", "not sent"):
            n += 1
        elif ("scheduled" in r and run.in_window(r["scheduled"])
              and not r["tokens"]):
            n += 1
    return n
