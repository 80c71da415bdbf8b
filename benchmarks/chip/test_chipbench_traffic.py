"""Traffic plans, the JAX-free load generator, and finding a new
configuration, traffic mix, cell or metric by name."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from chipbench import harness as H
from chipbench import tiny
from chipbench import traffic as T

ROOT = os.path.dirname(os.path.dirname(H.BENCH_DIR))


def _requests(plan):
    return ([r for c in plan["clients"] for r in c]
            if plan["loop"] == "closed" else plan["requests"])


def _traffic(name):
    """A committed traffic file, or the open loop that no committed cell
    uses yet (``tiny.OPEN`` at a chat cell's sizes)."""
    if name == "open":
        return dict(tiny.OPEN, rate_per_s=1.0, lead_s=10,
                    prompt={"dist": "lognormal", "median": 256,
                            "sigma": 1.0, "min": 16, "max": 2048})
    return H.load_json(H.BENCH_DIR, "traffic", f"{name}.json")


@pytest.mark.parametrize("name", ["decode-long", "doc-prefill", "open"])
def test_every_seed_offers_the_same_work(name):
    """Stratified sizes: the multiset of prompt lengths and of output
    lengths (first answers included) is the same for every seed; the seed
    changes their order and the token ids."""
    traffic = _traffic(name)
    ra, rb = [_requests(T.plan(traffic, s, 1000, 45.0))
              for s in (1, 2 ** 31 + 9)]
    assert sorted(len(r["prompt"]) for r in ra) == sorted(
        len(r["prompt"]) for r in rb)
    assert sorted(r["max_tokens"] for r in ra) == sorted(
        r["max_tokens"] for r in rb)
    assert [r["prompt"] for r in ra] != [r["prompt"] for r in rb]
    for r in ra:
        assert all(2 <= t < 1000 for t in r["prompt"])
        lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
        assert lo <= len(r["prompt"]) <= hi


def test_open_loop_arrivals():
    traffic = _traffic("open")
    plan = T.plan(traffic, 3, 1000, 45.0)
    at = [r["at"] for r in plan["requests"]]
    span = traffic["lead_s"] + 45.0
    assert len(at) == round(span * traffic["rate_per_s"])
    assert all(a < b for a, b in zip(at, at[1:]))
    assert 0 < at[0] and at[-1] < span


def test_first_answers_are_staggered():
    """Each client's first answer is a stratum of ``first_output``: all
    different, shorter than any later answer, and the same set for every
    seed."""
    traffic = _traffic("decode-long")
    firsts = []
    for seed in (3, 2 ** 33 + 1):
        plan = T.plan(traffic, seed, 1000, 45.0)
        firsts.append([c[0]["max_tokens"] for c in plan["clients"]])
        later = [r["max_tokens"] for c in plan["clients"] for r in c[1:]]
        assert max(firsts[-1]) <= min(later)
    assert len(set(firsts[0])) == len(firsts[0])
    assert firsts[0] != firsts[1] and sorted(firsts[0]) == sorted(firsts[1])
    spec = traffic["first_output"]
    assert spec["min"] <= min(firsts[0]) and max(firsts[0]) <= spec["max"]


class _Stub(BaseHTTPRequestHandler):
    """Streams ``max_tokens`` SSE tokens, 5 ms apart."""
    protocol_version = "HTTP/1.1"

    def log_message(self, *args):
        pass

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Connection", "close")
        self.end_headers()
        n = body["max_tokens"]
        try:
            for i in range(n):
                time.sleep(0.005)
                chunk = {"choices": [{"token_ids": [i % 7],
                                      "finish_reason": "length"
                                      if i == n - 1 else None}]}
                self.wfile.write(b"data: " + json.dumps(chunk).encode()
                                 + b"\n\n")
                self.wfile.flush()
            self.wfile.write(b"data: [DONE]\n\n")
        except (BrokenPipeError, ConnectionResetError):
            pass


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_load_generator_never_imports_jax(tmp_path, loop):
    """The child runs the plan against a stub server with a ``jax`` on its
    path that fails on import, reports the window, and times every
    token."""
    poison = tmp_path / "poison" / "jax"
    poison.mkdir(parents=True)
    (poison / "__init__.py").write_text(
        "raise ImportError('the load generator imported jax')\n")
    server = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    server.daemon_threads = True
    threading.Thread(target=server.serve_forever, daemon=True).start()
    traffic = dict(tiny.CLOSED if loop == "closed" else tiny.OPEN,
                   drain_s=5)
    spec = tmp_path / "spec.json"
    out = tmp_path / "out.json"
    spec.write_text(json.dumps({"port": server.server_address[1],
                                "traffic": traffic, "seed": 11,
                                "vocab": 512, "seconds": 1.5}))
    env = dict(os.environ, PYTHONPATH=str(tmp_path / "poison"))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "chipbench.loadgen", str(spec), str(out)],
            cwd=H.BENCH_DIR, env=env, capture_output=True, text=True,
            timeout=120)
    finally:
        server.shutdown()
    assert proc.returncode == 0, proc.stderr
    words = [line.split()[0] for line in proc.stdout.splitlines()]
    assert words == ["OPEN", "CLOSE"]
    result = json.loads(out.read_text())
    t0, t1 = result["window"]
    assert t1 - t0 == pytest.approx(1.5, abs=0.1)
    done = [r for r in result["records"] if r["status"] == "ok"]
    assert done
    for r in done:
        assert len(r["tokens"]) == len(r["times"]) == r["max_tokens"]
        assert r["times"] == sorted(r["times"])
    if loop == "open":
        assert result["lateness"]["max_s"] < 0.5


def test_hang_up_ends_open_streams(tmp_path):
    """At the close of a closed-loop window with no drain, every answer
    still streaming is cut at once (the server then aborts it), not read
    to its end."""
    server = ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    server.daemon_threads = True
    threading.Thread(target=server.serve_forever, daemon=True).start()
    traffic = dict(tiny.CLOSED, first_output=None,
                   output={"dist": "uniform", "min": 2000, "max": 2000})
    spec = tmp_path / "spec.json"
    out = tmp_path / "out.json"
    spec.write_text(json.dumps({"port": server.server_address[1],
                                "traffic": traffic, "seed": 11,
                                "vocab": 512, "seconds": 1.0}))
    t0 = time.monotonic()
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "chipbench.loadgen", str(spec), str(out)],
            cwd=H.BENCH_DIR, capture_output=True, text=True, timeout=120)
    finally:
        server.shutdown()
    assert proc.returncode == 0, proc.stderr
    assert time.monotonic() - t0 < 6.0          # each answer takes 10 s
    records = json.loads(out.read_text())["records"]
    assert records and {r["status"] for r in records} == {"hung up"}
    assert all(0 < len(r["tokens"]) < 2000 for r in records)


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, traffic mix, cell and metric reader added as new
    files (and named in BENCHMARK.json) are found with no edit to any
    existing file of the benchmark."""
    bench_rel = os.path.relpath(H.BENCH_DIR, ROOT)
    here = tmp_path / bench_rel
    shutil.copytree(H.BENCH_DIR, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    cfg = tiny.config("qwen3-4b")
    (here / "configs" / "new-model.json").write_text(json.dumps(cfg))
    (here / "traffic" / "new-mix.json").write_text(json.dumps(tiny.CLOSED))
    (here / "cells" / "new-model.new-mix.json").write_text(
        json.dumps({"limits": {"logit_gap": 0.5}}))
    (here / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": f"{bench_rel}/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "new_metric.x", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "itl_p95_ms",
                               "workloads": ["new-model.new-mix"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = H.Cell.find("new-model.new-mix", root=str(tmp_path))
    assert cell.config == cfg and cell.traffic == tiny.CLOSED
    assert cell.limits == {"logit_gap": 0.5}
    assert [m["name"] for m in cell.per_layer] == ["new_metric.x"]
    assert "itl_p95_ms" in [m["name"] for m in cell.end_to_end]
    assert H.reader("new_metric.x", str(here))(None) == 42.0
