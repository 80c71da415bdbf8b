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

import numpy as np
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


def _same_multisets(traffic, ra, rb):
    """The multiset of prompt lengths and of output lengths (first answers
    included) is the same for both seeds, and every length within the
    configured range."""
    assert sorted(len(r["prompt"]) for r in ra) == sorted(
        len(r["prompt"]) for r in rb)
    assert sorted(r["max_tokens"] for r in ra) == sorted(
        r["max_tokens"] for r in rb)
    assert [r["prompt"] for r in ra] != [r["prompt"] for r in rb]
    for r in ra:
        assert all(2 <= t < 1000 for t in r["prompt"])
        lo, hi = traffic["prompt"]["min"], traffic["prompt"]["max"]
        assert lo <= len(r["prompt"]) <= hi


def _sizes(client, key):
    return [len(r["prompt"]) if key == "prompt" else r["max_tokens"]
            for r in client]


def _per_client(traffic, pa, pb):
    """Every client's later requests (all of them, for a size without a
    ``first_`` distribution) hold the strata over their number, on both
    seeds."""
    n = traffic["requests_per_client"]
    for key in ("prompt", "output"):
        skip = 1 if traffic.get(f"first_{key}") else 0
        want = sorted(T.stratified(traffic[key], n - skip).tolist())
        for plan in (pa, pb):
            for client in plan["clients"]:
                assert sorted(_sizes(client, key)[skip:]) == want, key


def _first_prompts(traffic, pa, pb):
    """The clients' first prompts are the strata of ``first_prompt`` over
    the clients, on both seeds."""
    want = sorted(T.stratified(traffic["first_prompt"],
                               traffic["clients"]).tolist())
    for plan in (pa, pb):
        assert sorted(len(c[0]["prompt"]) for c in plan["clients"]) == want


def _ids_only(traffic, pa, pb):
    """Two seeds give every client the same sizes in the same order, and
    other token ids: the seed changes no work in the window."""
    for key in ("prompt", "output"):
        assert [_sizes(c, key) for c in pa["clients"]] == [
            _sizes(c, key) for c in pb["clients"]]
    ra, rb = _requests(pa), _requests(pb)
    assert all(a["prompt"] != b["prompt"] for a, b in zip(ra, rb))


def _follows_distributions(traffic, pa, pb):
    """The union of all requests follows the configured distributions: the
    sorted lengths at probability p lie between the distribution's
    quantiles at p -/+ the coarsest strata's width.  Where a ``first_``
    distribution differs from the later one, each is held to its own."""
    c, n = traffic["clients"], traffic["requests_per_client"]
    for key in ("prompt", "output"):
        spec, first = traffic[key], traffic.get(f"first_{key}")
        for plan in (pa, pb):
            sizes = [_sizes(client, key) for client in plan["clients"]]
            if first is None or first == spec:
                groups = [([v for row in sizes for v in row], spec,
                           min(c, n - (first is not None)))]
            else:
                groups = [([row[0] for row in sizes], first, c),
                          ([v for row in sizes for v in row[1:]], spec,
                           min(c, n - 1))]
            for got, want, strata in groups:
                got = sorted(got)
                for i, v in enumerate(got):
                    p = (i + 0.5) / len(got)
                    lo = T.quantile(want, max(p - 1 / strata, 1e-9))
                    hi = T.quantile(want, min(p + 1 / strata, 1 - 1e-9))
                    assert lo <= v <= hi, (key, i, v, lo, hi)


def _unpaired(traffic, pa, pb):
    """A prompt's rank says nothing of its answer's: over the clients'
    later requests the two lengths' ranks are all but uncorrelated (dealt
    rank to rank they would correlate fully)."""
    for plan in (pa, pb):
        later = [r for c in plan["clients"] for r in c[1:]]
        ranks = [np.argsort(np.argsort(v, kind="stable"))
                 for v in ([len(r["prompt"]) for r in later],
                           [r["max_tokens"] for r in later])]
        assert abs(np.corrcoef(*ranks)[0, 1]) < 0.3


_CLOSED_CASES = {"per_client": _per_client, "first_prompts": _first_prompts,
                 "ids_only": _ids_only,
                 "distributions": _follows_distributions,
                 "unpaired": _unpaired}


@pytest.mark.parametrize("name,case", [
    *(pytest.param(name, None, id=name)
      for name in ("decode-long", "doc-prefill", "open")),
    *(pytest.param(name, case, id=f"{name}-{case}")
      for name in ("decode-long", "doc-prefill") for case in _CLOSED_CASES)])
def test_every_seed_offers_the_same_work(name, case):
    """Stratified sizes: every seed offers the same work in another order.
    Without a case, the multisets over all requests; a closed loop's cases
    check how they are dealt to its clients."""
    traffic = _traffic(name)
    pa, pb = [T.plan(traffic, s, 1000, 45.0) for s in (1, 2 ** 31 + 9)]
    if case is None:
        _same_multisets(traffic, _requests(pa), _requests(pb))
    else:
        _CLOSED_CASES[case](traffic, pa, pb)


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
    different, shorter than any later answer, and each client's the same
    on every seed."""
    traffic = _traffic("decode-long")
    firsts = []
    for seed in (3, 2 ** 33 + 1):
        plan = T.plan(traffic, seed, 1000, 45.0)
        firsts.append([c[0]["max_tokens"] for c in plan["clients"]])
        later = [r["max_tokens"] for c in plan["clients"] for r in c[1:]]
        assert max(firsts[-1]) <= min(later)
    assert len(set(firsts[0])) == len(firsts[0])
    assert firsts[0] == firsts[1]
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
    existing file of the benchmark; so are the committed cells', and
    nemotron-4-15b.doc-prefill is judged on prompt_tok_s, output_tok_s and
    its own tail of token gaps, with its six prefill per-layer metrics."""
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
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert H.reader("new_metric.x", str(here))(None) == 42.0
    # every committed cell finds its files, and a reader for each metric
    committed = H.load_json(ROOT, "BENCHMARK.json")
    for w in committed["workloads"]:
        cell = H.Cell.find(w["name"])
        conf = next(c for c in committed["configs"]
                    if c["name"] == w["config"])
        assert cell.config == H.load_json(ROOT, conf["file"])
        assert cell.traffic == _traffic(w["traffic"])
        assert cell.limits["logit_gap"] > 0
        for m in cell.end_to_end + cell.per_layer:
            assert callable(H.reader(m["name"]))
    cell = H.Cell.find("nemotron-4-15b.doc-prefill")
    assert cell.config["name"] == "nemotron-4-15b"
    assert sorted(m["name"] for m in cell.end_to_end) == [
        "itl_p95_ms.prefill", "output_tok_s", "prompt_tok_s", "setup_s"]
    assert sorted(m["name"] for m in cell.per_layer) == sorted(
        f"{stem}.prefill" for stem in (
            "step_ms", "step_host_ms", "device_idle_share", "step_mfu",
            "gptq_roofline", "paged_attn_roofline"))
