"""Whole runs with the chip check skipped, on the CPU at a small size: a
sound run is correct; the control (the reference at fp8) is not; a run
whose tokens are altered where the engine produces them is not correct;
and the entry point refuses to run without a TPU or without the
program."""
from __future__ import annotations

import os
import shutil
import subprocess
import sys
import time

import pytest

from chipbench import harness as H
from chipbench import tiny

ROOT = os.path.dirname(os.path.dirname(H.BENCH_DIR))
SEED = 2 ** 31 + 12345


def _run(cell, **kw):
    return H.run_cell(cell, SEED, 4.0, False, t_start=time.monotonic(), **kw)


@pytest.mark.parametrize("name,loop", [("qwen3-4b", "closed"),
                                       ("nemotron-4-15b", "open")])
def test_sound_run_is_correct(name, loop):
    cell = tiny.cell(name, loop)
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["info"]["compared"]["tokens"] > 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(out)[-1] == "checks"
    statuses = set(out["info"]["statuses"])
    assert statuses <= {"ok", "hung up", "not sent"}, statuses


@pytest.mark.parametrize("name", ["qwen3-4b", "nemotron-4-15b"])
def test_control_is_not_correct(name):
    """The whole run with the control in the program's place (the reference
    at fp8 activations and K/V, its own first tokens compared at every
    position of the sampled answers): the run's own comparison decides it
    is not correct, while the tokens served in the same run are within the
    limit."""
    cell = tiny.cell(name)
    out = _run(cell, control="fp8")
    compared = out["info"]["compared"]
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] == compared["control_gap.fp8"]
    assert compared["tokens"] > 0
    assert compared["logit_gap"] <= cell.limits["logit_gap"] \
        < compared["control_gap.fp8"], compared


@pytest.mark.parametrize("name", ["qwen3-4b", "nemotron-4-15b"])
def test_token_altered_where_produced_is_not_correct(name):
    cell = tiny.cell(name)
    vocab = cell.config["vocab_size"]

    def alter_tokens(engine):
        fused = engine._fused

        def altered(*args, **kwargs):
            packed, cache, lens = fused(*args, **kwargs)
            packed = packed.at[:, 1].set((packed[:, 1] + 1) % vocab)
            return packed, cache, lens

        engine._fused = altered

    out = _run(cell, fault=alter_tokens)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > cell.limits["logit_gap"]


def _entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen3-4b.decode-long", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_nonzero_and_prints_no_result():
    proc = _entry(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(H.BENCH_DIR, tmp_path / os.path.relpath(H.BENCH_DIR, ROOT),
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _entry(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
