"""A new architecture enters the harness as new files only: a copy of the
benchmark gains a family of routed experts beside a leading dense layer
(``fixtures/new-family/``: family, configuration, cell, kernel class and
metric reader) and its ``BENCHMARK.json`` entries, and the copy's own
modules, run from the copy, find and use every one of them, while every
file the copy had stays byte for byte as it was."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

from chipbench import harness as H

ROOT = os.path.dirname(os.path.dirname(H.BENCH_DIR))
BENCH_REL = os.path.relpath(H.BENCH_DIR, ROOT)
NEW = os.path.join(H.BENCH_DIR, "fixtures", "new-family")

# run from the copy, with the copy's chipbench first on the path
SCRIPT = r'''
import json, sys, types
import jax, numpy as np
import chipbench
from chipbench import harness as H, program as P, tiny, tracereduce as TR
from chipbench import weights as W
from repro.models import build_model
from repro.models.blocks import layer_groups

root, seed = sys.argv[1], 2 ** 31 + 5
out = {}
cell = H.Cell.find("moe-gqa.decode-long", root=root)
out["cell"] = [cell.config["family"], [m["name"] for m in cell.per_layer],
               sorted(m["name"] for m in cell.end_to_end)]
cfg = tiny.config("moe-gqa")
fam = chipbench.family(cfg)
weights = W.draw_model(cfg, seed)
out["groups"] = {k: int(jax.tree_util.tree_leaves(v)[0].shape[0])
                 for k, v in weights.items() if k.startswith("group")}
draw = W.layer_drawer(cfg, seed)
same = []
for g, (first, count) in enumerate(fam.groups(cfg)):
    for j in range(count):
        one = jax.tree_util.tree_map(lambda a: a[j], weights[f"group{g}"])
        same.append(all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
            lambda a, b: bool(np.array_equal(a, b)), one, draw(first + j)))))
out["layer_draw_same"] = same
model = build_model(P.model_config(cfg))
out["program_groups"] = [n for n, _ in layer_groups(model.cfg)]
params = P.program_params(cfg, weights, model)
ffn = params["group1"]["ffn"]
out["params_are_the_weights"] = [
    ffn["experts"]["w_gate"].qweight is weights["group1"]["experts"][
        "w_gate"]["qweight"],
    ffn["router"]["w"] is weights["group1"]["router"],
    params["group0"]["ffn"]["w_down"]["w"].scales is weights["group0"][
        "w_down"]["scales"]]
step = {"batch": 4, "width": 1, "rows": [[9, 1, True], [3, 1, True]]}
calls = fam.step_calls(cfg, step)
out["step_calls"] = {k: len(v) for k, v in calls.items()}
classes = TR.classes(fam)
out["classes"] = sorted(classes)
events = [{"kind": "host", "name": "engine.step", "t": 0.0, "d": 1e6},
          {"kind": "device", "name": "%expert_gptq.3 custom-call bf16[8,128]",
           "t": 1e5, "d": 5e5}]
trace = TR.reduce(events, classes)
out["kernels"] = trace["kernels"]
run = H.Run(config=cfg, traffic={}, seconds=1.0, records=[],
            window=(0.0, 1.0), setup_s=0.0, counters_open={},
            counters_close={}, peaks=H.device_peaks("TPU v5 lite"), chips=1,
            trace=trace, steps=[step])
out["reader"] = H.reader("expert_gptq_roofline.decode")(run)
eng = types.SimpleNamespace(
    step=lambda: None, batch_rows=4, _step_width=lambda n: 1,
    pc=types.SimpleNamespace(lengths={}), _admit=None, _fused=None,
    _end_step=None, sched=types.SimpleNamespace(
        active={}, plan_chunks=lambda budget, reserve=1: {}))
rec = H.StepRecorder(eng, "unused", getattr(fam, "describe", None))
rec.state = "tracing"
eng.sched.plan_chunks(8)
out["recorded"] = rec.steps
print(json.dumps(out))
'''


def _digests(top) -> dict:
    out = {}
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_family_enters_as_new_files(tmp_path):
    here = tmp_path / BENCH_REL
    shutil.copytree(H.BENCH_DIR, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    before = _digests(tmp_path)
    added = []
    for d, _, files in os.walk(NEW):
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), NEW)
            if rel == "entries.json":
                continue
            assert not (here / rel).exists(), rel        # new files only
            os.makedirs((here / rel).parent, exist_ok=True)
            shutil.copy(os.path.join(NEW, rel), here / rel)
            added.append(rel)
    entries = H.load_json(NEW, "entries.json")
    bench = H.load_json(tmp_path, "BENCHMARK.json")
    for key in ("configs", "workloads", "per_layer"):
        bench[key] += entries[key]
    for m in bench["end_to_end"]:
        if m["name"] in entries["end_to_end_workloads"]:
            m["workloads"] += entries["end_to_end_workloads"][m["name"]]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONDONTWRITEBYTECODE="1",
               PYTHONPATH=os.pathsep.join([str(here),
                                           os.path.join(ROOT, "src")]))
    proc = subprocess.run([sys.executable, "-c", SCRIPT, str(tmp_path)],
                          cwd=here, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])

    assert out["cell"] == ["moe_gqa", ["expert_gptq_roofline.decode"],
                           ["itl_p95_ms", "output_tok_s", "setup_s"]]
    assert out["groups"] == {"group0": 1, "group1": 2}
    assert out["layer_draw_same"] == [True, True, True]
    assert out["program_groups"] == [1, 2]
    assert out["params_are_the_weights"] == [True, True, True]
    assert out["step_calls"] == {"gptq": 7 + 2 * 7, "expert_gptq": 2 * 4 * 3,
                                 "paged_attention": 3}
    assert "expert_gptq" in out["classes"] and "gptq" in out["classes"]
    assert out["kernels"]["expert_gptq"] > 0
    assert 0 < out["reader"] < 100
    assert out["recorded"] == [{"batch": 4, "width": 1, "rows": [],
                                "engine_rows": 4}]

    after = _digests(tmp_path)
    changed = {p for p in before if before[p] != after.get(p)}
    assert changed == {"BENCHMARK.json"}
    assert set(after) - set(before) == {os.path.join(BENCH_REL, a)
                                        for a in added}
    old = H.load_json(ROOT, "BENCHMARK.json")
    new = H.load_json(tmp_path, "BENCHMARK.json")
    for key in ("configs", "workloads", "per_layer"):      # entries added
        assert new[key][:len(old[key])] == old[key]
    for a, b in zip(old["end_to_end"], new["end_to_end"]):
        cells = a.get("workloads", [])
        assert b.get("workloads", [])[:len(cells)] == cells
        assert {**b, "workloads": cells} == {**a, "workloads": cells}
