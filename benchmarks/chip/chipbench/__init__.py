"""Chip benchmark of the served GPTQ path: weights, traffic, trace reduction,
work counts and the output check, driven by ``benchmarks/chip/run.py``.

Nothing here is imported by the program under test.  Only ``program.py``
imports the program (``repro``); ``references/``, ``families/`` and the
modules here stay independent of it.

An architecture's code is its family, ``families/<cfg["family"]>.py``:
``model_fields(cfg)`` (``ModelConfig`` fields), ``groups(cfg)`` (``[(first
layer, count)]`` as the program groups its layers), ``draw_top(cfg, key)``,
``draw_layer(cfg, key, layer, group)``, ``program_leaf(path, weights)``,
``step_calls(cfg, step)`` (``{kernel class: [(flops, bytes)]}``),
``useful_flops(cfg, step)``, ``TINY`` (CPU test sizes) and, optionally,
``KERNELS`` and ``describe(engine, step record)``.
"""
from __future__ import annotations

import functools
import importlib.util
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.cache
def load(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``<bench_dir>/<kind>/<name>.py`` as a module, loaded once."""
    spec = importlib.util.spec_from_file_location(
        f"{kind}_{name}", os.path.join(bench_dir, kind, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(cfg: dict):
    """The family module that ``cfg`` names."""
    return load("families", cfg["family"])
