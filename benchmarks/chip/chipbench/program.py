"""The system under test, built from a configuration file and the
benchmark's weights.  This is the one module here that imports the program.

The engine is ``repro.serving.engine.Engine`` built from an ``EngineConfig``
exactly as ``repro.launch.serve.build_engine`` builds it (paged bf16 KV,
int4 GPTQ projections, the backend's kernel lane), except that the weights
are the benchmark's (so the reference can redraw them) and the fused step's
token budget ``max_step_tokens`` is set, which ``build_engine`` cannot.
"""
from __future__ import annotations

import dataclasses

import chipbench


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: its registry
    entry ``cfg["arch"]`` with the family's fields for the file."""
    from repro.configs import get_config
    return dataclasses.replace(get_config(cfg["arch"]),
                               **chipbench.family(cfg).model_fields(cfg))


def _quantized(w: dict, group: int):
    from repro.core.gptq import QuantizedLinear
    k = w["qweight"].shape[-2] * 8
    n = w["scales"].shape[-1]
    return QuantizedLinear(qweight=w["qweight"], scales=w["scales"],
                           qzeros=w["qzeros"], perm=None, bias=None,
                           shape=(k, n), group_size=group)


def program_params(cfg: dict, weights: dict, model):
    """The program's parameter tree holding the benchmark's arrays (no
    copies; the family's ``program_leaf`` maps each path), checked leaf by
    leaf against the shapes, dtypes and group size the program expects."""
    import jax
    from repro.core.gptq import GPTQConfig, QuantizedLinear
    from repro.core.quantize_model import abstract_quantized_params
    group = cfg["quantization"]["group_size"]
    leaf_of = chipbench.family(cfg).program_leaf
    abstract = abstract_quantized_params(model.abstract_params(),
                                         GPTQConfig(group_size=group))

    def leaf(path, want):
        parts = [str(getattr(e, "key", getattr(e, "name", e))) for e in path]
        got = leaf_of(parts, weights)
        if got is None:
            raise ValueError(f"no benchmark weight for {'/'.join(parts)}")
        if isinstance(want, QuantizedLinear):
            got = _quantized(got, group)
            if (got.shape, got.group_size) != (want.shape, want.group_size):
                raise ValueError(f"{'/'.join(parts)}: {got.shape} g"
                                 f"{got.group_size}, program wants "
                                 f"{want.shape} g{want.group_size}")
            arrays = [(got.qweight, want.qweight), (got.scales, want.scales),
                      (got.qzeros, want.qzeros)]
        else:
            arrays = [(got, want)]
        for a, w in arrays:
            if a.shape != w.shape or a.dtype != w.dtype:
                raise ValueError(f"{'/'.join(parts)}: {a.shape} {a.dtype}, "
                                 f"program wants {w.shape} {w.dtype}")
        return got

    return jax.tree_util.tree_map_with_path(
        leaf, abstract, is_leaf=lambda x: isinstance(x, QuantizedLinear))


def build_engine(cfg: dict, weights: dict, seed: int):
    """``(model, engine)`` serving ``weights`` with the file's engine
    settings."""
    from repro.models import build_model
    from repro.serving.api import EngineConfig
    from repro.serving.engine import Engine
    model = build_model(model_config(cfg))
    eng_cfg = cfg["engine"]
    params = program_params(cfg, weights, model)
    engine = Engine(model, params, EngineConfig(
        batch_slots=eng_cfg["batch_slots"], max_len=eng_cfg["max_len"],
        eos_id=-1, cache="paged", page_size=eng_cfg["page_size"],
        page_pool_bytes=int(eng_cfg["page_pool_gib"] * 2 ** 30),
        max_step_tokens=eng_cfg["max_step_tokens"],
        seed=int(seed) % 2 ** 31))
    return model, engine
