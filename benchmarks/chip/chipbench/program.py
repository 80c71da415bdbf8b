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

# configuration file key -> repro ModelConfig field
_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "rope_theta": "rope_theta",
    "norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "qk_norm": "qk_norm", "norm_type": "norm_type", "torch_dtype": "dtype",
}
_ACT = {"silu": "swiglu", "relu2": "sq_relu"}


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for a configuration file: its registry
    entry ``cfg["arch"]`` with every size the file gives."""
    from repro.configs import get_config
    fields = {f: cfg[k] for k, f in _FIELDS.items()}
    fields["act"] = _ACT[cfg["hidden_act"]]
    return dataclasses.replace(get_config(cfg["arch"]), **fields)


def _quantized(w: dict, group: int):
    from repro.core.gptq import QuantizedLinear
    k = w["qweight"].shape[-2] * 8
    n = w["scales"].shape[-1]
    return QuantizedLinear(qweight=w["qweight"], scales=w["scales"],
                           qzeros=w["qzeros"], perm=None, bias=None,
                           shape=(k, n), group_size=group)


def program_params(weights: dict, model, group: int):
    """The program's parameter tree holding the benchmark's arrays (no
    copies), checked leaf by leaf against the shapes and dtypes the program
    expects."""
    import jax
    from repro.core.gptq import GPTQConfig, QuantizedLinear
    from repro.core.quantize_model import abstract_quantized_params
    abstract = abstract_quantized_params(model.abstract_params(),
                                         GPTQConfig(group_size=group))
    layers = weights["layers"]
    norms = {"norm1": "attn_norm", "norm2": "mlp_norm"}

    def leaf(path, want):
        parts = [str(getattr(e, "key", getattr(e, "name", e))) for e in path]
        if isinstance(want, QuantizedLinear):
            got = _quantized(layers[parts[-2]], group)
            if (got.shape, got.group_size) != (want.shape, want.group_size):
                raise ValueError(f"{'/'.join(parts)}: {got.shape} g"
                                 f"{got.group_size}, program wants "
                                 f"{want.shape} g{want.group_size}")
            arrays = [(got.qweight, want.qweight), (got.scales, want.scales),
                      (got.qzeros, want.qzeros)]
        else:
            if parts == ["embed", "embedding"]:
                got = weights["embed"]
            elif parts == ["head", "w"]:
                got = weights["head"]
            elif parts[0] == "final_norm":
                got = weights["final_norm"][parts[1]]
            elif parts[0] == "group0" and parts[1] in norms:
                got = layers[norms[parts[1]]][parts[2]]
            elif parts[:2] == ["group0", "attn"] and parts[2].endswith("norm"):
                got = layers[parts[2]]
            else:
                raise ValueError(f"no benchmark weight for {'/'.join(parts)}")
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
    params = program_params(weights, model,
                            cfg["quantization"]["group_size"])
    engine = Engine(model, params, EngineConfig(
        batch_slots=eng_cfg["batch_slots"], max_len=eng_cfg["max_len"],
        eos_id=-1, cache="paged", page_size=eng_cfg["page_size"],
        page_pool_bytes=int(eng_cfg["page_pool_gib"] * 2 ** 30),
        max_step_tokens=eng_cfg["max_step_tokens"],
        seed=int(seed) % 2 ** 31))
    return model, engine
