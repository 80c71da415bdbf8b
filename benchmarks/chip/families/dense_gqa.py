"""Dense GQA decoder (qwen3-4b, nemotron-4-15b): one group of layers, each
GQA attention (optional q/k RMSNorm) and a SwiGLU or squared-ReLU MLP."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench import work

TINY = dict(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, intermediate_size=256,
            vocab_size=4096)

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
_NORMS = {"norm1": "attn_norm", "norm2": "mlp_norm"}


def model_fields(cfg: dict) -> dict:
    return {**{f: cfg[k] for k, f in _FIELDS.items()},
            "act": _ACT[cfg["hidden_act"]]}


def groups(cfg: dict) -> list[tuple[int, int]]:
    return [(0, cfg["num_hidden_layers"])]


def projections(cfg: dict) -> dict[str, tuple[int, int]]:
    """(K, N) of every int4 projection of one layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv, ff = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["intermediate_size"])
    shapes = {"wq": (d, h * hd), "wk": (d, hkv * hd), "wv": (d, hkv * hd),
              "wo": (h * hd, d)}
    if gated(cfg):
        shapes["w_gate"] = (d, ff)
    shapes["w_up"] = (d, ff)
    shapes["w_down"] = (ff, d)
    return shapes


def gated(cfg: dict) -> bool:
    """SwiGLU (gate and up) or a plain activation on up alone."""
    act = cfg["hidden_act"]
    if act not in ("silu", "relu2"):
        raise ValueError(f"unknown hidden_act {act!r}")
    return act == "silu"


def layernorm(cfg: dict) -> bool:
    kind = cfg["norm_type"]
    if kind not in ("rmsnorm", "layernorm"):
        raise ValueError(f"unknown norm_type {kind!r}")
    return kind == "layernorm"


def draw_layer(cfg: dict, key, layer, group: int):
    """One layer's weights (``layer`` may be traced; one group)."""
    group_size = cfg["quantization"]["group_size"]
    ln = layernorm(cfg)
    out = {name: W.draw_norm(jax.random.fold_in(W.leaf_key(key, name),
                                                layer),
                             cfg["hidden_size"], ln)
           for name in ("attn_norm", "mlp_norm")}
    if cfg["qk_norm"]:
        for name in ("q_norm", "k_norm"):
            k = jax.random.fold_in(W.leaf_key(key, name), layer)
            out[name] = jax.random.uniform(
                k, (cfg["head_dim"],), jnp.float32, 0.9, 1.1
            ).astype(jnp.bfloat16)
    for name, (k, n) in projections(cfg).items():
        out[name] = W.draw_int4(
            jax.random.fold_in(W.leaf_key(key, name), layer), k, n,
            group_size)
    return out


def draw_top(cfg: dict, key) -> dict:
    """Embedding, final norm and (untied) output head."""
    v, d = cfg["vocab_size"], cfg["hidden_size"]
    out = {"embed": (0.02 * jax.random.normal(
               W.leaf_key(key, "embed"), (v, d), jnp.bfloat16)
           ).astype(jnp.bfloat16),
           "final_norm": W.draw_norm(W.leaf_key(key, "final_norm"), d,
                                     layernorm(cfg))}
    if not cfg["tie_word_embeddings"]:
        out["head"] = (d ** -0.5 * jax.random.normal(
            W.leaf_key(key, "head"), (d, v), jnp.bfloat16)
        ).astype(jnp.bfloat16)
    return out


def program_leaf(path: list[str], weights: dict):
    """The benchmark array (an int4 dict for a GPTQ linear) behind the
    program parameter ``path``; None for one it has not."""
    if path[0] == "group0" and path[1] in _NORMS:
        return weights["group0"][_NORMS[path[1]]][path[2]]
    if path[0] == "group0" and path[1] in ("attn", "ffn"):
        return weights["group0"].get(path[2])   # wq ... w_down, q/k_norm
    if path[0] == "final_norm":
        return weights["final_norm"][path[1]]
    if tuple(path) in (("embed", "embedding"), ("head", "w")):
        return weights[path[0]]
    return None


def step_calls(cfg: dict, step: dict) -> dict[str, list]:
    """Each projection of each layer at M = batch x width rows, as the
    fused step runs it, and one paged attention call per layer (the decode
    kernel at width 1, the prefill kernel above)."""
    m = step["batch"] * step["width"]
    g = cfg["quantization"]["group_size"]
    layers = cfg["num_hidden_layers"]
    gptq = [work.gptq_call(m, k, n, g) for k, n in projections(cfg).values()]
    attn = work.attention_rows(step["rows"], cfg["num_attention_heads"],
                               cfg["num_key_value_heads"], cfg["head_dim"])
    kernel = "paged_prefill" if step["width"] > 1 else "paged_attention"
    return {"gptq": gptq * layers, kernel: [attn] * layers}


def useful_flops(cfg: dict, step: dict) -> float:
    """Model operations of the tokens a step really computes: projections
    once per real token, attention at each token's real context, and the
    output head once per emitting row.  Padding rows and widths do not
    count."""
    tokens = sum(n for _, n, _ in step["rows"])
    per_token = 2.0 * sum(k * n for k, n in projections(cfg).values())
    attn, _ = work.attention_rows(step["rows"], cfg["num_attention_heads"],
                                  cfg["num_key_value_heads"], cfg["head_dim"])
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * sum(
        1 for _, _, emit in step["rows"] if emit)
    return cfg["num_hidden_layers"] * (tokens * per_token + attn) + head
