"""A test family: GQA attention over a leading dense layer and then
layers of routed experts (top-k) beside shared experts, the layout of
DeepSeek-style MoE models without latent attention.  It enters the
harness as new files only (``test_chipbench_family.py``): this module, its
configuration, a cell, a kernel class (``KERNELS``) and a metric reader.

Group 0 is the dense family's layer; group 1 keeps its attention and
norms and replaces the MLP with ``router`` (d, E) bf16, ``experts``
(E stacked int4 ``w_gate``/``w_up``/``w_down``) and ``shared`` (one
SwiGLU MLP of ``moe_intermediate_size * n_shared_experts``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import chipbench
from chipbench import weights as W
from chipbench import work

DENSE = chipbench.load("families", "dense_gqa")
TINY = dict(DENSE.TINY, num_hidden_layers=3, moe_intermediate_size=128,
            n_routed_experts=4, num_experts_per_tok=2, n_shared_experts=1)
KERNELS = {"expert_gptq": [r"^%expert_gptq\b"]}
_MLP = ("w_gate", "w_up", "w_down")
_NORMS = {"norm1": "attn_norm", "norm2": "mlp_norm"}


def model_fields(cfg: dict) -> dict:
    return dict(DENSE.model_fields(cfg), family="moe",
                num_experts=cfg["n_routed_experts"],
                num_experts_per_tok=cfg["num_experts_per_tok"],
                num_shared_experts=cfg["n_shared_experts"],
                moe_d_ff=cfg["moe_intermediate_size"],
                first_dense_layers=cfg["first_k_dense_replace"])


def groups(cfg: dict) -> list[tuple[int, int]]:
    first = cfg["first_k_dense_replace"]
    return [(0, first), (first, cfg["num_hidden_layers"] - first)]


def _mlp(cfg: dict, ff: int) -> dict:
    d = cfg["hidden_size"]
    return {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def draw_layer(cfg: dict, key, layer, group: int):
    out = DENSE.draw_layer(cfg, key, layer, group)
    if group == 0:
        return out
    g = cfg["quantization"]["group_size"]
    for name in _MLP:
        del out[name]

    def int4(name, k, n):
        return W.draw_int4(jax.random.fold_in(W.leaf_key(key, name), layer),
                           k, n, g)

    experts = {}
    for name, (k, n) in _mlp(cfg, cfg["moe_intermediate_size"]).items():
        kn = jax.random.fold_in(W.leaf_key(key, f"experts.{name}"), layer)
        experts[name] = jax.vmap(lambda e: W.draw_int4(
            jax.random.fold_in(kn, e), k, n, g))(
                jnp.arange(cfg["n_routed_experts"]))
    ff = cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    out["experts"] = experts
    out["shared"] = {name: int4(f"shared.{name}", k, n)
                     for name, (k, n) in _mlp(cfg, ff).items()}
    out["router"] = (cfg["hidden_size"] ** -0.5 * jax.random.normal(
        jax.random.fold_in(W.leaf_key(key, "router"), layer),
        (cfg["hidden_size"], cfg["n_routed_experts"]), jnp.float32)
    ).astype(jnp.bfloat16)
    return out


def draw_top(cfg: dict, key) -> dict:
    return DENSE.draw_top(cfg, key)


def program_leaf(path: list[str], weights: dict):
    if not path[0].startswith("group"):
        return DENSE.program_leaf(path, weights)
    layers = weights[path[0]]
    if path[1] in _NORMS:
        return layers[_NORMS[path[1]]][path[2]]
    if path[1] == "attn" or path[:2] == ["group0", "ffn"]:
        return layers.get(path[2])
    if path[2] in ("experts", "shared"):
        return layers[path[2]][path[3]]
    if path[2] == "router":
        return layers["router"]
    return None


def step_calls(cfg: dict, step: dict) -> dict[str, list]:
    """The dense family's calls for every layer, the MLP's replaced in
    the expert layers by the shared experts' (class ``gptq``) and, per
    routed expert, a call over its share of the rows (``expert_gptq``)."""
    m = step["batch"] * step["width"]
    g = cfg["quantization"]["group_size"]
    first = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - first
    calls = DENSE.step_calls({**cfg, "num_hidden_layers": 1}, step)
    dense_layer = calls.pop("gptq")
    attn = dense_layer[:4]
    ff = cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    shared = [work.gptq_call(m, k, n, g) for k, n in _mlp(cfg, ff).values()]
    e = cfg["n_routed_experts"]
    rows = max(1, m * cfg["num_experts_per_tok"] // e)
    expert = [work.gptq_call(rows, k, n, g)
              for k, n in _mlp(cfg, cfg["moe_intermediate_size"]).values()]
    out = {k: v * cfg["num_hidden_layers"] for k, v in calls.items()}
    out["gptq"] = dense_layer * first + (attn + shared) * moe
    out["expert_gptq"] = expert * e * moe
    return out


def useful_flops(cfg: dict, step: dict) -> float:
    return sum(f for calls in step_calls(cfg, step).values()
               for f, _ in calls)


def describe(engine, record: dict) -> None:
    """Adds the rows the engine holds, as an expert family would add the
    experts a step touched."""
    record["engine_rows"] = engine.batch_rows
