"""Compile-only checks of the served path for a described TPU v5e chip at
qwen3-4b widths (K, N from ``configs/qwen3_4b.py``; int4 group 128; 8 KV
heads x 128; page 16, int8 pools page 32).

Nothing runs: the installed TPU compiler compiles each kernel (and one
layer of the fused engine step) for a v5e that is described, not attached,
and refuses what the chip would refuse — unaligned blocks, unsupported
casts, VMEM overflow.  Interpret-mode tests cannot see any of that.

The topology is described inside a module fixture, never at import: only
the worker process that runs these tests loads the TPU library.  The last
two tests are CPU ones: the kernel lane resolves to interpret mode on the
CPU, and the compile cache goes where the environment or the checkout says.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.gptq import GPTQConfig
from repro.core.quantize_model import abstract_quantized_params
from repro.kernels import gptq_gemv, gptq_matmul, lane, paged_attention
from repro.models import build_model, layers as L

QWEN3_4B = get_config("qwen3_4b")
D, HKV, H = QWEN3_4B.head_dim, QWEN3_4B.num_kv_heads, QWEN3_4B.num_heads
GROUP = 128
# (K, N) of every GPTQ projection: wq, wk/wv, wo, w_gate/w_up, w_down
PROJECTIONS = [
    (QWEN3_4B.d_model, H * D), (QWEN3_4B.d_model, HKV * D),
    (H * D, QWEN3_4B.d_model), (QWEN3_4B.d_model, QWEN3_4B.d_ff),
    (QWEN3_4B.d_ff, QWEN3_4B.d_model)]
POOLS = [(16, jnp.bfloat16), (32, jnp.int8)]     # (page_size, pool dtype)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    prior_log = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_on = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:                   # no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if prior_log is None:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _compile(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()    # a Mosaic kernel ran
    return compiled


def _gptq_operands(spec, k, n):
    g = k // GROUP
    return (spec((k // 8, n)), spec((g, n), jnp.bfloat16),
            spec((g, n // 8)))


@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_gptq_gemv_compiles(spec, k, n):
    qw, s, qz = _gptq_operands(spec, k, n)
    _compile(functools.partial(gptq_gemv.gptq_gemv, group_size=GROUP,
                               interpret=False),
             spec((8, k), jnp.bfloat16), qw, s, qz, None)


@pytest.mark.parametrize("k,n", PROJECTIONS)
def test_gptq_matmul_compiles(spec, k, n):
    qw, s, qz = _gptq_operands(spec, k, n)
    _compile(functools.partial(gptq_matmul.gptq_matmul, group_size=GROUP,
                               interpret=False),
             spec((512, k), jnp.bfloat16), qw, s, qz)


def _pools(spec, page_size, dtype, pages=257):
    pool = spec((HKV, pages, page_size, D), dtype)
    if dtype != jnp.int8:
        return pool, {}
    scales = spec((HKV, pages, page_size), jnp.float32)
    return pool, {"k_scales": scales, "v_scales": scales}


@pytest.mark.parametrize("page_size,dtype", POOLS)
def test_paged_attention_compiles(spec, page_size, dtype):
    pool, scales = _pools(spec, page_size, dtype)
    _compile(functools.partial(paged_attention.paged_attention,
                               interpret=False),
             spec((8, H, D), jnp.bfloat16), pool, pool, spec((8, 32)),
             spec((8,)), **scales)


@pytest.mark.parametrize("page_size,dtype", POOLS)
def test_paged_prefill_compiles(spec, page_size, dtype):
    pool, scales = _pools(spec, page_size, dtype)
    _compile(functools.partial(paged_attention.paged_prefill,
                               interpret=False),
             spec((2, 256, H, D), jnp.bfloat16), pool, pool, spec((2, 32)),
             spec((2,)), spec((2,)), **scales)


def _compile_fused_step(spec, width):
    """One layer of the engine's fused step program at qwen3-4b widths,
    int4 params and a bf16 page pool, for a batch of 8 rows x ``width``."""
    from repro.serving.engine import Engine
    cfg = dataclasses.replace(QWEN3_4B, num_layers=1)
    model = build_model(cfg)

    def place(tree):
        return jax.tree_util.tree_map(lambda s: spec(s.shape, s.dtype), tree)

    params = place(abstract_quantized_params(
        model.abstract_params(), GPTQConfig(group_size=GROUP)))
    cache = place(jax.eval_shape(
        lambda: model.init_paged_cache(64, 16, dtype=jnp.bfloat16)))
    kernels = L.KernelConfig(use_pallas=True, interpret=False)
    step = functools.partial(Engine._fused_step_impl, model, kernels,
                             all_greedy=True)
    b = 8
    return _compile(step, params, spec((b, width)), spec((b,)),
                    spec((b, 0)), spec((b,)), spec((b,), jnp.bool_), cache,
                    spec((b,)), spec((b, 16)), spec((b,), jnp.bool_),
                    None, None, None, None, None, None)


@pytest.mark.parametrize("width", [1, 128])
def test_fused_step_layer_compiles(spec, width):
    """One layer of the engine's fused step program — decode (width 1) and
    a prefill chunk — with int4 params and a bf16 page pool."""
    _compile_fused_step(spec, width)


def _kernel_names(compiled) -> set:
    """Names of the Mosaic kernel ops in a compiled program's HLO."""
    return {m.group(1) for m in re.finditer(
        r"%(\w+)\.\d+ = .* custom-call\(.*"
        r"custom_call_target=\"tpu_custom_call\"", compiled.as_text())}


@pytest.mark.parametrize("width,kernels", [
    (1, {"gptq_gemv", "paged_attention"}),
    (128, {"gptq_matmul", "paged_prefill"})])
def test_fused_step_kernel_op_names(spec, width, kernels):
    """Each Mosaic kernel is named by its ``pallas_call`` (``name=``), not
    by the function around it: the device ops a profile shows are
    ``%gptq_gemv.N`` / ``%gptq_matmul.N`` / ``%paged_attention.N`` /
    ``%paged_prefill.N``, the names the chip benchmark's kernel classes
    (``benchmarks/chip/kernels.json``) match."""
    assert _kernel_names(_compile_fused_step(spec, width)) == kernels
    # the served GPTQ kernel keeps its name inside a function of another
    k, n = PROJECTIONS[0]

    def outer(x, qw, s, qz):
        return gptq_matmul.fused_full_k(
            x, qw, s, qz, None, group_size=GROUP, bm=8, bn=256, bk=512,
            out_dtype=jnp.bfloat16, interpret=False, name="gptq_gemv")

    compiled = _compile(outer, spec((8, k), jnp.bfloat16),
                        *_gptq_operands(spec, k, n))
    assert _kernel_names(compiled) == {"gptq_gemv"}


def test_kernel_lane_resolves_from_backend(monkeypatch):
    """On the CPU the lane is the Pallas interpreter (GPTQ linears on the
    jnp reference unless asked); off the CPU it is compiled Pallas and
    interpret mode is refused."""
    assert jax.default_backend() == "cpu"
    cpu = L.KernelConfig().resolve()
    assert cpu.interpret is True and cpu.use_pallas is False
    assert L.KernelConfig(use_pallas=True).resolve().interpret is True
    monkeypatch.setattr(lane, "default_interpret", lambda: False)
    tpu = L.KernelConfig().resolve()
    assert tpu.interpret is False and tpu.use_pallas is True
    with pytest.raises(ValueError, match="interpret"):
        L.KernelConfig(interpret=True).resolve()


def test_compile_cache_placement(monkeypatch):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    without it the cache is the checkout's fixed ``.jax_cache``."""
    from repro.launch import compile_cache as CC
    prior = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(CC.ENV_VAR, "/elsewhere/cache")
        jax.config.update("jax_compilation_cache_dir", None)
        assert CC.setup_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(CC.ENV_VAR)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert CC.REPO_CACHE_DIR == os.path.join(root, ".jax_cache")
        assert CC.setup_compile_cache() == CC.REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == CC.REPO_CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", prior)
