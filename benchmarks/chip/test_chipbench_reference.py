"""The plain reference, the benchmark's weights and the work counts, on the
CPU at a small size: every configuration file on disk draws the same layers
stacked and one by one, runs its registry widths, and gives prefill-then-
decode logits through the paged cache that agree with the float32
reference; the dense family's weights are the pinned ones, leaf for leaf;
and the work functions give the hand-counted numbers."""
from __future__ import annotations

import hashlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench
from chipbench import check as C
from chipbench import harness as H
from chipbench import program as P
from chipbench import tiny
from chipbench import weights as W
from chipbench import work

CONFIGS = sorted(f[:-len(".json")]
                 for f in os.listdir(os.path.join(H.BENCH_DIR, "configs"))
                 if f.endswith(".json"))
SEED = 2 ** 31 + 77          # wider than 32 signed bits, as seeds may be
PINS = H.load_json(H.BENCH_DIR, "fixtures", "dense-gqa-pins.json")


def _leaves(tree) -> dict:
    return {"/".join(str(e.key) for e in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name", CONFIGS)
def test_stacked_draw_matches_layer_draw(name):
    cfg = tiny.config(name)
    stacked = W.draw_model(cfg, SEED)
    layer = W.layer_drawer(cfg, SEED)
    top = W.top_drawer(cfg, SEED)
    for g, (first, count) in enumerate(chipbench.family(cfg).groups(cfg)):
        for j in range(count):
            one = jax.tree_util.tree_map(lambda a: a[j], stacked[f"group{g}"])
            jax.tree_util.tree_map(np.testing.assert_array_equal, one,
                                   layer(first + j))
    for key in top:
        jax.tree_util.tree_map(np.testing.assert_array_equal, stacked[key],
                               top[key])


@pytest.mark.parametrize("name", sorted(PINS["tiny"]))
def test_draw_is_the_pinned_draw(name):
    """Every leaf at the small sizes has the shape, dtype and bytes (sha256)
    pinned before the draw moved into the dense family."""
    got = _leaves(W.draw_model(tiny.config(name), PINS["seed"]))
    want = PINS["tiny"][name]
    assert sorted(got) == sorted(want)
    for path, leaf in got.items():
        a = np.asarray(leaf)
        assert [list(a.shape), str(a.dtype),
                hashlib.sha256(a.tobytes()).hexdigest()] == want[path], path


@pytest.mark.parametrize("name", sorted(PINS["published"]))
def test_published_shape_tree_is_pinned(name):
    """At the published widths (shapes only, nothing drawn) the tree of
    names, shapes and dtypes is the pinned one."""
    cfg = H.load_json(H.BENCH_DIR, "configs", f"{name}.json")
    got = _leaves(jax.eval_shape(lambda: W.draw_model(cfg, SEED)))
    assert {p: [list(s.shape), str(s.dtype)] for p, s in got.items()} == \
        PINS["published"][name]


def test_reference_dequant_reads_the_program_layout():
    """The reference's own int4 unpacking equals the program's
    ``dequantize`` on the same words."""
    from repro.core.gptq import dequantize
    cfg = tiny.config("qwen3-4b")
    ref = C.reference(cfg["reference"])
    lw = W.layer_drawer(cfg, SEED)(0)
    int4 = [w for w in lw.values() if isinstance(w, dict) and "qweight" in w]
    assert len(int4) == 7
    for w in int4:
        ql = P._quantized(w, cfg["quantization"]["group_size"])
        np.testing.assert_array_equal(np.asarray(ref.dequant(w)),
                                      np.asarray(dequantize(ql)))


@pytest.mark.parametrize("name", CONFIGS)
def test_committed_configs_run_the_registry_widths(name):
    """Nothing is reduced: the configuration file gives the program's
    registry entry unchanged."""
    from repro.configs import get_config
    cfg = H.load_json(H.BENCH_DIR, "configs", f"{name}.json")
    assert cfg["reduced"] == []
    assert P.model_config(cfg) == get_config(cfg["arch"])


def _reference_logits(cfg, tokens):
    """(S, V) float32 reference logits of one token row."""
    ref = C.reference(cfg["reference"])
    top = W.top_drawer(cfg, SEED)
    h = ref.final_hidden(cfg, top, W.layer_drawer(cfg, SEED),
                         np.asarray([tokens], np.int32))[0]
    head = top["embed"] if cfg["tie_word_embeddings"] else top["head"].T
    return np.asarray(jnp.matmul(h, head.astype(jnp.float32).T,
                                 precision=jax.lax.Precision.HIGHEST))


@pytest.mark.parametrize("name", CONFIGS)
def test_engine_prefill_then_decode_matches_reference(name):
    """A 41-token prompt prefilled in two chunks through the paged cache,
    then its last token decoded, through the engine's own forward; every
    logit row within a relative L2 of 5e-2 of the float32 reference.  The
    program computes in bf16: it rounds each dequantized weight to bf16
    before the product (0.9e-2 off here with float32 activations) and its
    activations and K/V to bf16 (1.4e-2 to 2.0e-2 off in all).  A wrong
    equation is off by tens of percent."""
    cfg = tiny.config(name)
    weights = W.draw_model(cfg, SEED)
    _, eng = P.build_engine(cfg, weights, SEED)
    rng = np.random.default_rng(5)
    prompt = rng.integers(2, cfg["vocab_size"], size=41).tolist()
    ps = eng.pc.page_size
    pages = -(-len(prompt) // ps)
    cache = eng.paged_cache_like(pages)
    tables = jnp.asarray(1 + np.arange(pages)[None], jnp.int32)
    fwd = eng.forward_probe()
    got, start = [], 0
    for chunk in (prompt[:24], prompt[24:40], prompt[40:]):
        n = len(chunk)
        logits, cache = fwd(eng.params, jnp.asarray([chunk], jnp.int32),
                            jnp.asarray([n], jnp.int32), cache,
                            jnp.asarray([start], jnp.int32), tables,
                            jnp.arange(n, dtype=jnp.int32)[None])
        got.append(np.asarray(logits[0]))
        start += n
    got = np.concatenate(got)
    want = _reference_logits(cfg, prompt)
    rel = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    assert rel.max() < 5e-2, rel.max()


def test_work_counts_by_hand():
    # one GPTQ call, M=8, K=256, N=128, group 128: 2 groups
    flops, nbytes = work.gptq_call(8, 256, 128, 128)
    assert flops == 2 * 8 * 256 * 128
    assert nbytes == (256 * 128 / 2          # int4 weights
                      + 2 * 128 * 2           # bf16 scales
                      + 2 * 128 / 2           # packed zeros
                      + 8 * 256 * 2 + 8 * 128 * 2)   # x in, y out
    # one paged attention call: a row with 3 cached tokens computes 2
    # more (they see 4 and 5 keys); 4 query heads over 2 kv heads of 8
    flops, nbytes = work.attention_rows([[3, 2, True]], 4, 2, 8)
    assert flops == 4 * 4 * 8 * (4 + 5)
    assert nbytes == 2 * 2 * 5 * 8 * 2 + 2 * 2 * 4 * 8 * 2


def test_useful_flops_skip_padding():
    cfg = tiny.config("qwen3-4b")
    fam = chipbench.family(cfg)
    proj = fam.projections(cfg)
    step = {"batch": 4, "width": 32, "rows": [[0, 20, True], [7, 1, True]]}
    per_token = 2 * sum(k * n for k, n in proj.values())
    attn, _ = work.attention_rows(step["rows"], 4, 2, 32)
    head = 2 * 128 * cfg["vocab_size"] * 2
    assert fam.useful_flops(cfg, step) == (
        2 * (21 * per_token + attn) + head)


@pytest.mark.parametrize("width,attention", [(1, "paged_attention"),
                                             (32, "paged_prefill")])
def test_step_calls_by_kernel_class(width, attention):
    """Each layer's projections at M = batch x width and one attention
    call per layer, under the class of the kernel that width runs."""
    cfg = tiny.config("qwen3-4b")
    fam = chipbench.family(cfg)
    step = {"batch": 4, "width": width, "rows": [[0, width, True],
                                                 [7, 1, True]]}
    calls = fam.step_calls(cfg, step)
    assert set(calls) == {"gptq", attention}
    one_layer = [work.gptq_call(4 * width, k, n, 128)
                 for k, n in fam.projections(cfg).values()]
    assert calls["gptq"] == one_layer * 2
    assert calls[attention] == [work.attention_rows(step["rows"], 4, 2,
                                                    32)] * 2
