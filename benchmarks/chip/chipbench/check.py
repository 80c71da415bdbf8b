"""The output check: served greedy tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the served answers (finished, or cut when the run ended; the longest among
them, then others drawn from the seed) is run through the reference once,
teacher-forced on each prompt and its served tokens.  The number compared is the widest gap by which a served
token's reference logit lies below the reference's best logit at that
position.  A greedy server that computes what the reference computes reads
(near) zero; one that computes something else picks tokens the reference
ranks lower.

The control reads the same gap for the tokens that the reference itself
picks when computed at a lower precision (``act_quant``), at the same
positions of the same sequences.
"""
from __future__ import annotations

import time

import numpy as np

import chipbench
from chipbench import weights as W


def reference(name: str):
    """The reference module ``references/<name>.py``."""
    return chipbench.load("references", name)


def checkable(rec: dict, vocab: int) -> bool:
    """Served tokens the check can compare: every id in the vocabulary with
    one arrival time each, and either the whole answer (``max_tokens``
    ids, finished for length) or the part streamed before the load
    generator hung up at the end of the run."""
    toks = rec["tokens"]
    if not toks or len(toks) != len(rec["times"]) or not all(
            isinstance(t, int) and 0 <= t < vocab for t in toks):
        return False
    if rec["status"] == "ok":
        return len(toks) == rec["max_tokens"] and rec["finish"] == "length"
    return rec["status"] == "hung up" and len(toks) <= rec["max_tokens"]


def malformed(records: list[dict], vocab: int) -> int:
    """Answers that were served (finished, or cut at the end) but are not
    checkable: wrong length, ids outside the vocabulary."""
    return sum(1 for r in records if r["tokens"]
               and r["status"] in ("ok", "hung up")
               and not checkable(r, vocab))


def sample(records: list[dict], vocab: int, seed: int, tokens: int,
           max_requests: int) -> list[dict]:
    """The checkable request with the most served tokens, then others drawn
    from the seed until ``tokens`` served tokens or ``max_requests``
    requests."""
    done = [r for r in records if checkable(r, vocab)]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed) % 2 ** 64, 7]).permutation(
        len(rest))
    out, n = [longest], len(longest["tokens"])
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i]["tokens"])
    return out


def _bucket(n: int, least: int = 256) -> int:
    """The power of two at or above ``n`` (at least ``least``): the
    reference's shapes repeat from run to run, so its programs come from
    the compile cache."""
    return max(least, 1 << (n - 1).bit_length())


def _hidden_at(ref, cfg, top, layer_fn, seqs, rows, act_quant):
    """Final hidden states at every served token's prediction position,
    padded to a bucket of positions."""
    import jax.numpy as jnp
    ids = [r["prompt"] + r["tokens"][:-1] for r in seqs]
    toks = np.zeros((rows, _bucket(max(map(len, ids)))), np.int32)
    for i, row in enumerate(ids):
        toks[i, :len(row)] = row
    hidden = ref.final_hidden(cfg, top, layer_fn, toks, act_quant=act_quant)
    idx_r = np.concatenate([np.full(len(r["tokens"]), i)
                            for i, r in enumerate(seqs)])
    idx_p = np.concatenate([len(r["prompt"]) - 1 + np.arange(len(r["tokens"]))
                            for r in seqs])
    pad = _bucket(idx_r.size) - idx_r.size
    idx_r, idx_p = np.pad(idx_r, (0, pad)), np.pad(idx_p, (0, pad))
    return hidden[jnp.asarray(idx_r), jnp.asarray(idx_p)]


def logit_gaps(cfg: dict, seed: int, seqs: list[dict], *,
               controls: tuple = ()) -> dict:
    """Widest gap (reference best logit minus the reference logit of the
    served token) over every served token of ``seqs``; for each of
    ``controls`` (a lower precision of the reference), also the widest gap
    of the tokens the reference picks at that precision."""
    t0 = time.monotonic()
    ref = reference(cfg["reference"])
    rows = _bucket(len(seqs), least=1)
    top = W.top_drawer(cfg, seed)
    layer_fn = W.layer_drawer(cfg, seed)
    served = np.concatenate([r["tokens"] for r in seqs]).astype(np.int32)
    n = served.size
    h = _hidden_at(ref, cfg, top, layer_fn, seqs, rows, None)
    targets = np.pad(served, (0, h.shape[0] - n))
    best, arg, at = ref.logit_stats(cfg, top, h, targets)
    best, arg, at = best[:n], arg[:n], at[:n]
    gaps = np.asarray(best - at)
    t1 = time.monotonic()
    out = {"logit_gap": float(gaps.max()), "tokens": int(served.size),
           "requests": len(seqs),
           "argmax_agree": float(np.mean(np.asarray(arg) == served)),
           "reference_s": t1 - t0}
    for control in controls:
        hc = _hidden_at(ref, cfg, top, layer_fn, seqs, rows, control)
        _, carg, _ = ref.logit_stats(cfg, top, hc, targets)
        _, _, cat = ref.logit_stats(
            cfg, top, h, np.pad(np.asarray(carg[:n]), (0, h.shape[0] - n)))
        out[f"control_gap.{control}"] = float(np.asarray(best - cat[:n]).max())
    out["controls_s"] = time.monotonic() - t1
    return out
