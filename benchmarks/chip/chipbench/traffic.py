"""Request plans drawn from a traffic file and a seed (numpy and the
standard library only: the load generator imports this, never JAX).

The distributions are ``repro.data.pipeline.sharegpt_stream``'s: lognormal
lengths (``exp(N(log median, sigma))``, clipped) and Poisson arrivals
(exponential gaps), plus uniform lengths.  Unlike that stream, the sizes
are stratified: ``n`` draws are the distribution's quantiles at
``(i + 0.5) / n``, and the seed only permutes them and picks the token ids.
Every seed therefore offers the same work in another order, so runs with
different seeds differ by order and not by amount.

A traffic file is a JSON object:

* ``loop``: ``"closed"`` (``clients`` each send their next request when the
  previous one has finished, ``requests_per_client`` of them at most) or
  ``"open"`` (requests sent at Poisson times at ``rate_per_s``, from a lead
  of ``lead_s`` seconds before the window to its end);
* ``prompt`` and ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
* ``first_output`` (closed loop, optional): a length distribution of the
  clients' first answers, stratified over the clients like the others, so
  completions are spread from the start and every seed reserves the same
  cache at the opening;
* ``drain_s``: how long after the window closes the generator waits for
  answers still open before it hangs up;
* ``trace_s``: the length of the traced part of a ``--trace 1`` run;
* ``check``: how many served tokens the output check compares
  (``tokens``) and over at most how many requests (``max_requests``).
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_STREAMS = {"order": 1, "tokens": 2, "gaps": 3, "first": 4}


def _rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, _STREAMS[stream]])


def quantile(spec: dict, p: float) -> int:
    """The length at probability ``p`` of a length distribution."""
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "uniform":
        return int(min(hi, lo + math.floor(p * (hi - lo + 1))))
    if spec["dist"] == "lognormal":
        z = NormalDist().inv_cdf(p)
        v = math.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return int(min(hi, max(lo, round(v))))
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def stratified(spec: dict, n: int) -> np.ndarray:
    return np.array([quantile(spec, (i + 0.5) / n) for i in range(n)],
                    np.int64)


def _requests(traffic: dict, seed: int, vocab: int, n: int,
              first: int = 0) -> list[dict]:
    """``n`` requests; the first ``first`` of them answer with a
    ``first_output`` length, the rest with an ``output`` length."""
    order = _rng(seed, "order")
    prompts = stratified(traffic["prompt"], n)[order.permutation(n)]
    outputs = stratified(traffic["output"], n - first)[
        order.permutation(n - first)]
    if first:
        firsts = stratified(traffic["first_output"], first)[
            _rng(seed, "first").permutation(first)]
        outputs = np.concatenate([firsts, outputs])
    tok = _rng(seed, "tokens")
    return [{"prompt": tok.integers(2, vocab, size=int(p)).tolist(),
             "max_tokens": int(o)} for p, o in zip(prompts, outputs)]


def plan(traffic: dict, seed: int, vocab: int, seconds: float) -> dict:
    """``{"loop": "closed", "clients": [[request, ...], ...]}`` or
    ``{"loop": "open", "requests": [request, ...]}`` where a request is
    ``{"prompt": [ids], "max_tokens": n}`` and, in an open loop, ``"at"``:
    its send time in seconds from the start."""
    loop = traffic["loop"]
    if loop == "closed":
        c = traffic["clients"]
        reqs = _requests(traffic, seed, vocab,
                         c * traffic["requests_per_client"],
                         first=c if traffic.get("first_output") else 0)
        return {"loop": loop, "clients": [reqs[i::c] for i in range(c)]}
    if loop == "open":
        rate = traffic["rate_per_s"]
        span = traffic["lead_s"] + seconds
        n = max(1, round(span * rate))
        gaps = np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                         for i in range(n)])
        at = np.cumsum(gaps[_rng(seed, "gaps").permutation(n)])
        at *= span / at[-1] * (n - 0.5) / n       # the last one due inside
        reqs = _requests(traffic, seed, vocab, n)
        for r, t in zip(reqs, at):
            r["at"] = float(t)
        return {"loop": loop, "requests": reqs}
    raise ValueError(f"unknown loop {loop!r}")
