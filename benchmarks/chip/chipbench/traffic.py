"""Request plans drawn from a traffic file and a seed (numpy and the
standard library only: the load generator imports this, never JAX).

The distributions are ``repro.data.pipeline.sharegpt_stream``'s: lognormal
lengths (``exp(N(log median, sigma))``, clipped) and Poisson arrivals
(exponential gaps), plus uniform lengths.  Unlike that stream, the sizes
are stratified: ``n`` draws are the distribution's quantiles at
``(i + 0.5) / n``.

How a closed loop deals them.  Client ``r`` (the ``r``-th to send its
first request; the load generator starts them in that order):

* first request: where the file gives ``first_prompt`` (``first_output``),
  its prompt (answer) length is one of that distribution's strata over
  the clients, each client a different one;
* later requests: its prompt lengths are the strata of ``prompt`` over
  its later requests, and so are its answer lengths (of ``output``, over
  all ``requests_per_client`` where no ``first_output`` is given).  Every
  client receives the same multiset, each in an order of its own;
* those orders come from fixed random streams, one for prompts and one
  for answers, so a prompt's rank says nothing of its answer's;
* the seed draws the token ids, and nothing else.

So the sizes, their order and the clients' order are the same on every
seed.  A closed loop's window holds a few rounds of each client, so an
order drawn from the seed changes the work in the window: with each
client's order drawn from the seed, ``nemotron-4-15b.doc-prefill`` read
109.5 to 136.4 prompt tokens/s over three seeds on one TPU v5e, where
two seeds of identical sizes read alike to 0.01%.  The window's work is
thus one fixed arrangement of the sizes.  An open loop's sizes are the
strata over all its requests, in an order drawn from the seed.

A traffic file is a JSON object:

* ``loop``: ``"closed"`` (``clients`` each send their next request when the
  previous one has finished, ``requests_per_client`` of them at most) or
  ``"open"`` (requests sent at Poisson times at ``rate_per_s``, from a lead
  of ``lead_s`` seconds before the window to its end);
* ``prompt`` and ``output``: ``{"dist": "lognormal", "median", "sigma",
  "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
* ``first_prompt``, ``first_output`` (closed loop, optional): length
  distributions of the clients' first prompts and answers, so that every
  seed prefills the same prompts before the window and completions are
  spread from its start;
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

# "prompt" and "output" deal a closed loop's sizes, with the seed 0
_STREAMS = {"order": 1, "tokens": 2, "gaps": 3, "prompt": 4, "output": 5}


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


def _requests(traffic: dict, seed: int, vocab: int, n: int) -> list[dict]:
    """``n`` requests whose sizes are the strata over ``n``, in an order
    drawn from the seed."""
    order = _rng(seed, "order")
    prompts = stratified(traffic["prompt"], n)[order.permutation(n)]
    outputs = stratified(traffic["output"], n)[order.permutation(n)]
    return _with_ids(seed, vocab, prompts, outputs)


def _with_ids(seed: int, vocab: int, prompts, outputs) -> list[dict]:
    tok = _rng(seed, "tokens")
    return [{"prompt": tok.integers(2, vocab, size=int(p)).tolist(),
             "max_tokens": int(o)} for p, o in zip(prompts, outputs)]


def _sizes(traffic: dict, key: str, clients: int,
           per_client: int) -> np.ndarray:
    """``(clients, per_client)`` lengths of ``traffic[key]``, each row in
    an order of its own from the fixed stream ``key``; column 0 the strata
    of ``first_<key>`` over the clients, where given."""
    deal = _rng(0, key)
    first = traffic.get(f"first_{key}")
    m = per_client - (first is not None)
    strata = stratified(traffic[key], m)
    later = np.stack([strata[deal.permutation(m)] for _ in range(clients)])
    if first is None:
        return later
    firsts = stratified(first, clients)[deal.permutation(clients)]
    return np.concatenate([firsts[:, None], later], axis=1)


def _closed(traffic: dict, seed: int, vocab: int) -> list[list[dict]]:
    c, n = traffic["clients"], traffic["requests_per_client"]
    prompts = _sizes(traffic, "prompt", c, n)
    outputs = _sizes(traffic, "output", c, n)
    reqs = _with_ids(seed, vocab, prompts.ravel(), outputs.ravel())
    return [reqs[i * n:(i + 1) * n] for i in range(c)]


def plan(traffic: dict, seed: int, vocab: int, seconds: float) -> dict:
    """``{"loop": "closed", "clients": [[request, ...], ...]}`` or
    ``{"loop": "open", "requests": [request, ...]}`` where a request is
    ``{"prompt": [ids], "max_tokens": n}`` and, in an open loop, ``"at"``:
    its send time in seconds from the start."""
    loop = traffic["loop"]
    if loop == "closed":
        return {"loop": loop, "clients": _closed(traffic, seed, vocab)}
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
