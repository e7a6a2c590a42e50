"""One general generator of request traffic, driven by a traffic file.

A traffic mix is data (``benchmarks/traffic/<name>.json``): lengths, rates,
sharing. This file turns such a mix and a seed into a schedule of requests.
It never reads the wall clock, and the program sees only the requests.

Every seed gets the same work in another order: lengths are the quantiles
of the stated distribution at ``(i + 0.5) / n`` and the gaps between the
arrivals of an open loop are the quantiles of the exponential
distribution, each permuted by the seed, and token ids come from the seed.
Two seeds differ by which request arrives when, after which gap, and by
token ids; not by how much work was drawn nor by how many gaps were short.
(Copied in spirit from ``loadgen/traces.py``'s seeded, clock-free
generators; those draw lengths and gaps independently, which makes a short
window's work swing from seed to seed.)

Mix keys (``kind: "requests"``):

- ``arrivals``: ``{"process": "poisson", "rate_per_s": r}`` (open loop: a
  request is due at its time whether or not earlier ones finished) or
  ``{"process": "closed", "clients": c}`` (each client sends its next
  request when its last completed).
- ``prompt``: ``{"shared_prefix_tokens": p, "body": <dist>}``: every
  prompt is the same ``p`` tokens followed by a body of its own.
- ``output``: ``<dist>`` of ``max_new_tokens``.
- ``<dist>``: ``{"dist": "pareto", "min": a, "max": b, "alpha": k}``
  (Pareto tail truncated to ``[a, b]``), ``{"dist": "uniform", "min": a,
  "max": b}`` or ``{"dist": "fixed", "value": v}``.
"""

from dataclasses import dataclass
from typing import Dict, List

import numpy as np

from zkbench.weights import seed32


@dataclass
class Request:
    index: int
    due_s: float  # open loop: seconds after the window opens; closed: 0
    client: int  # closed loop: the client that sends it; open: -1
    prompt: np.ndarray  # int32 token ids
    max_new_tokens: int


def dist_quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` whole-number lengths: the distribution's quantiles at
    ``(i + 0.5) / n``, ascending."""
    if n <= 0:
        return np.zeros((0,), np.int64)
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        x = np.full((n,), float(dist["value"]))
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    elif kind == "pareto":
        lo, hi, alpha = float(dist["min"]), float(dist["max"]), float(dist["alpha"])
        if not 0 < lo <= hi or alpha <= 0:
            raise ValueError(f"bad pareto {dist}")
        # F(x) = (1 - (lo/x)^a) / (1 - (lo/hi)^a) on [lo, hi]
        tail = 1.0 - (lo / hi) ** alpha
        x = lo / (1.0 - u * tail) ** (1.0 / alpha)
    else:
        raise ValueError(f"unknown dist {kind!r}")
    return np.rint(x).astype(np.int64)


def exponential_gaps(n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps of mean 1 (quantiles)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)


def _tokens(rng: np.random.Generator, lengths: np.ndarray, vocab: int):
    flat = rng.integers(0, vocab, size=int(lengths.sum()), dtype=np.int32)
    return np.split(flat, np.cumsum(lengths)[:-1]) if len(lengths) else []


def _assemble(prefix, bodies):
    return [np.concatenate([prefix, b]).astype(np.int32) for b in bodies]


def open_loop(mix: Dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """Poisson-like arrivals at the mix's fixed rate over ``seconds``: the
    count is ``round(rate * seconds)`` and the gaps are the same set for
    every seed, in the seed's order."""
    rng = np.random.default_rng([seed32(seed), 1])
    n = max(1, int(round(float(mix["arrivals"]["rate_per_s"]) * seconds)))
    gaps = rng.permutation(exponential_gaps(n + 1))
    due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
    body = rng.permutation(dist_quantiles(mix["prompt"]["body"], n))
    new = rng.permutation(dist_quantiles(mix["output"], n))
    prefix = shared_prefix(mix, seed, vocab)
    prompts = _assemble(prefix, _tokens(rng, body, vocab))
    return [
        Request(i, float(due[i]), -1, prompts[i], int(new[i]))
        for i in range(n)
    ]


def closed_loop(mix: Dict, seed: int, rounds: int, vocab: int) -> List[List[Request]]:
    """Per client, the requests it sends one after another. Each round (one
    request of every client) holds the same lengths, permuted."""
    rng = np.random.default_rng([seed32(seed), 2])
    clients = int(mix["arrivals"]["clients"])
    prefix = shared_prefix(mix, seed, vocab)
    per_client: List[List[Request]] = [[] for _ in range(clients)]
    index = 0
    for _ in range(rounds):
        body = rng.permutation(dist_quantiles(mix["prompt"]["body"], clients))
        new = rng.permutation(dist_quantiles(mix["output"], clients))
        prompts = _assemble(prefix, _tokens(rng, body, vocab))
        for c in range(clients):
            per_client[c].append(
                Request(index, 0.0, c, prompts[c], int(new[c]))
            )
            index += 1
    return per_client


def shared_prefix(mix: Dict, seed: int, vocab: int) -> np.ndarray:
    n = int(mix["prompt"].get("shared_prefix_tokens", 0))
    rng = np.random.default_rng([seed32(seed), 3])
    return rng.integers(0, vocab, size=n, dtype=np.int32)


def warmup_requests(mix: Dict, seed: int, vocab: int) -> List[Request]:
    """A few requests of the mix's own kind, sent before the window so
    that every program has run once and the shared prefix is cached as it
    is in a server that has been up for a while: the shortest and the
    longest body, and one in between."""
    rng = np.random.default_rng([seed32(seed), 4])
    n = int(mix.get("warmup_requests", 3))
    body = dist_quantiles(mix["prompt"]["body"], max(n, 1))
    pick = np.unique(np.linspace(0, len(body) - 1, n).astype(int))
    body = body[pick]
    new = np.full(len(body), int(mix.get("warmup_new_tokens", 8)))
    prompts = _assemble(
        shared_prefix(mix, seed, vocab), _tokens(rng, body, vocab)
    )
    return [
        Request(-1 - i, 0.0, -1, prompts[i], int(new[i]))
        for i in range(len(body))
    ]
