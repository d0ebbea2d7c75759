"""The general traffic generator: a traffic file's catalogue and popularity
turned into requests.

Every run sends the same schedule: the arrival gaps, request sizes and
models, and their order, come from the traffic file's ``order_seed``; the
run's seed draws only the prompts (and, elsewhere, the weights). Sizes follow
the catalogue's weights by exact counts (largest remainder), in blocks of
``block`` requests for a closed loop, so that any stretch of requests
carries the same work.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np


@dataclass
class Spec:
    """One request as the benchmark sends it."""
    index: int
    variant: int
    prompt: np.ndarray          # (prompt_len,) int32
    out_len: int
    # seconds after the window opens (open loop), then the perf_counter time
    # it was due (open loop) or sent (closed loop)
    due: Optional[float] = None
    # filled in as it runs (host clock, perf_counter seconds)
    gen_start: float = 0.0
    gen_end: float = 0.0
    tokens: Optional[np.ndarray] = None
    stats: object = None
    error: Optional[str] = None
    extra: dict = field(default_factory=dict)


def exact_counts(weights, n: int) -> List[int]:
    """Integer counts summing to ``n`` in the proportions of ``weights``."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[: n - counts.sum()]:
        counts[i] += 1
    return [int(c) for c in counts]


def popularity(traffic: dict) -> List[float]:
    n = traffic["variants"]
    pop = traffic.get("popularity", {"kind": "uniform"})
    if pop["kind"] == "zipf":
        return [1.0 / (i + 1) ** pop["s"] for i in range(n)]
    if pop["kind"] == "uniform":
        return [1.0] * n
    raise ValueError(f"unknown popularity {pop['kind']!r}")


def arrivals(traffic: dict):
    kind = traffic["arrivals"]["kind"]
    return importlib.import_module(f"bench.arrivals.{kind}")


def shapes(traffic: dict) -> List[tuple]:
    """Every (prompt_len, out_len) the traffic can send."""
    return [(int(p), int(o)) for p, o, _ in traffic["catalogue"]]


def _sizes(traffic: dict, n: int, rng: np.random.Generator) -> List[tuple]:
    cat = traffic["catalogue"]
    sizes = [(int(p), int(o)) for (p, o, _), c in
             zip(cat, exact_counts([w for *_, w in cat], n)) for _ in range(c)]
    return [sizes[i] for i in rng.permutation(n)]


def _variants(traffic: dict, n: int, rng: np.random.Generator) -> List[int]:
    vs = [v for v, c in enumerate(exact_counts(popularity(traffic), n))
          for _ in range(c)]
    return [vs[i] for i in rng.permutation(n)]


def _prompt(rng: np.random.Generator, length: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=length, dtype=np.int32)


def open_loop(traffic: dict, seconds: float, seed: int, vocab: int) -> List[Spec]:
    order = np.random.default_rng(traffic["order_seed"])
    due = arrivals(traffic).schedule(traffic["arrivals"], seconds, order)
    n = len(due)
    sizes, variants = _sizes(traffic, n, order), _variants(traffic, n, order)
    rng = np.random.default_rng(seed)
    return [Spec(i, variants[i], _prompt(rng, sizes[i][0], vocab), sizes[i][1],
                 due=due[i]) for i in range(n)]


class ClosedLoop:
    """Request streams of a closed loop: client ``c``'s ``k``-th request."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.traffic, self.vocab = traffic, vocab
        self.block = int(traffic.get("block", 10))
        self.n_clients = arrivals(traffic).clients(traffic["arrivals"])
        self.orders = [np.random.default_rng([traffic["order_seed"], c])
                       for c in range(self.n_clients)]
        self.rngs = [np.random.default_rng([seed, c])
                     for c in range(self.n_clients)]
        self.queues: List[list] = [[] for _ in range(self.n_clients)]
        self.count = 0

    def next(self, client: int) -> Spec:
        q, rng = self.queues[client], self.rngs[client]
        if not q:
            order = self.orders[client]
            q.extend(zip(_sizes(self.traffic, self.block, order),
                         _variants(self.traffic, self.block, order)))
        (plen, out), v = q.pop(0)
        spec = Spec(self.count, v, _prompt(rng, plen, self.vocab), out)
        self.count += 1
        return spec
