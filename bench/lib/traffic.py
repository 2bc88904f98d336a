"""The one traffic generator: turns a traffic file's parameters and a
seed into the requests a cell sends.

A traffic file (``traffic/<name>.json``) is data only:

  ``loop``           ``"closed"`` (each client waits for its answer
                     before the next call) or ``"open"`` (requests are
                     due on a schedule, whatever the server does);
  ``entry``          ``"infer"`` (``Engine.infer``) or ``"queue"``
                     (``RequestQueue.submit``);
  ``queue``          keyword arguments of the ``RequestQueue``;
  ``clients``        closed loop: callers in flight, client ``c`` asks
                     about graph ``c mod n_graphs``;
  ``rate_per_s``     open loop: mean Poisson arrival rate;
  ``pool``, ``keep`` feature matrices per graph made at set-up, each
                     keeping a random ``keep`` share of the rows;
  ``warm_requests``  requests sent through the served path before the
                     window opens.

Every seed gives the same amount of work: an open schedule's gaps are
the same set of exponential quantiles, only their order (and which pool
entry each request carries) follows the seed.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Iterator, List, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    graph: int                   # index into the configuration's graphs
    pool_idx: int                # which feature matrix of that graph
    due_s: Optional[float] = None  # open loop: offset from window start


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose)."""
    return np.random.default_rng([int(seed), int(stream)])


def open_schedule(traffic: dict, seed: int, seconds: float, n_graphs: int,
                  stream: int = 0) -> List[Request]:
    """``round(rate * seconds)`` requests with Poisson gaps, in due
    order, the first due at offset 0."""
    rate = float(traffic["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    rng = rng_for(seed, stream)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= n / (rate * gaps.sum())          # mean gap exactly 1 / rate
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    graphs = rng.permutation(np.arange(n) % n_graphs)
    pool = traffic["pool"]
    order = rng.permutation(pool)
    return [Request(int(g), int(order[i % pool]), float(d))
            for i, (g, d) in enumerate(zip(graphs, due))]


def closed_streams(traffic: dict, seed: int, n_graphs: int,
                   stream: int = 0) -> List[Iterator[Request]]:
    """One endless request stream per client: client ``c`` cycles its
    graph's pool in a seeded order."""
    rng = rng_for(seed, stream)
    pool = traffic["pool"]
    streams = []
    for c in range(int(traffic["clients"])):
        order = [int(k) for k in rng.permutation(pool)]
        streams.append(_cycle(c % n_graphs, order))
    return streams


def _cycle(graph: int, order: list) -> Iterator[Request]:
    for k in itertools.cycle(order):
        yield Request(graph, k)


def max_batch(traffic: dict, n_graphs: int) -> int:
    """Largest group a queue can close under this traffic: its
    ``target_batch`` (default 8), or fewer where a closed loop keeps
    fewer requests of one graph in flight."""
    target = int(traffic.get("queue", {}).get("target_batch", 8))
    if traffic["loop"] == "closed":
        per_graph = -(-int(traffic["clients"]) // n_graphs)
        target = min(target, 1 << (per_graph - 1).bit_length())
    return target
