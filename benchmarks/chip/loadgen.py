"""Open-loop request traffic from a traffic file, and its replay.

The mechanism follows the serve load generator the repository already has
(Poisson arrivals, discrete prompt-length buckets so each length compiles
once, output budgets ``min(1 + Geom(1/mean), cap)``, an open-loop replay
that submits each request at its scheduled time whether or not the engine
has kept up), kept here so that a change to the program cannot move the
yardstick.

Every seed gets the same multiset of prompt lengths, output budgets and
inter-arrival gaps, drawn once from the traffic file's ``shape_seed``; the
run's seed only shuffles their order and draws the prompt tokens. So runs
with different seeds do the same work in another order.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    tokens: np.ndarray
    max_new_tokens: int
    arrival_s: float


def count(traffic, seconds: float) -> int:
    """Requests scheduled in a window of ``seconds`` (with a margin that
    no Poisson draw at this rate crosses in practice)."""
    return int(traffic["rate"] * seconds * 1.3) + 16


def generate(traffic, seconds: float, seed_rng, vocab: int) -> List[Req]:
    n = count(traffic, seconds)
    shape = np.random.default_rng(traffic["shape_seed"])
    gaps = shape.exponential(1.0 / traffic["rate"], n)
    w = np.asarray(traffic["prompt_weights"], np.float64)
    lens = shape.choice(np.asarray(traffic["prompt_lens"]), size=n,
                        p=w / w.sum())
    budgets = np.minimum(1 + shape.geometric(1.0 / traffic["mean_new_tokens"],
                                             n), traffic["max_new_cap"])
    gaps = seed_rng.permutation(gaps)
    order = seed_rng.permutation(n)
    lens, budgets = lens[order], budgets[order]
    arrivals = np.cumsum(gaps)
    return [Req(rid=i,
                tokens=seed_rng.integers(0, vocab, int(lens[i]),
                                         dtype=np.int32),
                max_new_tokens=int(budgets[i]),
                arrival_s=float(arrivals[i])) for i in range(n)]


def replay(engine, requests, seconds: float, drain_s: float, on_tick):
    """Submit each request when the engine clock reaches its arrival; tick
    while there is work. Arrivals stop at ``seconds``; after that the
    engine drains what arrived for at most ``drain_s`` more. ``on_tick()``
    runs after every tick. Returns (submitted requests, lateness of each
    submission in seconds)."""
    from repro.serve.engine import Request
    pending = [r for r in requests if r.arrival_s < seconds]
    i, late = 0, []
    engine.start_clock()
    while True:
        now = engine.now()
        while i < len(pending) and pending[i].arrival_s <= now:
            r = pending[i]
            engine.submit(Request(rid=r.rid, tokens=r.tokens,
                                  max_new_tokens=r.max_new_tokens,
                                  arrival_s=r.arrival_s))
            late.append(now - r.arrival_s)
            i += 1
        if engine.has_work:
            engine.step()
            on_tick()
        elif i < len(pending):
            time.sleep(min(max(pending[i].arrival_s - engine.now(), 0.0),
                           0.005))
        if i >= len(pending) and (not engine.has_work or
                                  engine.now() >= seconds + drain_s):
            break
    return pending[:i], late
