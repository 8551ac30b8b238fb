"""The one traffic generator: reads a mix's data file and the run's seed.

Every seed gets the same work in another order.  What a window offers is
drawn once from the mix's frozen ``pool_seed``; the run's ``--seed`` only
orders it.  Annealing demand (chunks to target) is a property of each
request's annealing seed, so seeds that drew their own requests would each
offer a different amount of work; a frozen pool keeps the work fixed and
leaves the seed to move it around.

A mix file ``chipbench/traffic/<name>.json`` names its ``kind``, the loop
``chipbench/loops/<kind>.py`` that sends it:

* ``closed_batch``: one sweep client in a closed loop.  The pool is
  ``pool_lists`` lists of ``list_size`` requests, round-robin over the
  configuration's instances, each with its instance's frozen
  ``target_cut``.  The client sends the pool's lists in an order drawn from
  the seed, a fresh order each time round, the next list when the previous
  returns.

Annealing seeds are drawn from [2**20, 2**31); the frozen targets were set
on seeds below 2**20, which the traffic never uses.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional

import numpy as np

SEED_LO, SEED_HI = 2 ** 20, 2 ** 31


@dataclasses.dataclass(frozen=True)
class Req:
    """One request as the traffic defines it, before the program sees it."""
    index: int
    instance: int              # index into the configuration's instances
    seed: int                  # annealing seed
    target: Optional[int]      # target cut; None runs the full budget


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def closed_pool(mix: dict, targets: List[int]) -> List[List[Req]]:
    """The frozen lists a closed-loop run draws from."""
    if mix["kind"] != "closed_batch":
        raise ValueError(f"not a closed_batch mix: {mix['kind']!r}")
    size, n_lists = int(mix["list_size"]), int(mix["pool_lists"])
    seeds = _rng(mix["pool_seed"], 0).integers(SEED_LO, SEED_HI,
                                               size=size * n_lists)
    return [[Req(i, i % len(targets), int(seeds[i]), targets[i % len(targets)])
             for i in range(k * size, (k + 1) * size)]
            for k in range(n_lists)]


def closed_lists(mix: dict, targets: List[int], seed: int) -> Iterator[List[Req]]:
    """Endless lists for a closed loop: the pool in orders from the seed."""
    pool = closed_pool(mix, targets)
    rng = _rng(seed, 0)
    while True:
        for k in rng.permutation(len(pool)):
            yield pool[k]


WARMUP_TARGET = 1


def warmup_list(mix: dict, targets: List[int]) -> List[Req]:
    """The same warm-up list in every run: the cell's list size and
    instances, fixed seeds below the traffic's range, and a target cut of
    ``WARMUP_TARGET`` that the first chunk reaches, so the list stops after
    one chunk."""
    return [Req(i, i % len(targets), 1000 + i, WARMUP_TARGET)
            for i in range(int(mix["list_size"]))]
