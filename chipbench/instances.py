"""Max-Cut instances made from a seed, kept with the benchmark.

Each configuration file names its instances by family and parameters:

* ``toroidal``: the rudy toroidal-grid family of G-set (G11-G13 are 800
  vertices, G81 is 100x200): a 2-D torus on ``rows x cols`` vertices, each
  vertex joined to its right and lower neighbour with wrap-around, weights
  drawn uniformly from {-1, +1}.
* ``complete``: the complete graph on ``n`` vertices with uniform {-1, +1}
  weights (the K2000 class of Inagaki et al., Science 354:603, 2016).

The generators draw weights as ``numpy.random.default_rng(seed).choice`` over
the edges in the order listed, so an instance is fixed by its parameters.
An :class:`Instance` holds plain arrays; :func:`to_program` hands the same
arrays to the service under test.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Instance:
    name: str
    n: int
    edges: np.ndarray    # (E, 2) int64, i < j not required
    weights: np.ndarray  # (E,) int64

    @property
    def w_total(self) -> int:
        return int(self.weights.sum())

    def cut(self, spins: np.ndarray) -> np.ndarray:
        """Cut of each row of ``spins`` (..., n) in {-1, +1}, from the edge list."""
        s = np.asarray(spins)
        i, j = self.edges[:, 0], self.edges[:, 1]
        return (s[..., i] != s[..., j]).astype(np.int64) @ self.weights


def toroidal(name: str, rows: int, cols: int, seed: int) -> Instance:
    r, c = np.meshgrid(np.arange(rows), np.arange(cols), indexing="ij")
    v = (r * cols + c).reshape(-1)
    right = (r * cols + (c + 1) % cols).reshape(-1)
    down = (((r + 1) % rows) * cols + c).reshape(-1)
    # Per vertex: the right edge, then the down edge.
    edges = np.stack([np.stack([v, right], 1), np.stack([v, down], 1)],
                     axis=1).reshape(-1, 2).astype(np.int64)
    weights = np.random.default_rng(seed).choice(
        np.array([-1, 1], dtype=np.int64), size=len(edges))
    return Instance(name, rows * cols, edges, weights)


def complete(name: str, n: int, seed: int) -> Instance:
    ii, jj = np.triu_indices(n, k=1)
    edges = np.stack([ii, jj], axis=1).astype(np.int64)
    weights = np.random.default_rng(seed).choice(
        np.array([-1, 1], dtype=np.int64), size=len(edges))
    return Instance(name, n, edges, weights)


FAMILIES = {"toroidal": toroidal, "complete": complete}


def make(spec: dict) -> Instance:
    """An instance from its entry in a configuration file."""
    params = {k: v for k, v in spec.items()
              if k not in ("family", "target_cut")}
    return FAMILIES[spec["family"]](**params)


def to_program(inst: Instance):
    """The same instance as the service's input type."""
    from repro.core.ising import MaxCutProblem

    return MaxCutProblem(n=inst.n, edges=inst.edges, weights=inst.weights,
                         name=inst.name)
