"""Ising-model substrate for the HA-SSA/SSA/SA annealers.

The paper (Sec. II-A) represents a combinatorial optimization problem as an
Ising network: spins m_i ∈ {-1,+1}, biases h_i, couplings J_ij, Hamiltonian

    H = - Σ_i h_i m_i - 1/2 Σ_{i,j} J_ij m_i m_j                       (Eq. 1)

MAX-CUT maps onto it with J_ij = -w_ij, h_i = 0, so that
cut(m) = (Σ_{i<j} w_ij - Σ_{i<j} w_ij m_i m_j) / 2 = (W_sum + H) / 2 ... see
:func:`MaxCutProblem.cut_value` for the exact sign bookkeeping.

Representations
---------------
Problems in the paper's benchmark set are *sparse* (4- or 8-regular), while
the SSA literature also targets *dense* instances (K2000).  We keep both:

* **Padded adjacency** ``(nbr_idx, nbr_w)`` of shape ``(N, max_deg)`` — the
  TPU/CPU-friendly sparse form (pure gathers, no segment ops).  Padding
  entries point at the row's own vertex with weight 0, so they contribute
  nothing to local fields.
* **Dense matrix** ``J`` of shape ``(N, N)`` — fed to the MXU/Pallas path
  for dense problems and for batched-replica matmuls.

All coupling/bias arithmetic is integer-valued (the paper's hardware uses
4-bit integers; we use int32 carriers).  The dense matmul path runs in
float32 for MXU/CPU speed, which is exact for |field| < 2^24 — asserted at
model construction.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "IsingModel",
    "MaxCutProblem",
    "ising_energy",
    "local_fields_dense",
    "local_fields_popcount",
    "local_fields_sparse",
    "local_fields_tiled",
]

# Exactness bound for the float32 matmul path: fields must stay below 2^24.
_F32_EXACT_BOUND = 1 << 24


@dataclasses.dataclass(frozen=True)
class IsingModel:
    """An Ising model with both sparse (padded adjacency) and dense views.

    Attributes:
      n: number of spins.
      h: int32[n] biases.
      nbr_idx: int32[n, max_deg] neighbor indices (padded with self-index).
      nbr_w: int32[n, max_deg] coupling weights J_ij (padded with 0).
      name: human-readable instance name.
    """

    n: int
    h: np.ndarray
    nbr_idx: np.ndarray
    nbr_w: np.ndarray
    name: str = "ising"

    @property
    def max_degree(self) -> int:
        return int(self.nbr_idx.shape[1])

    # -- constructors -----------------------------------------------------
    @staticmethod
    def from_edges(
        n: int,
        edges: np.ndarray,
        weights: np.ndarray,
        h: Optional[np.ndarray] = None,
        name: str = "ising",
    ) -> "IsingModel":
        """Build from an undirected edge list (i, j, J_ij)."""
        w_in = np.asarray(weights)
        if np.issubdtype(w_in.dtype, np.floating) and not np.all(np.isfinite(w_in)):
            raise ValueError("weights must be finite (got NaN/inf)")
        h_in = None if h is None else np.asarray(h)
        if (
            h_in is not None
            and np.issubdtype(h_in.dtype, np.floating)
            and not np.all(np.isfinite(h_in))
        ):
            raise ValueError("h must be finite (got NaN/inf)")
        edges = np.asarray(edges, dtype=np.int64)
        weights = w_in.astype(np.int64)
        if edges.ndim != 2 or edges.shape[1] != 2:
            raise ValueError(f"edges must be (E,2), got {edges.shape}")
        if len(weights) != len(edges):
            raise ValueError("weights/edges length mismatch")
        if len(edges) and np.any(edges[:, 0] == edges[:, 1]):
            raise ValueError("self-loops are not Ising couplings")
        if len(edges) and (edges.min() < 0 or edges.max() >= n):
            raise ValueError(f"edge endpoints must lie in [0, {n})")
        # Vectorized bucketing: each undirected edge contributes two directed
        # half-edges.  Flattening (E,2) row-major interleaves them exactly in
        # the order a per-edge fill would visit (i before j within an edge),
        # so a stable sort by source vertex reproduces the sequential slot
        # assignment.  Half-edge k points at src[k ^ 1] with edge k >> 1's
        # weight.  Up to 2^16 spins the sources sort as uint16, which numpy
        # radix-sorts (same order, half the time of an int32 sort).
        src = edges.reshape(-1).astype(np.uint16 if n <= 1 << 16 else np.int32)
        deg = np.bincount(src, minlength=n)
        max_deg = int(deg.max()) if len(edges) else 1
        nbr_idx = np.tile(np.arange(n, dtype=np.int32)[:, None], (1, max_deg))
        nbr_w = np.zeros((n, max_deg), dtype=np.int32)
        if len(edges):
            order = np.argsort(src, kind="stable")
            # Sorted half-edge k takes slot k - starts[v] of its source v.
            starts = np.cumsum(deg) - deg
            flat = np.arange(len(order)) + np.repeat(
                np.arange(n, dtype=np.int64) * max_deg - starts, deg)
            nbr_idx.reshape(-1)[flat] = src[order ^ 1]
            nbr_w.reshape(-1)[flat] = weights.astype(np.int32)[order >> 1]
        hh = np.zeros(n, dtype=np.int64) if h_in is None else h_in.astype(np.int64)
        model = IsingModel(
            n=n, h=hh.astype(np.int32), nbr_idx=nbr_idx, nbr_w=nbr_w, name=name,
        )
        bound = int(np.abs(hh).max(initial=0) + np.abs(nbr_w).sum(axis=1).max(initial=0))
        if bound >= _F32_EXACT_BOUND:
            raise ValueError(
                f"field bound {bound} exceeds float32-exact range; "
                "use a smaller weight scale"
            )
        return model

    @staticmethod
    def from_dense(J: np.ndarray, h: Optional[np.ndarray] = None, name: str = "ising") -> "IsingModel":
        J = np.asarray(J)
        if np.issubdtype(J.dtype, np.floating) and not np.all(np.isfinite(J)):
            raise ValueError("J must be finite (got NaN/inf)")
        if not np.allclose(J, J.T):
            raise ValueError("J must be symmetric")
        if np.any(np.diag(J) != 0):
            raise ValueError("J must have zero diagonal")
        n = J.shape[0]
        ii, jj = np.nonzero(np.triu(J, k=1))
        edges = np.stack([ii, jj], axis=1)
        return IsingModel.from_edges(n, edges, J[ii, jj], h=h, name=name)

    # -- views -------------------------------------------------------------
    def dense_J(self) -> np.ndarray:
        """Materialize the symmetric dense coupling matrix (int32)."""
        J = np.zeros((self.n, self.n), dtype=np.int64)
        rows = np.repeat(np.arange(self.n), self.max_degree)
        cols = self.nbr_idx.reshape(-1)
        vals = self.nbr_w.reshape(-1)
        np.add.at(J, (rows, cols), vals)
        # padded entries are (i, i, 0): harmless.
        return J.astype(np.int32)

    def edge_list(self) -> Tuple[np.ndarray, np.ndarray]:
        """Recover the unique undirected edge list (E,2), weights (E,)."""
        J = self.dense_J()
        ii, jj = np.nonzero(np.triu(J, k=1))
        return np.stack([ii, jj], axis=1), J[ii, jj]

    def device_arrays(self):
        """jnp copies of (h, nbr_idx, nbr_w) for use inside jitted code."""
        return (
            jnp.asarray(self.h, jnp.int32),
            jnp.asarray(self.nbr_idx, jnp.int32),
            jnp.asarray(self.nbr_w, jnp.int32),
        )


# ---------------------------------------------------------------------------
# Local-field + energy math (pure functions usable under jit/vmap/scan).
# ---------------------------------------------------------------------------
def local_fields_sparse(m, h, nbr_idx, nbr_w):
    """h_i + Σ_j J_ij m_j with padded adjacency.  m: int32[..., N] in {-1,+1}."""
    neigh = jnp.take(m, nbr_idx, axis=-1)  # [..., N, D]
    return h + jnp.sum(nbr_w * neigh, axis=-1)


def local_fields_dense(m, h, J_f32):
    """Float32 MXU path: exact for |field| < 2^24 (asserted at construction)."""
    mf = m.astype(jnp.float32)
    return h + jnp.matmul(mf, J_f32).astype(jnp.int32)


def local_fields_tiled(m, h, nbr_idx, nbr_w, *, tile_n: int = 512,
                       double_buffer: bool = False):
    """Dense-matmul field without ever materializing the (N, N) coupling matrix.

    Streams J one ``(tile_n, N)`` row slab at a time: each scan step scatters
    the slab from the padded adjacency (integer-valued float32, exact) and
    contracts it against the full spin state on the MXU, so the only J-shaped
    buffer alive at any point is one slab — O(tile_n·N) instead of O(N²).
    This is what admits G77/G81-class instances (N = 10k–20k) on the dense
    datapath: at N=16384, one 512-row slab is 32 MB vs 1 GB for dense J.

    The contraction is rectangular: the row count comes from the adjacency
    (``nbr_idx (R, D)``, with ``h (R,)``) and the column count from the spin
    state ``m [..., N]`` — a spin-sharded device passes its own J row shard
    against the all-gathered full spins and gets back its shard's fields
    (DESIGN.md §11).  Unsharded callers have R == N and nothing changes.

    ``double_buffer=True`` software-pipelines the stream the way the
    dual-BRAM p-bit annealer pipelines its coupling reads (arXiv:2602.16143):
    the scan carry holds slab k while the body *first* scatters slab k+1 and
    only then contracts slab k — the slab build (gather/DMA-shaped work) for
    the next step carries no data dependence on the matmul, so the scheduler
    can overlap them.  Same slabs, same per-slab contraction: bit-identical.

    Bit-identical to :func:`local_fields_dense` on the same model (both are
    integer-valued f32 contractions below the 2^24 exactness bound, summation
    order immaterial) — property-tested.  ``m``: [..., N] spins in {-1,+1}.
    """
    n_rows = nbr_idx.shape[0]
    n_cols = m.shape[-1]
    tile_n = int(tile_n)
    nt = -(-n_rows // tile_n)
    pad = nt * tile_n - n_rows
    idx = jnp.pad(jnp.asarray(nbr_idx, jnp.int32), ((0, pad), (0, 0)))
    w = jnp.pad(jnp.asarray(nbr_w, jnp.int32), ((0, pad), (0, 0)))
    mf = m.astype(jnp.float32)
    rows = jnp.arange(tile_n)

    def make_slab(t):
        it = jax.lax.dynamic_slice_in_dim(idx, t * tile_n, tile_n)
        wt = jax.lax.dynamic_slice_in_dim(w, t * tile_n, tile_n)
        # slab = J[t·tile_n : (t+1)·tile_n, :], scattered on the fly.
        return jnp.zeros((tile_n, n_cols), jnp.float32).at[
            rows[:, None], it
        ].add(wt.astype(jnp.float32))

    if double_buffer:
        def one_slab(slab, t):
            # Prefetch t+1 *before* consuming slab t (dynamic_slice clamps,
            # so the dangling prefetch past the last slab is safe/unused).
            nxt = make_slab(t + 1)
            return nxt, jnp.matmul(mf, slab.T)

        _, cols = jax.lax.scan(one_slab, make_slab(0), jnp.arange(nt))
    else:
        def one_slab(_, t):
            return 0, jnp.matmul(mf, make_slab(t).T)

        _, cols = jax.lax.scan(one_slab, 0, jnp.arange(nt))  # (nt, ..., tile_n)
    field = jnp.moveaxis(cols, 0, -2).reshape(m.shape[:-1] + (nt * tile_n,))
    return h + field[..., :n_rows].astype(jnp.int32)


def _popcount_fields_block(m_words, sign, mags):
    """XNOR-popcount contraction of one row block, minus h/base terms.

    m_words: uint32[..., Nw] packed spins; sign: uint32[R, Nw];
    mags: uint32[n_bits, R, Nw].  Returns int32[..., R] equal to
    Σ_b 2^{b+1} · popcount(XNOR(m, sign_r) & mags[b, r]) per row r.
    """
    from repro.kernels.bitplane import popcount_u32

    # XNOR(a, b) = ~(a ^ b) = a ^ ~b; the AND with the magnitude mask
    # confines the contraction to real couplings (tail bits are 0 there).
    x = m_words[..., None, :] ^ ~sign  # [..., R, Nw]
    acc = jnp.sum(popcount_u32(x & mags[0]), axis=-1) << 1
    for b in range(1, mags.shape[0]):
        acc = acc + (jnp.sum(popcount_u32(x & mags[b]), axis=-1) << (b + 1))
    return acc


def local_fields_popcount(m_words, h, packed_j, *, tile_n: Optional[int] = None):
    """Bit-parallel field contraction on uint32 bitplanes (DESIGN.md §8).

    The paper's FPGA datapath computed in software: with J packed as a sign
    plane plus magnitude bitplanes (`kernels.bitplane.PackedJ`), the field

        field_i = h_i + Σ_j J_ij m_j
                = h_i + base_i + Σ_b 2^{b+1}·popcount(XNOR(m, sign_i) & mag_bi)

    is evaluated 32 spins per word op, entirely in uint32/int32 — no unpack
    to ±1 floats anywhere (jaxpr-asserted in tests/test_popcount.py), and
    exact-integer equal to :func:`local_fields_dense` for any integer J.

    ``m_words``: uint32[..., Nw] packed spins (`bitplane.pack_spins`); tail
    bits of the last word may hold anything — the magnitude masks kill them.
    ``tile_n``: row-tile size; None contracts all N rows in one block,
    an int streams (tile_n, Nw) row slabs through a scan so the broadcast
    XNOR buffer stays O(tile_n·Nw) — the G77/G81-class regime.
    """
    sign, mags, base = packed_j.sign, packed_j.mags, packed_j.base
    n = sign.shape[0]
    if tile_n is None or int(tile_n) >= n:
        return h + base + _popcount_fields_block(m_words, sign, mags)

    tile_n = int(tile_n)
    nt = -(-n // tile_n)
    pad = nt * tile_n - n
    sign_p = jnp.pad(sign, ((0, pad), (0, 0)))
    mags_p = jnp.pad(mags, ((0, 0), (0, pad), (0, 0)))

    def one_slab(_, t):
        st = jax.lax.dynamic_slice_in_dim(sign_p, t * tile_n, tile_n)
        mt = jax.lax.dynamic_slice_in_dim(mags_p, t * tile_n, tile_n, axis=1)
        return 0, _popcount_fields_block(m_words, st, mt)

    _, cols = jax.lax.scan(one_slab, 0, jnp.arange(nt))  # (nt, ..., tile_n)
    acc = jnp.moveaxis(cols, 0, -2).reshape(
        m_words.shape[:-1] + (nt * tile_n,)
    )
    return h + base + acc[..., :n]


def ising_energy(m, h, nbr_idx, nbr_w):
    """H = -Σ h_i m_i - 1/2 Σ_ij J_ij m_i m_j  (Eq. 1), int32 exact.

    Works on batched m ([..., N]).
    """
    fields = local_fields_sparse(m, jnp.zeros_like(h), nbr_idx, nbr_w)
    pair = jnp.sum(m * fields, axis=-1) // 2  # Σ_ij double-counts; halve (always even)
    return -(jnp.sum(h * m, axis=-1) + pair)


# ---------------------------------------------------------------------------
# MAX-CUT
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class MaxCutProblem:
    """A MAX-CUT instance G=(V,E,w) and its Ising embedding (Sec. II-C).

    cut(m) = Σ_{(i,j)∈E} w_ij · (1 - m_i m_j) / 2.

    Ising embedding: J = -w, h = 0, so H = Σ_{i<j} w_ij m_i m_j and
    cut = (W_sum - Σ_{i<j} w_ij m_i m_j) / 2 = (W_sum + H·sign) ... concretely
    ``cut = (w_total - pair_sum) / 2`` with ``pair_sum = -H`` when h = 0.
    """

    n: int
    edges: np.ndarray  # (E, 2) int
    weights: np.ndarray  # (E,) int
    name: str = "maxcut"
    best_known: Optional[int] = None

    @property
    def w_total(self) -> int:
        return int(np.sum(self.weights))

    def to_ising(self) -> IsingModel:
        return IsingModel.from_edges(
            self.n, self.edges, -np.asarray(self.weights), name=f"{self.name}-ising"
        )

    def cut_value(self, m) -> jnp.ndarray:
        """Cut value of spin assignment m (int, [..., N], vals in {-1,+1})."""
        wi = jnp.asarray(self.weights, jnp.int32)
        ei = jnp.asarray(self.edges[:, 0], jnp.int32)
        ej = jnp.asarray(self.edges[:, 1], jnp.int32)
        mi = jnp.take(m, ei, axis=-1)
        mj = jnp.take(m, ej, axis=-1)
        return jnp.sum(wi * (1 - mi * mj), axis=-1) // 2

    def cut_from_energy(self, H) -> jnp.ndarray:
        """With J = -w, h = 0:  H = +Σ_{i<j} w_ij m_i m_j, so
        cut = (w_total - H) / 2.  Verified against cut_value in tests."""
        return (self.w_total - H) // 2


def fig4_example() -> MaxCutProblem:
    """The 4-vertex example of paper Fig. 4 (optimal cut = 3).

    Edges: A-B (w=-1), A-C (+1), A-D (+1), B-C (+1), C-D (-1) reproduce the
    figure's structure: partition {A,B} | {C,D} cuts A-C, A-D, B-C = 3, while
    {A} | {B,C,D} cuts A-B, A-C, A-D = 1.
    """
    edges = np.array([[0, 1], [0, 2], [0, 3], [1, 2], [2, 3]])
    weights = np.array([-1, 1, 1, 1, -1])
    return MaxCutProblem(n=4, edges=edges, weights=weights, name="fig4", best_known=3)
