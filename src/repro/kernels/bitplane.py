"""Bitplane codec: ±1 spins as uint32 sign-bit words (DESIGN.md §4).

The FPGA stores one spin per BRAM bit — an 800-spin state is a single
800-bit word.  The TPU transcription is this codec: a spin vector
``m ∈ {-1,+1}^N`` becomes ``ceil(N/32)`` uint32 words, bit ``k`` of word
``w`` holding the sign of spin ``n = 32·w + k`` (1 ⇔ +1).  The same layout
is used

* for the HBM-resident engine state under ``storage_layout='packed'``
  (`repro.core.engine`): spins and best-spins live as bitplanes between
  plateau launches, 32× smaller than the seed's float32 spins;
* for the trajectory planes of ``record='traj'`` (the Eq. 5/6 witness);
* inside the streamed-noise resident kernel
  (`repro.kernels.ssa_update.ssa_plateau_packed_batched`), whose HBM-facing
  spin refs are these words — `_unpack_pm1_f32` / `_pack_bits` are the
  kernel-side halves of the codec, operating on lane-aligned (N % 128 == 0)
  tiles in VMEM (packing there runs as exact MXU contractions, because
  Mosaic does not lower the lane-splitting reshape used here).

Everything here is pure `jnp` on uint32 (no Pallas imports), so the codec
is usable from `repro.core` without pulling in the kernel toolchain, and
identically inside kernel bodies (interpret mode and Mosaic share the ops).

Tail handling: for N not a multiple of 32 the last word's high bits are
zero-padded on pack and sliced off on unpack — roundtrip-exact for any N
(property-tested in tests/test_bitplane.py).

Since PR 7 the bitplane is also the *arithmetic* format, not just storage:
:class:`PackedJ` packs the coupling matrix itself as a sign plane plus
magnitude bitplanes (integer weights = a sum of shifted ±1 planes), and
:func:`popcount_u32` is the primitive the XNOR-popcount field contraction
(`repro.core.ising.local_fields_popcount`) is built from.  The FPGA
identity per coupling plane is

    sum_j sign_ij * m_j  =  2 * popcount(XNOR(m_words, sign_words) & mask)
                            - popcount(mask)

— 32 spins per word op, no unpack to f32 anywhere on the path.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "packed_words",
    "pack_spins",
    "unpack_spins",
    "packed_nbytes",
    "popcount_u32",
    "PackedJ",
    "pack_couplings",
    "pack_couplings_from_adjacency",
    "Coalesced",
    "coalesce_adjacency",
    "coalesced_planes",
    "adjacency_planes",
    "adjacency_weight_bits",
    "packed_j_nbytes",
]

# Host constant (never a traced value, safe under jit) — jnp ops accept it.
_SHIFTS = np.arange(32, dtype=np.uint32)


def _shifts():
    return _SHIFTS


def packed_words(n: int) -> int:
    """Words needed for an N-spin bitplane: ceil(N/32)."""
    return (int(n) + 31) // 32


def packed_nbytes(n: int) -> int:
    """Bytes of one packed N-spin plane (uint32 words)."""
    return 4 * packed_words(n)


def pack_spins(m: jnp.ndarray) -> jnp.ndarray:
    """Pack ±1 spins [..., N] into uint32 bitplanes [..., ceil(N/32)].

    Bit k of word w is the sign bit of spin 32·w + k (1 ⇔ m > 0); tail bits
    of the last word are 0.  Accepts any numeric spin dtype.
    """
    n = m.shape[-1]
    nw = packed_words(n)
    pad = nw * 32 - n
    bits = (m > 0).astype(jnp.uint32)
    if pad:
        bits = jnp.concatenate(
            [bits, jnp.zeros(bits.shape[:-1] + (pad,), jnp.uint32)], axis=-1
        )
    bits = bits.reshape(bits.shape[:-1] + (nw, 32))
    return jnp.sum(bits << _shifts(), axis=-1, dtype=jnp.uint32)


def unpack_spins(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of pack_spins; returns int8 spins in {-1,+1}, shape [..., n]."""
    bits = (packed[..., None] >> _shifts()) & jnp.uint32(1)
    flat = bits.reshape(bits.shape[:-2] + (-1,))[..., :n]
    return jnp.where(flat == 1, 1, -1).astype(jnp.int8)


def popcount_u32(x: jnp.ndarray) -> jnp.ndarray:
    """Per-word population count of uint32 words, as int32.

    The single arithmetic primitive of the XNOR-popcount field path — one
    VPU op covering 32 spins.  Rejects non-uint32 inputs instead of
    casting: a silent widen would mean the caller left the packed domain.
    """
    if x.dtype != jnp.uint32:
        raise TypeError(f"popcount_u32 expects uint32 words, got {x.dtype}")
    return jax.lax.population_count(x).astype(jnp.int32)


class PackedJ(NamedTuple):
    """Coupling matrix as bitplanes: the XNOR-popcount operand layout.

    For a symmetric integer J (the same row convention as the sparse
    adjacency — ``field_i = h_i + sum_j J_ij m_j``):

    sign:  (N, Nw) uint32 — bit j of row i is 1 ⇔ J_ij > 0.
    mags:  (n_bits, N, Nw) uint32 — bit j of plane b row i is bit b of
           |J_ij|; plane b is the mask of couplings whose magnitude has
           that binary digit, so J = Σ_b 2^b · (±1 plane b).
    base:  (N,) int32 — −Σ_b 2^b · popcount(mags[b, i]) , the constant
           −degree terms of every plane folded into one vector, so

               field = h + base + Σ_b 2^{b+1} · popcount(XNOR & mags[b])

    All tail/padding bits (column ≥ N) are zero in every plane, which makes
    the contraction immune to garbage in the spin words' tail bits: the
    AND with the magnitude mask kills them.  ±1-weight instances (all of
    G-set) have n_bits == 1 — a single XNOR-popcount per row.
    """

    sign: jnp.ndarray
    mags: jnp.ndarray
    base: jnp.ndarray

    @property
    def n_bits(self) -> int:
        return self.mags.shape[0]

    @property
    def n_words(self) -> int:
        return self.sign.shape[-1]


def _pack_bits_np(bits: np.ndarray) -> np.ndarray:
    """Host-side pack of a 0/1 array [..., N] into uint32 words."""
    n = bits.shape[-1]
    nw = packed_words(n)
    pad = nw * 32 - n
    b = bits.astype(np.uint32)
    if pad:
        b = np.concatenate(
            [b, np.zeros(b.shape[:-1] + (pad,), np.uint32)], axis=-1
        )
    b = b.reshape(b.shape[:-1] + (nw, 32))
    return (b << _SHIFTS).sum(axis=-1, dtype=np.uint32)


def _popcount_np(words: np.ndarray) -> np.ndarray:
    """Host-side popcount summed over the word axis: [..., Nw] -> [...]."""
    u8 = np.ascontiguousarray(words).view(np.uint8)
    return np.unpackbits(u8, axis=-1).sum(axis=-1, dtype=np.int64)


def _resolve_n_bits(max_mag: int, n_bits) -> int:
    need = max(1, int(max_mag).bit_length())
    if n_bits is None:
        return need
    n_bits = int(n_bits)
    if n_bits < need:
        raise ValueError(
            f"couplings need {need} magnitude bitplanes, caller forced "
            f"{n_bits} — weights up to {max_mag} cannot be represented"
        )
    return n_bits


def pack_couplings(J: np.ndarray, n_bits=None) -> PackedJ:
    """Pack a dense symmetric integer coupling matrix into bitplanes.

    Raises on non-integral weights — the popcount path is exact-integer by
    construction and refuses inputs it cannot represent exactly.  ``n_bits``
    forces the magnitude-plane count (zero planes pad the top) so stacked
    problems share one layout; it must cover max|J|.
    """
    J = np.asarray(J)
    Ji = np.asarray(np.rint(J), dtype=np.int64)
    if not np.array_equal(Ji, np.asarray(J, dtype=np.float64)):
        raise ValueError("pack_couplings requires integer weights")
    mag = np.abs(Ji)
    n_bits = _resolve_n_bits(mag.max(initial=0), n_bits)
    sign = _pack_bits_np(Ji > 0)
    mags = np.stack(
        [_pack_bits_np((mag >> b) & 1) for b in range(n_bits)]
    )
    degs = _popcount_np(mags)  # (n_bits, N)
    shifts = (np.int64(1) << np.arange(n_bits, dtype=np.int64))[:, None]
    base = -(degs * shifts).sum(axis=0).astype(np.int32)
    return PackedJ(jnp.asarray(sign), jnp.asarray(mags), jnp.asarray(base))


# The dense route bins every cell of an n x n matrix; it is taken where those
# n² cells are at most this many times the adjacency's n·max_deg slots.
_DENSE_CELLS_PER_SLOT = 4


def _narrowest_int(max_abs: int):
    """The narrowest signed integer type that holds ±``max_abs``."""
    return next(t for t in (np.int8, np.int16, np.int32, np.int64)
                if max_abs <= np.iinfo(t).max)


class Coalesced(NamedTuple):
    """A model's couplings with duplicate (i, j) adjacency slots weight-summed
    and zero sums dropped — J as ``IsingModel.dense_J`` has it.

    :func:`coalesce_adjacency` fills one of two forms, by its route:
    ``dense``, J as an (n, n) matrix (``rows``, ``cols``, ``wsum`` None); or
    ``rows``, ``cols`` (int32) and ``wsum``, J's nonzero entries in
    row-major order (``dense`` None).  Weights are held in the narrowest
    signed integer type that holds ``max_abs``, max |J_ij|.
    """

    n: int
    max_abs: int
    dense: Optional[np.ndarray]
    rows: Optional[np.ndarray]
    cols: Optional[np.ndarray]
    wsum: Optional[np.ndarray]

    @property
    def weight_bits(self) -> int:
        """Magnitude bitplanes the couplings need (≥ 1)."""
        return max(1, self.max_abs.bit_length())


def coalesce_adjacency(n: int, nbr_idx, nbr_w) -> Coalesced:
    """Sum the duplicate (i, j) slots of a padded adjacency, in one pass.

    The route follows the shape; both give the same J:

    * **dense**, where the n² cells are at most 4× the adjacency's slots
      (K2000: 2048² cells for 2048×1999 slots): one ``np.bincount`` over the
      flat ``row·n + col`` keys.  Its float64 sums are exact while the
      total |weight| stays below 2^53, which is checked.
    * **sorted**, for sparse instances (degree-4 G-set; G81 at bucket
      32768, whose n² cells would be 8 GiB): one stable sort of the live
      slots' keys and ``np.add.reduceat`` over runs of equal keys, in
      int64.  No n² array is made.
    """
    n = int(n)
    idx = np.asarray(nbr_idx)
    w = np.asarray(nbr_w)
    rows = np.arange(n, dtype=np.int64)[:, None]
    if n * n <= _DENSE_CELLS_PER_SLOT * idx.size:
        if np.abs(w, dtype=np.int64).sum() >= 1 << 53:
            raise ValueError("coupling weights too large to sum exactly")
        J = np.bincount((rows * n + idx).reshape(-1),
                        weights=w.reshape(-1), minlength=n * n)
        max_abs = int(max(-J.min(initial=0), J.max(initial=0)))
        return Coalesced(n, max_abs,
                         J.astype(_narrowest_int(max_abs)).reshape(n, n),
                         None, None, None)
    live = w != 0
    keys = np.broadcast_to(rows * n, idx.shape)[live] + idx[live]
    order = np.argsort(keys, kind="stable")
    keys = keys[order]
    start = np.flatnonzero(np.diff(keys, prepend=-1))
    wsum = (np.add.reduceat(w[live][order].astype(np.int64), start)
            if start.size else np.zeros(0, np.int64))
    nz = wsum != 0
    keys, wsum = keys[start][nz], wsum[nz]
    max_abs = int(np.abs(wsum).max(initial=0))
    return Coalesced(n, max_abs, None, (keys // n).astype(np.int32),
                     (keys % n).astype(np.int32),
                     wsum.astype(_narrowest_int(max_abs)))


def adjacency_weight_bits(n: int, nbr_idx, nbr_w) -> int:
    """Magnitude bitplanes needed for a model's couplings (≥ 1).

    Operates on the *coalesced* weights (duplicate adjacency slots summed,
    matching ``IsingModel.dense_J``), so the answer is exactly the plane
    count :func:`pack_couplings_from_adjacency` would produce.  This is the
    number `field_mode='auto'` compares against POPCOUNT_AUTO_MAX_BITS.
    """
    return coalesce_adjacency(n, nbr_idx, nbr_w).weight_bits


def pack_couplings_from_adjacency(
    n: int, nbr_idx: np.ndarray, nbr_w: np.ndarray, n_bits=None
) -> PackedJ:
    """Pack couplings from the padded adjacency without materializing J.

    ``nbr_idx``/``nbr_w`` are the `IsingModel` padded neighbor lists
    (weight 0 = padding slot).  Duplicate (i, j) entries are weight-summed
    first, matching ``IsingModel.dense_J``.  O(N·max_deg) host work on
    sparse instances — this is the constructor the 20k-spin instances use.
    """
    return PackedJ(*(jnp.asarray(a) for a in
                     adjacency_planes(n, nbr_idx, nbr_w, n_bits)))


def adjacency_planes(n: int, nbr_idx: np.ndarray, nbr_w: np.ndarray,
                     n_bits=None):
    """The planes of :func:`pack_couplings_from_adjacency` as host arrays:
    ``(sign uint32 (N, W), mags uint32 (n_bits, N, W), base int32 (N,))``,
    for callers that stack many problems on the host before one transfer.
    """
    return coalesced_planes(coalesce_adjacency(n, nbr_idx, nbr_w), n, n_bits)


def coalesced_planes(c: Coalesced, n: int, n_bits=None):
    """:func:`adjacency_planes` of coalesced couplings, padded to ``n`` spins.

    Each (i, j) is in ``c`` once, so its bit is set by a sum, not an OR:
    the dense form packs 0/1 matrices with ``np.packbits``; the sorted form
    sums each word's bits over the runs of its word index, which row-major
    entries keep sorted.  ``base`` is −Σ_j |J_ij|: every plane bit of row i
    weighted by 2^b.
    """
    n = int(n)
    if c.n > n:
        raise ValueError(f"couplings of {c.n} spins do not fit {n} spins")
    nw = packed_words(n)
    n_bits = _resolve_n_bits(c.max_abs, n_bits)
    # Little-endian words, so a word's bytes are its columns' packed bytes
    # in order; planes above max_abs stay 0.
    sign = np.zeros((n, nw), "<u4")
    mags = np.zeros((n_bits, n, nw), "<u4")
    if c.dense is not None:
        mag = np.abs(c.dense)

        def fill(out, bits):  # (c.n, c.n) bool into the (n, nw) words
            packed = np.packbits(bits, axis=1, bitorder="little")
            out.view(np.uint8)[:c.n, :packed.shape[1]] = packed

        fill(sign, c.dense > 0)
        for b in range(c.max_abs.bit_length()):
            fill(mags[b], (mag & (1 << b)) != 0)
        base = np.zeros(n, np.int64)
        base[:c.n] = -mag.sum(axis=1, dtype=np.int64)
    else:
        r, col, wsum = c.rows, c.cols, c.wsum
        word = r.astype(np.int64) * nw + col // 32
        bit = np.uint32(1) << (col % 32).astype(np.uint32)
        mag = np.abs(wsum)

        def fill(out, sel):  # the selected entries' bits into the words
            ws = word[sel]
            start = np.flatnonzero(np.diff(ws, prepend=-1))
            if start.size:
                out.reshape(-1)[ws[start]] = np.add.reduceat(
                    bit[sel], start, dtype=np.uint32)

        fill(sign, wsum > 0)
        for b in range(c.max_abs.bit_length()):
            fill(mags[b], (mag & (1 << b)) != 0)
        base = -np.bincount(r, weights=mag, minlength=n)
    return sign, mags, base.astype(np.int32)


def packed_j_nbytes(n: int, n_bits: int = 1) -> int:
    """Bytes of a PackedJ layout: sign + n_bits magnitude planes + base."""
    nw = packed_words(n)
    return 4 * n * nw * (1 + int(n_bits)) + 4 * int(n)
