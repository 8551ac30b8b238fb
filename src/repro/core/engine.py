"""Plateau-structured annealing engine (DESIGN.md §2).

The paper's HA-SSA treats the temperature *plateau* — τ cycles at constant
pseudo-inverse temperature I0 — as the natural unit of execution and of
storage (Eq. 4–6): the schedule advances plateau-by-plateau, and the BRAM
write-enable is a *per-plateau* predicate (I0 == I0max), not a per-cycle
mask.  This module makes the plateau the unit of the software architecture
too.

There is one engine: the batched backends.  A :class:`BatchedBackend`
advances B stacked, bucket-padded problems through one compiled plateau
program (``stack / init_noise / init_state / run_plateau / run_shots /
finalize``).  The serving layer runs it at whatever B a request group
needs; the library drivers (``ssa.anneal``, ``pt.anneal_pt_ssa``) run it at
B=1 with the bucket at the model's own width (:func:`single_problem_backend`).

* :class:`BatchedSparseBackend` / :class:`BatchedDenseBackend` — `lax.scan`
  over :func:`run_plateau_scan`, vmapped over the problem axis.  The
  local-field contraction runs **once per cycle**: the field computed for
  the Eq. (2a) update of state m(t) is reused to evaluate H(m(t)) for
  solution tracking and energy traces.
* :class:`BatchedPallasBackend` — the resident plateau kernels on a
  (B, R-tile) grid with J pinned in VMEM.  With xorshift noise the per-cycle
  noise is generated in-kernel from carried xorshift lanes — no (C, R, N)
  noise buffer exists anywhere — and with ``field_mode='popcount'`` a whole
  plateau chain runs in one launch.

Per-cycle *outputs* (energy traces, trajectory planes) come from
:meth:`BatchedBackend.run_plateau_traced`, which runs a plateau on the scan
path through the backend's own field, bit-identically to its production
program; the resident kernels produce none.

Storage layouts (DESIGN.md §4): every backend carries a
``storage_layout`` axis — 'dense' keeps :class:`EngineState` (int8 spins),
'packed' keeps :class:`PackedEngineState` (uint32 bitplanes between
launches).  Results are bit-identical; only the resident bytes differ.
The dense-field backend additionally carries ``j_mode`` — 'tiled' streams
(tile_n, N) J slabs instead of materializing (N, N), admitting
G77/G81-class instances.

HA-SSA's storage policy is expressed as per-plateau *eligibility*: a plateau
with ``eligible=True`` folds the states it produces into the running
arg-best (record='best') or emits their bit-packed planes (record='traj').
Under ``storage='i0max'`` only the final plateau of each iteration is
eligible; ``storage='all'`` recovers conventional SSA.

Tracking semantics (shared by all backends, matching the resident kernel and
:mod:`repro.kernels.ref`): within a plateau starting at state m(t0), the
states *produced by this plateau* — m(t0+1) … m(t0+C) — are folded into the
running best under this plateau's eligibility.  The incoming state m(t0)
belongs to the previous plateau and is skipped; the final state m(t0+C) is
folded by one extra field evaluation after the cycle loop.  Chained over a
schedule this tracks every state exactly once, under the eligibility of the
plateau that produced it — bit-identical across backends and to the seed's
flat per-cycle scan.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .ising import (
    IsingModel,
    MaxCutProblem,
    local_fields_dense,
    local_fields_popcount,
    local_fields_sparse,
    local_fields_tiled,
)
from .rng import (
    threefry_noise,
    xorshift_init,
    xorshift_init_slice,
    xorshift_next_bits,
)
from .schedule import Schedule

__all__ = [
    "BIG_ENERGY",
    "TILED_J_THRESHOLD",
    "MIN_RESIDENT_N",
    "POPCOUNT_AUTO_MAX_BITS",
    "BaseResult",
    "EngineState",
    "PackedEngineState",
    "pack_state",
    "unpack_state",
    "Plateau",
    "resolve_backend",
    "route_backend",
    "resolve_field_mode",
    "pallas_vmem_shortfall",
    "resident_kernel",
    "resolve_j_mode",
    "resolve_noise_mode",
    "resolve_partition",
    "spin_axis_size",
    "SPIN_SHARD_MIN_N",
    "MAX_UNSHARDED_SPINS",
    "model_couplings",
    "model_weight_bits",
    "plateau_cycle_schedules",
    "normalize_problem",
    "validate_model",
    "MAX_MODEL_SPINS",
    "finalize_cut",
    "schedule_plateaus",
    "tile_plateaus",
    "run_plateau_scan",
    "pack_spins",
    "unpack_spins",
    "packed_words",
    "ssa_cycle_update",
    "energy_from_field",
    "next_pow2",
    "bucket_n",
    "pad_model",
    "pad_degree",
    "extract_slot",
    "splice_slot",
    "padded_noise_init",
    "padded_noise_init_slice",
    "BatchedBackend",
    "BatchedSparseBackend",
    "BatchedDenseBackend",
    "BatchedPallasBackend",
    "BATCHED_BACKENDS",
    "make_batched_backend",
    "single_problem_backend",
]

# Sentinel "no solution yet" energy (any real H is far below this).
BIG_ENERGY = 2**30

# Dense (N, N) J above this spin count is not materialized: j_mode='auto'
# resolves to the tiled path that streams (tile_n, N) slabs instead.
TILED_J_THRESHOLD = 4096

# Below this spin count the resident Pallas kernel's launch overhead beats
# its residency win (measured: ~2.4 s pallas vs ~1.5 s dense on the 32-spin
# frontend smokes) — backend='auto' dispatches the scan backends instead.
# Asserted structurally in benchmarks/other_problems.py --smoke.
MIN_RESIDENT_N = 256

# field_mode='auto' uses the XNOR-popcount contraction up to this many
# magnitude bitplanes (the paper's hardware is 4-bit); wider integer weights
# fall back to the f32 matmul, whose cost is bit-depth independent.
POPCOUNT_AUTO_MAX_BITS = 4


# ---------------------------------------------------------------------------
# Bit packing (the 800-bit BRAM word, as uint32 lanes) — the codec lives in
# repro.kernels.bitplane so the Pallas kernels and the engine share one bit
# layout; re-exported here for the core-level callers.
# ---------------------------------------------------------------------------
from repro.kernels.bitplane import (  # noqa: E402
    Coalesced,
    PackedJ,
    coalesce_adjacency,
    coalesced_planes,
    pack_spins,
    packed_words,
    unpack_spins,
)


def model_couplings(model: IsingModel) -> Coalesced:
    """A model's coalesced couplings: the one pass that both its weight-bit
    count and its bitplanes are read from."""
    return coalesce_adjacency(model.n, model.nbr_idx, model.nbr_w)


def model_weight_bits(model: IsingModel) -> int:
    """Magnitude bitplanes a model's couplings need (coalesced max |J_ij|)."""
    return model_couplings(model).weight_bits


# ---------------------------------------------------------------------------
# The p-bit update (Eq. 2a–2c), shared by every backend and the kernel oracle
# ---------------------------------------------------------------------------
def ssa_cycle_update(field, itanh, r, i0, n_rnd):
    """Elementwise epilogue of one SSA cycle.

    Args:
      field: int32[..., N]  h_i + Σ_j J_ij m_j(t)      (the matvec part)
      itanh: int32[..., N]  Itanh_i(t)
      r:     int32[..., N]  noise in {-1,+1}
      i0:    int32 scalar   pseudo-inverse temperature I0(t)
      n_rnd: int            noise magnitude
    Returns:
      (m_new int8[...,N], itanh_new int32[...,N])
    """
    I = field + n_rnd * r + itanh  # noqa: E741 — Eq. (2a) current
    itanh_new = jnp.clip(I, -i0, i0 - 1)                # (2b)
    m_new = jnp.where(itanh_new >= 0, 1, -1).astype(jnp.int8)  # (2c)
    return m_new, itanh_new


def energy_from_field(m, field, h):
    """H = -(h·m + m·field)/2, exact int32 (field = h + Jm)."""
    m32 = m.astype(jnp.int32)
    hm = jnp.sum(h * m32, axis=-1)
    mf = jnp.sum(m32 * field, axis=-1)
    return -(hm + mf) // 2


# ---------------------------------------------------------------------------
# Problem / result plumbing shared by the SSA, SA and PT drivers
# ---------------------------------------------------------------------------
def normalize_problem(
    problem: Union[MaxCutProblem, IsingModel, Any],
) -> Tuple[Optional[MaxCutProblem], IsingModel]:
    """Split a problem into (maxcut-or-None, IsingModel).

    Accepts a :class:`MaxCutProblem`, a raw :class:`IsingModel`, or any
    encoded problem exposing an IsingModel ``model`` attribute (the
    :class:`repro.problems.ProblemEncoding` frontend) — duck-typed so the
    engine never imports the problems package.
    """
    if isinstance(problem, MaxCutProblem):
        return problem, problem.to_ising()
    if isinstance(problem, IsingModel):
        return None, problem
    model = getattr(problem, "model", None)
    if isinstance(model, IsingModel):
        return None, model
    raise TypeError(
        f"cannot interpret {type(problem).__name__} as an annealing problem; "
        "pass a MaxCutProblem, an IsingModel, or a ProblemEncoding"
    )


# Admission ceiling on the spin count: far above anything the backends can
# actually serve today (G81 is 20k), but low enough that a corrupted or
# adversarial shape is rejected before any padding/stacking is attempted.
MAX_MODEL_SPINS = 1 << 22


def validate_model(model: IsingModel, *, max_spins: int = MAX_MODEL_SPINS):
    """Admission-time structural validation of an Ising model.

    :meth:`IsingModel.from_edges` / :meth:`~IsingModel.from_dense` validate
    at construction, but the dataclass can also be built directly — the
    serving layer re-checks here so a malformed model is rejected with a
    clear error instead of poisoning a compiled batch.  Raises ValueError
    (callers wrap it into their own typed admission error).
    """
    n = int(model.n)
    if n <= 0:
        raise ValueError(f"model {model.name!r}: need n > 0, got {n}")
    if n > max_spins:
        raise ValueError(
            f"model {model.name!r}: n={n} exceeds the service ceiling "
            f"{max_spins} — absurd shape rejected at admission"
        )
    h = np.asarray(model.h)
    idx = np.asarray(model.nbr_idx)
    w = np.asarray(model.nbr_w)
    if h.shape != (n,):
        raise ValueError(f"model {model.name!r}: h shape {h.shape} != ({n},)")
    if idx.ndim != 2 or idx.shape[0] != n or idx.shape != w.shape:
        raise ValueError(
            f"model {model.name!r}: adjacency shapes nbr_idx {idx.shape} / "
            f"nbr_w {w.shape} inconsistent with n={n}"
        )
    for name, arr in (("h", h), ("nbr_w", w)):
        if not np.all(np.isfinite(arr.astype(np.float64, copy=False))):
            raise ValueError(
                f"model {model.name!r}: non-finite values in {name}"
            )
    if idx.size and (int(idx.min()) < 0 or int(idx.max()) >= n):
        raise ValueError(
            f"model {model.name!r}: neighbor indices outside [0, {n})"
        )


def finalize_cut(best_H, maxcut: Optional[MaxCutProblem]):
    """Map best Ising energies to the reported objective (cut or -H)."""
    if maxcut is not None:
        return (maxcut.w_total - best_H) // 2
    return -best_H


@dataclasses.dataclass
class BaseResult:
    """Outcome fields shared by the SSA/HA-SSA, SA and PT drivers."""

    best_cut: np.ndarray          # best objective per trial (cut for maxcut)
    best_energy: np.ndarray       # Ising energy of the best tracked state
    best_m: np.ndarray            # spins of the best tracked state
    energy_mean: Optional[np.ndarray]  # per-cycle mean H over trials
    energy_min: Optional[np.ndarray]   # per-cycle min H over trials

    @property
    def overall_best_cut(self) -> int:
        return int(np.max(self.best_cut))

    @property
    def mean_best_cut(self) -> float:
        return float(np.mean(self.best_cut))


# ---------------------------------------------------------------------------
# Plateaus: the schedule, grouped into its natural execution unit
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Plateau:
    """One constant-I0 run of cycles — HA-SSA's unit of execution/storage.

    ``eligible`` is the storage write-enable for the states this plateau
    *produces*: under HA-SSA (Eq. 6) only the I0 == I0max plateau asserts it;
    conventional SSA (Eq. 5) asserts it everywhere.

    ``jperp`` is the SSQA Trotter-replica ring coupling J⊥ held over this
    plateau (DESIGN.md §13); 0 — the default, and the only value classical
    SSA/HA-SSA schedules produce — disables the coupling entirely.
    """

    i0: int
    length: int
    eligible: bool
    jperp: int = 0


def _group_runs(
    i0_seq: np.ndarray, elig_seq: np.ndarray, jperp_seq=None
) -> Tuple[Plateau, ...]:
    jp = (
        np.zeros(len(i0_seq), np.int64)
        if jperp_seq is None
        else np.asarray(jperp_seq)
    )
    out = []
    start = 0
    n = len(i0_seq)
    for k in range(1, n + 1):
        if (
            k == n
            or i0_seq[k] != i0_seq[start]
            or elig_seq[k] != elig_seq[start]
            or jp[k] != jp[start]
        ):
            out.append(
                Plateau(
                    int(i0_seq[start]),
                    k - start,
                    bool(elig_seq[start]),
                    int(jp[start]),
                )
            )
            start = k
    return tuple(out)


def schedule_plateaus(sched: Schedule, storage: str = "i0max") -> Tuple[Plateau, ...]:
    """Group one iteration's per-cycle schedule into plateaus.

    storage='i0max' → HA-SSA eligibility (the BRAM write-enable);
    storage='all'   → every plateau eligible (conventional SSA).
    SSQA schedules additionally carry ``jperp_per_cycle``, split at the
    same plateau boundaries.
    """
    i0 = np.asarray(sched.i0_per_cycle)
    if storage == "i0max":
        elig = np.asarray(sched.store_mask)
    elif storage == "all":
        elig = np.ones(len(i0), dtype=bool)
    else:
        raise ValueError(f"unknown storage {storage!r}")
    return _group_runs(i0, elig, getattr(sched, "jperp_per_cycle", None))


def tile_plateaus(plateaus: Sequence[Plateau], total_cycles: int) -> Tuple[Plateau, ...]:
    """Tile an iteration's plateau list to exactly ``total_cycles`` cycles,
    truncating the final plateau (conventional-SSA cycle-count duration,
    paper Fig. 12 mode)."""
    if not plateaus and total_cycles > 0:
        raise ValueError("cannot tile an empty plateau sequence")
    out = []
    remaining = int(total_cycles)
    while remaining > 0:
        for p in plateaus:
            if remaining <= 0:
                break
            take = min(p.length, remaining)
            out.append(Plateau(p.i0, take, p.eligible, p.jperp))
            remaining -= take
    return tuple(out)


def plateau_cycle_schedules(plateaus: Sequence[Plateau]):
    """Per-cycle schedule operands for the multi-plateau resident kernel.

    Flattens a plateau chain into ``(i0_sched (C,), fold_sched (C+1,),
    jperp_sched (C,))`` int32 host arrays: ``i0_sched[c]`` is the I0 of
    cycle c, ``fold_sched[c]`` the storage write-enable of the plateau that
    *produced* the state current at cycle c — 0 at c = 0 (the chain's
    incoming state belongs to the previous chunk), eligibility of cycle
    c−1's plateau for c ≥ 1, and ``fold_sched[C]`` covers the final state —
    and ``jperp_sched[c]`` the replica coupling applied by cycle c's update
    (all-zero for classical chains).  Feeding these to
    `ssa_plateau_popcount[_batched]` reproduces chained per-plateau
    execution bit-identically in one launch.
    """
    i0s, elig, jps = [], [], []
    for p in plateaus:
        i0s.extend([int(p.i0)] * int(p.length))
        elig.extend([int(bool(p.eligible))] * int(p.length))
        jps.extend([int(p.jperp)] * int(p.length))
    if not i0s:
        raise ValueError("empty plateau chain")
    return (
        np.asarray(i0s, np.int32),
        np.asarray([0] + elig, np.int32),
        np.asarray(jps, np.int32),
    )


# ---------------------------------------------------------------------------
# Engine state and the shared one-plateau scan
# ---------------------------------------------------------------------------
class EngineState(NamedTuple):
    """Carry threaded through plateaus; canonical spin dtype is int8 ±1."""

    noise_state: Any         # xorshift (4,T,N) u32 lanes or a threefry key
    m: jnp.ndarray           # (T, N) int8 spins
    itanh: jnp.ndarray       # (T, N) int32 Itanh FSM state
    best_H: jnp.ndarray      # (T,) int32 running best energy
    best_m: jnp.ndarray      # (T, N) int8 spins of the running best


class PackedEngineState(NamedTuple):
    """EngineState with spins stored as uint32 bitplanes (DESIGN.md §4).

    Under ``storage_layout='packed'`` this is the state that lives in HBM
    between plateau/chunk launches: spins and best-spins occupy 1 bit per
    (trial, spin) — 8× below int8, 32× below the float32 crossing the old
    kernel boundary — matching the FPGA's one-spin-per-BRAM-bit layout.
    The Itanh FSM counter stays int32 (it is genuinely multi-bit state).
    """

    noise_state: Any              # xorshift (4,T,N) u32 lanes or threefry key
    m_packed: jnp.ndarray         # (T, ceil(N/32)) uint32 bitplanes
    itanh: jnp.ndarray            # (T, N) int32
    best_H: jnp.ndarray           # (T,) int32
    best_m_packed: jnp.ndarray    # (T, ceil(N/32)) uint32


def pack_state(state: EngineState) -> PackedEngineState:
    """Pack an engine state's spin planes (exact: spins are ±1)."""
    return PackedEngineState(
        state.noise_state,
        pack_spins(state.m),
        state.itanh,
        state.best_H,
        pack_spins(state.best_m),
    )


def unpack_state(state: PackedEngineState, n: int) -> EngineState:
    """Inverse of :func:`pack_state` for an N-spin model."""
    return EngineState(
        state.noise_state,
        unpack_spins(state.m_packed, n),
        state.itanh,
        state.best_H,
        unpack_spins(state.best_m_packed, n),
    )


def replica_coupling(m: jnp.ndarray, n_replicas: int) -> jnp.ndarray:
    """Sum of ring-adjacent Trotter-replica spins, per (trial, spin) lane.

    The trial axis (axis -2 of ``(..., T, N)`` spins) is G = T/R independent
    rings of R consecutive replicas — the same grouping the resident kernels
    use (one R-tile per ring), so scan and kernel paths couple identical
    neighbor pairs.  Returns int32 ``m[k-1] + m[k+1]`` with ring wraparound
    (for R = 2 the single neighbor is counted from both sides, the standard
    doubled edge of a 2-cycle).
    """
    R = int(n_replicas)
    shape = m.shape
    T = shape[-2]
    if T % R:
        raise ValueError(f"n_trials {T} not divisible by n_replicas {R}")
    mr = m.reshape(shape[:-2] + (T // R, R, shape[-1])).astype(jnp.int32)
    nb = jnp.roll(mr, 1, axis=-2) + jnp.roll(mr, -1, axis=-2)
    return nb.reshape(shape[:-2] + (T, shape[-1]))


def run_plateau_scan(
    field_fn: Callable[[jnp.ndarray], jnp.ndarray],
    noise_step: Callable,
    h: jnp.ndarray,
    n_rnd: int,
    state: EngineState,
    i0,
    *,
    length: int,
    eligible: bool,
    track_energy: bool = False,
    emit: bool = False,
    energy_fn: Callable = None,
    jperp: int = 0,
    n_replicas: int = 0,
):
    """One constant-I0 plateau as a `lax.scan` — ONE contraction per cycle.

    The field computed for the Eq. (2a) update of m(t) doubles as the field
    needed for H(m(t)); the scan's first step skips best-tracking because
    m(t0) belongs to the previous plateau, and one epilogue field evaluation
    folds the final state m(t0+C) — exactly the resident kernel's semantics
    (kernels/ssa_update.py, kernels/ref.py).

    ``energy_fn(m, field, h)`` overrides :func:`energy_from_field` for the
    best-fold/trace evaluations — the spin-sharded step passes a variant
    that psums per-shard partial sums over the model axis (int32 addition is
    exact and order-free, so the fold stays bit-identical; DESIGN.md §11).

    ``jperp``/``n_replicas`` enable SSQA's Trotter-replica ring coupling
    (DESIGN.md §13): the Eq. (2a) *update* field gains
    ``jperp · (m[k-1] + m[k+1])`` over :func:`replica_coupling` rings on the
    trial axis, while the best-fold/trace energies keep the BASE field — the
    coupling steers the dynamics, the reported energy stays the classical
    per-replica Ising energy.

    Returns (state', trace, planes) where trace is (mean_H (C,), min_H (C,))
    aligned to the produced states m(t0+1..t0+C) when ``track_energy``, and
    planes is the (C, T, ceil(N/32)) bit-packed trajectory when ``emit``.
    """
    if energy_fn is None:
        energy_fn = energy_from_field
    i0 = jnp.asarray(i0, jnp.int32)
    eligible = bool(eligible)
    track_energy = bool(track_energy)
    emit = bool(emit)
    need_H = eligible or track_energy
    jperp = int(jperp)
    couple = bool(jperp) and int(n_replicas) > 0

    def cyc(carry, not_first):
        ns, m, itanh, best_H, best_m = carry
        field = field_fn(m)
        ys = {}
        if need_H:
            H = energy_fn(m, field, h)
            if eligible:
                better = not_first & (H < best_H)
                best_H = jnp.where(better, H, best_H)
                best_m = jnp.where(better[..., None], m, best_m)
            if track_energy:
                ys["mean"] = jnp.mean(H.astype(jnp.float32))
                ys["min"] = jnp.min(H)
        ns, r = noise_step(ns)
        upd = field
        if couple:
            upd = field + (
                jperp * replica_coupling(m, n_replicas)
            ).astype(field.dtype)
        m_new, it_new = ssa_cycle_update(upd, itanh, r, i0, n_rnd)
        if emit:
            ys["plane"] = pack_spins(m_new)
        return (ns, m_new, it_new, best_H, best_m), ys

    not_first = jnp.arange(length) > 0
    carry, ys = jax.lax.scan(cyc, tuple(state), not_first)
    ns, m, itanh, best_H, best_m = carry

    trace = None
    if need_H:
        # Epilogue: the plateau's final state needs one extra field.
        field = field_fn(m)
        H = energy_fn(m, field, h)
        if eligible:
            better = H < best_H
            best_H = jnp.where(better, H, best_H)
            best_m = jnp.where(better[..., None], m, best_m)
        if track_energy:
            trace = (
                jnp.concatenate(
                    [ys["mean"][1:], jnp.mean(H.astype(jnp.float32))[None]]
                ),
                jnp.concatenate([ys["min"][1:], jnp.min(H)[None]]),
            )
    planes = ys["plane"] if emit else None
    return EngineState(ns, m, itanh, best_H, best_m), trace, planes


# ---------------------------------------------------------------------------
# Routing: backend, field arithmetic, noise datapath and partition
# ---------------------------------------------------------------------------
def resolve_j_mode(j_mode: str, n: int) -> str:
    """'auto' picks tiled above TILED_J_THRESHOLD spins, dense below."""
    if j_mode == "auto":
        return "tiled" if n > TILED_J_THRESHOLD else "dense"
    if j_mode not in ("dense", "tiled"):
        raise ValueError(f"unknown j_mode {j_mode!r}")
    return j_mode


def resolve_field_mode(field_mode: str, j_bits: int) -> str:
    """Field-contraction arithmetic: 'popcount' (XNOR-popcount on uint32
    bitplanes, exact-integer) vs 'dense' (f32 matmul / tiled slabs).
    'auto' uses popcount while the couplings fit POPCOUNT_AUTO_MAX_BITS
    magnitude planes — the contraction costs one XNOR-popcount pass per
    plane, so deep integer weights favor the bit-depth-independent matmul.
    """
    if field_mode == "auto":
        return (
            "popcount" if int(j_bits) <= POPCOUNT_AUTO_MAX_BITS else "dense"
        )
    if field_mode not in ("dense", "popcount"):
        raise ValueError(f"unknown field_mode {field_mode!r}")
    return field_mode


def resident_kernel(field_mode: str, noise_mode: str) -> str:
    """The resident kernel family a pallas backend launches: 'popcount'
    (XNOR-popcount chain), 'streamed' (in-kernel xorshift, f32 J) or
    'pregen' (per-plateau noise buffer, f32 J)."""
    if field_mode == "popcount":
        return "popcount"
    return "streamed" if noise_mode == "streamed" else "pregen"


def pallas_vmem_shortfall(n: int, *, noise: str = "xorshift",
                          noise_mode: str = "auto", field_mode: str = "dense",
                          j_bits: int = 1, block_r: int = 8,
                          n_replicas: int = 0, j_dtype=jnp.float32,
                          n_cycles: int = 1, **_other) -> Optional[str]:
    """Why the resident kernel cannot run at ``n`` spins, or None if it can.

    The kernel's VMEM need comes from its block shapes
    (:func:`repro.kernels.ssa_update.plateau_vmem_bytes`) and is checked
    against the chip's budget before anything is built or dispatched.
    ``n_cycles`` (the plateau length) only sizes the pregen noise buffer.
    Options the pallas backend does not take are ignored, so callers may
    pass a backend-opts union.
    """
    from repro.kernels import ssa_update as kssa  # lazy: keeps core light

    kernel = resident_kernel(resolve_field_mode(field_mode, j_bits),
                             resolve_noise_mode(noise_mode, noise))
    br = int(n_replicas) if n_replicas else int(block_r)
    need = kssa.plateau_vmem_bytes(
        kernel, int(n), block_r=br, n_cycles=int(n_cycles), j_dtype=j_dtype,
        j_bits=int(j_bits), n_replicas=int(n_replicas),
    )
    budget = kssa.vmem_budget_bytes()
    if need <= budget:
        return None
    return (f"the {kernel} resident kernel needs ~{need / 2**20:.1f} MiB of "
            f"VMEM at {int(n)} spins, over the {budget / 2**20:.1f} MiB budget")


def route_backend(backend: str, n: int,
                  **kernel_opts) -> Tuple[str, Optional[str]]:
    """``(backend, why)`` for an ``n``-spin program.

    'auto' dispatches the resident Pallas kernel only at or above
    MIN_RESIDENT_N spins (below it the launch overhead loses to the scan
    backends, the measured 32-spin smoke regression) and only where that
    kernel fits the chip's VMEM budget (:func:`pallas_vmem_shortfall`, fed
    ``kernel_opts``); otherwise it picks XLA dense, and ``why`` is the
    budget shortfall when that is the reason.  Non-'auto' names pass
    through untouched.
    """
    if backend != "auto":
        return backend, None
    if int(n) < MIN_RESIDENT_N:
        return "dense", None
    why = pallas_vmem_shortfall(n, **kernel_opts)
    return ("dense" if why else "pallas"), why


def resolve_backend(backend: str, n: int, **kernel_opts) -> str:
    """The backend name :func:`route_backend` picks."""
    return route_backend(backend, n, **kernel_opts)[0]


def _require_vmem_fit(n: int, **kernel_opts) -> None:
    """Refuse an explicit pallas backend whose kernel cannot fit the chip."""
    why = pallas_vmem_shortfall(n, **kernel_opts)
    if why is not None:
        from repro.kernels.ssa_update import VmemBudgetError

        raise VmemBudgetError(
            f"backend='pallas': {why}; use backend='auto' (routes this size "
            "to XLA dense) or 'dense'/'sparse'"
        )


# Spin-sharded execution (DESIGN.md §11). partition='auto' splits the spin
# axis over the mesh's model axis only at/above this N: below it the
# per-cycle all-gather dominates the O(N·Ns) shard contraction it buys.
SPIN_SHARD_MIN_N = 2048

# A single-device (partition='problem') plateau program above this many spins
# is rejected at service admission: the per-cycle state alone (itanh i32 +
# lanes 4×u32 per (trial, spin)) makes the unsharded path the wrong tool —
# giant requests must route to partition='spin' on a multi-device mesh.
MAX_UNSHARDED_SPINS = 1 << 15


def spin_axis_size(mesh, axis: str = "model") -> int:
    """Devices on a mesh's spin-sharding axis (1 for no mesh / absent axis)."""
    if mesh is None:
        return 1
    try:
        return int(mesh.shape[axis]) if axis in mesh.shape else 1
    except TypeError:
        return 1


def resolve_partition(partition: str, n: int, mesh=None, *,
                      axis: str = "model") -> str:
    """Resolve the work-partitioning axis for an N-spin plateau program.

    'problem' stacks whole problems per device (the PR 3 serving batch);
    'spin' shards the spin axis of each problem over the mesh's ``axis``
    devices via `shard_map` collectives (DESIGN.md §11).  'auto' picks
    'spin' only when a real multi-device axis exists, N is at/above
    SPIN_SHARD_MIN_N, and the shard width divides evenly — otherwise the
    problem-partitioned path is both simpler and faster.
    """
    if partition not in ("problem", "spin", "auto"):
        raise ValueError(f"unknown partition {partition!r}")
    if partition != "auto":
        return partition
    p = spin_axis_size(mesh, axis)
    if p > 1 and int(n) >= SPIN_SHARD_MIN_N and int(n) % p == 0:
        return "spin"
    return "problem"


def resolve_noise_mode(noise_mode: str, noise: str) -> str:
    """Resident-kernel noise datapath: 'streamed' (in-kernel xorshift, no
    noise buffer) vs 'pregen' (the legacy per-plateau (C, R, N) buffer).
    'auto' streams whenever the source is xorshift; threefry cannot be
    reproduced in-kernel, so it always pregenerates."""
    if noise_mode == "auto":
        return "streamed" if noise == "xorshift" else "pregen"
    if noise_mode not in ("streamed", "pregen"):
        raise ValueError(f"unknown noise_mode {noise_mode!r}")
    if noise_mode == "streamed" and noise != "xorshift":
        raise ValueError("noise_mode='streamed' requires noise='xorshift'")
    return noise_mode


# ---------------------------------------------------------------------------
# Shape buckets and padded problems (the serving substrate, DESIGN.md §7)
# ---------------------------------------------------------------------------
def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    n = int(n)
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def bucket_n(n: int, min_bucket: int = 64) -> int:
    """The serving shape bucket for an N-spin problem: power-of-two width.

    Every instance is zero-padded up to its bucket so heterogeneous request
    streams share compiled executables (one program per bucket, not per N).
    """
    if n <= 0:
        raise ValueError(f"need n > 0, got {n}")
    return max(next_pow2(int(min_bucket)), next_pow2(n))


def pad_model(model: IsingModel, n_bucket: int) -> IsingModel:
    """Zero-pad an Ising model to ``n_bucket`` spins.

    Padded rows carry h=0 and self-index/zero-weight adjacency, so their
    local field is identically 0 and they contribute nothing to H: the live
    lanes of a padded run evolve exactly as in the unpadded run (given a
    padding-invariant noise stream — see :func:`padded_noise_init`).
    """
    if model.n == n_bucket:
        return model
    if model.n > n_bucket:
        raise ValueError(f"model has {model.n} spins > bucket {n_bucket}")
    pad = n_bucket - model.n
    d = model.max_degree
    h = np.concatenate([np.asarray(model.h, np.int32), np.zeros(pad, np.int32)])
    idx = np.concatenate(
        [
            np.asarray(model.nbr_idx, np.int32),
            np.tile(np.arange(model.n, n_bucket, dtype=np.int32)[:, None], (1, d)),
        ],
        axis=0,
    )
    w = np.concatenate(
        [np.asarray(model.nbr_w, np.int32), np.zeros((pad, d), np.int32)], axis=0
    )
    return IsingModel(
        n=n_bucket, h=h, nbr_idx=idx, nbr_w=w, name=f"{model.name}@pad{n_bucket}"
    )


def padded_noise_init(noise: str, seed: int, n_trials: int, n_live: int, n_bucket: int):
    """Init a noise state over (n_trials, n_bucket) lanes, padding-invariant.

    The live lanes [0, n_live) are seeded exactly as an unpadded
    ``xorshift_init(seed, (n_trials, n_live))`` run would seed them; pad
    lanes get an independent (inert) stream.  Because xorshift lanes are
    elementwise-independent, a bucket-padded run is then bit-identical to
    the unpadded run on the live lanes — the padding-invariance property the
    serving layer relies on.

    ``threefry`` draws are shape-dependent, so threefry has no
    padding-invariant form; it is supported for service use but padded runs
    are *not* bit-comparable to unpadded ones.
    """
    if noise == "xorshift":
        live = xorshift_init(seed, (n_trials, n_live))
        if n_bucket == n_live:
            return live
        pad = xorshift_init(seed ^ 0x9E3779B9, (n_trials, n_bucket - n_live))
        return jnp.concatenate([live, pad], axis=-1)
    if noise == "threefry":
        return jax.random.PRNGKey(seed)
    raise ValueError(f"unknown noise {noise!r}")


def padded_noise_init_slice(seed: int, n_trials: int, n_live: int,
                            n_bucket: int, lo: int, hi: int) -> np.ndarray:
    """Columns [lo, hi) of :func:`padded_noise_init` ('xorshift'), shard-local.

    Bit-identical to ``padded_noise_init('xorshift', ...)[..., lo:hi]``
    without materializing the full (4, T, n_bucket) lane array: live columns
    are seeded from the *unpadded* (T, n_live) lane grid, pad columns from
    the independent pad stream, each via :func:`repro.core.rng
    .xorshift_init_slice`.  This is the PR 4 padding-invariance extended to
    shard-local lane offsets — each device of a spin-sharded run seeds only
    its own shard, and the result equals the single-device stream
    (DESIGN.md §11; property-tested).
    """
    lo, hi = int(lo), int(hi)
    n_live, n_bucket = int(n_live), int(n_bucket)
    if not 0 <= lo <= hi <= n_bucket:
        raise ValueError(f"slice [{lo}, {hi}) outside [0, {n_bucket})")
    parts = []
    if lo < n_live:
        parts.append(xorshift_init_slice(
            seed, (n_trials, n_live), lo, min(hi, n_live)
        ))
    if hi > n_live:
        parts.append(xorshift_init_slice(
            seed ^ 0x9E3779B9, (n_trials, n_bucket - n_live),
            max(lo, n_live) - n_live, hi - n_live,
        ))
    if not parts:
        return np.zeros((4, int(n_trials), 0), np.uint32)
    return parts[0] if len(parts) == 1 else np.concatenate(parts, axis=-1)


# ---------------------------------------------------------------------------
# Batched backends: B stacked problems through one compiled plateau program
# ---------------------------------------------------------------------------
class BatchedBackend:
    """Batched execution of B stacked, bucket-padded problems — the engine.

    Problem arrays are **call-time arguments** (a dict of stacked jnp arrays
    from :meth:`stack`), not constructor state, so one jitted program per
    (backend, N_bucket, B, n_trials, schedule signature) serves every request
    group that shape-matches — the serving layer's compiled-executable cache
    keys on exactly those statics (DESIGN.md §7).  The library drivers run
    the same classes at B=1 (:func:`single_problem_backend`).

    State layout is :class:`EngineState` with a leading problem axis:
    spins (B, T, N), best_H (B, T), xorshift lanes (B, 4, T, N).  ``sparse``
    and ``dense`` vmap the one-problem plateau scan over the problem axis;
    ``pallas`` launches the resident kernel on a (B, R-tile) grid.  All
    three are bit-identical per problem to one another and, on live lanes,
    to an unpadded run of the same problem — property-tested.
    """

    name = "abstract"
    # Whether the field streams row slabs of the couplings through a scan
    # (row-tiled popcount or j_mode='tiled') rather than one block.
    row_tiled = False
    # Whether run_shots' ``live`` mask saves work: a lane marked dead then
    # costs no compute and keeps its spins, Itanh and bests.  Under vmap a
    # per-lane branch becomes a select that computes both sides, so only
    # the resident kernels skip.
    skips_dead_lanes = False

    def __init__(
        self,
        *,
        n_bucket: int,
        n_trials: int,
        n_rnd: int = 2,
        noise: str = "xorshift",
        storage_layout: str = "dense",
        n_replicas: int = 0,
    ):
        if storage_layout not in ("dense", "packed"):
            raise ValueError(f"unknown storage_layout {storage_layout!r}")
        self.n_bucket = int(n_bucket)
        self.n_trials = int(n_trials)
        self.n_rnd = int(n_rnd)
        self.noise = noise
        self.storage_layout = storage_layout
        self.n_replicas = int(n_replicas)
        if self.n_replicas:
            if self.n_replicas < 2:
                raise ValueError(
                    f"n_replicas must be >= 2, got {self.n_replicas}"
                )
            if self.n_trials % self.n_replicas:
                raise ValueError(
                    f"n_trials={self.n_trials} not divisible by "
                    f"n_replicas={self.n_replicas}"
                )
        lanes = (self.n_trials, self.n_bucket)
        if noise == "xorshift":
            self._noise_step_one = xorshift_next_bits
        elif noise == "threefry":

            def step(key):
                key, sub = jax.random.split(key)
                return key, threefry_noise(sub, lanes)

            self._noise_step_one = step
        else:
            raise ValueError(f"unknown noise {noise!r}")
        self._noise_step = jax.vmap(self._noise_step_one)

    # -- host side --------------------------------------------------------
    def stack(self, models: Sequence[IsingModel],
              couplings=model_couplings) -> dict:
        """Pad each model to the bucket and stack its arrays over axis 0.

        A layout packed from J's bitplanes reads each model's
        ``couplings(model)`` (:func:`model_couplings` unless the caller
        already holds them); the others read the adjacency.
        """
        raise NotImplementedError

    def init_noise(self, seeds: Sequence[int], n_lives: Sequence[int]):
        """Stacked per-problem noise states (padding-invariant live lanes)."""
        return jnp.stack(
            [
                padded_noise_init(self.noise, int(s), self.n_trials, int(nl), self.n_bucket)
                for s, nl in zip(seeds, n_lives)
            ]
        )

    # -- traced -----------------------------------------------------------
    def init_state(self, problem: dict, noise0):
        """Random ±1 start from the first noise draw (shared stream layout)."""
        ns, r0 = self._noise_step(noise0)
        m0 = r0.astype(jnp.int8)
        itanh0 = jnp.where(m0 > 0, 0, -1).astype(jnp.int32)
        best_H = jnp.full(m0.shape[:-1], BIG_ENERGY, jnp.int32)
        st = EngineState(ns, m0, itanh0, best_H, m0)
        return pack_state(st) if self.storage_layout == "packed" else st

    def run_plateau(self, problem: dict, state, i0, *, length, eligible,
                    jperp=0):
        if self.storage_layout == "packed":
            st = unpack_state(state, self.n_bucket)
            st = self._run_plateau_dense(
                problem, st, i0, length=length, eligible=eligible, jperp=jperp
            )
            return pack_state(st)
        return self._run_plateau_dense(
            problem, state, i0, length=length, eligible=eligible, jperp=jperp
        )

    def run_shots(self, problem: dict, state, plateaus, n_shots: int,
                  live=None):
        """Advance ``n_shots`` full iterations (plateau chains) — one chunk.

        The chunk launch boundary is where the storage layout is *real*:
        under 'packed' the state entering/leaving this method — the HBM-
        resident buffers between service chunks — carries spins as uint32
        bitplanes.  ``live`` is a (B,) int32 mask of the lanes whose answer
        is still wanted; where :attr:`skips_dead_lanes` holds, a 0 lane
        costs no compute and keeps its spins, Itanh and bests, else the
        mask is ignored.
        """
        if self.storage_layout == "packed":
            st = unpack_state(state, self.n_bucket)
            st = self._run_shots_dense(problem, st, plateaus, n_shots, live)
            return pack_state(st)
        return self._run_shots_dense(problem, state, plateaus, n_shots, live)

    def run_plateau_traced(self, problem: dict, state, plateau: Plateau, *,
                           track_energy: bool = False, emit: bool = False):
        """One plateau with its per-cycle outputs (the library drivers).

        The resident kernels produce no per-cycle outputs, so a plateau that
        wants energy traces or trajectory planes runs on the scan path
        through this backend's own field — bit-identical to its production
        program.  Wanting neither, it is :meth:`run_plateau`.

        Returns ``(state', trace, planes)``: trace is ``(mean_H, min_H)``,
        each (C,) over every lane, aligned to the produced states, when
        ``track_energy``; planes the (C, B, T, ceil(N/32)) uint32 bit-packed
        trajectory when ``emit``.
        """
        p = plateau
        if not (track_energy or emit):
            st = self.run_plateau(problem, state, p.i0, length=p.length,
                                  eligible=p.eligible, jperp=p.jperp)
            return st, None, None
        packed = self.storage_layout == "packed"
        st = unpack_state(state, self.n_bucket) if packed else state
        st, trace, planes = self._plateau_scan(
            problem, st, p, track_energy=track_energy, emit=emit
        )
        return (pack_state(st) if packed else st), trace, planes

    def _plateau_scan(self, problem: dict, st: EngineState, p: Plateau, *,
                      track_energy: bool = False, emit: bool = False):
        """:func:`run_plateau_scan` over every lane at once (dense layout)."""
        return run_plateau_scan(
            lambda m: self._field(problem, m), self._noise_step,
            problem["h"][:, None, :], self.n_rnd, st, p.i0,
            length=p.length, eligible=p.eligible, track_energy=track_energy,
            emit=emit, energy_fn=self._energy, jperp=p.jperp,
            n_replicas=self.n_replicas,
        )

    def _field_one(self, prob: dict, m: jnp.ndarray) -> jnp.ndarray:
        """Local fields of one problem lane's (T, N) spins."""
        raise NotImplementedError

    def _field(self, problem: dict, m: jnp.ndarray) -> jnp.ndarray:
        """Local fields of every lane's (B, T, N) spins."""
        return jax.vmap(self._field_one)(problem, m)

    def _energy(self, m, field, h):
        return energy_from_field(m, field, h)

    def _run_plateau_dense(self, problem: dict, state: EngineState, i0, *,
                           length, eligible, jperp=0):
        raise NotImplementedError

    def _run_shots_dense(self, problem: dict, state: EngineState, plateaus,
                         n_shots: int, live=None):
        raise NotImplementedError

    def finalize(self, state) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if self.storage_layout == "packed":
            return state.best_H, unpack_spins(state.best_m_packed, self.n_bucket)
        return state.best_H, state.best_m


class _VmapBatchedBackend(BatchedBackend):
    """Shared vmap-over-problems implementation (sparse/dense fields)."""

    def _run_one_plateaus(self, prob, st, plateaus):
        field_fn = lambda m: self._field_one(prob, m)  # noqa: E731
        for p in plateaus:
            st, _, _ = run_plateau_scan(
                field_fn, self._noise_step_one, prob["h"], self.n_rnd, st,
                p.i0, length=p.length, eligible=p.eligible,
                jperp=p.jperp, n_replicas=self.n_replicas,
            )
        return st

    def _run_plateau_dense(self, problem, state, i0, *, length, eligible,
                           jperp=0):
        p = (Plateau(int(i0), int(length), bool(eligible), int(jperp)),)
        return jax.vmap(lambda pr, st: self._run_one_plateaus(pr, st, p))(
            problem, state
        )

    def _run_shots_dense(self, problem, state, plateaus, n_shots, live=None):
        plateaus = tuple(plateaus)

        def one(prob, st):
            def iteration(st, _):
                return self._run_one_plateaus(prob, st, plateaus), None

            st, _ = jax.lax.scan(iteration, st, None, length=n_shots)
            return st

        return jax.vmap(one)(problem, state)


def pad_degree(model: IsingModel, d: int) -> IsingModel:
    """Pad a model's adjacency to ``d`` neighbor columns.

    The extra columns are self-index/zero-weight entries, so the gathered
    local field is unchanged — degree padding is results-invariant the same
    way bucket padding is (:func:`pad_model`).  The sparse/tiled stacked
    representation's neighbor width is program-structural, so anything that
    splices problems into an existing stacked batch (the streaming slot
    tables) must pre-pad every model to the batch's degree.
    """
    d = int(d)
    if model.max_degree == d:
        return model
    if model.max_degree > d:
        raise ValueError(
            f"model degree {model.max_degree} exceeds target degree {d}"
        )
    extra = d - model.max_degree
    idx, w = np.asarray(model.nbr_idx), np.asarray(model.nbr_w)
    self_idx = np.tile(np.arange(model.n, dtype=np.int32)[:, None], (1, extra))
    return IsingModel(
        n=model.n,
        h=np.asarray(model.h, np.int32),
        nbr_idx=np.concatenate([idx, self_idx], axis=1),
        nbr_w=np.concatenate([w, np.zeros((model.n, extra), np.int32)], axis=1),
        name=model.name,
    )


def _distinct(models):
    """``(distinct, lanes)``: each distinct model object once, in first-seen
    order, and for every lane the index of its model in ``distinct``.

    Identity, not equality: equal but distinct objects stay apart.  The
    caller's list holds every model, so no id is reused meanwhile.
    """
    index: dict = {}
    distinct: list = []
    lanes: list = []
    for m in models:
        k = index.get(id(m))
        if k is None:
            k = index[id(m)] = len(distinct)
            distinct.append(m)
        lanes.append(k)
    return distinct, lanes


def _stack_sparse_models(models, n_bucket: int) -> dict:
    """Stacked, bucket-padded adjacency views {h, nbr_idx, nbr_w}.

    Each distinct model is padded once; lanes that carry the same model
    object index its one copy.
    """
    distinct, lanes = _distinct(models)
    padded = [pad_model(m, n_bucket) for m in distinct]
    d = max(m.max_degree for m in padded)
    padded = [pad_degree(m, d) for m in padded]

    def stacked(field):
        views = [np.asarray(getattr(m, field), np.int32) for m in padded]
        return jnp.asarray(np.stack([views[k] for k in lanes]), jnp.int32)

    return {"h": stacked("h"), "nbr_idx": stacked("nbr_idx"),
            "nbr_w": stacked("nbr_w")}


def extract_slot(tree, slot: int):
    """Slice one problem lane out of a batched pytree, keeping a size-1 axis.

    Works on anything whose leaves carry the problem axis leading —
    :class:`EngineState` / :class:`PackedEngineState`, stacked problem dicts,
    noise-state stacks.  The size-1 leading axis makes the result directly
    comparable (and splicable) to a B=1 batched run of the same request,
    which is what makes per-slot checkpoints interchangeable with solo-group
    checkpoints.
    """
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a)[slot : slot + 1], tree)


def splice_slot(tree, slot: int, sub):
    """Write a size-1-problem-axis pytree into lane ``slot`` of a batched one.

    The slot-backfill primitive of the streaming service: because per-problem
    lanes never interact (the padding-invariance property), replacing one
    lane's problem arrays + engine state leaves every other lane's
    trajectory bit-identical.  ``sub`` must be structure- and shape-
    compatible with ``extract_slot(tree, slot)``.
    """
    return jax.tree_util.tree_map(
        lambda a, s: jnp.asarray(a).at[slot].set(jnp.asarray(s)[0]), tree, sub
    )


class BatchedSparseBackend(_VmapBatchedBackend):
    """Padded-adjacency gather field, vmapped over the problem axis."""

    name = "sparse"

    def stack(self, models, couplings=model_couplings):
        return _stack_sparse_models(models, self.n_bucket)

    def _field_one(self, prob, m):
        return local_fields_sparse(
            m.astype(jnp.int32), prob["h"], prob["nbr_idx"], prob["nbr_w"]
        )


def _stack_dense_models(models, n_bucket: int, j_dtype) -> dict:
    """Stacked, bucket-padded dense views {h (B,N), J (B,N,N)}.

    Each distinct model's J is built once; lanes that carry the same model
    object stack its one copy.
    """
    from repro.kernels.ssa_update import pad_to  # lazy: keeps core light

    distinct, lanes = _distinct(models)
    Js, hs = [], []
    for m in distinct:
        Js.append(
            pad_to(pad_to(jnp.asarray(m.dense_J(), j_dtype), 0, n_bucket), 1, n_bucket)
        )
        hs.append(pad_to(jnp.asarray(m.h, jnp.int32), 0, n_bucket))
    return {"h": jnp.stack([hs[k] for k in lanes]),
            "J": jnp.stack([Js[k] for k in lanes])}


def _stack_packed_models(models, n_bucket: int, j_bits: int,
                         couplings=model_couplings) -> dict:
    """Stacked, bucket-padded PackedJ views {h, sign, mags, base}.

    ``j_bits`` forces the magnitude-plane count for *every* model so the
    stacked ``mags`` tensor has one uniform shape (a program-structural
    parameter — the executable cache keys on it); callers pass the group
    maximum of :func:`model_weight_bits`.  Each distinct model is packed
    once on the host, from ``couplings(model)`` (the service passes the
    coalescing its weight-bit scan already made; padding rows carry no
    coupling, so the unpadded model's is the padded model's); the (B, ...)
    arrays index those packings by lane and move to the device in one
    transfer each.
    """
    distinct, lanes = _distinct(models)
    planes = []   # per distinct model: (h, sign, mags, base) host arrays
    for m in distinct:
        sign, mags, base = coalesced_planes(couplings(m), n_bucket,
                                            n_bits=j_bits)
        h = np.zeros(n_bucket, np.int32)
        h[:m.n] = m.h
        planes.append((h, sign, mags, base))
    return {key: jnp.asarray(np.stack([planes[k][i] for k in lanes]))
            for i, key in enumerate(("h", "sign", "mags", "base"))}


class BatchedDenseBackend(_VmapBatchedBackend):
    """(T,N)·(N,N) matmul field per problem, vmapped over the problem axis.

    ``j_mode='tiled'`` (auto above TILED_J_THRESHOLD spins) stacks the
    adjacency instead of dense J and streams (tile_n, N) slabs per problem —
    no (B, N, N) buffer ever exists, which is what admits G77/G81-class
    buckets through the service.

    ``field_mode='popcount'`` stacks `PackedJ` bitplanes instead (``j_bits``
    magnitude planes each, the group maximum) and contracts by XNOR-popcount
    — exact-integer equal to the matmul, ~32× less J traffic per problem.
    """

    name = "dense"

    def __init__(self, *, j_dtype=jnp.float32, j_mode: str = "auto",
                 tile_n: int = 512, field_mode: str = "dense",
                 j_bits: int = 1, double_buffer: bool = False, **kw):
        super().__init__(**kw)
        self.j_dtype = j_dtype
        self.j_mode = resolve_j_mode(j_mode, self.n_bucket)
        self.tile_n = int(tile_n)
        self.double_buffer = bool(double_buffer)
        self.j_bits = int(j_bits)
        self.field_mode = resolve_field_mode(field_mode, self.j_bits)
        self._pc_tile = (
            None if self.n_bucket <= TILED_J_THRESHOLD else self.tile_n
        )
        self.row_tiled = (self._pc_tile is not None
                          if self.field_mode == "popcount"
                          else self.j_mode == "tiled")

    def stack(self, models, couplings=model_couplings):
        if self.field_mode == "popcount":
            return _stack_packed_models(models, self.n_bucket, self.j_bits,
                                        couplings)
        if self.j_mode == "tiled":
            return _stack_sparse_models(models, self.n_bucket)
        return _stack_dense_models(models, self.n_bucket, self.j_dtype)

    def _field_one(self, prob, m):
        if self.field_mode == "popcount":
            pj = PackedJ(prob["sign"], prob["mags"], prob["base"])
            return local_fields_popcount(
                pack_spins(m), prob["h"], pj, tile_n=self._pc_tile
            )
        if self.j_mode == "tiled":
            return local_fields_tiled(
                m, prob["h"], prob["nbr_idx"], prob["nbr_w"],
                tile_n=self.tile_n, double_buffer=self.double_buffer,
            )
        return local_fields_dense(m, prob["h"], prob["J"])


class BatchedPallasBackend(BatchedBackend):
    """The resident plateau kernel on a (B, R-tile) grid.

    One `pallas_call` per plateau advances **all problems and all trials**:
    each grid step (b, i) pins problem b's J in VMEM and runs every cycle of
    the plateau for one R-tile of trials — the serving transcription of the
    FPGA's "one pipeline, many instances" operating mode.

    With ``xorshift`` noise the plateau is the streamed-noise packed kernel
    (:func:`repro.kernels.ssa_update.ssa_plateau_packed_batched`): noise is
    generated in-kernel from the carried lanes and the HBM-facing spin refs
    are uint32 bitplanes — no (B, C, T, N) noise buffer exists anywhere.
    ``threefry`` keeps per-plateau pregen (reference path only).

    ``field_mode='popcount'`` upgrades to the bit-parallel chain kernel
    (:func:`repro.kernels.ssa_update.ssa_plateau_popcount_batched`): J is
    VMEM-resident as stacked `PackedJ` bitplanes (``j_bits`` planes, the
    group maximum) and :meth:`run_shots` launches each full iteration's
    plateau chain as ONE `pallas_call` — multi-plateau residency.

    All three kernels take :meth:`run_shots`' ``live`` mask into SMEM: the
    grid steps of a dead lane copy its state through and compute nothing.
    Plateaus that want per-cycle outputs (:meth:`run_plateau_traced`) run
    the scan path over the same arithmetic instead.
    """

    name = "pallas"
    skips_dead_lanes = True

    def __init__(self, *, j_dtype=jnp.float32, block_r: int = 8,
                 interpret: Optional[bool] = None, noise_mode: str = "auto",
                 field_mode: str = "dense", j_bits: int = 1, **kw):
        super().__init__(**kw)
        from repro.kernels import ssa_update as kssa  # lazy

        self._kssa = kssa
        self.j_dtype = j_dtype
        # SSQA: replica rings demand whole rings per R-tile (the ring roll
        # happens over the tile's trial axis), so n_replicas pins block_r.
        self.block_r = self.n_replicas if self.n_replicas else int(block_r)
        self.interpret = interpret
        self.noise_mode = resolve_noise_mode(noise_mode, self.noise)
        self.j_bits = int(j_bits)
        self.field_mode = resolve_field_mode(field_mode, self.j_bits)
        if self.field_mode == "popcount" and self.noise_mode != "streamed":
            raise ValueError(
                "field_mode='popcount' on the batched pallas backend "
                "requires noise_mode='streamed' (noise='xorshift')"
            )
        _require_vmem_fit(
            self.n_bucket, noise=self.noise, noise_mode=self.noise_mode,
            field_mode=self.field_mode, j_bits=self.j_bits,
            block_r=self.block_r, n_replicas=self.n_replicas, j_dtype=j_dtype,
        )

    def stack(self, models, couplings=model_couplings):
        if self.field_mode == "popcount":
            return _stack_packed_models(models, self.n_bucket, self.j_bits,
                                        couplings)
        return _stack_dense_models(models, self.n_bucket, self.j_dtype)

    def _field_one(self, prob, m):
        # The scan path (per-cycle outputs) keeps the kernel's arithmetic:
        # XNOR-popcount on the same bitplanes, or the f32 J matmul.
        if self.field_mode == "popcount":
            pj = PackedJ(prob["sign"], prob["mags"], prob["base"])
            return local_fields_popcount(pack_spins(m), prob["h"], pj)
        return local_fields_dense(m, prob["h"], prob["J"])

    def _pregen(self, ns, length: int):
        def draw(ns, _):
            ns, r = self._noise_step(ns)
            return ns, r.astype(jnp.int8)

        return jax.lax.scan(draw, ns, None, length=length)

    def _plateau_packed(self, problem, st: PackedEngineState, i0, length,
                        eligible, jperp=0, live=None) -> PackedEngineState:
        jperp = int(jperp)
        mp_o, it_o, rng_o, bh_o, bmp_o = self._kssa.ssa_plateau_packed_batched(
            st.m_packed,
            st.itanh,
            problem["J"],
            problem["h"],
            st.noise_state,
            jnp.asarray(i0, jnp.int32),
            st.best_H,
            st.best_m_packed,
            n_cycles=int(length),
            n_rnd=self.n_rnd,
            eligible=bool(eligible),
            block_r=self.block_r,
            interpret=self.interpret,
            jperp=jperp,
            n_replicas=self.n_replicas if jperp else 0,
            live=live,
        )
        return PackedEngineState(rng_o, mp_o, it_o, bh_o, bmp_o)

    def _chain_popcount(self, problem, st: PackedEngineState, i0_sched,
                        fold_sched, jperp_sched=None,
                        live=None) -> PackedEngineState:
        mp_o, it_o, rng_o, bh_o, bmp_o = self._kssa.ssa_plateau_popcount_batched(
            st.m_packed,
            st.itanh,
            problem["sign"],
            problem["mags"],
            problem["base"],
            problem["h"],
            st.noise_state,
            jnp.asarray(i0_sched, jnp.int32),
            jnp.asarray(fold_sched, jnp.int32),
            st.best_H,
            st.best_m_packed,
            n_rnd=self.n_rnd,
            block_r=self.block_r,
            interpret=self.interpret,
            jperp_sched=(
                None if jperp_sched is None
                else jnp.asarray(jperp_sched, jnp.int32)
            ),
            n_replicas=self.n_replicas,
            live=live,
        )
        return PackedEngineState(rng_o, mp_o, it_o, bh_o, bmp_o)

    def run_plateau(self, problem, state, i0, *, length, eligible, jperp=0):
        if self.noise_mode != "streamed":
            return super().run_plateau(
                problem, state, i0, length=length, eligible=eligible,
                jperp=jperp,
            )
        packed_in = self.storage_layout == "packed"
        st = state if packed_in else pack_state(state)
        if self.field_mode == "popcount":
            i0_sched = jnp.broadcast_to(
                jnp.asarray(i0, jnp.int32), (int(length),)
            )
            fold_sched = np.asarray(
                [0] + [int(bool(eligible))] * int(length), np.int32
            )
            jperp_sched = (
                np.full(int(length), int(jperp), np.int32) if jperp else None
            )
            st = self._chain_popcount(
                problem, st, i0_sched, fold_sched, jperp_sched
            )
        else:
            st = self._plateau_packed(problem, st, i0, length, eligible, jperp)
        return st if packed_in else unpack_state(st, self.n_bucket)

    def run_shots(self, problem, state, plateaus, n_shots, live=None):
        plateaus = tuple(plateaus)
        if self.noise_mode != "streamed":
            return super().run_shots(problem, state, plateaus, n_shots, live)
        packed_in = self.storage_layout == "packed"
        st = state if packed_in else pack_state(state)

        if self.field_mode == "popcount":
            # Multi-plateau residency: one launch per iteration, the whole
            # plateau chain carried inside the kernel.
            i0_sched, fold_sched, jperp_sched = plateau_cycle_schedules(plateaus)
            if not jperp_sched.any():
                jperp_sched = None  # classical chain: keep the v1 jaxpr

            def iteration(st, _):
                return self._chain_popcount(
                    problem, st, i0_sched, fold_sched, jperp_sched, live
                ), None
        else:

            def iteration(st, _):
                for p in plateaus:
                    st = self._plateau_packed(
                        problem, st, p.i0, p.length, p.eligible, p.jperp, live
                    )
                return st, None

        st, _ = jax.lax.scan(iteration, st, None, length=n_shots)
        return st if packed_in else unpack_state(st, self.n_bucket)

    def _run_plateau_dense(self, problem, state, i0, *, length, eligible,
                           jperp=0, live=None):
        if jperp:
            # The pregen kernel has no replica coupling: an SSQA plateau
            # runs the scan path over the same f32 J, every lane.
            p = Plateau(int(i0), int(length), bool(eligible), int(jperp))
            return self._plateau_scan(problem, state, p)[0]
        ns, noise = self._pregen(state.noise_state, length)  # (C, B, T, N)
        noise = jnp.swapaxes(noise, 0, 1)                    # (B, C, T, N)
        m_o, it_o, bh_o, bm_o = self._kssa.ssa_plateau_batched(
            state.m.astype(jnp.float32),
            state.itanh,
            problem["J"],
            problem["h"],
            noise,
            jnp.asarray(i0, jnp.int32),
            state.best_H,
            state.best_m,
            n_rnd=self.n_rnd,
            eligible=bool(eligible),
            block_r=self.block_r,
            interpret=self.interpret,
            live=live,
        )
        return EngineState(ns, m_o.astype(jnp.int8), it_o, bh_o, bm_o)

    def _run_shots_dense(self, problem, state, plateaus, n_shots, live=None):
        def iteration(st, _):
            for p in plateaus:
                st = self._run_plateau_dense(
                    problem, st, p.i0, length=p.length, eligible=p.eligible,
                    jperp=p.jperp, live=live,
                )
            return st, None

        st, _ = jax.lax.scan(iteration, state, None, length=n_shots)
        return st


BATCHED_BACKENDS = {
    "sparse": BatchedSparseBackend,
    "dense": BatchedDenseBackend,
    "pallas": BatchedPallasBackend,
}


def make_batched_backend(
    backend: str = None,
    *,
    n_bucket: int,
    n_trials: int,
    n_rnd: int = 2,
    noise: str = None,
    partition: str = None,
    mesh=None,
    partition_axis: str = "model",
    config=None,
    **opts,
) -> BatchedBackend:
    if config is not None:
        from .config import legacy_kwargs_to_config

        cfg = legacy_kwargs_to_config(
            "make_batched_backend", config,
            backend=backend, noise=noise, partition=partition,
        )
        backend, noise, partition = cfg.backend, cfg.noise, cfg.partition
        mesh = cfg.mesh if mesh is None else mesh
        merged = cfg.engine_opts()
        merged.update(opts)
        opts = merged
    if backend is None:
        backend = "sparse"
    if noise is None:
        noise = "xorshift"
    if partition is None:
        partition = "problem"
    part = resolve_partition(partition, n_bucket, mesh, axis=partition_axis)
    if part == "spin":
        from .distributed import BatchedSpinShardedBackend  # lazy: circular

        return BatchedSpinShardedBackend(
            base_backend=backend, mesh=mesh, axis=partition_axis,
            n_bucket=n_bucket, n_trials=n_trials, n_rnd=n_rnd, noise=noise,
            **opts,
        )
    if isinstance(backend, str):
        backend = resolve_backend(backend, n_bucket, noise=noise, **opts)
    try:
        cls = BATCHED_BACKENDS[backend]
    except (KeyError, TypeError):
        raise ValueError(
            f"unknown batched backend {backend!r}; known: {sorted(BATCHED_BACKENDS)}"
        ) from None
    return cls(n_bucket=n_bucket, n_trials=n_trials, n_rnd=n_rnd, noise=noise, **opts)


def single_problem_backend(
    model: IsingModel,
    backend: str = "sparse",
    *,
    n_trials: int,
    n_rnd: int = 2,
    noise: str = "threefry",
    partition: str = "problem",
    mesh=None,
    partition_axis: str = "model",
    **opts,
) -> Tuple[BatchedBackend, dict]:
    """``(bk, problem)``: a batched backend at B=1 and ``model`` stacked on it.

    The library drivers' engine.  The bucket is the model's own width, so
    the noise draws keep the unpadded shape (threefry draws are shape-
    dependent).  Under ``partition='spin'`` the bucket rounds up to a
    multiple of the mesh axis instead — the pad columns are inert and the
    xorshift lanes that partition requires are padding-invariant — and
    ``mesh`` defaults to every device.  The field-capable backends get the
    model's own ``j_bits``; ``backend='auto'`` routes on them.  Callers
    slice lane 0, and the first ``model.n`` spins, out of :meth:`finalize`.
    """
    part = resolve_partition(partition, model.n, mesh, axis=partition_axis)
    n_bucket = model.n
    j_bits = model_weight_bits(model)
    if part == "spin":
        from repro.sharding import mesh_axis_size, spin_mesh  # lazy

        mesh = spin_mesh(axis=partition_axis) if mesh is None else mesh
        n_dev = mesh_axis_size(mesh, partition_axis)
        n_bucket = -(-model.n // n_dev) * n_dev
        opts.setdefault("j_bits", j_bits)
    else:
        backend = resolve_backend(backend, model.n, **{
            "noise": noise, "j_bits": j_bits, **opts,
        })
        if backend in ("dense", "pallas"):
            opts.setdefault("j_bits", j_bits)
    bk = make_batched_backend(
        backend, n_bucket=n_bucket, n_trials=n_trials, n_rnd=n_rnd,
        noise=noise, partition=part, mesh=mesh,
        partition_axis=partition_axis, **opts,
    )
    return bk, bk.stack([model])
