"""Pallas TPU kernels for the SSA/HA-SSA spin update (DESIGN.md §2.3).

The FPGA's spin-gate array computes, for all spins in one clock,

    field_i = h_i + Σ_j J_ij m_j        (MUX tree + adder)
    Itanh   = clamp(field + n·r + Itanh, -I0, I0-1)   (saturating counter)
    m       = sign(Itanh)

On TPU we batch replicas (trials) on a leading axis so the field computation
is a (R,N)·(N,N) matmul on the MXU; the FSM is a fused VPU epilogue.  Three
kernels:

* :func:`local_field` — tiled matmul ``m @ J + h`` with a standard
  (R-tile, N-tile, K-tile) grid and a float32 VMEM accumulator.  Used as the
  drop-in dense-field backend.  Exact: ±1 spins × integer J accumulate in
  f32 (< 2^24).

* :func:`ssa_plateau` / :func:`ssa_plateau_batched` — the **resident**
  kernel: one launch executes all C cycles of a temperature plateau with J
  pinned in VMEM, streaming only pre-generated noise in and nothing but
  final state + running best out.  Per-cycle HBM traffic drops from O(N²)
  (re-reading J) to O(R·N) (noise), raising arithmetic intensity by ~C×.
  It also fuses the solution tracking (energy + arg-best restricted to
  storage-eligible plateaus), which is HA-SSA's storage policy executed
  entirely on-chip.  Since the packed kernel landed this is the *threefry
  reference path* (threefry noise cannot be generated in-kernel).

* :func:`ssa_plateau_packed` / :func:`ssa_plateau_packed_batched` — the
  **streamed-noise packed** kernel (DESIGN.md §4): the HBM-facing spin refs
  are uint32 bitplanes (`repro.kernels.bitplane` layout) and the per-cycle
  noise is generated *inside* the kernel by stepping carried xorshift128
  lanes, bit-identical to `repro.core.rng.xorshift_next_bits` — the noise
  buffer is gone entirely and per-plateau HBM traffic is O(R·N) lanes +
  O(R·N/32) packed spins.  The production path for xorshift noise.

* :func:`ssa_plateau_popcount` / :func:`ssa_plateau_popcount_batched` — the
  **bit-parallel multi-plateau** kernel (DESIGN.md §8): the field
  contraction itself runs on the bitplanes via XNOR-popcount against a
  packed-J sign/magnitude layout (`repro.kernels.bitplane.PackedJ`), 32
  spins per word op, no f32 anywhere in the body (the MXU is idle — this is
  the software twin of the FPGA's XNOR/popcount adder tree).  One launch
  additionally carries I0 and eligibility across an *entire plateau chain*
  (per-cycle schedule operands), so a full iteration costs one kernel
  dispatch instead of one per plateau — the small-N launch-overhead fix.

All are validated against :mod:`.ref` oracles / the scan engine in
interpret mode (CPU) over a shape/dtype sweep, and compiled for a described
TPU v5e at the service buckets (tests/test_tpu_compile.py).  Every
`pallas_call` carries explicit Mosaic params: ``dimension_semantics`` and a
``vmem_limit_bytes`` derived from its block shapes
(:func:`plateau_vmem_bytes`); a kernel over the chip's budget raises
:class:`VmemBudgetError` before dispatch.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "local_field",
    "ssa_plateau",
    "ssa_plateau_batched",
    "ssa_plateau_packed",
    "ssa_plateau_packed_batched",
    "ssa_plateau_popcount",
    "ssa_plateau_popcount_batched",
    "pad_to",
    "default_interpret",
    "plateau_vmem_bytes",
    "vmem_budget_bytes",
    "VmemBudgetError",
]

def default_interpret() -> bool:
    """Whether kernels run in interpret mode when the caller does not say.

    Decided at call (trace) time from the platform JAX runs on, never at
    import: off the TPU the kernel body executes as Python (the validation
    mode of CPU test runs); on the TPU it always lowers to Mosaic.
    """
    return jax.default_backend() != "tpu"


# Whole-array scalar operands (I0, J⊥, schedules) live in SMEM: the kernels
# index them with the traced cycle counter, which VMEM loads cannot take.
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _skip_dead_lanes(kernel, n_in: int, state_in: Tuple[int, ...]):
    """``kernel`` behind a per-lane live mask, a leading (1, B) SMEM ref.

    Grid step (b, i) of a live lane (``live[b] != 0``) runs ``kernel`` as
    it is.  A dead lane's step copies its state inputs (``state_in``, ref
    indices among the ``n_in`` inputs) to the outputs, which follow the
    inputs in the same order, and computes nothing: its output blocks are
    written back either way, so they must hold the state and not whatever
    VMEM held.
    """

    def gated(live_ref, *refs):
        alive = live_ref[0, pl.program_id(0)] != 0

        @pl.when(alive)
        def _run():
            kernel(*refs)

        @pl.when(jnp.logical_not(alive))
        def _copy():
            for k, i in enumerate(state_in):
                refs[n_in + k][...] = refs[i][...]

    return gated


def _live_operand(live, B: int):
    """``(specs, args)`` that prepend the live mask to a batched kernel's
    operands; none when ``live`` is None (every lane live, the mask-free
    kernel)."""
    if live is None:
        return [], []
    return [_SMEM], [jnp.asarray(live, jnp.int32).reshape(1, B)]


LANE = 128
# VMEM per TensorCore of TPU v5e, the chip the kernels are sized for when no
# TPU backs JAX (`pltpu.get_tpu_info` gives the real figure on the chip), so
# routing decisions are the same in interpret mode as on the chip.
TARGET_VMEM_BYTES = 128 * 2**20
# Share of VMEM a resident kernel may claim; the rest stays with Mosaic for
# its internal scratch and relayout buffers.
VMEM_BUDGET_FRACTION = 0.75
# Added to a kernel's own estimate for its vmem_limit_bytes.  The estimate
# already sat above the smallest limit each kernel compiled under (v5e,
# 1024-4096 spins), so this is slack, not a correction.
_VMEM_HEADROOM = 4 * 2**20


class VmemBudgetError(ValueError):
    """A resident kernel's blocks do not fit the chip's VMEM budget."""


def vmem_budget_bytes() -> int:
    """VMEM bytes one resident kernel may claim on the current chip."""
    if jax.default_backend() == "tpu":
        cap = pltpu.get_tpu_info().vmem_capacity_bytes
    else:
        cap = TARGET_VMEM_BYTES
    return int(cap * VMEM_BUDGET_FRACTION)


def _buf_bytes(shape, dtype) -> int:
    """VMEM bytes of one buffer in Mosaic's (8·packing, 128) tiled layout."""
    item = jnp.dtype(dtype).itemsize
    *lead, rows, cols = (1, 1, *shape)[-max(2, len(shape)):]
    sub = 8 * max(1, 4 // item)
    return (math.prod(lead) * (-(-rows // sub) * sub)
            * (-(-cols // LANE) * LANE) * item)


def _vmem_bytes(blocks, scratch=(), temps=()) -> int:
    """Pipelined in/out blocks count twice (double-buffered); scratch and
    the body's large temporaries once."""
    return (2 * sum(_buf_bytes(*b) for b in blocks)
            + sum(_buf_bytes(*b) for b in (*scratch, *temps)))


def _compiler_params(kernel: str, need: int, grid_dims: int,
                     semantics: Optional[Tuple[str, ...]] = None):
    """Mosaic params for one `pallas_call`, refusing what cannot fit.

    ``vmem_limit_bytes`` is the kernel's own estimate plus the headroom
    Mosaic keeps for itself, capped at the budget; a kernel whose estimate
    exceeds :func:`vmem_budget_bytes` raises :class:`VmemBudgetError` here,
    before anything is dispatched.
    """
    budget = vmem_budget_bytes()
    if need > budget:
        raise VmemBudgetError(
            f"{kernel} needs ~{need / 2**20:.1f} MiB of VMEM for its resident "
            f"blocks, over the {budget / 2**20:.1f} MiB budget "
            f"({VMEM_BUDGET_FRACTION:.0%} of the chip's VMEM)"
        )
    return pltpu.CompilerParams(
        dimension_semantics=semantics or ("parallel",) * grid_dims,
        vmem_limit_bytes=min(budget, need + _VMEM_HEADROOM),
    )


def plateau_vmem_bytes(kernel: str, n: int, *, block_r: int = 8,
                       n_cycles: int = 1, j_dtype=jnp.float32,
                       j_bits: int = 1, n_replicas: int = 0,
                       field_tile: int = LANE) -> int:
    """VMEM estimate of one resident plateau kernel at ``n`` spins.

    ``kernel`` is 'pregen' (:func:`ssa_plateau_batched`), 'streamed'
    (:func:`ssa_plateau_packed_batched`) or 'popcount'
    (:func:`ssa_plateau_popcount_batched`).  The figure comes from the block
    shapes the wrapper hands `pallas_call` — the same function the wrapper
    derives its ``vmem_limit_bytes`` from — so the engine's resolvers can
    check it against :func:`vmem_budget_bytes` before choosing a backend.
    """
    np_ = n + (-n) % LANE
    nwp = np_ // 32
    br = block_r
    i32, u32, f32 = jnp.int32, jnp.uint32, jnp.float32
    plane = ((br, np_), i32)          # one (bR, Np) 32-bit state plane
    words = ((br, nwp), u32)          # packed spins of one R-tile
    best = ((br, 1), i32)
    vec = ((1, np_), i32)
    lanes = ((4, br, np_), u32)       # xorshift128 state
    # The body's per-cycle field/update values spill whole planes; the
    # codecs hold the bf16 pack weights and the (bR, Nw, 32) unpack buffer.
    temps = [plane] * 4
    codec = [((np_, nwp), jnp.bfloat16)] * 2 + [((br, nwp, 32), u32)]
    if kernel == "pregen":
        spins8 = ((br, np_), jnp.int8)
        blocks = [plane, plane, ((np_, np_), j_dtype), vec,
                  ((n_cycles, br, np_), jnp.int8), best, spins8,   # in
                  plane, plane, best, spins8]                      # out
        scratch = [plane, plane, best, plane]
    elif kernel == "streamed":
        blocks = [words, plane, ((np_, np_), j_dtype), vec, lanes, best,
                  words,                                           # in
                  words, plane, lanes, best, words]                # out
        scratch = [plane, plane, lanes, best, plane]
        temps += codec
    elif kernel == "popcount":
        planes = ((np_, nwp), u32)
        blocks = [words, plane, planes, ((j_bits, np_, nwp), u32), vec,
                  vec, lanes, best, words,                         # in
                  words, plane, lanes, best, words]                # out
        scratch = [words, plane, plane, lanes, best, words, plane]
        if n_replicas:
            scratch.append(((2, br, np_), i32))
        # XNOR words, masked words and their popcounts for one row tile.
        temps += codec + [((br, field_tile, nwp), u32)] * 3
    else:
        raise ValueError(f"unknown resident kernel {kernel!r}")
    return _vmem_bytes(blocks, scratch, temps)


def pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    """Zero-pad ``axis`` up to a multiple of ``mult`` (TPU lane alignment)."""
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


# ---------------------------------------------------------------------------
# Kernel A: tiled local-field matmul  field = m @ J + h
# ---------------------------------------------------------------------------
def _field_kernel(m_ref, j_ref, h_ref, out_ref, acc_ref, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        m_ref[...], j_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        out_ref[...] = (acc_ref[...] + h_ref[...].astype(jnp.float32)).astype(
            jnp.int32
        )


@functools.partial(
    jax.jit, static_argnames=("block_r", "block_n", "block_k", "interpret")
)
def local_field(
    m: jnp.ndarray,  # (R, N) ±1, any float/int dtype
    h: jnp.ndarray,  # (N,) int32
    J: jnp.ndarray,  # (N, N) float32/bfloat16 (integer-valued)
    *,
    block_r: int = 8,
    block_n: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """field = h + m @ J, int32 exact, via the tiled Pallas kernel."""
    interpret = default_interpret() if interpret is None else interpret
    R, N = m.shape
    mf = pad_to(pad_to(m.astype(J.dtype), 1, block_k), 0, block_r)
    Jp = pad_to(pad_to(J, 0, block_k), 1, block_n)
    hp = pad_to(h.astype(jnp.int32).reshape(1, -1), 1, block_n)
    Rp, Kp = mf.shape
    Np = Jp.shape[1]
    nk = Kp // block_k
    grid = (Rp // block_r, Np // block_n, nk)
    out = pl.pallas_call(
        functools.partial(_field_kernel, nk=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_r, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_r, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Rp, Np), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_r, block_n), jnp.float32)],
        compiler_params=_compiler_params(
            "local_field",
            _vmem_bytes(
                [((block_r, block_k), mf.dtype), ((block_k, block_n), Jp.dtype),
                 ((1, block_n), jnp.int32), ((block_r, block_n), jnp.int32)],
                [((block_r, block_n), jnp.float32)],
            ),
            3, ("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(mf, Jp, hp)
    return out[:R, :N]


# ---------------------------------------------------------------------------
# Kernel B: resident plateau kernel — C fused cycles, J pinned in VMEM
# ---------------------------------------------------------------------------
def _plateau_kernel(
    i0_ref,      # (1, 1) int32 SMEM-ish scalar
    m_ref,       # (1, bR, N) float32  spins ±1 (leading problem-block axis)
    it_ref,      # (1, bR, N) int32    Itanh state
    j_ref,       # (1, N, N)  J dtype  resident couplings of THIS problem
    h_ref,       # (1, 1, N)  int32    biases
    noise_ref,   # (1, C, bR, N) int8  per-cycle ±1 noise
    bh_ref,      # (1, bR, 1) int32    running best energy (input)
    bm_ref,      # (1, bR, N) int8     running best spins  (input)
    m_out,       # (1, bR, N) float32
    it_out,      # (1, bR, N) int32
    bh_out,      # (1, bR, 1) int32
    bm_out,      # (1, bR, N) int8
    m_s,         # scratch (bR, N) float32
    it_s,        # scratch (bR, N) int32
    bh_s,        # scratch (bR, 1) float32 (exact ints)
    bm_s,        # scratch (bR, N) float32 (±1)
    *,
    n_cycles: int,
    n_rnd: int,
    eligible: bool,
):
    m_s[...] = m_ref[0]
    it_s[...] = it_ref[0]
    bh_s[...] = bh_ref[0].astype(jnp.float32)
    bm_s[...] = bm_ref[0].astype(jnp.float32)
    i0 = i0_ref[0, 0]
    hf = h_ref[0].astype(jnp.float32)  # (1, N)
    jm = j_ref[0]

    def energy(m, field):
        # H = -(h·m + m·field)/2 ; exact in f32 for |field| < 2^24
        hm = jnp.sum(hf * m, axis=-1, keepdims=True)
        mf_ = jnp.sum(m * field, axis=-1, keepdims=True)
        return -(hm + mf_) * 0.5

    def track_best(c, m, field):
        if not eligible:
            return
        H = energy(m, field)
        better = H < bh_s[...]
        bh_s[...] = jnp.where(better, H, bh_s[...])
        bm_s[...] = jnp.where(better, m, bm_s[...])

    def body(c, _):
        field = (
            jnp.dot(m_s[...], jm, preferred_element_type=jnp.float32) + hf
        )
        # m_s currently holds m(t0+c): produced by THIS plateau for c >= 1.
        @pl.when(c >= 1)
        def _():
            track_best(c, m_s[...], field)

        r = noise_ref[0, c].astype(jnp.int32)
        I = field.astype(jnp.int32) + n_rnd * r + it_s[...]  # noqa: E741
        it_new = jnp.clip(I, -i0, i0 - 1)
        it_s[...] = it_new
        m_s[...] = jnp.where(it_new >= 0, 1.0, -1.0).astype(jnp.float32)
        return 0

    jax.lax.fori_loop(0, n_cycles, body, 0)
    # final state m(t0+C): one more field evaluation for its energy
    field = jnp.dot(m_s[...], jm, preferred_element_type=jnp.float32) + hf
    track_best(n_cycles, m_s[...], field)

    m_out[...] = m_s[...][None]
    it_out[...] = it_s[...][None]
    bh_out[...] = bh_s[...].astype(jnp.int32)[None]
    bm_out[...] = bm_s[...].astype(jnp.int8)[None]


@functools.partial(
    jax.jit,
    static_argnames=("n_rnd", "eligible", "block_r", "interpret"),
)
def ssa_plateau_batched(
    m: jnp.ndarray,       # (B, R, N) float32 ±1
    itanh: jnp.ndarray,   # (B, R, N) int32
    J: jnp.ndarray,       # (B, N, N) float32/bfloat16 — one J per problem
    h: jnp.ndarray,       # (B, N) int32
    noise: jnp.ndarray,   # (B, C, R, N) int8 ±1
    i0: jnp.ndarray,      # scalar int32 (shared: same schedule per bucket)
    best_H: jnp.ndarray,  # (B, R) int32
    best_m: jnp.ndarray,  # (B, R, N) int8
    *,
    n_rnd: int = 2,
    eligible: bool = True,
    block_r: int = 8,
    interpret: Optional[bool] = None,
    live: Optional[jnp.ndarray] = None,  # (B,) int32; 0 = skip the lane
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run one constant-I0 plateau for B stacked problems fully on-chip.

    The grid is (B, R-tiles): grid step (b, i) pins problem b's J in VMEM
    and runs all C cycles for one R-tile of trials — one launch serves a
    whole shape bucket of heterogeneous instances (the serving layer's
    batched hot path).  Per-problem semantics are identical to the B=1
    kernel; :func:`ssa_plateau` is exactly this with B=1.

    ``live`` masks lanes: a lane whose entry is 0 computes nothing and
    returns its state as it came in (:func:`_skip_dead_lanes`).  None runs
    every lane with the mask-free kernel.
    """
    interpret = default_interpret() if interpret is None else interpret
    B, R, N = m.shape
    C = noise.shape[1]
    mf = pad_to(pad_to(m.astype(jnp.float32), 2, LANE), 1, block_r)
    itp = pad_to(pad_to(itanh, 2, LANE), 1, block_r)
    Jp = pad_to(pad_to(J, 1, LANE), 2, LANE)
    hp = pad_to(h.astype(jnp.int32).reshape(B, 1, -1), 2, LANE)
    np_ = pad_to(pad_to(noise, 3, LANE), 2, block_r)
    bhp = pad_to(best_H.reshape(B, -1, 1), 1, block_r)
    bmp = pad_to(pad_to(best_m, 2, LANE), 1, block_r)
    _, Rp, Np = mf.shape
    grid = (B, Rp // block_r)
    i0a = jnp.asarray(i0, jnp.int32).reshape(1, 1)

    kernel = functools.partial(
        _plateau_kernel, n_cycles=C, n_rnd=n_rnd, eligible=eligible
    )
    live_specs, live_args = _live_operand(live, B)
    if live_args:
        # State in: m, itanh, best_H, best_m; out in the same order.
        kernel = _skip_dead_lanes(kernel, 8, (1, 2, 6, 7))
    m_o, it_o, bh_o, bm_o = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            *live_specs,
            _SMEM,
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Np, Np), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, Np), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, C, block_r, Np), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Rp, Np), jnp.float32),
            jax.ShapeDtypeStruct((B, Rp, Np), jnp.int32),
            jax.ShapeDtypeStruct((B, Rp, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, Rp, Np), jnp.int8),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_r, Np), jnp.float32),
            pltpu.VMEM((block_r, Np), jnp.int32),
            pltpu.VMEM((block_r, 1), jnp.float32),
            pltpu.VMEM((block_r, Np), jnp.float32),
        ],
        compiler_params=_compiler_params(
            "ssa_plateau_batched",
            plateau_vmem_bytes("pregen", Np, block_r=block_r, n_cycles=C,
                               j_dtype=J.dtype),
            2,
        ),
        interpret=interpret,
    )(*live_args, i0a, mf, itp, Jp.astype(J.dtype), hp, np_, bhp, bmp)
    return (
        m_o[:, :R, :N],
        it_o[:, :R, :N],
        bh_o[:, :R, 0],
        bm_o[:, :R, :N],
    )


# ---------------------------------------------------------------------------
# Kernel C: streamed-noise packed plateau kernel — the bit-packed datapath
# ---------------------------------------------------------------------------
def _unpack_pm1_f32(words: jnp.ndarray) -> jnp.ndarray:
    """Kernel-side codec: (bR, Nw) u32 words → (bR, 32·Nw) f32 spins ±1.

    Bit layout matches repro.kernels.bitplane (bit k of word w = spin
    32·w + k; 1 ⇔ +1).  Runs on lane-aligned tiles (32·Nw % 128 == 0).
    """
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(words.shape[0], -1)
    return jnp.where(flat == 1, 1.0, -1.0).astype(jnp.float32)


def _pack_matrices(n: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(n, n/32) bf16 weights that pack sign bits by two MXU contractions.

    ``lo[i, w] = 2^(i%32)`` where spin i sits in the low half of word w (bit
    ``i%32 < 16``), ``hi[i, w] = 2^(i%32 - 16)`` in the high half, else 0.
    Powers of two up to 2^15 and 0/1 bits are exact in bf16, and each
    half-word sum stays below 2^16, so the f32-accumulated products are
    exact.  Built from 2-D iotas (Mosaic has no 1-D iota).
    """
    shape = (n, n // 32)
    i = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    own = (i >> 5) == jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    k = i & 31
    val = (jnp.int32(1) << (k & 15)).astype(jnp.float32)
    lo = jnp.where(own & (k < 16), val, 0.0).astype(jnp.bfloat16)
    hi = jnp.where(own & (k >= 16), val, 0.0).astype(jnp.bfloat16)
    return lo, hi


def _pack_bits(bits: jnp.ndarray, mats) -> jnp.ndarray:
    """Kernel-side codec: (bR, N) bool sign bits → (bR, N/32) u32 words.

    Bit k of word w is spin 32·w + k (the `repro.kernels.bitplane` layout).
    Grouping 32 lanes into one word is a lane-splitting reshape that Mosaic
    does not lower, so the two 16-bit halves of every word come out of exact
    bf16 contractions against :func:`_pack_matrices` instead.
    """
    lo_m, hi_m = mats
    b = bits.astype(jnp.bfloat16)
    lo = jnp.dot(b, lo_m, preferred_element_type=jnp.float32).astype(jnp.int32)
    hi = jnp.dot(b, hi_m, preferred_element_type=jnp.float32).astype(jnp.int32)
    return jax.lax.bitcast_convert_type(lo | (hi << 16), jnp.uint32)


def _plateau_streamed_kernel(
    *refs,
    # i0_ref,    # (1, 1) int32 scalar
    # [jperp_ref]  (1, 1) int32 scalar — ONLY when n_replicas > 0 (SSQA)
    # mp_ref,    # (1, bR, Nw) uint32   spins, packed sign bits
    # it_ref,    # (1, bR, N)  int32    Itanh state
    # j_ref,     # (1, N, N)   J dtype  resident couplings of THIS problem
    # h_ref,     # (1, 1, N)   int32    biases
    # rng_ref,   # (1, 4, bR, N) uint32 xorshift128 lanes (carried)
    # bh_ref,    # (1, bR, 1)  int32    running best energy (input)
    # bmp_ref,   # (1, bR, Nw) uint32   running best spins, packed (input)
    # mp_out,    # (1, bR, Nw) uint32
    # it_out,    # (1, bR, N)  int32
    # rng_out,   # (1, 4, bR, N) uint32
    # bh_out,    # (1, bR, 1)  int32
    # bmp_out,   # (1, bR, Nw) uint32
    # m_s,       # scratch (bR, N) float32
    # it_s,      # scratch (bR, N) int32
    # rng_s,     # scratch (4, bR, N) uint32
    # bh_s,      # scratch (bR, 1) float32 (exact ints)
    # bm_s,      # scratch (bR, N) float32 (±1)
    n_cycles: int,
    n_rnd: int,
    eligible: bool,
    n_replicas: int = 0,
):
    """All C cycles of a plateau with packed HBM refs and in-kernel noise.

    The HBM-facing spin state is the uint32 bitplane codec; the per-cycle
    noise is generated *inside* the kernel by stepping the carried Marsaglia
    xorshift128 lanes (bit-identical to repro.core.rng.xorshift_next_bits),
    so no (C, R, N) noise buffer exists anywhere.  Per-plateau HBM traffic
    drops from O(C·R·N) int8 noise to O(R·N) uint32 lanes + O(R·N/32)
    packed spins.

    ``n_replicas > 0`` is the SSQA mode (DESIGN.md §13): the R-tile is one
    Trotter ring (block_r == n_replicas enforced by the wrapper) and a
    ``jperp_ref`` scalar operand adds the nearest-replica coupling
    ``J⊥·(m[k-1] + m[k+1])`` — a roll over the tile's trial axis — to the
    *update* field only; best-tracking keeps the classical per-replica
    energy.  ``n_replicas == 0`` compiles the exact classical body (no
    extra operand, identical jaxpr).
    """
    if n_replicas:
        (i0_ref, jperp_ref, mp_ref, it_ref, j_ref, h_ref, rng_ref, bh_ref,
         bmp_ref, mp_out, it_out, rng_out, bh_out, bmp_out,
         m_s, it_s, rng_s, bh_s, bm_s) = refs
    else:
        (i0_ref, mp_ref, it_ref, j_ref, h_ref, rng_ref, bh_ref,
         bmp_ref, mp_out, it_out, rng_out, bh_out, bmp_out,
         m_s, it_s, rng_s, bh_s, bm_s) = refs
    m_s[...] = _unpack_pm1_f32(mp_ref[0])
    it_s[...] = it_ref[0]
    rng_s[...] = rng_ref[0]
    bh_s[...] = bh_ref[0].astype(jnp.float32)
    bm_s[...] = _unpack_pm1_f32(bmp_ref[0])
    i0 = i0_ref[0, 0]
    hf = h_ref[0].astype(jnp.float32)  # (1, N)
    jm = j_ref[0]
    one = jnp.uint32(1)

    def energy(m, field):
        hm = jnp.sum(hf * m, axis=-1, keepdims=True)
        mf_ = jnp.sum(m * field, axis=-1, keepdims=True)
        return -(hm + mf_) * 0.5

    def track_best(m, field):
        if not eligible:
            return
        H = energy(m, field)
        better = H < bh_s[...]
        bh_s[...] = jnp.where(better, H, bh_s[...])
        bm_s[...] = jnp.where(better, m, bm_s[...])

    def body(c, _):
        field = (
            jnp.dot(m_s[...], jm, preferred_element_type=jnp.float32) + hf
        )
        # m_s currently holds m(t0+c): produced by THIS plateau for c >= 1.
        @pl.when(c >= 1)
        def _():
            track_best(m_s[...], field)

        # One Marsaglia xorshift128 step per lane — the FPGA's per-spin-gate
        # bit stream, bit-identical to repro.core.rng.xorshift_next_bits.
        x, y, z, w = rng_s[0], rng_s[1], rng_s[2], rng_s[3]
        t = x ^ (x << jnp.uint32(11))
        w_new = (w ^ (w >> jnp.uint32(19))) ^ (t ^ (t >> jnp.uint32(8)))
        rng_s[0] = y
        rng_s[1] = z
        rng_s[2] = w
        rng_s[3] = w_new
        r = jnp.where((w_new >> jnp.uint32(31)) & one, 1, -1).astype(jnp.int32)

        upd = field.astype(jnp.int32)
        if n_replicas:
            # Trotter-ring coupling over the tile's trial axis (one ring per
            # R-tile): m is ±1 f32, the sum of two neighbors is exact.
            coup = (
                jnp.roll(m_s[...], 1, axis=0) + jnp.roll(m_s[...], -1, axis=0)
            ).astype(jnp.int32)
            upd = upd + jperp_ref[0, 0] * coup
        I = upd + n_rnd * r + it_s[...]  # noqa: E741
        it_new = jnp.clip(I, -i0, i0 - 1)
        it_s[...] = it_new
        m_s[...] = jnp.where(it_new >= 0, 1.0, -1.0).astype(jnp.float32)
        return 0

    jax.lax.fori_loop(0, n_cycles, body, 0)
    # final state m(t0+C): one more field evaluation for its energy
    field = jnp.dot(m_s[...], jm, preferred_element_type=jnp.float32) + hf
    track_best(m_s[...], field)

    mats = _pack_matrices(m_s.shape[-1])
    mp_out[...] = _pack_bits(m_s[...] > 0, mats)[None]
    it_out[...] = it_s[...][None]
    rng_out[...] = rng_s[...][None]
    bh_out[...] = bh_s[...].astype(jnp.int32)[None]
    bmp_out[...] = _pack_bits(bm_s[...] > 0, mats)[None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_cycles", "n_rnd", "eligible", "block_r", "interpret", "n_replicas"
    ),
)
def ssa_plateau_packed_batched(
    m_packed: jnp.ndarray,   # (B, R, Nw) uint32 packed ±1 spins
    itanh: jnp.ndarray,      # (B, R, N) int32
    J: jnp.ndarray,          # (B, N, N) float32/bfloat16 — one J per problem
    h: jnp.ndarray,          # (B, N) int32
    rng: jnp.ndarray,        # (B, 4, R, N) uint32 xorshift lanes (carried)
    i0: jnp.ndarray,         # scalar int32 (shared: same schedule per bucket)
    best_H: jnp.ndarray,     # (B, R) int32
    best_m_packed: jnp.ndarray,  # (B, R, Nw) uint32
    *,
    n_cycles: int,
    n_rnd: int = 2,
    eligible: bool = True,
    block_r: int = 8,
    interpret: Optional[bool] = None,
    jperp=0,                 # scalar int32 replica coupling (SSQA)
    n_replicas: int = 0,     # 0 = classical; >0 = SSQA Trotter-ring mode
    live: Optional[jnp.ndarray] = None,  # (B,) int32; 0 = skip the lane
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Streamed-noise resident plateau for B stacked problems, packed refs.

    Semantically `ssa_plateau_batched` with the plateau's noise equal to
    ``n_cycles`` successive `xorshift_next_bits` draws from ``rng`` — but no
    (B, C, R, N) buffer is ever materialized: noise bits are generated in
    VMEM from the carried lanes, and the HBM-facing spin state crosses the
    launch boundary as uint32 bitplanes (32× smaller than float32 spins).
    ``live`` masks lanes as in :func:`ssa_plateau_batched`; a dead lane's
    xorshift lanes do not advance either.

    Returns (m_packed, itanh, rng, best_H, best_m_packed) after the plateau.
    """
    interpret = default_interpret() if interpret is None else interpret
    B, R, N = itanh.shape
    if n_replicas:
        if block_r != n_replicas:
            raise ValueError(
                f"SSQA needs block_r == n_replicas (one Trotter ring per "
                f"R-tile), got block_r={block_r}, n_replicas={n_replicas}"
            )
        if R % n_replicas:
            raise ValueError(
                f"n_trials={R} not divisible by n_replicas={n_replicas}"
            )
    Np = N + (-N) % LANE
    Nwp = Np // 32
    # Pad packed words up to the padded lane count; zero words decode to -1
    # pad spins, which J's zero pad rows/cols make inert.
    mp = pad_to(pad_to(m_packed, 2, Nwp), 1, block_r)
    bmp = pad_to(pad_to(best_m_packed, 2, Nwp), 1, block_r)
    itp = pad_to(pad_to(itanh, 2, LANE), 1, block_r)
    Jp = pad_to(pad_to(J, 1, LANE), 2, LANE)
    hp = pad_to(h.astype(jnp.int32).reshape(B, 1, -1), 2, LANE)
    # Zero-state pad lanes are xorshift fixed points (constant -1 noise).
    rngp = pad_to(pad_to(rng, 3, LANE), 2, block_r)
    bhp = pad_to(best_H.reshape(B, -1, 1), 1, block_r)
    Rp = itp.shape[1]
    grid = (B, Rp // block_r)
    i0a = jnp.asarray(i0, jnp.int32).reshape(1, 1)

    kernel = functools.partial(
        _plateau_streamed_kernel, n_cycles=n_cycles, n_rnd=n_rnd,
        eligible=eligible, n_replicas=n_replicas,
    )
    jperp_specs, jperp_args = [], []
    if n_replicas:
        jperp_specs = [_SMEM]
        jperp_args = [jnp.asarray(jperp, jnp.int32).reshape(1, 1)]
    live_specs, live_args = _live_operand(live, B)
    if live_args:
        # State in: mp, itanh, rng, best_H, best_m; out in the same order.
        o = len(jperp_args)
        kernel = _skip_dead_lanes(kernel, 8 + o,
                                  (1 + o, 2 + o, 5 + o, 6 + o, 7 + o))
    mp_o, it_o, rng_o, bh_o, bmp_o = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            *live_specs,
            _SMEM,
            *jperp_specs,
            pl.BlockSpec((1, block_r, Nwp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Np, Np), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, Np), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 4, block_r, Np), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Nwp), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_r, Nwp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 4, block_r, Np), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Nwp), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Rp, Nwp), jnp.uint32),
            jax.ShapeDtypeStruct((B, Rp, Np), jnp.int32),
            jax.ShapeDtypeStruct((B, 4, Rp, Np), jnp.uint32),
            jax.ShapeDtypeStruct((B, Rp, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, Rp, Nwp), jnp.uint32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_r, Np), jnp.float32),
            pltpu.VMEM((block_r, Np), jnp.int32),
            pltpu.VMEM((4, block_r, Np), jnp.uint32),
            pltpu.VMEM((block_r, 1), jnp.float32),
            pltpu.VMEM((block_r, Np), jnp.float32),
        ],
        compiler_params=_compiler_params(
            "ssa_plateau_packed_batched",
            plateau_vmem_bytes("streamed", Np, block_r=block_r,
                               j_dtype=J.dtype),
            2,
        ),
        interpret=interpret,
    )(*live_args, i0a, *jperp_args, mp, itp, Jp.astype(J.dtype), hp, rngp,
      bhp, bmp)
    nw = (N + 31) // 32
    return (
        mp_o[:, :R, :nw],
        it_o[:, :R, :N],
        rng_o[:, :, :R, :N],
        bh_o[:, :R, 0],
        bmp_o[:, :R, :nw],
    )


def ssa_plateau_packed(
    m_packed: jnp.ndarray,   # (R, Nw) uint32
    itanh: jnp.ndarray,      # (R, N) int32
    J: jnp.ndarray,          # (N, N)
    h: jnp.ndarray,          # (N,) int32
    rng: jnp.ndarray,        # (4, R, N) uint32
    i0: jnp.ndarray,
    best_H: jnp.ndarray,     # (R,) int32
    best_m_packed: jnp.ndarray,  # (R, Nw) uint32
    *,
    n_cycles: int,
    n_rnd: int = 2,
    eligible: bool = True,
    block_r: int = 8,
    interpret: Optional[bool] = None,
    jperp=0,
    n_replicas: int = 0,
):
    """B=1 slice of :func:`ssa_plateau_packed_batched` (one kernel body)."""
    mp, it, rs, bh, bmp = ssa_plateau_packed_batched(
        m_packed[None],
        itanh[None],
        J[None],
        h[None],
        rng[None],
        i0,
        best_H[None],
        best_m_packed[None],
        n_cycles=n_cycles,
        n_rnd=n_rnd,
        eligible=eligible,
        block_r=block_r,
        interpret=interpret,
        jperp=jperp,
        n_replicas=n_replicas,
    )
    return mp[0], it[0], rs[0], bh[0], bmp[0]


@functools.partial(
    jax.jit,
    static_argnames=("n_rnd", "eligible", "block_r", "interpret"),
)
def ssa_plateau(
    m: jnp.ndarray,       # (R, N) float32 ±1
    itanh: jnp.ndarray,   # (R, N) int32
    J: jnp.ndarray,       # (N, N) float32/bfloat16
    h: jnp.ndarray,       # (N,) int32
    noise: jnp.ndarray,   # (C, R, N) int8 ±1
    i0: jnp.ndarray,      # scalar int32
    best_H: jnp.ndarray,  # (R,) int32
    best_m: jnp.ndarray,  # (R, N) int8
    *,
    n_rnd: int = 2,
    eligible: bool = True,
    block_r: int = 8,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run one constant-I0 plateau of C cycles fully on-chip.

    Returns (m, itanh, best_H, best_m) after the plateau.  ``eligible``
    implements HA-SSA's storage policy: only plateaus with I0 == I0max
    update the running best (Eq. 6); passing eligible=True for every plateau
    recovers conventional SSA's policy (Eq. 5).  This is the B=1 slice of
    :func:`ssa_plateau_batched` (one kernel body serves both).
    """
    m_o, it_o, bh_o, bm_o = ssa_plateau_batched(
        m[None],
        itanh[None],
        J[None],
        h[None],
        noise[None],
        i0,
        best_H[None],
        best_m[None],
        n_rnd=n_rnd,
        eligible=eligible,
        block_r=block_r,
        interpret=interpret,
    )
    return m_o[0], it_o[0], bh_o[0], bm_o[0]


# ---------------------------------------------------------------------------
# Kernel D: bit-parallel multi-plateau kernel — XNOR-popcount field, all-int
# ---------------------------------------------------------------------------
def _unpack_pm1_i32(words: jnp.ndarray) -> jnp.ndarray:
    """Kernel-side codec: (bR, Nw) u32 words → (bR, 32·Nw) int32 spins ±1."""
    shifts = jnp.arange(32, dtype=jnp.uint32)
    bits = (words[..., None] >> shifts) & jnp.uint32(1)
    flat = bits.reshape(words.shape[0], -1)
    return jnp.where(flat == 1, 1, -1).astype(jnp.int32)


def _plateau_popcount_kernel(
    *refs,
    # i0_ref,    # (1, C)   int32   per-cycle I0 schedule (whole chain)
    # [jperp_ref]  (1, C)  int32   per-cycle J⊥ — ONLY when n_replicas > 0
    # fold_ref,  # (1, C+1) int32   per-state storage write-enable
    # mp_ref,    # (1, bR, Nwp) uint32  spins, packed sign bits
    # it_ref,    # (1, bR, Np)  int32   Itanh state
    # sign_ref,  # (1, Np, Nwp) uint32  packed-J sign plane of THIS problem
    # mags_ref,  # (1, nb, Np, Nwp) uint32  packed-J magnitude bitplanes
    # base_ref,  # (1, 1, Np)  int32   −Σ_b 2^b·deg_b (PackedJ.base)
    # h_ref,     # (1, 1, Np)  int32   biases
    # rng_ref,   # (1, 4, bR, Np) uint32 xorshift128 lanes (carried)
    # bh_ref,    # (1, bR, 1)  int32   running best energy (input)
    # bmp_ref,   # (1, bR, Nwp) uint32 running best spins, packed (input)
    # mp_out,    # (1, bR, Nwp) uint32
    # it_out,    # (1, bR, Np)  int32
    # rng_out,   # (1, 4, bR, Np) uint32
    # bh_out,    # (1, bR, 1)  int32
    # bmp_out,   # (1, bR, Nwp) uint32
    # mw_s,      # scratch (bR, Nwp) uint32  packed current spins
    # m_s,       # scratch (bR, Np) int32    ±1 current spins (energy dots)
    # it_s,      # scratch (bR, Np) int32
    # rng_s,     # scratch (4, bR, Np) uint32
    # bh_s,      # scratch (bR, 1) int32
    # bmw_s,     # scratch (bR, Nwp) uint32  packed best spins
    # f_s,       # scratch (bR, Np) int32    field accumulator (row tiles)
    # [ring_s]   # scratch (2, bR, Np) int32 — ONLY when n_replicas > 0
    n_cycles: int,
    n_rnd: int,
    field_tile: int,
    n_replicas: int = 0,
):
    """A whole plateau *chain* with the field computed on bitplanes.

    Two departures from the streamed kernel above:

    * The contraction is XNOR-popcount against the resident packed-J planes
      — `field = h + base + Σ_b 2^{b+1}·popcount(XNOR(m, sign) & mag_b)` —
      entirely uint32/int32; there is no f32 value (and no MXU op) in this
      body.  Best spins are tracked *packed* (one uint32 select per word).
    * The launch covers C cycles spanning several plateaus: ``i0_ref`` holds
      the per-cycle I0 and ``fold_ref[c]`` the storage write-enable of the
      plateau that *produced* the state current at cycle c (fold[0] = 0 —
      the chain's incoming state belongs to the previous chunk; fold[C]
      covers the final state, folded in the epilogue).  Bit-identical to
      chaining one launch per plateau, minus the per-boundary re-dispatch
      and duplicate field evaluation.

    ``n_replicas > 0`` is the SSQA chain mode (DESIGN.md §13): the R-tile
    is one Trotter ring and a per-cycle ``jperp_ref`` schedule adds the
    nearest-replica coupling to the update field.  The replica planes are
    **double-buffered** through a two-plane ``ring_s`` scratch (the
    dual-BRAM layout of arXiv:2602.16143): cycle c reads plane c%2 and
    writes the updated spins to plane (c+1)%2, so the coupling always sees
    the coherent previous-cycle ring while the new one streams in.
    """
    if n_replicas:
        (i0_ref, jperp_ref, fold_ref, mp_ref, it_ref, sign_ref, mags_ref,
         base_ref, h_ref, rng_ref, bh_ref, bmp_ref,
         mp_out, it_out, rng_out, bh_out, bmp_out,
         mw_s, m_s, it_s, rng_s, bh_s, bmw_s, f_s, ring_s) = refs
    else:
        (i0_ref, fold_ref, mp_ref, it_ref, sign_ref, mags_ref,
         base_ref, h_ref, rng_ref, bh_ref, bmp_ref,
         mp_out, it_out, rng_out, bh_out, bmp_out,
         mw_s, m_s, it_s, rng_s, bh_s, bmw_s, f_s) = refs
    mw_s[...] = mp_ref[0]
    m_s[...] = _unpack_pm1_i32(mp_ref[0])
    it_s[...] = it_ref[0]
    rng_s[...] = rng_ref[0]
    bh_s[...] = bh_ref[0]
    bmw_s[...] = bmp_ref[0]
    if n_replicas:
        ring_s[0] = m_s[...]
        ring_s[1] = m_s[...]
    nb = mags_ref.shape[1]
    n_pad = sign_ref.shape[1]
    hf = h_ref[0]             # (1, Np) int32
    hb = hf + base_ref[0]     # field constant: h + base
    nt = n_pad // field_tile
    one = jnp.uint32(1)
    mats = _pack_matrices(n_pad)

    def field_of(mw):
        """(bR, Nwp) packed spins → (bR, Np) int32 fields, row-tiled.

        Each tile of J rows is read from the resident planes by ref slice
        (no whole-plane value is loaded) and its fields land in ``f_s``.
        """

        def tile_body(t, carry):
            off = pl.multiple_of(t * field_tile, field_tile)
            nst = ~sign_ref[0, pl.ds(off, field_tile), :]   # XNOR(a,b)=a^~b
            xs = mw[:, None, :] ^ nst[None]      # (bR, tile, Nwp) XNOR words
            f = jnp.zeros(xs.shape[:2], jnp.int32)
            for b in range(nb):
                mt = mags_ref[0, b, pl.ds(off, field_tile), :]
                pc = jnp.sum(
                    jax.lax.population_count(xs & mt[None]).astype(jnp.int32),
                    axis=-1,
                )
                f = f + (pc << (b + 1))
            f_s[:, pl.ds(off, field_tile)] = f
            return carry

        jax.lax.fori_loop(0, nt, tile_body, 0)
        return f_s[...] + hb

    def track_best(fold, field):
        # H = -(h·m + m·field)/2, exact int32 (the sum is always even).
        hm = jnp.sum(hf * m_s[...], axis=-1, keepdims=True)
        mf_ = jnp.sum(m_s[...] * field, axis=-1, keepdims=True)
        H = -(hm + mf_) // 2
        better = (fold > 0) & (H < bh_s[...])
        bh_s[...] = jnp.where(better, H, bh_s[...])
        bmw_s[...] = jnp.where(better, mw_s[...], bmw_s[...])

    def body(c, _):
        field = field_of(mw_s[...])
        # m_s holds the state current at cycle c; fold_ref[c] is the
        # write-enable of the plateau that produced it (0 at c == 0).
        track_best(fold_ref[0, c], field)

        x, y, z, w = rng_s[0], rng_s[1], rng_s[2], rng_s[3]
        t = x ^ (x << jnp.uint32(11))
        w_new = (w ^ (w >> jnp.uint32(19))) ^ (t ^ (t >> jnp.uint32(8)))
        rng_s[0] = y
        rng_s[1] = z
        rng_s[2] = w
        rng_s[3] = w_new
        r = jnp.where((w_new >> jnp.uint32(31)) & one, 1, -1).astype(jnp.int32)

        i0 = i0_ref[0, c]
        upd = field
        if n_replicas:
            # Double-buffered replica planes: read the coherent ring of the
            # cycle parity, write the updated plane to the other buffer.
            even = (c % 2) == 0
            ring = jnp.where(even, ring_s[0], ring_s[1])
            coup = jnp.roll(ring, 1, axis=0) + jnp.roll(ring, -1, axis=0)
            upd = field + jperp_ref[0, c] * coup
        I = upd + n_rnd * r + it_s[...]  # noqa: E741 — Eq. (2a)
        it_new = jnp.clip(I, -i0, i0 - 1)
        it_s[...] = it_new
        bits = it_new >= 0
        m_new = jnp.where(bits, 1, -1).astype(jnp.int32)
        m_s[...] = m_new
        mw_s[...] = _pack_bits(bits, mats)
        if n_replicas:

            @pl.when(even)
            def _wr_odd():
                ring_s[1] = m_new

            @pl.when(~even)
            def _wr_even():
                ring_s[0] = m_new

        return 0

    jax.lax.fori_loop(0, n_cycles, body, 0)
    # Final state of the chain: one epilogue field for its energy.
    field = field_of(mw_s[...])
    track_best(fold_ref[0, n_cycles], field)

    mp_out[...] = mw_s[...][None]
    it_out[...] = it_s[...][None]
    rng_out[...] = rng_s[...][None]
    bh_out[...] = bh_s[...][None]
    bmp_out[...] = bmw_s[...][None]


@functools.partial(
    jax.jit,
    static_argnames=(
        "n_rnd", "block_r", "field_tile", "interpret", "n_replicas"
    ),
)
def ssa_plateau_popcount_batched(
    m_packed: jnp.ndarray,   # (B, R, Nw) uint32 packed ±1 spins
    itanh: jnp.ndarray,      # (B, R, N) int32
    sign: jnp.ndarray,       # (B, N, Nw) uint32 packed-J sign plane
    mags: jnp.ndarray,       # (B, nb, N, Nw) uint32 packed-J magnitude planes
    base: jnp.ndarray,       # (B, N) int32 PackedJ.base (−Σ 2^b·deg_b)
    h: jnp.ndarray,          # (B, N) int32
    rng: jnp.ndarray,        # (B, 4, R, N) uint32 xorshift lanes (carried)
    i0_sched: jnp.ndarray,   # (C,) int32 per-cycle I0 over the whole chain
    fold_sched: jnp.ndarray,  # (C+1,) int32 per-state fold mask
    best_H: jnp.ndarray,     # (B, R) int32
    best_m_packed: jnp.ndarray,  # (B, R, Nw) uint32
    *,
    n_rnd: int = 2,
    block_r: int = 8,
    field_tile: int = 128,
    interpret: Optional[bool] = None,
    jperp_sched: Optional[jnp.ndarray] = None,  # (C,) int32 per-cycle J⊥
    n_replicas: int = 0,     # 0 = classical; >0 = SSQA Trotter-ring mode
    live: Optional[jnp.ndarray] = None,  # (B,) int32; 0 = skip the lane
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Bit-parallel resident chain for B stacked problems (multi-plateau).

    Runs ``C = len(i0_sched)`` cycles — typically a full iteration's plateau
    chain — in ONE `pallas_call`, with the coupling matrix resident as
    packed bitplanes (`PackedJ` layout: ~n_bits·N²/32 words instead of N²
    floats) and the field contraction done by XNOR-popcount.  Schedule
    operands come from :func:`repro.core.engine.plateau_cycle_schedules`.
    Bit-identical to running the same chain plateau-by-plateau through any
    other backend (property-tested in tests/test_popcount.py).

    ``live`` masks lanes as in :func:`ssa_plateau_batched`: a dead lane's
    grid steps copy its state through and run no cycle.  None (every lane
    live) compiles the mask-free kernel.

    Returns (m_packed, itanh, rng, best_H, best_m_packed) after the chain.
    """
    interpret = default_interpret() if interpret is None else interpret
    B, R, N = itanh.shape
    C = i0_sched.shape[0]
    if jperp_sched is None:
        # Classical chain: no coupling operand, no ring scratch — the exact
        # pre-SSQA jaxpr (asserted in tests/test_popcount.py).
        n_replicas = 0
    elif n_replicas:
        if block_r != n_replicas:
            raise ValueError(
                f"SSQA needs block_r == n_replicas (one Trotter ring per "
                f"R-tile), got block_r={block_r}, n_replicas={n_replicas}"
            )
        if R % n_replicas:
            raise ValueError(
                f"n_trials={R} not divisible by n_replicas={n_replicas}"
            )
    else:
        raise ValueError("jperp_sched given but n_replicas == 0")
    nb = mags.shape[1]
    Np = N + (-N) % LANE
    Nwp = Np // 32
    if Np % field_tile:
        raise ValueError(
            f"field_tile {field_tile} must divide padded width {Np}"
        )
    mp = pad_to(pad_to(m_packed, 2, Nwp), 1, block_r)
    bmp = pad_to(pad_to(best_m_packed, 2, Nwp), 1, block_r)
    itp = pad_to(pad_to(itanh, 2, LANE), 1, block_r)
    # Padded J rows/words are zero in every plane: pad columns contribute 0
    # to every field regardless of the spin words' tail-bit garbage.
    signp = pad_to(pad_to(sign, 1, LANE), 2, Nwp)
    magsp = pad_to(pad_to(mags, 2, LANE), 3, Nwp)
    basep = pad_to(base.astype(jnp.int32).reshape(B, 1, -1), 2, LANE)
    hp = pad_to(h.astype(jnp.int32).reshape(B, 1, -1), 2, LANE)
    rngp = pad_to(pad_to(rng, 3, LANE), 2, block_r)
    bhp = pad_to(best_H.reshape(B, -1, 1), 1, block_r)
    Rp = itp.shape[1]
    grid = (B, Rp // block_r)
    i0a = jnp.asarray(i0_sched, jnp.int32).reshape(1, C)
    folda = jnp.asarray(fold_sched, jnp.int32).reshape(1, C + 1)

    kernel = functools.partial(
        _plateau_popcount_kernel, n_cycles=C, n_rnd=n_rnd,
        field_tile=field_tile, n_replicas=n_replicas,
    )
    jperp_specs, jperp_args, ring_scratch = [], [], []
    if n_replicas:
        jperp_specs = [_SMEM]
        jperp_args = [jnp.asarray(jperp_sched, jnp.int32).reshape(1, C)]
        ring_scratch = [pltpu.VMEM((2, block_r, Np), jnp.int32)]
    live_specs, live_args = _live_operand(live, B)
    if live_args:
        # State in: mp, itanh, rng, best_H, best_m; out in the same order.
        o = len(jperp_args)
        kernel = _skip_dead_lanes(kernel, 11 + o,
                                  (2 + o, 3 + o, 8 + o, 9 + o, 10 + o))
    mp_o, it_o, rng_o, bh_o, bmp_o = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            *live_specs,
            _SMEM,
            *jperp_specs,
            _SMEM,
            pl.BlockSpec((1, block_r, Nwp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Np, Nwp), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, nb, Np, Nwp), lambda b, i: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, Np), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, Np), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 4, block_r, Np), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Nwp), lambda b, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_r, Nwp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Np), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 4, block_r, Np), lambda b, i: (b, 0, i, 0)),
            pl.BlockSpec((1, block_r, 1), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_r, Nwp), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Rp, Nwp), jnp.uint32),
            jax.ShapeDtypeStruct((B, Rp, Np), jnp.int32),
            jax.ShapeDtypeStruct((B, 4, Rp, Np), jnp.uint32),
            jax.ShapeDtypeStruct((B, Rp, 1), jnp.int32),
            jax.ShapeDtypeStruct((B, Rp, Nwp), jnp.uint32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_r, Nwp), jnp.uint32),
            pltpu.VMEM((block_r, Np), jnp.int32),
            pltpu.VMEM((block_r, Np), jnp.int32),
            pltpu.VMEM((4, block_r, Np), jnp.uint32),
            pltpu.VMEM((block_r, 1), jnp.int32),
            pltpu.VMEM((block_r, Nwp), jnp.uint32),
            pltpu.VMEM((block_r, Np), jnp.int32),
            *ring_scratch,
        ],
        compiler_params=_compiler_params(
            "ssa_plateau_popcount_batched",
            plateau_vmem_bytes("popcount", Np, block_r=block_r, j_bits=nb,
                               n_replicas=n_replicas, field_tile=field_tile),
            2,
        ),
        interpret=interpret,
    )(*live_args, i0a, *jperp_args, folda, mp, itp, signp, magsp, basep, hp,
      rngp, bhp, bmp)
    nw = (N + 31) // 32
    return (
        mp_o[:, :R, :nw],
        it_o[:, :R, :N],
        rng_o[:, :, :R, :N],
        bh_o[:, :R, 0],
        bmp_o[:, :R, :nw],
    )


def ssa_plateau_popcount(
    m_packed: jnp.ndarray,   # (R, Nw) uint32
    itanh: jnp.ndarray,      # (R, N) int32
    sign: jnp.ndarray,       # (N, Nw) uint32
    mags: jnp.ndarray,       # (nb, N, Nw) uint32
    base: jnp.ndarray,       # (N,) int32
    h: jnp.ndarray,          # (N,) int32
    rng: jnp.ndarray,        # (4, R, N) uint32
    i0_sched: jnp.ndarray,   # (C,) int32
    fold_sched: jnp.ndarray,  # (C+1,) int32
    best_H: jnp.ndarray,     # (R,) int32
    best_m_packed: jnp.ndarray,  # (R, Nw) uint32
    *,
    n_rnd: int = 2,
    block_r: int = 8,
    field_tile: int = 128,
    interpret: Optional[bool] = None,
    jperp_sched: Optional[jnp.ndarray] = None,
    n_replicas: int = 0,
):
    """B=1 slice of :func:`ssa_plateau_popcount_batched` (one kernel body)."""
    mp, it, rs, bh, bmp = ssa_plateau_popcount_batched(
        m_packed[None],
        itanh[None],
        sign[None],
        mags[None],
        base[None],
        h[None],
        rng[None],
        i0_sched,
        fold_sched,
        best_H[None],
        best_m_packed[None],
        n_rnd=n_rnd,
        block_r=block_r,
        field_tile=field_tile,
        interpret=interpret,
        jperp_sched=jperp_sched,
        n_replicas=n_replicas,
    )
    return mp[0], it[0], rs[0], bh[0], bmp[0]
