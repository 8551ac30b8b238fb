"""Distributed SSA/HA-SSA: the paper's annealer on the production mesh.

Parallel axes (DESIGN.md §2.4):
  * stacked problems (the serving layer's bucketed batch axis) → `data`:
    independent instances of one shape bucket shard across hosts,
  * replicas (independent trials) → `data` in the single-problem step (the
    paper runs trials sequentially on one FPGA; a pod runs thousands at
    once),
  * spins → `model` for dense instances (K2000-class): the per-cycle local
    field is a (T, N)·(N, N) matmul with J's rows sharded over `model`;
    GSPMD turns the contraction into partial-sum all-reduces — the only
    collective in the loop, exactly the FPGA's "all spins talk to all
    spin-gates" wiring mapped onto ICI.

``make_iteration_step`` is built from the plateau engine's
:func:`repro.core.engine.run_plateau_scan`: one full I0min→I0max iteration
is the chain of its constant-I0 plateaus, with HA-SSA's storage policy as
per-plateau eligibility and ONE field contraction per cycle (the same
single-matvec semantics as every local backend — bit-identical, tested).
``make_batched_iteration_step`` is the same chain over a leading problem
axis — `run_plateau_scan` is batch-transparent, so the bucketed service
batch threads straight through to the mesh (problems on `data`, spins on
`model`).  It also carries the packed-memory subsystem's axes
(DESIGN.md §4): ``storage_layout='packed'`` makes the state crossing the
pjit launch boundary uint32 spin bitplanes, and ``j_mode='tiled'`` replaces
the (B, N, N) J argument with the stacked adjacency and streams
(tile_n, N) slabs — both bit-identical per problem to the default step.
``anneal_step_lowering`` / ``batched_anneal_step_lowering`` lower the
pjit'd steps for the dry-run; the same steps run for real on any mesh.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.kernels.bitplane import PackedJ
from repro.sharding import mesh_axis_size, spin_mesh

from .engine import (
    BatchedBackend,
    EngineState,
    PackedEngineState,
    Plateau,
    TILED_J_THRESHOLD,
    _stack_packed_models,
    _stack_sparse_models,
    model_couplings,
    pack_spins,
    resolve_backend,
    resolve_field_mode,
    run_plateau_scan,
    padded_noise_init_slice,
    schedule_plateaus,
    unpack_spins,
)
from .ising import local_fields_popcount, local_fields_sparse, local_fields_tiled
from .rng import xorshift_next_bits
from .ssa import SSAHyperParams

__all__ = [
    "make_iteration_step",
    "anneal_step_lowering",
    "make_batched_iteration_step",
    "batched_anneal_step_lowering",
    "SPIN_AXIS",
    "BatchedSpinShardedBackend",
]

# Default mesh-axis name the spin axis shards over (DESIGN.md §11).
SPIN_AXIS = "model"


def make_iteration_step(hp: SSAHyperParams, mesh: Optional[Mesh] = None):
    """One full I0min→I0max iteration (HA-SSA storage policy fused).

    step(rng (4,T,N) u32, m (T,N) f32, itanh (T,N) i32, best_H (T,) i32,
         best_m (T,N) i8, J (N,N) f32, h (N,) i32) → updated state tuple.
    """
    plateaus = schedule_plateaus(hp.schedule("hassa"), "i0max")

    def constrain(x, spec):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def step(rng, m, itanh, best_H, best_m, J, h):
        def field_fn(m8):
            mf = constrain(m8.astype(jnp.float32), P("data", "model"))
            return (h + jnp.matmul(mf, J)).astype(jnp.int32)

        state = EngineState(rng, m.astype(jnp.int8), itanh, best_H, best_m)
        for p in plateaus:
            state, _, _ = run_plateau_scan(
                field_fn, xorshift_next_bits, h, hp.n_rnd, state, p.i0,
                length=p.length, eligible=p.eligible,
            )
        return (
            state.noise_state,
            constrain(state.m.astype(jnp.float32), P("data", "model")),
            state.itanh,
            state.best_H,
            state.best_m,
        )

    return step


def anneal_step_lowering(
    mesh: Mesh,
    n_spins: int = 2000,
    n_trials: int = 4096,
    hp: Optional[SSAHyperParams] = None,
):
    """Lower+compile the distributed iteration step (dry-run, no allocation)."""
    hp = hp or SSAHyperParams(n_trials=n_trials)
    step = make_iteration_step(hp, mesh)
    T, N = n_trials, n_spins
    dm = NamedSharding(mesh, P("data", "model"))
    dd = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    jm = NamedSharding(mesh, P("model"))
    shapes = (
        jax.ShapeDtypeStruct((4, T, N), jnp.uint32),   # rng lanes
        jax.ShapeDtypeStruct((T, N), jnp.float32),     # m
        jax.ShapeDtypeStruct((T, N), jnp.int32),       # itanh
        jax.ShapeDtypeStruct((T,), jnp.int32),         # best_H
        jax.ShapeDtypeStruct((T, N), jnp.int8),        # best_m
        jax.ShapeDtypeStruct((N, N), jnp.float32),     # J
        jax.ShapeDtypeStruct((N,), jnp.int32),         # h
    )
    rng_sh = NamedSharding(mesh, P(None, "data", "model"))
    shardings = (rng_sh, dm, dm, dd, dm, jm, rep)
    jitted = jax.jit(step, in_shardings=shardings, donate_argnums=(0, 1, 2, 3, 4))
    with mesh:
        return jitted.lower(*shapes)


def make_batched_iteration_step(
    hp: SSAHyperParams,
    mesh: Optional[Mesh] = None,
    *,
    storage_layout: str = "dense",
    j_mode: str = "dense",
    tile_n: int = 512,
    field_mode: str = "dense",
):
    """One full iteration over B stacked (bucket-padded) problems.

    The serving layer's batch axis on the mesh: problems shard over `data`,
    spins over `model`; trials stay local.  `run_plateau_scan` is
    batch-transparent, so this is the *same* plateau chain as
    :func:`make_iteration_step` with a leading problem axis — per problem
    bit-identical to the single-problem step (tested).

    Default (dense layout, dense J):
      step(rng (4,B,T,N) u32, m (B,T,N) f32, itanh (B,T,N) i32,
           best_H (B,T) i32, best_m (B,T,N) i8, J (B,N,N) f32, h (B,N) i32)
      → updated state tuple.

    ``storage_layout='packed'`` replaces m/best_m at the step boundary with
    (B, T, ceil(N/32)) uint32 bitplanes — the HBM-resident state between
    pjit launches is the packed layout, 32×/8× smaller than f32/i8 spins.
    ``j_mode='tiled'`` replaces J with the stacked padded adjacency
    ``nbr_idx (B,N,D) i32, nbr_w (B,N,D) i32`` and streams (tile_n, N) J
    slabs per problem — no (B, N, N) buffer, admitting G77/G81-class N.
    ``field_mode='popcount'`` (takes precedence over j_mode) replaces J
    with the stacked `PackedJ` bitplanes ``sign (B,N,Nw) u32,
    mags (B,nb,N,Nw) u32, base (B,N) i32`` and contracts by XNOR-popcount
    (DESIGN.md §8) — exact-integer, ~32×/n_bits less J traffic.
    All are bit-identical per problem to the default step (tested).

    Sharding caveat: the "spins over `model`" layout above applies to the
    dense-J step (the matmul contraction is what GSPMD partitions).  The
    tiled step constrains spins to P("data", None, None) — replicated over
    the model axis, each device scattering/contracting its problems' slabs
    locally — trading redundant field compute for zero collectives; its
    scale-out axis is the problem batch on `data`.
    """
    if storage_layout not in ("dense", "packed"):
        raise ValueError(f"unknown storage_layout {storage_layout!r}")
    if j_mode not in ("dense", "tiled"):
        raise ValueError(f"unknown j_mode {j_mode!r}")
    if field_mode not in ("dense", "popcount"):
        raise ValueError(f"unknown field_mode {field_mode!r}")
    plateaus = schedule_plateaus(hp.schedule("hassa"), "i0max")

    def constrain(x, spec):
        if mesh is None:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    def step(rng, m, itanh, best_H, best_m, *problem):
        n = itanh.shape[-1]
        if field_mode == "popcount":
            from repro.kernels.bitplane import PackedJ  # lazy, like engine

            sign, mags, base, h = problem

            def field_fn(m8):
                # Like the tiled step: spins replicated over `model`, each
                # device contracting its problems' bitplanes locally — the
                # scale-out axis is the problem batch on `data`.
                mw = pack_spins(constrain(m8, P("data", None, None)))
                return jax.vmap(
                    lambda w, hh, s, g, b: local_fields_popcount(
                        w, hh, PackedJ(s, g, b)
                    )
                )(mw, h, sign, mags, base)
        elif j_mode == "tiled":
            nbr_idx, nbr_w, h = problem

            def field_fn(m8):
                mc = constrain(m8, P("data", None, None))
                return jax.vmap(
                    lambda mm, hh, ii, ww: local_fields_tiled(
                        mm, hh, ii, ww, tile_n=tile_n
                    )
                )(mc, h, nbr_idx, nbr_w)
        else:
            J, h = problem

            def field_fn(m8):
                mf = constrain(m8.astype(jnp.float32), P("data", None, "model"))
                return (
                    h[:, None, :] + jnp.einsum("btn,bnk->btk", mf, J)
                ).astype(jnp.int32)

        h3 = h[:, None, :]  # (B, 1, N): broadcasts against (B, T, N) spins
        if storage_layout == "packed":
            m8 = unpack_spins(m, n)
            bm8 = unpack_spins(best_m, n)
        else:
            m8, bm8 = m.astype(jnp.int8), best_m
        state = EngineState(rng, m8, itanh, best_H, bm8)
        for p in plateaus:
            state, _, _ = run_plateau_scan(
                field_fn, xorshift_next_bits, h3, hp.n_rnd, state, p.i0,
                length=p.length, eligible=p.eligible,
            )
        if storage_layout == "packed":
            m_out, bm_out = pack_spins(state.m), pack_spins(state.best_m)
        else:
            m_out = constrain(
                state.m.astype(jnp.float32), P("data", None, "model")
            )
            bm_out = state.best_m
        return (state.noise_state, m_out, state.itanh, state.best_H, bm_out)

    return step


def batched_anneal_step_lowering(
    mesh: Mesh,
    n_problems: int = 8,
    n_spins: int = 2048,
    n_trials: int = 512,
    hp: Optional[SSAHyperParams] = None,
    *,
    storage_layout: str = "dense",
    j_mode: str = "dense",
    max_degree: int = 4,
    tile_n: int = 512,
    field_mode: str = "dense",
    j_bits: int = 1,
):
    """Lower+compile the batched iteration step (dry-run, no allocation)."""
    hp = hp or SSAHyperParams(n_trials=n_trials)
    step = make_batched_iteration_step(
        hp, mesh, storage_layout=storage_layout, j_mode=j_mode, tile_n=tile_n,
        field_mode=field_mode,
    )
    B, T, N = n_problems, n_trials, n_spins
    dm = NamedSharding(mesh, P("data", None, "model"))
    dd = NamedSharding(mesh, P("data"))
    hb = NamedSharding(mesh, P("data", None))
    if storage_layout == "packed":
        nw = (N + 31) // 32
        spin_sh = NamedSharding(mesh, P("data", None, None))
        m_shape = jax.ShapeDtypeStruct((B, T, nw), jnp.uint32)
        bm_shape = jax.ShapeDtypeStruct((B, T, nw), jnp.uint32)
    else:
        spin_sh = dm
        m_shape = jax.ShapeDtypeStruct((B, T, N), jnp.float32)
        bm_shape = jax.ShapeDtypeStruct((B, T, N), jnp.int8)
    shapes = [
        jax.ShapeDtypeStruct((4, B, T, N), jnp.uint32),  # rng lanes
        m_shape,                                         # m (layout-dependent)
        jax.ShapeDtypeStruct((B, T, N), jnp.int32),      # itanh
        jax.ShapeDtypeStruct((B, T), jnp.int32),         # best_H
        bm_shape,                                        # best_m
    ]
    if field_mode == "popcount":
        jw = (N + 31) // 32
        prob_shapes = [
            jax.ShapeDtypeStruct((B, N, jw), jnp.uint32),          # sign
            jax.ShapeDtypeStruct((B, j_bits, N, jw), jnp.uint32),  # mags
            jax.ShapeDtypeStruct((B, N), jnp.int32),               # base
        ]
        prob_sh = [
            NamedSharding(mesh, P("data", None, None)),
            NamedSharding(mesh, P("data", None, None, None)),
            NamedSharding(mesh, P("data", None)),
        ]
    elif j_mode == "tiled":
        prob_shapes = [
            jax.ShapeDtypeStruct((B, N, max_degree), jnp.int32),  # nbr_idx
            jax.ShapeDtypeStruct((B, N, max_degree), jnp.int32),  # nbr_w
        ]
        prob_sh = [NamedSharding(mesh, P("data", None, None))] * 2
    else:
        prob_shapes = [jax.ShapeDtypeStruct((B, N, N), jnp.float32)]  # J
        prob_sh = [NamedSharding(mesh, P("data", "model", None))]
    shapes += prob_shapes + [jax.ShapeDtypeStruct((B, N), jnp.int32)]  # h
    rng_sh = NamedSharding(mesh, P(None, "data", None, "model"))
    shardings = tuple([rng_sh, spin_sh, dm, dd, spin_sh] + prob_sh + [hb])
    jitted = jax.jit(step, in_shardings=shardings, donate_argnums=(0, 1, 2, 3, 4))
    with mesh:
        return jitted.lower(*tuple(shapes))


# ---------------------------------------------------------------------------
# Spin-sharded execution (DESIGN.md §11): partition='spin'
#
# The problem-partitioned paths above replicate the spin axis and scale out
# over the *problem* batch; a single giant instance (100k+ spins) needs the
# spin axis itself split.  These backends run the exact plateau engine
# (`run_plateau_scan`, unchanged) inside a `shard_map` over one mesh axis:
#
#   * state shards: each device owns spins [i·Ns, (i+1)·Ns) of every trial —
#     its itanh, its xorshift lanes (seeded shard-locally via
#     `padded_noise_init_slice`, bit-identical to the global stream), its
#     best-m columns.  best_H stays replicated (it is psum'd every fold).
#   * J shards by rows: the f32-tiled slabs and the PackedJ popcount
#     bitplanes are both row-rectangular contractions, so each device holds
#     only its Ns rows — per-device J residency drops ~linearly in devices.
#   * one collective per cycle: the update m(t) → m(t+1) needs the *full*
#     spin state on every device.  Spins are ±1, so the all-gather moves
#     packed uint32 bitplanes — N/32 words per (trial, plane), 8×/32× below
#     int8/f32 — the bitplane format is what makes the collective cheap.
#   * energy: H folds/traces psum the per-shard partial sums *before* the
#     floor division (local h·m + m·field may be odd; int32 addition is
#     exact and order-free, so sharded H is bit-identical to unsharded).
#
# `check_vma=False`: replication of best_H is guaranteed by the psum, not
# inferred by shard_map's varying-axes check, and asserted (bit-identity vs
# the unsharded backends) in tests.
# ---------------------------------------------------------------------------


class BatchedSpinShardedBackend(BatchedBackend):
    """B stacked problems with the *spin axis* sharded over a mesh axis.

    The serving path for instances too big for one device: the same
    bucket/stack/chunk protocol as every :class:`BatchedBackend` (so
    `AnnealService` drives it unchanged), but problem arrays are laid out
    row-sharded over ``mesh`` at :meth:`stack` time and every plateau runs
    as a `shard_map` collective program.  Bit-identical per problem to the
    problem-partitioned backends on live lanes (property-tested).

    ``base_backend`` picks the field contraction the shards run locally:
    'sparse' gathers from the all-gathered spins through the padded
    adjacency; 'dense'/'pallas' use the rectangular f32 tiled-slab stream
    (``field_mode='dense'``, with ``double_buffer`` prefetch pipelining) or
    the XNOR-popcount bitplane contraction (``field_mode='popcount'``).
    The resident Pallas kernels are single-device programs, so under spin
    sharding 'pallas' runs its arithmetic through these scan paths.
    Without a ``mesh`` the spin axis shards over every device JAX sees
    (:func:`repro.sharding.spin_mesh`).
    """

    name = "spinshard"

    def __init__(self, *, mesh: Optional[Mesh] = None, axis: str = SPIN_AXIS,
                 base_backend: str = "dense", j_mode: str = "auto",
                 tile_n: int = 512, field_mode: str = "auto", j_bits: int = 1,
                 double_buffer: bool = True, j_dtype=None, block_r=None,
                 interpret=None, noise_mode=None, **kw):
        super().__init__(**kw)
        if self.noise != "xorshift":
            raise ValueError(
                "partition='spin' requires noise='xorshift': shard-local "
                "lane seeding is what makes sharded runs bit-identical"
            )
        del j_mode, j_dtype, block_r, interpret, noise_mode  # single-device knobs
        self.mesh = spin_mesh(axis=axis) if mesh is None else mesh
        self.axis = axis
        self.n_dev = mesh_axis_size(self.mesh, axis)
        if self.n_bucket % self.n_dev:
            raise ValueError(
                f"partition='spin': bucket {self.n_bucket} not divisible by "
                f"the {self.n_dev}-way {axis!r} mesh axis"
            )
        self.n_shard = self.n_bucket // self.n_dev
        self.tile_n = int(tile_n)
        self.j_bits = int(j_bits)
        self.double_buffer = bool(double_buffer)
        base = resolve_backend(base_backend, self.n_bucket)
        if base == "sparse":
            self.field_mode = "dense"
            self.field_style = "sparse"
        else:
            self.field_mode = resolve_field_mode(field_mode, self.j_bits)
            self.field_style = (
                "popcount" if self.field_mode == "popcount" else "tiled"
            )
        self.base_backend = base
        # Row-tile the popcount contraction in the regime the matmul would
        # tile J — but against the *shard's* row count, not the bucket's.
        self._pc_tile = (
            None if self.n_shard <= TILED_J_THRESHOLD else self.tile_n
        )
        self.row_tiled = (self.field_style == "tiled"
                          or (self.field_style == "popcount"
                              and self._pc_tile is not None))
        # Packed-layout spin words shard over devices only when each shard
        # is word-aligned; otherwise the (tiny) planes stay replicated and
        # each device slices its columns after the local unpack.
        self._words_shardable = self.n_shard % 32 == 0

    # -- sharding layout --------------------------------------------------
    def _problem_specs(self) -> dict:
        ax = self.axis
        if self.field_style == "popcount":
            return {
                "h": P(None, ax),
                "sign": P(None, ax, None),
                "mags": P(None, None, ax, None),
                "base": P(None, ax),
            }
        return {
            "h": P(None, ax),
            "nbr_idx": P(None, ax, None),
            "nbr_w": P(None, ax, None),
        }

    def _state_specs(self):
        ax = self.axis
        lanes = P(None, None, None, ax)
        spins = P(None, None, ax)
        rep = P(None, None)
        if self.storage_layout == "packed":
            words = spins if self._words_shardable else rep
            return PackedEngineState(lanes, words, spins, rep, words)
        return EngineState(lanes, spins, spins, rep, spins)

    def _put_state(self, st):
        def put(x, spec):
            sh = NamedSharding(self.mesh, spec)
            if isinstance(x, jax.core.Tracer):
                return jax.lax.with_sharding_constraint(x, sh)
            return jax.device_put(x, sh)

        return type(st)(*(put(x, s) for x, s in zip(st, self._state_specs())))

    # -- host side --------------------------------------------------------
    def stack(self, models, couplings=model_couplings) -> dict:
        """Host arrays of the stacked problems, each device's row block put
        on that device straight from the host: no device ever holds
        another's rows of J."""
        if self.field_style == "popcount":
            problem = _stack_packed_models(models, self.n_bucket, self.j_bits,
                                           couplings)
        else:
            problem = _stack_sparse_models(models, self.n_bucket)
        specs = self._problem_specs()
        return {
            k: jax.device_put(v, NamedSharding(self.mesh, specs[k]))
            for k, v in problem.items()
        }

    def gather_bytes(self, b_bucket: int, plateaus, n_shots: int) -> int:
        """Bytes of spins one chunk program all-gathers over the mesh.

        One gather per field evaluation: every cycle of every plateau, plus
        the epilogue of each eligible plateau (its final state's fold).
        Each gathers the (B, T, N_bucket) spins as packed words, N_bucket/8
        bytes per (lane, trial) row, when every shard is word-aligned, else
        as int8, N_bucket bytes per row; packed and unaligned, the chunk's
        end gathers m and best_m once more as int8.  So, word-aligned:
        n_shots x (cycles + eligible plateaus) x B x T x N_bucket / 8.
        """
        rows = int(b_bucket) * self.n_trials
        per_gather = rows * self.n_bucket // (8 if self._words_shardable
                                              else 1)
        gathers = int(n_shots) * sum(p.length + int(p.eligible)
                                     for p in plateaus)
        end = (2 * rows * self.n_bucket
               if self.storage_layout == "packed"
               and not self._words_shardable else 0)
        return gathers * per_gather + end

    def init_noise(self, seeds, n_lives):
        """Shard-local lane seeding: each device seeds only its columns.

        `make_array_from_callback` hands every device its slice of the
        global (B, 4, T, N_bucket) lane array; `padded_noise_init_slice`
        seeds exactly those columns bit-identically to the full
        `padded_noise_init` — no device ever materializes the global lanes.
        """
        seeds = [int(s) for s in seeds]
        n_lives = [int(x) for x in n_lives]
        T, nb = self.n_trials, self.n_bucket
        shape = (len(seeds), 4, T, nb)
        sh = NamedSharding(self.mesh, P(None, None, None, self.axis))

        def cb(index):
            lo, hi, _ = index[3].indices(nb)
            return np.stack([
                padded_noise_init_slice(s, T, nl, nb, lo, hi)
                for s, nl in zip(seeds, n_lives)
            ])

        return jax.make_array_from_callback(shape, sh, cb)

    # -- traced -----------------------------------------------------------
    def init_state(self, problem, noise0):
        return self._put_state(super().init_state(problem, noise0))

    def _energy(self, m, field, h):
        # energy_from_field with the trial sums psum'd over shards BEFORE
        # the floor division: local (h·m + m·field) may be odd, the global
        # sum is what's even; int32 addition is order-free, so this is
        # bit-identical to the unsharded fold.
        m32 = m.astype(jnp.int32)
        s = jnp.sum(h * m32, axis=-1) + jnp.sum(m32 * field, axis=-1)
        return -jax.lax.psum(s, self.axis) // 2

    def _gather_words(self, m_local):
        """Local spin shard → full packed bitplanes (the cheap collective)."""
        if self.n_shard % 32 == 0:
            w = pack_spins(m_local)
            return jax.lax.all_gather(w, self.axis, axis=-1, tiled=True)
        m_full = jax.lax.all_gather(m_local, self.axis, axis=-1, tiled=True)
        return pack_spins(m_full)

    def _gather_spins(self, m_local):
        """Local spin shard → full int8 spins, moved packed when aligned."""
        if self.n_shard % 32 == 0:
            return unpack_spins(self._gather_words(m_local), self.n_bucket)
        return jax.lax.all_gather(m_local, self.axis, axis=-1, tiled=True)

    def _field(self, prob, m_local):
        """This shard's fields from its J rows + the all-gathered spins."""
        if self.field_style == "popcount":
            mw = self._gather_words(m_local)
            return jax.vmap(
                lambda w, hh, s, g, b: local_fields_popcount(
                    w, hh, PackedJ(s, g, b), tile_n=self._pc_tile
                )
            )(mw, prob["h"], prob["sign"], prob["mags"], prob["base"])
        m_full = self._gather_spins(m_local)
        if self.field_style == "sparse":
            return jax.vmap(
                lambda mm, hh, ii, ww: local_fields_sparse(
                    mm.astype(jnp.int32), hh, ii, ww
                )
            )(m_full, prob["h"], prob["nbr_idx"], prob["nbr_w"])
        return jax.vmap(
            lambda mm, hh, ii, ww: local_fields_tiled(
                mm, hh, ii, ww, tile_n=self.tile_n,
                double_buffer=self.double_buffer,
            )
        )(m_full, prob["h"], prob["nbr_idx"], prob["nbr_w"])

    def _unpack_local(self, st: PackedEngineState) -> EngineState:
        if self._words_shardable:
            return EngineState(
                st.noise_state, unpack_spins(st.m_packed, self.n_shard),
                st.itanh, st.best_H,
                unpack_spins(st.best_m_packed, self.n_shard),
            )
        i = jax.lax.axis_index(self.axis)

        def cols(words):
            full = unpack_spins(words, self.n_bucket)
            return jax.lax.dynamic_slice_in_dim(
                full, i * self.n_shard, self.n_shard, axis=full.ndim - 1
            )

        return EngineState(
            st.noise_state, cols(st.m_packed), st.itanh, st.best_H,
            cols(st.best_m_packed),
        )

    def _pack_local(self, st: EngineState) -> PackedEngineState:
        if self._words_shardable:
            return PackedEngineState(
                st.noise_state, pack_spins(st.m), st.itanh, st.best_H,
                pack_spins(st.best_m),
            )
        mf = jax.lax.all_gather(st.m, self.axis, axis=-1, tiled=True)
        bf = jax.lax.all_gather(st.best_m, self.axis, axis=-1, tiled=True)
        return PackedEngineState(
            st.noise_state, pack_spins(mf), st.itanh, st.best_H,
            pack_spins(bf),
        )

    def _local_chain(self, prob, st, plateaus, n_shots):
        def iteration(st, _):
            for p in plateaus:
                st, _, _ = self._plateau_scan(prob, st, p)
            return st, None

        st, _ = jax.lax.scan(iteration, st, None, length=n_shots)
        return st

    def _sharded_chain(self, plateaus, n_shots: int):
        plateaus = tuple(plateaus)
        packed = self.storage_layout == "packed"
        sspec = self._state_specs()

        def local_fn(prob, st):
            if packed:
                st = self._unpack_local(st)
            st = self._local_chain(prob, st, plateaus, n_shots)
            if packed:
                st = self._pack_local(st)
            return st

        return jax.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=(self._problem_specs(), sspec), out_specs=sspec,
            check_vma=False,
        )

    def run_plateau(self, problem, state, i0, *, length, eligible, jperp=0):
        p = Plateau(int(i0), int(length), bool(eligible), int(jperp))
        return self._sharded_chain((p,), 1)(problem, state)

    def run_plateau_traced(self, problem, state, plateau: Plateau, *,
                           track_energy: bool = False, emit: bool = False):
        """The shared traced plateau, run inside the shard_map program.

        Energies are psum'd over the shards, so the trace is the unsharded
        one.  Trajectory planes are not supported: emit semantics would be
        per-device partial planes — use partition='problem' for them.
        """
        if emit:
            raise NotImplementedError(
                "record='traj' is not supported under partition='spin'; "
                "use partition='problem' for trajectory capture"
            )
        if not track_energy:
            return super().run_plateau_traced(problem, state, plateau)
        packed = self.storage_layout == "packed"
        sspec = self._state_specs()

        def local_fn(prob, st):
            if packed:
                st = self._unpack_local(st)
            st, trace, _ = self._plateau_scan(prob, st, plateau,
                                              track_energy=True)
            if packed:
                st = self._pack_local(st)
            return st, trace

        st, trace = jax.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=(self._problem_specs(), sspec),
            out_specs=(sspec, (P(None), P(None))),
            check_vma=False,
        )(problem, state)
        return st, trace, None

    def run_shots(self, problem, state, plateaus, n_shots, live=None):
        return self._sharded_chain(tuple(plateaus), int(n_shots))(
            problem, state
        )
