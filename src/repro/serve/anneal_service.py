"""Shape-bucketed annealing service: one compiled plateau program serving
batched heterogeneous Max-Cut requests (DESIGN.md §7), with a resilience
layer that degrades gracefully on any fault below the request boundary
(DESIGN.md §10).

The paper's operating mode is "one fixed pipeline, many instances": the FPGA
streams Max-Cut problems through a single annealing datapath.  The TPU
transcription is this service:

* **Shape buckets** — incoming problems are zero-padded to power-of-two N
  (:func:`repro.core.engine.bucket_n` / :func:`~repro.core.engine.pad_model`),
  so a heterogeneous request stream collapses onto a handful of shapes.
* **Compiled-executable cache** — one jitted plateau program per
  ``(algorithm, backend, backend_opts, N_bucket, B_bucket, n_trials, n_rnd,
  noise, storage, Schedule.signature(), chunk)``.  Problem arrays are
  *arguments* to the program, never closed-over constants, so every
  same-bucket request group reuses the same executable: 4 G-set instances
  in one bucket compile the plateau program exactly once (trace-count
  tested).
* **Problem-axis batching** — same-bucket requests are stacked on a leading
  problem axis and solved in ONE device launch via the engine's batched
  backends (vmap for sparse/dense, the (B, R-tile)-grid resident kernel for
  pallas).  Batched runs are bit-identical per problem to unbatched,
  unpadded runs on the live lanes (padding-invariance tested) when the
  noise source is ``xorshift``.
* **Chunked execution with early stop** — the m_shot iteration budget runs
  in chunks; after each chunk the per-request best energy is reported
  (streaming progress) and a group whose requests have all reached their
  ``target_cut`` stops early.
* **Packed storage + tiled J** — ``storage_layout='packed'`` carries the
  engine state between chunk launches as uint32 spin bitplanes (and, for
  the pallas backend with xorshift noise, runs the streamed-noise packed
  kernel: no noise buffer, packed HBM refs).  The dense backend's
  ``j_mode='auto'`` streams (tile_n, N) J slabs above
  ``engine.TILED_J_THRESHOLD`` spins instead of materializing (B, N, N) —
  G77/G81-class buckets (N = 10k–20k) serve through the same entry.  Both
  axes ride the executable-cache key; results stay bit-identical.

Resilience (DESIGN.md §10).  Because *all* live state between plateau
chunks is a tiny explicit buffer — spin (bit)planes, the carried
xorshift128 lanes, ``best_H`` and the chunk index — faults recover
*bit-identically*, not best-effort:

* **Chunk-level checkpoint/resume** — with
  ``ResiliencePolicy(checkpoint_dir=...)`` each group snapshots its engine
  state through :class:`repro.checkpoint.ckpt.CheckpointManager` at chunk
  boundaries, keyed by a stable group fingerprint.  A process killed
  mid-solve resumes from the last boundary and produces bit-identical
  ``best_cut``/spins to an uninterrupted run (chaos-tested for all three
  backends with ``noise='xorshift'``).
* **Backend fallback chain** — a compile/launch failure walks
  pallas→dense→sparse; a dense-J OOM downgrades to tiled-J first.  The
  fallback re-enters the executable cache under its own key, and the
  downgrade is recorded on ``AnnealResponse.status``/``events``.
* **Watchdogs** — a per-request wall-clock ``deadline_s`` returns
  best-so-far with ``status='deadline'`` at the next chunk boundary; a
  non-finite energy detector quarantines the offending request (solo retry
  with exponential backoff and a re-autotuned I0max) without touching its
  batchmates' bit-exactness; admission validation rejects non-finite
  weights and absurd shapes with typed :class:`AdmissionError`\\ s before
  any device work happens.
* **Fault injection** — every failure path above is exercised by the hook
  points an attached :class:`repro.ft.faults.FaultInjector` fires
  (compile / oom / nan / kill), driven by the chaos suite.

Beyond Max-Cut, any :class:`~repro.problems.ProblemEncoding` (QUBO, MIS,
coloring, partitioning — DESIGN.md §9) rides the same entry, and
``hp='auto'`` resolves per-instance hyperparameters before grouping
(:mod:`repro.core.autotune`), so autotuning composes with batching and the
executable cache instead of fragmenting them.

SA (:class:`~repro.core.sa.SAHyperParams`) and PT-SSA
(:class:`~repro.core.pt.PTSSAHyperParams`) requests ride the same entry:
they are grouped, bucketed, stacked, chunked, checkpointed and
early-stopped identically — SA through the vmapped Metropolis core,
PT-SSA through :func:`repro.core.pt.pt_ssa_rounds` with the replica ladder
on the engine's trial axis.  (SA groups never need the backend fallback
chain: their Metropolis core is backend-independent.)

SSQA (:class:`~repro.core.ssqa.SSQAHyperParams`, ``algo='ssqa'``) is the
fourth family (DESIGN.md §13): it rides the SSA plateau path with the
Trotter-replica ring on the trial axis — the group solver injects
``n_replicas`` into the backend opts (program-structural: ring width per
R-tile) and the J⊥ ramp rides the schedule signature, so SSQA groups get
their own cached executables while sharing every line of the batching,
chunking, checkpointing and fallback machinery.  Family dispatch and the
per-family admission rules live in :mod:`repro.serve.registry`.
"""
from __future__ import annotations

import collections
import dataclasses
import itertools
import os
import threading
import time
from typing import Callable, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.ckpt import CheckpointManager, latest_step
from repro.core.autotune import (
    AutotuneReport,
    autotune_hyperparams,
    resolve_hyperparams,
)
from repro.core.engine import (
    MAX_UNSHARDED_SPINS,
    bucket_n,
    finalize_cut,
    make_batched_backend,
    model_couplings,
    next_pow2,
    normalize_problem,
    resolve_field_mode,
    resolve_partition,
    route_backend,
    schedule_plateaus,
    validate_model,
)
from repro.core.config import SolverConfig
from repro.core.ising import IsingModel, MaxCutProblem
from repro.core.pt import PTSSAHyperParams, PTSSAResult, pt_ssa_rounds
from repro.core.rng import xorshift_lanes_ok
from repro.core.sa import SAHyperParams, SAResult, sa_cycles, sa_init
from repro.core.schedule import sa_temperature_ladder
from repro.core.ssa import AnnealResult, SSAHyperParams
from repro.core.ssqa import SSQAHyperParams
from repro.ft.faults import FaultInjector
from repro.problems import ProblemEncoding
from repro.sharding import mesh_fingerprint

from .registry import family_for, registered_algos
from .spans import Spans

from .resilience import (
    STATUS_DEADLINE,
    STATUS_FAILED,
    STATUS_FALLBACK,
    STATUS_OK,
    STATUS_QUARANTINED,
    AdmissionError,
    QuarantineFault,
    ResiliencePolicy,
    ServiceEvent,
    classify_fault,
    fallback_step,
    filter_backend_opts,
    group_fingerprint,
)

__all__ = [
    "AnnealRequest",
    "AnnealResponse",
    "AnnealProgress",
    "AnnealService",
]

HyperParams = Union[SSAHyperParams, SAHyperParams, PTSSAHyperParams,
                    SSQAHyperParams]


@dataclasses.dataclass(frozen=True)
class AnnealRequest:
    """One problem + hyperparameters, as the service accepts it.

    ``problem`` is a Max-Cut instance, a raw Ising model, or any encoded
    problem from :mod:`repro.problems` (QUBO, MIS, coloring, partitioning…)
    — encoded problems come back with a decoded, feasibility-verified domain
    solution on the response.

    ``hp`` selects the algorithm family through the registry
    (:mod:`repro.serve.registry`): SSAHyperParams → SSA/HA-SSA (the paper's
    annealer), SSQAHyperParams → Trotter-replica SSQA, SAHyperParams →
    Metropolis SA, PTSSAHyperParams → PT on the plateau engine.  ``algo``
    optionally names the family explicitly (``'ssa'``/``'sa'``/``'ptssa'``/
    ``'ssqa'``): it is validated against the hp type, and with ``hp='auto'``
    it selects which family the autotuner targets (``algo='ssqa'`` tunes
    the Trotter ring too).  The string ``'auto'`` requests
    local-energy-distribution autotuning (:mod:`repro.core.autotune`).
    ``config`` is a per-request :class:`~repro.core.config.SolverConfig`
    override of the service's backend/backend-option defaults (its
    ``noise``/``storage_layout`` must match the service's — those axes are
    service-wide contracts); its ``signature()`` joins the batching key so
    differently-configured requests never share a compiled program.
    ``target_cut`` arms chunk-level early stop.  ``deadline_s`` is the
    per-request wall-clock budget, measured from the ``solve()`` call: once
    it elapses, the request stops participating in its group's continuation
    and its response returns best-so-far with ``status='deadline'`` at the
    next chunk boundary — it never raises.
    """

    problem: Union[MaxCutProblem, IsingModel, ProblemEncoding]
    hp: Union[HyperParams, str] = SSAHyperParams()
    seed: int = 0
    storage: str = "i0max"         # SSA only: 'i0max' (HA-SSA) | 'all' (SSA)
    schedule_kind: str = "hassa"   # SSA only
    target_cut: Optional[int] = None
    auto_base: Optional[SSAHyperParams] = None  # budget knobs for hp='auto'
    deadline_s: Optional[float] = None  # wall-clock budget from solve() entry
    algo: Optional[str] = None     # explicit family name (registry-validated)
    config: Optional[SolverConfig] = None  # per-request solver-option override


@dataclasses.dataclass
class AnnealResponse:
    request: AnnealRequest
    result: object                 # AnnealResult | SAResult | PTSSAResult | None
    wall_s: float                  # group wall time (the batch solves together)
    bucket: int                    # padded N the request ran at
    batch: int                     # live requests stacked in its group
    chunks_run: int                # chunks executed (early stop may cut short)
    chunks_total: int
    chunk_best_cut: np.ndarray     # (chunks_run,) streaming best-objective trace
    solution: object = None        # decoded domain solution (encoded problems)
    objective: Optional[int] = None  # domain objective of `solution` if feasible
    feasible: Optional[bool] = None  # verifier verdict (None: raw Ising/maxcut)
    autotune: Optional[AutotuneReport] = None  # set when hp='auto' resolved
    status: str = STATUS_OK        # 'ok'|'fallback'|'deadline'|'quarantined'|'failed'|'shed'
    events: List[ServiceEvent] = dataclasses.field(default_factory=list)
    # Per-lane latency honesty (streaming): a lane that early-stops reports
    # the wall time to ITS chunk-boundary stop, not the whole group's.
    lane_wall_s: Optional[float] = None  # group start → this lane's stop boundary
    queued_s: Optional[float] = None     # streaming only: submit → first seated
    # The backend the request's plateau program ran on, after 'auto' routing
    # and any fallback (None for SA, whose Metropolis core has no backend).
    backend: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class AnnealProgress:
    """One streaming progress report (per group, per chunk)."""

    kind: str                      # 'ssa' | 'sa' | 'ptssa' | 'ssqa'
    bucket: int
    chunk: int
    chunks_total: int
    request_indices: tuple         # indices into the solve() request list
    best_cut: tuple                # best objective so far, per request


def _largest_divisor_leq(n: int, k: int) -> int:
    k = max(1, min(int(k), int(n)))
    while n % k:
        k -= 1
    return k


def _opts_key(opts: dict) -> tuple:
    """Hashable projection of backend_opts for the executable-cache key."""
    return tuple(sorted((k, repr(v)) for k, v in opts.items()))


class _ByIdentity:
    """``fn(obj)`` computed once per distinct object, for one ``solve()`` call.

    Keyed by ``id(obj)``; each entry holds its object, so no id is reused
    while the memo lives.  Equal but distinct objects are computed apart.
    """

    def __init__(self, fn):
        self._fn = fn
        self._memo: dict = {}

    def __call__(self, obj):
        ent = self._memo.get(id(obj))
        if ent is None:
            ent = self._memo[id(obj)] = (obj, self._fn(obj))
        return ent[1]


class _Program:
    """A jitted service program that records its one compile.

    The first call lowers and compiles ahead of time — the same executable
    jit's own dispatch then reuses — so the service can report the compile
    seconds and show the compiled text (which kernels the program launches)
    without a second compile.  That compile runs inside the ``compile``
    span, so a compile inside a measured window shows on the timeline.
    """

    def __init__(self, fn, key, backend, spans: Spans):
        self.key = key
        self.backend = backend        # the BatchedBackend it was built from
        self._jit = jax.jit(fn)
        self._spans = spans
        self.compiled = None
        self.compile_s: Optional[float] = None

    def __call__(self, *args):
        if self.compiled is None:
            with self._spans("compile", program=self.key[-1]):
                t0 = time.perf_counter()
                self.compiled = self._jit.lower(*args).compile()
                self.compile_s = time.perf_counter() - t0
        return self._jit(*args)


class _LRUCache:
    """Bounded LRU map for compiled executables.

    Under diverse streaming traffic the per-group-key program population is
    unbounded (every new (bucket, batch, schedule, opts) shape compiles a
    fresh program and its XLA executable stays live), so the cache evicts
    least-recently-used entries past ``capacity``, counting evictions into
    the service's ``stats``.  Thread-safe: concurrent ``solve()`` calls and
    the streaming scheduler hit it from different threads.  Two threads
    missing on the same key may both build the program; the second ``put``
    wins and the loser's executable is garbage — wasteful but correct
    (build-outside-lock keeps compiles from serializing the service).
    """

    def __init__(self, capacity: int, stats: collections.Counter):
        if capacity < 1:
            raise ValueError(f"max_cached_executables must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._od: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()
        self._stats = stats

    def get(self, key):
        with self._lock:
            ent = self._od.get(key)
            if ent is not None:
                self._od.move_to_end(key)
            return ent

    def __setitem__(self, key, ent):
        with self._lock:
            self._od[key] = ent
            self._od.move_to_end(key)
            while len(self._od) > self.capacity:
                self._od.popitem(last=False)
                self._stats["program_cache_evictions"] += 1

    def __len__(self):
        with self._lock:
            return len(self._od)

    def __contains__(self, key):
        with self._lock:
            return key in self._od

    def __iter__(self):
        with self._lock:
            return iter(list(self._od))

    def values(self):
        with self._lock:
            return list(self._od.values())


class _GroupCtx:
    """Per-attempt execution context for one request group.

    Carries the effective backend (which the fallback chain may have
    downgraded from the service default), the fault-injection hooks, the
    group's checkpoint namespace, and the per-request statuses/events the
    chunk loop accumulates (deadline expirations, resumes, …).
    """

    def __init__(self, service: "AnnealService", kind: str, nb: int, items,
                 backend: str, backend_opts: dict, solve_t0: float,
                 chunk: int, couplings,
                 events: Optional[List[ServiceEvent]] = None):
        self.kind = kind
        self.couplings = couplings
        self.backend = backend
        self.backend_opts = dict(backend_opts)
        self.solve_t0 = solve_t0
        self.faults: Optional[FaultInjector] = service.faults
        self.policy: ResiliencePolicy = service.policy
        self.noise = service.noise
        self.events: List[ServiceEvent] = list(events or [])
        self.statuses: dict = {}
        self.row_tiled = False   # set by AnnealService._stack
        self.ckpt: Optional[CheckpointManager] = None
        self._dir: Optional[str] = None
        if self.policy.checkpoint_dir:
            part = service.partition_for(kind, nb)
            tag = group_fingerprint(kind, nb, backend, service.storage_layout,
                                    service.noise, chunk, items,
                                    partition=part,
                                    mesh_fp=(mesh_fingerprint(service.mesh)
                                             if part == "spin" else ()))
            self._dir = os.path.join(self.policy.checkpoint_dir, tag)
            self.ckpt = CheckpointManager(
                self._dir,
                save_interval=max(1, int(self.policy.checkpoint_interval)),
                keep=self.policy.keep_checkpoints,
                async_save=False,  # deterministic crash window
            )

    # -- fault hooks ------------------------------------------------------
    def fire(self, point: str, **ctx):
        if self.faults is None:
            return None
        return self.faults.fire(point, **ctx)

    def _event(self, kind: str, **detail):
        self.events.append(
            ServiceEvent(kind, detail, time.perf_counter() - self.solve_t0)
        )

    # -- checkpointing ----------------------------------------------------
    def maybe_resume(self, template, n_items: int):
        """(start_chunk, state, traces) — resuming if a valid snapshot exists."""
        if self.ckpt is None or latest_step(self._dir) is None:
            return 0, template, None
        state, meta = self.ckpt.restore_latest(template)
        traces = meta.get("traces")
        ok = isinstance(traces, list) and len(traces) == n_items
        if ok and self.noise == "xorshift":
            lanes = getattr(state, "noise_state", None)
            # Batched lane layout (B, 4, T, N): the 4-word axis is axis 1.
            ok = lanes is not None and xorshift_lanes_ok(lanes, axis=1)
        if not ok:
            self._event("checkpoint_rejected", dir=self._dir)
            return 0, template, None
        start = int(meta["step"])
        self._event("resume", chunk=start, dir=self._dir)
        return start, state, [list(map(int, t)) for t in traces]

    def save(self, step: int, state, traces):
        if self.ckpt is not None:
            self.ckpt.maybe_save(step, state, meta={"traces": traces})

    def finish_success(self):
        if self.ckpt is not None and self.policy.cleanup_on_success:
            self.ckpt.purge()


class AnnealService:
    """Batched annealing-as-a-service over the plateau engine.

    One service instance owns a backend choice, a noise source, the
    compiled-executable cache, and a :class:`ResiliencePolicy`.
    ``solve(requests)`` groups requests by (algorithm, shape bucket,
    hyperparameters), stacks each group on the problem axis, and runs it
    through one cached compiled program; any fault below the request
    boundary (compile failure, OOM, non-finite energies, deadline) degrades
    that group gracefully instead of failing the batch — see the module
    docstring and DESIGN.md §10 for the failure model.

    Bit-exactness contract (noise='xorshift'): an SSA or PT-SSA request
    solved through the service — padded, stacked, chunked, checkpointed,
    resumed — returns the same best energy/spins on its live lanes as the
    library driver (`anneal` / `anneal_pt_ssa`, the same backend at B=1) on
    the unpadded instance.  SA requests are valid runs but not bit-comparable
    (their threefry init draw is shape-dependent).
    """

    def __init__(
        self,
        backend: str = "sparse",
        *,
        noise: str = "xorshift",
        storage_layout: str = "dense",
        chunk_shots: int = 1,
        sa_chunks: int = 8,
        min_bucket: int = 64,
        backend_opts: Optional[dict] = None,
        autotune_seed: int = 0,
        resilience: Optional[ResiliencePolicy] = None,
        faults: Optional[FaultInjector] = None,
        partition: str = "problem",
        mesh=None,
        max_cached_executables: int = 64,
        config: Optional[SolverConfig] = None,
    ):
        """``storage_layout='packed'`` keeps the HBM-resident engine state
        between chunk launches as uint32 spin bitplanes (DESIGN.md §4).
        ``backend='auto'`` resolves per shape bucket (resident pallas at or
        above ``engine.MIN_RESIDENT_N`` spins, dense below — the small-N
        launch-overhead rule), filtering ``backend_opts`` to whatever the
        chosen backend accepts.  ``backend_opts={'field_mode': 'auto'}``
        additionally resolves the XNOR-popcount contraction per group
        (DESIGN.md §8): groups whose couplings fit
        ``engine.POPCOUNT_AUTO_MAX_BITS`` magnitude bitplanes run bit-
        parallel, with the group's plane count folded into the executable-
        cache key.  ``resilience`` configures checkpointing/fallback/retry
        (defaults: fallback + admission validation on, checkpointing off);
        ``faults`` attaches a fault injector whose hook points the service
        fires (testing/chaos only — never set in production).

        ``partition`` selects the work-partitioning axis for SSA groups
        (DESIGN.md §11): ``'problem'`` (default) stacks whole problems per
        device; ``'spin'`` shards the spin axis of every problem over
        ``mesh``'s model axis via shard_map collectives — the only way
        instances above ``engine.MAX_UNSHARDED_SPINS`` are admitted;
        ``'auto'`` resolves per shape bucket.  Spin-sharded groups require
        ``noise='xorshift'`` (shard-local lane seeding is what makes sharded
        runs bit-identical to single-device runs).  SA and PT-SSA groups
        always run problem-partitioned.

        ``config`` supplies the whole knob set from one
        :class:`~repro.core.config.SolverConfig` — its backend, noise,
        storage_layout, field/J/noise-mode options, partition and mesh
        replace the corresponding individual kwargs (which remain for
        compatibility and are ignored when ``config`` is given).
        """
        if config is not None:
            backend = config.backend
            noise = config.noise
            storage_layout = config.storage_layout
            backend_opts = config.engine_opts()
            backend_opts.pop("storage_layout", None)  # passed apart below
            partition = config.partition
            mesh = config.mesh if config.mesh is not None else mesh
        if storage_layout not in ("dense", "packed"):
            raise ValueError(f"unknown storage_layout {storage_layout!r}")
        if partition not in ("problem", "spin", "auto"):
            raise ValueError(f"unknown partition {partition!r}")
        self.backend = backend
        self.noise = noise
        self.storage_layout = storage_layout
        self.chunk_shots = int(chunk_shots)   # SSA iterations / PT rounds per chunk
        self.sa_chunks = int(sa_chunks)       # SA: report/early-stop points per run
        self.min_bucket = int(min_bucket)
        self.autotune_seed = int(autotune_seed)
        self.backend_opts = dict(backend_opts or {})
        self.policy = resilience or ResiliencePolicy()
        self.faults = faults
        self.partition = partition
        self.mesh = mesh
        self.stats = collections.Counter()
        # Host spans (repro.serve.spans) count into the same stats.
        self.spans = Spans(self.stats)
        self._solve_seq = itertools.count()
        # LRU-bounded: diverse streaming traffic would otherwise grow one
        # live XLA executable per unique group key forever.
        self._programs = _LRUCache(max_cached_executables, self.stats)

    def partition_for(self, kind: str, nb: int) -> str:
        """Effective partition for one group: 'problem' or 'spin'.

        Spin sharding applies only to the plateau path (SSA and SSQA — the
        replica ring lives on the shard-local trial axis, so sharding the
        spin axis needs no extra collectives) — SA and PT-SSA run through
        per-problem field closures the shard_map backend doesn't expose, so
        they stay problem-partitioned regardless of the knob.
        """
        if kind not in ("ssa", "ssqa"):
            return "problem"
        return resolve_partition(self.partition, nb, self.mesh)

    # ------------------------------------------------------------------
    # Public API
    # ------------------------------------------------------------------
    def solve(
        self,
        requests: Sequence[AnnealRequest],
        progress: Optional[Callable[[AnnealProgress], None]] = None,
    ) -> List[AnnealResponse]:
        """Solve a batch of heterogeneous requests; responses keep order.

        ``solve([])`` returns ``[]``.  The same request object may appear
        multiple times in one batch (aliased requests): each occurrence gets
        its own response.  Requests that carry the same ``problem`` object
        share its preparation within the call: it is normalized, validated,
        scanned for weight bits and packed once, and its lanes index that
        one result.  Equal but distinct objects are prepared apart.  So the
        problems' arrays must not be mutated while a call runs.
        ``hp='auto'`` requests are resolved *before*
        grouping — autotuned hyperparameters are ordinary call-time
        arguments by the time the bucketing and the compiled-executable
        cache see them.  Admission validation (non-finite weights, absurd
        shapes, bad knobs) rejects the batch with a typed
        :class:`AdmissionError` before any device work happens.
        """
        if not requests:
            return []
        with self.spans("solve", solve=next(self._solve_seq)):
            return self._solve(requests, progress)

    def _solve(self, requests, progress) -> List[AnnealResponse]:
        t_solve0 = time.perf_counter()
        spans = self.spans
        self.stats["requests"] += len(requests)
        responses: List[Optional[AnnealResponse]] = [None] * len(requests)
        reports: dict = {}
        groups = collections.defaultdict(list)
        # Each distinct problem object is prepared once per call; the entry
        # holds the object, so its id is not reused while the call runs.
        prepared: dict = {}   # id(problem) -> (problem, maxcut, model)
        # Each distinct model's couplings are coalesced once per call: the
        # weight-bit scans and the bitplane packing all read that one pass.
        couplings = _ByIdentity(self._coalesce)
        for idx, req in enumerate(requests):
            seen = prepared.get(id(req.problem))
            with spans("normalize"):
                if seen is not None:
                    _, maxcut, model = seen
                else:
                    try:
                        maxcut, model = normalize_problem(req.problem)
                    except TypeError as e:
                        raise AdmissionError(f"request {idx}: {e}") from e
            if self.policy.validate_admission:
                with spans("admit"):
                    self._admit(idx, req, model, check_model=seen is None)
            if seen is None:
                prepared[id(req.problem)] = (req.problem, maxcut, model)
                self.stats["prep_instances"] += 1
            if isinstance(req.hp, str):
                with spans("autotune"):
                    hp, reports[idx] = resolve_hyperparams(
                        req.hp, model, base=req.auto_base,
                        seed=self.autotune_seed, algo=req.algo,
                    )
                req = dataclasses.replace(req, hp=hp)
            fam = family_for(req.hp, algo=req.algo)  # raises AdmissionError
            if fam.validate is not None:
                # Family-owned admission rules are correctness (a backend
                # the family cannot run on), not optional hygiene — they
                # fire even with policy.validate_admission off.
                fam.validate(self, idx, req, req.hp)
            nb = bucket_n(model.n, self.min_bucket)
            groups[self._group_key(req, nb)].append((idx, req, maxcut, model))
        self.stats["groups"] += len(groups)
        for key, items in sorted(groups.items(), key=lambda kv: repr(kv[0])):
            kind, nb = key[0], key[1]
            self._solve_group_resilient(kind, nb, items, responses, progress,
                                        t_solve0, couplings=couplings)
        with spans("decode"):
            for idx, resp in enumerate(responses):
                resp.autotune = reports.get(idx)
                if resp.result is None:
                    continue
                enc = resp.request.problem
                if isinstance(enc, ProblemEncoding):
                    sol, obj, feas = enc.best_feasible(resp.result.best_m)
                    resp.solution, resp.objective, resp.feasible = sol, obj, feas
        return responses  # type: ignore[return-value]

    def programs(self) -> List[_Program]:
        """Every plateau program in the executable cache (SSA and SSQA
        groups), each with its ``backend`` and, once it has run, its
        ``compile_s`` and ``compiled`` executable."""
        return [p for ent in self._programs.values() for p in ent
                if isinstance(p, _Program)]

    def cache_info(self) -> dict:
        """Executable-cache observability (programs + trace counters)."""
        return {
            "programs": len(self._programs),
            "capacity": self._programs.capacity,
            "evictions": self.stats["program_cache_evictions"],
            "keys": sorted(repr(k) for k in self._programs),
            **{k: v for k, v in self.stats.items()},
        }

    # ------------------------------------------------------------------
    # Admission
    # ------------------------------------------------------------------
    def _admit(self, idx: int, req: AnnealRequest, model: IsingModel, *,
               check_model: bool = True):
        """Admission checks of one request; ``check_model=False`` skips the
        model's own validation (done for an earlier request of the call)."""
        if check_model:
            try:
                validate_model(model)
            except ValueError as e:
                self.stats["admission_rejects"] += 1
                raise AdmissionError(f"request {idx}: {e}") from e
        if req.deadline_s is not None and not float(req.deadline_s) > 0:
            self.stats["admission_rejects"] += 1
            raise AdmissionError(
                f"request {idx}: deadline_s must be > 0, got {req.deadline_s}"
            )
        if req.config is not None:
            # Per-request configs may retarget backend/field options, but
            # noise and storage layout are service-wide contracts (they key
            # checkpoint fingerprints and the packed-state carry format).
            if req.config.noise != self.noise:
                self.stats["admission_rejects"] += 1
                raise AdmissionError(
                    f"request {idx}: config.noise={req.config.noise!r} "
                    f"differs from the service's noise={self.noise!r}"
                )
            if req.config.storage_layout != self.storage_layout:
                self.stats["admission_rejects"] += 1
                raise AdmissionError(
                    f"request {idx}: config.storage_layout="
                    f"{req.config.storage_layout!r} differs from the "
                    f"service's storage_layout={self.storage_layout!r}"
                )
        if model.n > MAX_UNSHARDED_SPINS:
            # Giant instances are admissible only when they will actually
            # route to the spin-sharded SSA path (DESIGN.md §11) — on the
            # problem-partitioned path a single (N, N)-coupled instance of
            # this size is an OOM/compile hazard, not a request.
            ssa_family = isinstance(req.hp, (SSAHyperParams, str))
            nb = bucket_n(model.n, self.min_bucket)
            if not (ssa_family and self.partition_for("ssa", nb) == "spin"):
                self.stats["admission_rejects"] += 1
                raise AdmissionError(
                    f"request {idx}: n={model.n} exceeds the single-device "
                    f"ceiling MAX_UNSHARDED_SPINS={MAX_UNSHARDED_SPINS}; "
                    "construct the service with partition='spin' (or 'auto') "
                    "and a multi-device mesh (repro.sharding.spin_mesh) to "
                    "shard the spin axis"
                )

    # ------------------------------------------------------------------
    # Grouping
    # ------------------------------------------------------------------
    def _group_key(self, req: AnnealRequest, nb: int):
        """Family key from the registry + the per-request config signature.

        Requests batch together only when the family's own key components
        match AND they carry the same (or no) :class:`SolverConfig` — two
        requests pinned to different backends must never share a program.
        """
        fam = family_for(req.hp, algo=req.algo)
        cfg_sig = req.config.signature() if req.config is not None else None
        return fam.group_key(req, req.hp, nb) + (cfg_sig,)

    def _resolve_field_opts(self, backend: str, opts: dict, items,
                            couplings=model_couplings) -> dict:
        """Resolve field_mode='auto' + group ``j_bits`` for one request group.

        The popcount contraction's magnitude-plane count is program-
        structural (the stacked ``mags`` tensor's shape), so it must be
        uniform across the group: every model packs to the group maximum.
        The resolved values land in the opts dict — and therefore in the
        executable-cache key via ``_opts_key`` — so a ±1 group and a 3-bit
        group never collide on one compiled program.  ``couplings`` gives
        each model's coalesced couplings (memoized per call by :meth:`solve`).
        """
        if backend not in ("dense", "pallas") or "field_mode" not in opts:
            return dict(opts)
        opts = dict(opts)
        jb = max(couplings(model).weight_bits for *_, model in items)
        opts["field_mode"] = resolve_field_mode(opts["field_mode"], jb)
        if opts["field_mode"] == "popcount":
            opts["j_bits"] = max(jb, int(opts.get("j_bits", 1)))
        else:
            opts.pop("j_bits", None)
        return opts

    def _coalesce(self, model):
        """:func:`model_couplings`, counting in ``stats["coalesce_dense"]``
        the models whose couplings took the dense route."""
        c = model_couplings(model)
        if c.dense is not None:
            with self.spans.lock:
                self.stats["coalesce_dense"] += 1
        return c

    def _pad_group(self, items):
        """Pad a request group to a power-of-two batch (executable reuse).

        Dummy slots repeat the first request; their outputs are discarded.
        """
        b_live = len(items)
        b_bucket = next_pow2(b_live)
        padded = list(items) + [items[0]] * (b_bucket - b_live)
        return padded, b_live, b_bucket

    def _stack(self, bk, padded, ctx):
        """``bk.stack`` of a padded group's models, in the ``stack`` span.

        Adds the stacked arrays' bytes to ``stats["stack_bytes"]`` (shapes
        only, no device sync) and notes on ``ctx`` whether the backend's
        field streams row slabs, which the group counts in ``tiled_lanes``
        once it succeeds.
        """
        with self.spans("stack"):
            stacked = bk.stack([model for _, _, _, model in padded],
                               couplings=ctx.couplings)
        nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(stacked))
        with self.spans.lock:
            self.stats["stack_bytes"] += nbytes
        ctx.row_tiled = bk.row_tiled
        return stacked

    # ------------------------------------------------------------------
    # Resilient group dispatch: fallback chain + quarantine + retry
    # ------------------------------------------------------------------
    def _solve_group_resilient(self, kind, nb, items, responses, progress,
                               solve_t0, *, couplings,
                               requeue_quarantine: bool = True):
        """Run one group with the resilience wrapper (DESIGN.md §10).

        A classified compile/OOM fault walks the fallback chain and re-runs
        the group from scratch on the downgraded backend (bit-identity is
        preserved — the trajectory depends only on the noise stream, not the
        backend).  A quarantine signal splits the group: healthy requests
        re-run as a fresh group, offenders retry solo with backoff.  Kills
        and unclassified errors propagate.  ``couplings`` is the call's
        memoized coalescing, shared by routing, the field options and the
        packing.
        """
        solver = getattr(self, registered_algos()[kind].solver)
        cfg = items[0][1].config
        if cfg is not None:
            # Per-request SolverConfig override: backend + engine options
            # come from the config (noise/storage_layout were admission-
            # checked to match the service, and the group key carries the
            # config signature, so every item in the group agrees).
            backend = cfg.backend
            opts = cfg.engine_opts()
            opts.pop("storage_layout", None)  # service-wide, passed apart
        else:
            backend, opts = self.backend, dict(self.backend_opts)
        carried_events: List[ServiceEvent] = []
        if backend == "auto":
            with self.spans("weight_bits"):  # routing scans the weight bits
                backend, opts, why = self.route_auto(kind, nb, items, opts,
                                                     couplings)
            if why is not None:
                carried_events.append(ServiceEvent(
                    "route", {"backend": backend, "reason": why},
                    time.perf_counter() - solve_t0,
                ))
        while True:
            ctx = _GroupCtx(self, kind, nb, items, backend, opts, solve_t0,
                            self._chunk_of(kind, items), couplings,
                            events=carried_events)
            try:
                # One span per attempt; a quarantine's re-runs and solo
                # retries open their own after this one has closed.
                with self.spans("group", kind=kind, bucket=nb,
                                batch=len(items)):
                    solver(nb, items, responses, progress, ctx)
            except QuarantineFault as qf:
                if not requeue_quarantine:
                    raise
                self.stats["quarantines"] += 1
                self._handle_quarantine(kind, nb, items, qf, responses,
                                        progress, solve_t0, ctx)
                return
            except Exception as exc:  # noqa: BLE001 — classified below
                fault = None
                if kind != "sa":  # SA's Metropolis core is backend-independent
                    fault = classify_fault(exc, backend)
                nxt = (fallback_step(backend, opts, fault, nb)
                       if fault is not None and self.policy.fallback else None)
                if nxt is None:
                    raise
                self.stats[f"fallback_{fault}"] += 1
                carried_events = list(ctx.events)
                carried_events.append(ServiceEvent(
                    "fallback",
                    {"from": backend, "to": nxt[0], "fault": fault,
                     "from_opts": dict(opts), "to_opts": dict(nxt[1]),
                     "error": f"{type(exc).__name__}: {exc}"[:200]},
                    time.perf_counter() - solve_t0,
                ))
                backend, opts = nxt
                continue
            # Success: finalize statuses/events and clean up checkpoints.
            default = (STATUS_FALLBACK
                       if any(ev.kind == "fallback" for ev in ctx.events)
                       else STATUS_OK)
            for idx, *_rest in items:
                resp = responses[idx]
                resp.status = ctx.statuses.get(idx, default)
                resp.events = list(ctx.events)
                resp.backend = None if kind == "sa" else ctx.backend
            if kind != "sa":
                with self.spans.lock:  # concurrent solve() calls
                    self.stats[f"route_lanes.{ctx.backend}"] += len(items)
                    if ctx.row_tiled:
                        self.stats["tiled_lanes"] += len(items)
            ctx.finish_success()
            return

    def route_auto(self, kind, nb, items, opts, couplings=model_couplings):
        """Resolve backend='auto' for one group: ``(backend, opts, why)``.

        Resident pallas at or above ``engine.MIN_RESIDENT_N`` spins where its
        kernel fits the chip's VMEM budget, XLA dense otherwise; ``why`` is
        the budget shortfall when that is what sent the group to dense.  The
        opts are then filtered to what the chosen backend accepts — 'auto'
        users pass a union.  ``couplings`` is the call's per-model coalescing.
        """
        hp = items[0][1].hp
        kernel_opts = dict(
            opts, noise=self.noise, n_cycles=getattr(hp, "tau", 1),
            j_bits=max(couplings(model).weight_bits for *_, model in items),
        )
        if kind == "ssqa":
            kernel_opts["n_replicas"] = hp.n_replicas
            kernel_opts.setdefault("noise_mode", "streamed")
        backend, why = route_backend("auto", nb, **kernel_opts)
        opts = filter_backend_opts(backend, opts,
                                   partition=self.partition_for(kind, nb))
        return backend, opts, why

    def _chunk_of(self, kind, items) -> int:
        """The group's chunk width (part of its checkpoint fingerprint)."""
        hp = items[0][1].hp
        if kind in ("ssa", "ssqa"):
            return _largest_divisor_leq(hp.m_shot, self.chunk_shots)
        if kind == "ptssa":
            return _largest_divisor_leq(hp.n_rounds, self.chunk_shots)
        return hp.n_cycles // _largest_divisor_leq(hp.n_cycles, self.sa_chunks)

    def _handle_quarantine(self, kind, nb, items, qf, responses, progress,
                           solve_t0, ctx):
        """Split a poisoned group: healthy slots re-run, offenders go solo.

        Per-problem lanes are independent (the padding-invariance property),
        so re-running the healthy requests as a fresh group is bit-identical
        to what the original batch would have produced for them.
        """
        bad = set(qf.slots)
        good = [it for s, it in enumerate(items) if s not in bad]
        bad_items = [it for s, it in enumerate(items) if s in bad]
        if good:
            self._solve_group_resilient(kind, nb, good, responses, progress,
                                        solve_t0, couplings=ctx.couplings)
        for it in bad_items:
            self._retry_solo(kind, nb, it, responses, progress, solve_t0,
                             ctx.couplings)

    def _retry_solo(self, kind, nb, item, responses, progress, solve_t0,
                    couplings):
        """Quarantined request: exponential backoff + re-autotuned I0max.

        Each attempt re-derives the I0 clamp from the instance's local-field
        distribution (:mod:`repro.core.autotune`) — if the non-finite energy
        came from an I0/field-scale mismatch, the retuned clamp is the
        principled fix; injected bursts simply clear on retry.  After
        ``max_retries`` the response is returned with ``status='failed'``
        (never an exception).
        """
        idx, req, maxcut, model = item
        events: List[ServiceEvent] = [ServiceEvent(
            "quarantine", {"request": idx},
            time.perf_counter() - solve_t0,
        )]
        hp = req.hp
        for attempt in range(self.policy.max_retries):
            time.sleep(self.policy.backoff_base_s * (2 ** attempt))
            if isinstance(hp, SSAHyperParams):
                tuned, rep = autotune_hyperparams(
                    model, hp, seed=self.autotune_seed + attempt + 1
                )
                hp = dataclasses.replace(hp, i0_max=tuned.i0_max)
                detail = {"request": idx, "attempt": attempt,
                          "i0_max": tuned.i0_max, "z_max": rep.z_max}
            else:
                detail = {"request": idx, "attempt": attempt}
            events.append(ServiceEvent(
                "retry", detail, time.perf_counter() - solve_t0
            ))
            req_retry = dataclasses.replace(req, hp=hp)
            try:
                self._solve_group_resilient(
                    kind, nb, [(idx, req_retry, maxcut, model)], responses,
                    progress, solve_t0, requeue_quarantine=False,
                    couplings=couplings,
                )
            except QuarantineFault:
                self.stats["retry_requarantined"] += 1
                continue
            resp = responses[idx]
            resp.status = STATUS_QUARANTINED
            resp.events = events + resp.events
            self.stats["quarantine_recoveries"] += 1
            return
        self.stats["quarantine_failures"] += 1
        responses[idx] = AnnealResponse(
            request=req, result=None,
            wall_s=time.perf_counter() - solve_t0, bucket=nb, batch=1,
            chunks_run=0, chunks_total=0,
            chunk_best_cut=np.zeros(0, np.int64),
            status=STATUS_FAILED, events=events,
        )

    # ------------------------------------------------------------------
    # SSA / HA-SSA groups (the tentpole hot path)
    # ------------------------------------------------------------------
    def _ssa_programs(self, *, nb, b_bucket, hp, storage, schedule_kind,
                      backend, opts, chunk, fire=None, kind="ssa"):
        """Compiled SSA/SSQA plateau programs for one (bucket, batch) shape.

        Returns ``(bk, init_fn, chunk_fn, plateaus)`` from the bounded
        executable cache, compiling on miss.  Shared by the one-shot group
        solver and the streaming slot tables (:mod:`repro.serve.stream`) —
        the cache key deliberately excludes ``m_shot``: the plateau chain per
        iteration is budget-independent, so a slot table can serve mixed
        chunk budgets through one program.  SSQA groups arrive with
        ``kind='ssqa'`` and ``opts['n_replicas']`` set; the schedule
        signature (which carries the J⊥ ramp) plus the opts key keep them on
        distinct programs from classical groups.
        """
        plateaus = schedule_plateaus(hp.schedule(schedule_kind), storage)
        sig = hp.schedule(schedule_kind).signature()
        part = self.partition_for(kind, nb)
        cache_key = (kind, backend, _opts_key(opts), self.storage_layout, nb,
                     b_bucket, hp.n_trials, hp.n_rnd, self.noise, storage,
                     sig, chunk, part,
                     mesh_fingerprint(self.mesh) if part == "spin" else ())
        ent = self._programs.get(cache_key)
        if ent is None:
            if fire is not None:
                fire("compile", backend=backend, kind=kind, bucket=nb)
            self.stats["program_cache_misses"] += 1
            bk = make_batched_backend(
                backend, n_bucket=nb, n_trials=hp.n_trials,
                n_rnd=hp.n_rnd, noise=self.noise,
                storage_layout=self.storage_layout,
                partition=part, mesh=self.mesh, **opts,
            )

            def init_fn(problem, ns0):
                self.stats["traces_init"] += 1
                return bk.init_state(problem, ns0)

            def chunk_fn(problem, state, live):
                # ``live``: (b_bucket,) int32, a device operand and no part
                # of the key.  The vmapped backends compute every lane
                # whatever it says, so they are not handed it.
                self.stats["traces_chunk"] += 1
                if bk.skips_dead_lanes:
                    return bk.run_shots(problem, state, plateaus, chunk,
                                        live=live)
                return bk.run_shots(problem, state, plateaus, chunk)

            ent = (bk, _Program(init_fn, cache_key + ("init",), bk, self.spans),
                   _Program(chunk_fn, cache_key + ("chunk",), bk, self.spans))
            self._programs[cache_key] = ent
        else:
            self.stats["program_cache_hits"] += 1
        return (*ent, plateaus)

    def _solve_ssa_group(self, nb, items, responses, progress, ctx):
        t0 = time.perf_counter()
        spans = self.spans
        _, req0, _, _ = items[0]
        hp: SSAHyperParams = req0.hp
        chunk = _largest_divisor_leq(hp.m_shot, self.chunk_shots)
        n_chunks = hp.m_shot // chunk

        padded, b_live, b_bucket = self._pad_group(items)
        backend, opts = ctx.backend, ctx.backend_opts
        with spans("weight_bits"):
            opts = self._resolve_field_opts(backend, opts, items,
                                            ctx.couplings)
        nr = int(getattr(hp, "n_replicas", 0) or 0)
        if nr:
            # SSQA: the Trotter depth is program-structural (ring width per
            # R-tile), so it rides opts into the backend ctor AND the
            # executable-cache key; pallas replica rings exist only in the
            # streamed-noise kernel.
            opts = dict(opts)
            opts["n_replicas"] = nr
            if backend == "pallas":
                opts.setdefault("noise_mode", "streamed")
        with spans("program"):
            bk, init_fn, chunk_fn, plateaus = self._ssa_programs(
                nb=nb, b_bucket=b_bucket, hp=hp, storage=req0.storage,
                schedule_kind=req0.schedule_kind, backend=backend, opts=opts,
                chunk=chunk, fire=ctx.fire, kind=ctx.kind,
            )
        stored_per_iter = sum(p.length for p in plateaus if p.eligible)

        stacked = self._stack(bk, padded, ctx)
        ctx.fire("oom", backend=backend, kind="ssa", bucket=nb, batch=b_bucket,
                 j_mode=getattr(bk, "j_mode", None))
        with spans("init"):
            ns0 = bk.init_noise(
                [req.seed for _, req, _, _ in padded],
                [model.n for _, _, _, model in padded],
            )
            state = init_fn(stacked, ns0)

        state, chunk_traces, stops = self._chunk_loop(
            ctx.kind, nb, items, n_chunks, progress,
            lambda st, c, live: chunk_fn(stacked, st, live), state,
            lambda st: st.best_H, ctx, width=b_bucket,
            snap=lambda st: bk.finalize(st), masks=bk.skips_dead_lanes,
        )
        with spans("finalize"):
            bh_dev, bm_dev = bk.finalize(state)  # unpacks bitplanes too
            self._group_responses(
                items, responses, np.asarray(bh_dev), np.asarray(bm_dev),
                chunk_traces, stops, nb=nb, b_live=b_live, n_chunks=n_chunks,
                t0=t0,
                result=lambda req, model, **f: AnnealResult(
                    **f, traj=None,
                    stored_bits_per_iter=model.n * stored_per_iter,
                    hp=req.hp),
            )

    def _group_responses(self, items, responses, best_H, best_m, traces,
                         stops, *, nb, b_live, n_chunks, t0, result):
        """One response per request of a finished group.

        Each lane reports its bests frozen at its own stop boundary, or the
        group's final ``best_H``/``best_m`` when it ran to the end.
        ``result(req, model, **fields)`` builds the family's result object.
        """
        wall = time.perf_counter() - t0
        for slot, (idx, req, maxcut, model) in enumerate(items):
            stop = stops[slot]
            if stop is not None and stop.get("best_H") is not None:
                bh, bm_full = stop["best_H"], stop["best_m"]
            else:
                bh, bm_full = best_H[slot], best_m[slot]
            res = result(
                req, model,
                best_cut=np.asarray(finalize_cut(bh, maxcut)),
                best_energy=bh,
                best_m=bm_full[:, : model.n],
                energy_mean=None,
                energy_min=None,
            )
            responses[idx] = AnnealResponse(
                request=req, result=res, wall_s=wall, bucket=nb,
                batch=b_live, chunks_run=len(traces[slot]),
                chunks_total=n_chunks,
                chunk_best_cut=np.asarray(traces[slot]),
                lane_wall_s=(stop["t_abs"] - t0 if stop is not None else wall),
            )

    # ------------------------------------------------------------------
    # SA groups
    # ------------------------------------------------------------------
    def _solve_sa_group(self, nb, items, responses, progress, ctx):
        t0 = time.perf_counter()
        _, req0, _, _ = items[0]
        hp: SAHyperParams = req0.hp
        n_chunks = _largest_divisor_leq(hp.n_cycles, self.sa_chunks)
        chunk_cycles = hp.n_cycles // n_chunks

        padded, b_live, b_bucket = self._pad_group(items)
        with self.spans("program"):
            init_fn, chunk_fn = self._sa_programs(nb, b_bucket, hp,
                                                  chunk_cycles, ctx)

        # SA reuses the sparse stacking (gather-based ΔH).
        stacker = make_batched_backend(
            "sparse", n_bucket=nb, n_trials=hp.n_trials, noise="xorshift"
        )
        stacked = self._stack(stacker, padded, ctx)
        with self.spans("init"):
            keys = jnp.stack(
                [jax.random.PRNGKey(req.seed) for _, req, _, _ in padded]
            )
            n_lives = jnp.asarray([model.n for _, _, _, model in padded],
                                  jnp.int32)
            temps = np.asarray(
                sa_temperature_ladder(hp.t_start, hp.t_end, hp.n_cycles),
                np.float32,
            )
            carry = init_fn(stacked, keys)
            chunk_arrays = [
                jnp.asarray(temps[c * chunk_cycles : (c + 1) * chunk_cycles])
                for c in range(n_chunks)
            ]

        carry, chunk_traces, stops = self._chunk_loop(
            "sa", nb, items, n_chunks, progress,
            lambda ca, c, live: chunk_fn(stacked, ca, chunk_arrays[c],
                                         n_lives),
            carry, lambda ca: ca[3], ctx, width=b_bucket,
            snap=lambda ca: (ca[3], ca[4]),
        )
        with self.spans("finalize"):
            _, _, _, best_H, best_m = carry
            self._group_responses(
                items, responses, np.asarray(best_H), np.asarray(best_m),
                chunk_traces, stops, nb=nb, b_live=b_live, n_chunks=n_chunks,
                t0=t0,
                result=lambda req, model, **f: SAResult(**f, hp=req.hp),
            )

    def _sa_programs(self, nb, b_bucket, hp, chunk_cycles, ctx):
        """Jitted ``(init_fn, chunk_fn)`` of an SA group shape, cached."""
        cache_key = ("sa", nb, b_bucket, hp.n_trials, chunk_cycles)
        ent = self._programs.get(cache_key)
        if ent is None:
            ctx.fire("compile", backend="sa-core", kind="sa", bucket=nb)
            self.stats["program_cache_misses"] += 1

            def init_fn(problem, keys):
                self.stats["traces_init"] += 1
                return jax.vmap(
                    lambda pr, k: sa_init(
                        pr["h"], pr["nbr_idx"], pr["nbr_w"], k,
                        n_trials=hp.n_trials,
                    )
                )(problem, keys)

            def chunk_fn(problem, carry, temps, n_lives):
                self.stats["traces_chunk"] += 1
                def one(pr, ca, nl):
                    ca, _ = sa_cycles(
                        pr["h"], pr["nbr_idx"], pr["nbr_w"], ca, temps,
                        n_live=nl,
                    )
                    return ca
                return jax.vmap(one)(problem, carry, n_lives)

            ent = (jax.jit(init_fn), jax.jit(chunk_fn))
            self._programs[cache_key] = ent
        else:
            self.stats["program_cache_hits"] += 1
        return ent

    # ------------------------------------------------------------------
    # PT-SSA groups
    # ------------------------------------------------------------------
    def _solve_ptssa_group(self, nb, items, responses, progress, ctx):
        t0 = time.perf_counter()
        _, req0, _, _ = items[0]
        hp: PTSSAHyperParams = req0.hp
        backend, opts = ctx.backend, ctx.backend_opts
        if backend == "pallas":
            raise ValueError(
                "pt-ssa needs per-replica I0 columns; run the service with "
                "backend='sparse' or 'dense' for PTSSAHyperParams requests"
            )
        chunk = _largest_divisor_leq(hp.n_rounds, self.chunk_shots)
        n_chunks = hp.n_rounds // chunk

        padded, b_live, b_bucket = self._pad_group(items)
        with self.spans("weight_bits"):
            opts = self._resolve_field_opts(backend, opts, items,
                                            ctx.couplings)
        with self.spans("program"):
            bk, init_fn, chunk_fn = self._ptssa_programs(
                nb, b_bucket, hp, backend, opts, chunk, ctx)

        stacked = self._stack(bk, padded, ctx)
        ctx.fire("oom", backend=backend, kind="ptssa", bucket=nb,
                 batch=b_bucket, j_mode=getattr(bk, "j_mode", None))
        with self.spans("init"):
            ns0 = bk.init_noise(
                [req.seed for _, req, _, _ in padded],
                [model.n for _, _, _, model in padded],
            )
            state = init_fn(stacked, ns0)

            # Same swap-key derivation as anneal_pt_ssa, split once over all
            # rounds then sliced per chunk — chunked == unchunked, bitwise.
            all_keys = jnp.stack([
                jax.random.split(
                    jax.random.PRNGKey(req.seed ^ 0x5CA1AB1E), hp.n_rounds
                )
                for _, req, _, _ in padded
            ])  # (B, n_rounds, 2)
            parities = jnp.arange(hp.n_rounds, dtype=jnp.int32) % 2

        def step(st, c, live):
            sl = slice(c * chunk, (c + 1) * chunk)
            return chunk_fn(stacked, st, all_keys[:, sl], parities[sl])

        state, chunk_traces, stops = self._chunk_loop(
            "ptssa", nb, items, n_chunks, progress, step, state,
            lambda st: st.best_H, ctx, width=b_bucket,
            snap=lambda st: (st.best_H, st.best_m),
        )
        with self.spans("finalize"):
            self._group_responses(
                items, responses, np.asarray(state.best_H),
                np.asarray(state.best_m), chunk_traces, stops, nb=nb,
                b_live=b_live, n_chunks=n_chunks, t0=t0,
                result=lambda req, model, **f: PTSSAResult(**f, hp=req.hp),
            )

    def _ptssa_programs(self, nb, b_bucket, hp, backend, opts, chunk, ctx):
        """``(bk, init_fn, chunk_fn)`` of a PT-SSA group shape, cached."""
        cache_key = ("ptssa", backend, _opts_key(opts), nb, b_bucket, hp,
                     self.noise, chunk)
        ent = self._programs.get(cache_key)
        if ent is None:
            ctx.fire("compile", backend=backend, kind="ptssa", bucket=nb)
            self.stats["program_cache_misses"] += 1
            bk = make_batched_backend(
                backend, n_bucket=nb, n_trials=hp.n_replicas,
                n_rnd=hp.n_rnd, noise=self.noise, **opts,
            )

            def init_fn(problem, ns0):
                self.stats["traces_init"] += 1
                return bk.init_state(problem, ns0)

            def chunk_fn(problem, state, keys, parities):
                self.stats["traces_chunk"] += 1

                def one(pr, st, ks):
                    field_fn = lambda m: bk._field_one(pr, m)  # noqa: E731
                    return pt_ssa_rounds(
                        field_fn, bk._noise_step_one, pr["h"], hp, st,
                        ks, parities,
                    )

                return jax.vmap(one)(problem, state, keys)

            ent = (bk, jax.jit(init_fn), jax.jit(chunk_fn))
            self._programs[cache_key] = ent
        else:
            self.stats["program_cache_hits"] += 1
        return ent

    # ------------------------------------------------------------------
    # Shared chunk loop: streaming best_H reports, early stop, checkpoints,
    # deadline watchdog, non-finite detector, fault hooks
    # ------------------------------------------------------------------
    def _chunk_loop(self, kind, nb, items, n_chunks, progress, step, state,
                    best_of, ctx, *, width=None, snap=None, masks=False):
        """Run up to n_chunks ``step(state, c, live)`` calls from the last
        checkpoint; report per-chunk bests; stop early when every request is
        done (target_cut reached or deadline expired).

        Chunk boundaries are where all the resilience machinery lives: the
        state snapshot (checkpoint), the kill/nan fault hooks, the
        non-finite detector (quarantine), and the deadline watchdog.  A
        request that stops early — target reached or deadline expired — has
        its streaming trace *and its result* frozen at its own chunk
        boundary (the ``snap`` callable reads best_H/best_m there), so
        per-lane latency and result reporting are honest even while the rest
        of the group keeps annealing.  The third return value carries one
        stop record per lane: ``{'chunk', 't_abs'[, 'best_H', 'best_m']}``,
        or None for a lane that ran to the group's end (its result comes
        from the final state).  ``width`` is the padded batch width, feeding
        the slot/live-lane occupancy counters the streaming benchmark reads.

        ``live`` is a (width,) int32 mask built at every launch: 1 for a
        lane not yet done, 0 for a stopped lane (from the chunk after its
        stop) and for ``_pad_group``'s padding lanes (from chunk 0).  A
        resume starts with every request lane live.  ``masks`` says the
        step skips the dead lanes, which ``stats["masked_lane_chunks"]``
        then counts.
        """
        spans = self.spans
        traces = [[] for _ in items]
        start = 0
        if ctx is not None and ctx.ckpt is not None:
            start, state, restored = ctx.maybe_resume(state, len(items))
            if restored is not None:
                traces = restored
        done = [False] * len(items)
        frozen = [False] * len(items)
        stops: List[Optional[dict]] = [None] * len(items)
        for c in range(start, n_chunks):
            with spans("chunk", chunk=c):
                with spans("chunk.launch"):
                    live = np.zeros(width or len(items), np.int32)
                    live[:len(items)] = [not d for d in done]
                    n_live = int(live.sum())
                    self.stats["slot_chunks"] += live.size
                    self.stats["live_lane_chunks"] += n_live
                    if masks:
                        self.stats["masked_lane_chunks"] += live.size - n_live
                    state = step(state, c, live)
                with spans("chunk.sync"):
                    best_H = np.asarray(best_of(state))  # the report
                with spans("chunk.book"):
                    newly = self._chunk_book(kind, nb, items, n_chunks, c,
                                             progress, state, best_H, traces,
                                             done, frozen, stops, ctx)
                group_ends = (c + 1 == n_chunks) or (bool(done) and all(done))
                if newly and not group_ends and snap is not None:
                    # The group continues past these lanes' stop boundary:
                    # freeze their result here so later chunks (which they
                    # no longer participate in, logically) can't change it.
                    with spans("chunk.snap"):
                        bh_s, bm_s = snap(state)
                        bh_s, bm_s = np.asarray(bh_s), np.asarray(bm_s)
                        for slot in newly:
                            stops[slot]["best_H"] = bh_s[slot].copy()
                            stops[slot]["best_m"] = bm_s[slot].copy()
            if done and all(done) and c + 1 < n_chunks:
                self.stats["early_stops"] += 1
                break
        return state, traces, stops

    def _chunk_book(self, kind, nb, items, n_chunks, c, progress, state,
                    best_H, traces, done, frozen, stops, ctx) -> List[int]:
        """Host bookkeeping at one chunk boundary: the non-finite watchdog,
        per-lane bests and traces, progress, checkpoint and kill hook, then
        the target and deadline stop checks.  Returns the lanes that stopped
        at this boundary."""
        # Non-finite watchdog.  The 'nan' hook corrupts the detector's
        # float view of the readings (slots it names), emulating a
        # numeric blow-up; detection itself is the production check.
        readings = best_H.astype(np.float64)
        spec = ctx.fire("nan", kind=kind, chunk=c) if ctx else None
        if spec is not None:
            slots = [s for s in (spec.slots or range(len(items)))
                     if s < len(items)]
            for s in slots:
                readings[s] = np.nan
        bad = tuple(
            s for s in range(len(items))
            if not np.all(np.isfinite(readings[s]))
        )
        if bad:
            self.stats["nonfinite_detected"] += 1
            raise QuarantineFault(bad)
        bests = []
        for slot, (idx, req, maxcut, model) in enumerate(items):
            obj = np.asarray(finalize_cut(best_H[slot], maxcut))
            best = int(np.max(obj))
            if not frozen[slot]:
                traces[slot].append(best)
            bests.append(best)
        self.stats["chunks_run"] += 1
        if progress is not None:
            progress(AnnealProgress(
                kind=kind, bucket=nb, chunk=c, chunks_total=n_chunks,
                request_indices=tuple(idx for idx, *_ in items),
                best_cut=tuple(bests),
            ))
        now = time.perf_counter()
        newly: List[int] = []
        if ctx is not None:
            ctx.save(c + 1, state, traces)
            ctx.fire("kill", kind=kind, chunk=c)
        for slot, (idx, req, _, _) in enumerate(items):
            if done[slot]:
                continue
            if req.target_cut is not None and bests[slot] >= req.target_cut:
                done[slot] = frozen[slot] = True
                stops[slot] = {"chunk": c + 1, "t_abs": now}
                newly.append(slot)
            elif (ctx is not None and req.deadline_s is not None
                  and now - ctx.solve_t0 >= req.deadline_s):
                done[slot] = frozen[slot] = True
                stops[slot] = {"chunk": c + 1, "t_abs": now}
                newly.append(slot)
                ctx.statuses[idx] = STATUS_DEADLINE
                ctx._event("deadline", request=idx, chunk=c,
                           best=bests[slot])
                self.stats["deadline_expirations"] += 1
        return newly
