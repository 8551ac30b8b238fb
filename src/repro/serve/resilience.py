"""Resilience policy for the annealing service (DESIGN.md §10).

Everything the service needs to degrade gracefully instead of failing the
batch lives here: the policy knobs (:class:`ResiliencePolicy`), the typed
admission errors, the fault taxonomy (:func:`classify_fault`), the backend
fallback chain (:func:`fallback_step`), the structured event records
(:class:`ServiceEvent`), and the stable group fingerprint that keys
chunk-level checkpoints (:func:`group_fingerprint`).

The design leans on the same property the paper's HA-SSA storage trick
leans on: *all* live state between plateau chunks is a tiny explicit
buffer — spin (bit)planes, the carried xorshift128 lanes, ``best_H`` and
the chunk index — so checkpoint/resume and group re-execution are
bit-identical, not best-effort.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, Optional, Tuple

import numpy as np

from repro.core.engine import resolve_j_mode

__all__ = [
    "STATUS_OK",
    "STATUS_FALLBACK",
    "STATUS_DEADLINE",
    "STATUS_QUARANTINED",
    "STATUS_FAILED",
    "STATUS_SHED",
    "AdmissionError",
    "QueueFullError",
    "QuarantineFault",
    "ServiceEvent",
    "ResiliencePolicy",
    "classify_fault",
    "fallback_step",
    "filter_backend_opts",
    "group_fingerprint",
]

# AnnealResponse.status values (DESIGN.md §10, §12).
STATUS_OK = "ok"                   # solved on the configured backend
STATUS_FALLBACK = "fallback"       # solved after >=1 backend/j_mode downgrade
STATUS_DEADLINE = "deadline"       # deadline expired; best-so-far returned
STATUS_QUARANTINED = "quarantined"  # non-finite detection; solved solo on retry
STATUS_FAILED = "failed"           # retries exhausted; no result
STATUS_SHED = "shed"               # streaming: dropped from the queue unstarted
#                                    (deadline already unmeetable); no result


class AdmissionError(ValueError):
    """A request rejected at admission (bad weights, absurd shape, bad knobs).

    Raised before any group starts solving, so a rejected batch does no
    device work at all.
    """


class QueueFullError(AdmissionError):
    """Streaming admission control: the request queue is at capacity.

    Raised by :meth:`repro.serve.stream.StreamingAnnealService.submit` when
    the queue's depth or aggregate cost bound is hit — backpressure belongs
    at the front door, not in an unbounded queue.  Subclasses
    :class:`AdmissionError` so clients can treat both as "not accepted".
    """


class QuarantineFault(RuntimeError):
    """Internal signal: non-finite readings detected for some batch slots.

    Carries the *group-slot* indices of the offending requests; the service
    re-runs the healthy slots as a fresh group (bit-identical — per-problem
    lanes are independent) and retries the offenders solo.
    """

    def __init__(self, slots: Tuple[int, ...]):
        super().__init__(f"non-finite energies in batch slots {sorted(slots)}")
        self.slots = tuple(slots)


@dataclasses.dataclass(frozen=True)
class ServiceEvent:
    """One structured resilience event, attached to the responses it touched.

    ``kind``: 'fallback' | 'resume' | 'deadline' | 'quarantine' | 'retry'
    | 'checkpoint_rejected', plus the streaming lifecycle kinds 'seat' |
    'retire' | 'shed' | 'retries_exhausted' (DESIGN.md §12).  ``t`` is
    seconds since the ``solve()`` call began (streaming: since submission).
    Events are group-scoped (every response in the group carries the
    group's events) except quarantine/retry, which are per-request.
    """

    kind: str
    detail: Dict[str, object]
    t: float


@dataclasses.dataclass(frozen=True)
class ResiliencePolicy:
    """Service-level failure-handling knobs.

    checkpoint_dir:        root for chunk-level group checkpoints (None =
                           checkpointing off).  Each request group writes
                           under ``<dir>/<group_fingerprint>/``.
    checkpoint_interval:   save every k-th chunk boundary.
    keep_checkpoints:      keep-last-n per group (crash window = interval).
    cleanup_on_success:    purge a group's checkpoints when it completes.
    fallback:              enable the backend fallback chain
                           (pallas→dense→sparse, dense-J→tiled-J on OOM).
    max_retries:           solo retries for a quarantined request.
    backoff_base_s:        exponential-backoff base for those retries.
    validate_admission:    reject non-finite weights / absurd shapes / bad
                           knobs with :class:`AdmissionError` before solving.
    """

    checkpoint_dir: Optional[str] = None
    checkpoint_interval: int = 1
    keep_checkpoints: int = 2
    cleanup_on_success: bool = True
    fallback: bool = True
    max_retries: int = 3
    backoff_base_s: float = 0.05
    validate_admission: bool = True


# Constructor keywords each batched backend accepts beyond the common set —
# fallback must drop e.g. pallas block_r when downgrading to dense.  Both
# field-capable backends carry field_mode/j_bits, so a pallas→dense
# downgrade keeps the XNOR-popcount arithmetic (and its bit-exactness).
# n_replicas (the SSQA Trotter depth) is accepted everywhere: the replica
# ring is a trial-axis property, so every backend in the fallback chain
# must preserve it — dropping it would silently turn SSQA into SSA.
_BACKEND_OPT_KEYS = {
    "sparse": frozenset({"n_replicas"}),
    "dense": frozenset(
        {"j_dtype", "j_mode", "tile_n", "field_mode", "j_bits",
         "double_buffer", "n_replicas"}
    ),
    "pallas": frozenset(
        {"j_dtype", "block_r", "interpret", "noise_mode", "field_mode",
         "j_bits", "n_replicas"}
    ),
    # partition='spin': the shard_map backend wraps any base field style and
    # tolerates (ignores) the single-device resident-kernel knobs, so the
    # fallback chain can walk pallas→dense→sparse under spin sharding too.
    "spinshard": frozenset(
        {"j_dtype", "j_mode", "tile_n", "field_mode", "j_bits",
         "double_buffer", "block_r", "interpret", "noise_mode", "n_replicas"}
    ),
}


def filter_backend_opts(backend: str, opts: dict, *,
                        partition: str = "problem") -> dict:
    """Project backend_opts onto what ``backend`` actually accepts.

    Under ``partition='spin'`` the group runs on the spin-sharded shard_map
    backend regardless of the base backend name, so the wider 'spinshard'
    keyset applies.
    """
    if partition == "spin":
        backend = "spinshard"
    keys = _BACKEND_OPT_KEYS.get(backend, frozenset())
    return {k: v for k, v in opts.items() if k in keys}


def classify_fault(exc: BaseException, backend: str) -> Optional[str]:
    """Map an exception from a group solve to a fault class.

    Returns 'oom', 'compile', or None (not recoverable by fallback — the
    exception propagates).  Injected kills and quarantine signals are never
    classified: a kill must escape like a real process death, and
    quarantines have their own path.  Nor is a resident kernel refused for
    its VMEM budget: that is a configuration error, caught before dispatch,
    which a silent rerun on another backend would hide.  For the pallas
    backend any unexpected error during the group solve is treated as a
    compile/launch failure —
    that backend failing while dense/sparse can still serve the batch is
    precisely the fault the chain exists for.
    """
    from repro.ft.faults import (
        InjectedCompileFailure,
        InjectedKill,
        InjectedOOM,
    )
    from repro.kernels.ssa_update import VmemBudgetError

    if isinstance(exc, (InjectedKill, QuarantineFault, AdmissionError,
                        VmemBudgetError, KeyboardInterrupt)):
        return None
    if isinstance(exc, (InjectedOOM, MemoryError)):
        return "oom"
    if isinstance(exc, InjectedCompileFailure):
        return "compile"
    msg = str(exc)
    if "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower():
        return "oom"
    if type(exc).__name__ == "JaxRuntimeError":
        return "compile"
    if backend == "pallas":
        return "compile"
    return None


def fallback_step(
    backend: str, opts: dict, fault: str, n_bucket: int
) -> Optional[Tuple[str, dict]]:
    """One step down the fallback chain; None = chain exhausted.

    compile/launch: pallas → dense → sparse.
    oom on dense with materialized J: dense-J → tiled-J first (same
    backend, re-keyed executable), then sparse.
    """
    if backend == "dense" and fault == "oom":
        if resolve_j_mode(opts.get("j_mode", "auto"), n_bucket) != "tiled":
            return "dense", {**filter_backend_opts("dense", opts), "j_mode": "tiled"}
        return "sparse", filter_backend_opts("sparse", opts)
    if backend == "pallas":
        return "dense", filter_backend_opts("dense", opts)
    if backend == "dense":
        return "sparse", filter_backend_opts("sparse", opts)
    return None


def group_fingerprint(kind: str, n_bucket: int, backend: str,
                      storage_layout: str, noise: str, chunk: int,
                      items, *, partition: str = "problem",
                      mesh_fp: tuple = ()) -> str:
    """Stable identity of a request group, for checkpoint keying.

    Hashes the execution configuration plus, per request, the seed, the
    request knobs and the *problem arrays themselves* — so a resumed
    ``solve()`` in a fresh process maps onto the interrupted run's
    checkpoints iff it would replay the identical computation.

    ``partition``/``mesh_fp`` fold the spin-sharding layout in: a checkpoint
    written by a spin-sharded group on one mesh shape must not be resumed
    under another (the *state values* are layout-invariant, but mixing
    layouts silently would hide device-count configuration mistakes).
    """
    hsh = hashlib.sha256()
    hsh.update(repr((kind, n_bucket, backend, storage_layout, noise,
                     chunk, partition, mesh_fp)).encode())
    for _idx, req, _maxcut, model in items:
        cfg = getattr(req, "config", None)
        hsh.update(repr((req.seed, req.storage, req.schedule_kind,
                         req.target_cut, req.hp,
                         cfg.signature() if cfg is not None else None,
                         getattr(req, "algo", None))).encode())
        for arr in (model.h, model.nbr_idx, model.nbr_w):
            a = np.ascontiguousarray(np.asarray(arr))
            hsh.update(str(a.dtype).encode())
            hsh.update(a.tobytes())
    return hsh.hexdigest()[:20]
