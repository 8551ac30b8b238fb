"""Typed solver configuration (DESIGN.md §13): the consolidated option surface.

Nine PRs of organic growth threaded ~8 loose kwargs (backend, storage_layout,
field_mode, j_mode, noise, partition, mesh, backend_opts) hand-to-hand through
driver → service → stream → CLI.  :class:`SolverConfig` replaces that sprawl
with ONE frozen, validated object whose stable :meth:`SolverConfig.signature`
is what executable-cache keys, checkpoint ``group_fingerprint``s, and
``filter_backend_opts`` consume.

``anneal()``, :class:`~repro.serve.AnnealRequest`, ``AnnealService``, and
``make_batched_backend`` all accept ``config=SolverConfig(...)``; the old
kwargs keep working through :func:`legacy_kwargs_to_config`, which warns
``DeprecationWarning`` once per call site.

Signature stability contract: the payload is versioned ("SolverConfig/v1").
Any change to field semantics must bump the version string so cached
executables / checkpoints keyed on the old payload are never silently reused.
"""
from __future__ import annotations

import dataclasses
import hashlib
import warnings
from typing import Any, Dict, Optional, Tuple

__all__ = ["SolverConfig", "legacy_kwargs_to_config"]

_BACKENDS = ("auto", "sparse", "dense", "pallas")
_LAYOUTS = ("dense", "packed")
_FIELD_MODES = ("auto", "dense", "popcount")
_J_MODES = ("auto", "dense", "tiled")
_NOISES = ("xorshift", "threefry")
_NOISE_MODES = ("auto", "pregen", "streamed")
_PARTITIONS = ("problem", "spin", "auto")


def _canon_opts(opts: Optional[Dict[str, Any]]) -> Tuple[Tuple[str, Any], ...]:
    """Canonical tuple view of the backend-opts dict (live values, key-sorted)."""
    return tuple(sorted((opts or {}).items(), key=lambda kv: kv[0]))


def _mesh_fp(mesh) -> Tuple:
    if mesh is None:
        return ()
    from repro.sharding import mesh_fingerprint

    return mesh_fingerprint(mesh)


@dataclasses.dataclass(frozen=True)
class SolverConfig:
    """Every execution-surface option of the plateau engine, in one object.

    Fields mirror the historical kwargs one-for-one:

    * ``backend`` — 'sparse' | 'dense' | 'pallas' (field contraction).
    * ``storage_layout`` — 'dense' | 'packed' inter-plateau spin state.
    * ``field_mode`` — 'auto' | 'dense' | 'popcount' (dense/pallas only).
    * ``j_mode`` — 'auto' | 'dense' | 'tiled' (dense backend only).
    * ``noise`` — 'xorshift' | 'threefry' noise *family* (the RNG).
    * ``noise_mode`` — 'auto' | 'pregen' | 'streamed' (pallas: where noise
      is generated; 'streamed' requires the xorshift family).
    * ``partition`` — 'problem' | 'spin' | 'auto' device partitioning.
    * ``mesh`` — optional ``jax.sharding.Mesh`` (excluded from equality;
      its :func:`repro.sharding.mesh_fingerprint` enters the signature).
    * ``backend_opts`` — residual per-backend tuning knobs (block_r, tile_n,
      j_dtype, j_bits, interpret, double_buffer, n_replicas, …) as a
      key-sorted tuple of live (key, value) pairs.

    The object is frozen and validated at construction; ``signature()`` is a
    16-hex-digit digest that is stable across processes and injective over
    the option grid (property-tested in tests/test_solver_config.py).
    """

    backend: str = "sparse"
    storage_layout: str = "dense"
    field_mode: str = "auto"
    j_mode: str = "auto"
    noise: str = "xorshift"
    noise_mode: str = "auto"
    partition: str = "problem"
    mesh: Optional[Any] = dataclasses.field(default=None, compare=False)
    backend_opts: Tuple[Tuple[str, Any], ...] = ()

    def __post_init__(self):
        # PR-8 spelling rode partition/mesh inside backend_opts.  Hoist them
        # into the typed fields so a backend never receives them twice and
        # the signature never falls back to repr() of a live Mesh object.
        opts = dict(self.backend_opts) if self.backend_opts else {}
        for key, default in (("partition", "problem"), ("mesh", None)):
            if key in opts:
                val = opts.pop(key)
                cur = getattr(self, key)
                if cur != default and cur != val:
                    raise ValueError(
                        f"backend_opts[{key!r}] conflicts with {key}={cur!r}"
                    )
                object.__setattr__(self, key, val)
        object.__setattr__(self, "backend_opts", _canon_opts(opts))
        if self.backend not in _BACKENDS:
            raise ValueError(
                f"backend {self.backend!r} not in {_BACKENDS}"
            )
        if self.storage_layout not in _LAYOUTS:
            raise ValueError(
                f"storage_layout {self.storage_layout!r} not in {_LAYOUTS}"
            )
        if self.field_mode not in _FIELD_MODES:
            raise ValueError(
                f"field_mode {self.field_mode!r} not in {_FIELD_MODES}"
            )
        if self.j_mode not in _J_MODES:
            raise ValueError(f"j_mode {self.j_mode!r} not in {_J_MODES}")
        if self.noise not in _NOISES:
            raise ValueError(f"noise {self.noise!r} not in {_NOISES}")
        if self.noise_mode not in _NOISE_MODES:
            raise ValueError(
                f"noise_mode {self.noise_mode!r} not in {_NOISE_MODES}"
            )
        if self.partition not in _PARTITIONS:
            raise ValueError(
                f"partition {self.partition!r} not in {_PARTITIONS}"
            )
        if self.noise_mode == "streamed" and self.noise != "xorshift":
            raise ValueError(
                "noise_mode='streamed' requires the xorshift noise family "
                "(threefry cannot be generated in-kernel)"
            )

    # -- views ------------------------------------------------------------
    def opts_dict(self) -> Dict[str, Any]:
        """backend_opts as a live dict (values as passed at construction)."""
        return dict(self.backend_opts)

    def engine_opts(self) -> Dict[str, Any]:
        """kwargs for ``make_batched_backend(**...)`` minus backend/noise.

        Typed fields that are per-backend-family knobs are only emitted when
        the configured backend's constructor accepts them (sparse rejects
        ``field_mode``/``j_mode``/``noise_mode``); live ``backend_opts``
        entries are merged in — callers that need cross-backend safety
        should still run the result through
        :func:`repro.serve.resilience.filter_backend_opts`.
        """
        out: Dict[str, Any] = {"storage_layout": self.storage_layout}
        bk = self.backend
        if self.field_mode != "auto" and bk != "sparse":
            out["field_mode"] = self.field_mode
        if self.j_mode != "auto" and bk in ("dense", "auto"):
            out["j_mode"] = self.j_mode
        if self.noise_mode != "auto" and bk in ("pallas", "auto"):
            out["noise_mode"] = self.noise_mode
        out.update(self.opts_dict())
        return out

    def signature(self) -> str:
        """Stable 16-hex digest over every behavior-affecting field."""
        payload = (
            "SolverConfig/v1",
            self.backend,
            self.storage_layout,
            self.field_mode,
            self.j_mode,
            self.noise,
            self.noise_mode,
            self.partition,
            tuple(_mesh_fp(self.mesh)),
            tuple((k, repr(v)) for k, v in self.backend_opts),
        )
        return hashlib.sha256(repr(payload).encode()).hexdigest()[:16]

    def replace(self, **kw) -> "SolverConfig":
        return dataclasses.replace(self, **kw)


_WARNED_SITES: set = set()


def legacy_kwargs_to_config(
    site: str,
    config: Optional[SolverConfig],
    *,
    warn: bool = True,
    **legacy,
) -> SolverConfig:
    """Fold legacy loose kwargs into a :class:`SolverConfig` (the shim).

    ``legacy`` maps SolverConfig field names to explicitly-passed legacy
    values (pass only the ones the caller actually received — ``None``
    entries are ignored).  If ``config`` is given, any non-None legacy kwarg
    is a conflict.  Otherwise the legacy values build a config and a
    ``DeprecationWarning`` fires once per ``site`` (per process).
    """
    supplied = {k: v for k, v in legacy.items() if v is not None}
    if config is not None:
        if supplied:
            raise TypeError(
                f"{site}: pass either config= or legacy kwargs "
                f"({sorted(supplied)}), not both"
            )
        return config
    if supplied and warn and site not in _WARNED_SITES:
        _WARNED_SITES.add(site)
        warnings.warn(
            f"{site}: loose solver kwargs ({sorted(supplied)}) are "
            "deprecated; pass config=SolverConfig(...) instead",
            DeprecationWarning,
            stacklevel=3,
        )
    return SolverConfig(**supplied)
