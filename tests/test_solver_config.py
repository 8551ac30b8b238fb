"""SolverConfig — the consolidated typed option surface (DESIGN.md §13).

Contracts under test:

* ``signature()`` is injective over the option grid (distinct configs never
  collide; equal configs always do) and stable across construction spelling;
* the legacy-kwarg shim produces bit-identical results to the typed config,
  warns ``DeprecationWarning`` exactly once per site, and rejects mixing;
* a service built from a config and one built from the historical kwargs
  populate the executable cache with IDENTICAL keys (no silent recompiles
  when callers migrate);
* ``engine_opts()`` only emits per-backend-family knobs the configured
  backend accepts.
"""
import itertools
import warnings

import numpy as np
import pytest

from repro.core import SolverConfig, SSAHyperParams, anneal, gset
from repro.core import config as config_mod
from repro.core.config import legacy_kwargs_to_config
from repro.serve import AnnealRequest, AnnealService

TORUS = gset.toroidal_grid(50, seed=17)
HP = SSAHyperParams(n_trials=4, m_shot=2, tau=4, i0_min=1, i0_max=8)


def _grid():
    """A deliberately overlapping sample of the option space."""
    cfgs = [
        SolverConfig(backend=b, storage_layout=sl, noise=n)
        for b, sl, n in itertools.product(
            ("sparse", "dense", "pallas"), ("dense", "packed"),
            ("xorshift", "threefry"))
    ]
    cfgs += [SolverConfig(backend="dense", field_mode=fm)
             for fm in ("auto", "dense", "popcount")]
    cfgs += [SolverConfig(backend="dense", j_mode=jm)
             for jm in ("auto", "dense", "tiled")]
    cfgs += [SolverConfig(backend="pallas", noise_mode=nm)
             for nm in ("auto", "pregen", "streamed")]
    cfgs += [
        SolverConfig(partition="spin"),
        SolverConfig(backend_opts={"n_replicas": 8}),
        SolverConfig(backend_opts={"n_replicas": 4}),
        SolverConfig(backend_opts={"n_replicas": 8, "j_bits": 2}),
        SolverConfig(backend="pallas", backend_opts={"block_r": 8}),
    ]
    return cfgs


# ---------------------------------------------------------------------------
# Signature: injectivity + stability
# ---------------------------------------------------------------------------
def test_signature_injective_over_grid():
    cfgs = _grid()
    for a, b in itertools.product(cfgs, cfgs):
        if a == b:
            assert a.signature() == b.signature(), (a, b)
        else:
            assert a.signature() != b.signature(), (a, b)


def test_signature_stable_across_spelling():
    # dict vs pre-sorted tuple vs reversed-order dict: one canonical form
    a = SolverConfig(backend_opts={"j_bits": 2, "n_replicas": 8})
    b = SolverConfig(backend_opts=(("j_bits", 2), ("n_replicas", 8)))
    c = SolverConfig(backend_opts={"n_replicas": 8, "j_bits": 2})
    assert a == b == c
    assert a.signature() == b.signature() == c.signature()
    assert isinstance(a.signature(), str) and len(a.signature()) == 16


def test_validation_rejects_bad_knobs():
    with pytest.raises(ValueError, match="backend"):
        SolverConfig(backend="fpga")
    with pytest.raises(ValueError, match="storage_layout"):
        SolverConfig(storage_layout="sparse")
    with pytest.raises(ValueError, match="noise_mode"):
        SolverConfig(noise_mode="inline")
    with pytest.raises(ValueError, match="xorshift"):
        SolverConfig(noise="threefry", noise_mode="streamed")


def test_engine_opts_gated_by_backend_family():
    # sparse accepts neither field_mode nor j_mode nor noise_mode
    sparse = SolverConfig(backend="sparse", field_mode="popcount",
                          j_mode="tiled", noise_mode="streamed")
    assert sparse.engine_opts() == {"storage_layout": "dense"}
    dense = SolverConfig(backend="dense", field_mode="popcount",
                         j_mode="tiled", noise_mode="streamed")
    assert dense.engine_opts() == {
        "storage_layout": "dense", "field_mode": "popcount",
        "j_mode": "tiled"}
    pallas = SolverConfig(backend="pallas", field_mode="popcount",
                          noise_mode="streamed",
                          backend_opts={"n_replicas": 4})
    assert pallas.engine_opts() == {
        "storage_layout": "dense", "field_mode": "popcount",
        "noise_mode": "streamed", "n_replicas": 4}


def test_partition_and_mesh_hoisted_out_of_backend_opts():
    # PR-8 spelling: partition/mesh rode inside backend_opts.  They must be
    # hoisted into the typed fields (so a backend never sees them twice)
    # and never linger in backend_opts/engine_opts.
    cfg = SolverConfig(backend_opts={"partition": "spin", "tile_n": 64})
    assert cfg.partition == "spin"
    assert cfg.opts_dict() == {"tile_n": 64}
    assert "partition" not in cfg.engine_opts()
    assert cfg.signature() == SolverConfig(
        partition="spin", backend_opts={"tile_n": 64}).signature()
    # equal spellings don't conflict; contradictory ones do
    assert SolverConfig(partition="spin",
                        backend_opts={"partition": "spin"}).partition == "spin"
    with pytest.raises(ValueError, match="conflicts"):
        SolverConfig(partition="spin", backend_opts={"partition": "problem"})
    # the legacy anneal(backend_opts={'partition': ..., 'mesh': ...}) path
    # (benchmarks/scale.py, tests/test_spinshard.py) must keep working
    from repro.sharding import spin_mesh
    mesh = spin_mesh(1)
    r = anneal(TORUS, HP, seed=5, noise="xorshift",
               backend_opts={"partition": "spin", "mesh": mesh})
    ref = anneal(TORUS, HP, seed=5, config=SolverConfig())
    np.testing.assert_array_equal(r.best_energy, ref.best_energy)
    np.testing.assert_array_equal(r.best_m, ref.best_m)


# ---------------------------------------------------------------------------
# The legacy shim
# ---------------------------------------------------------------------------
def test_shim_warns_once_per_site_and_builds_equal_config():
    site = "tests.test_solver_config.shim_once"
    config_mod._WARNED_SITES.discard(site)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        c1 = legacy_kwargs_to_config(site, None, backend="dense",
                                     noise="xorshift")
        c2 = legacy_kwargs_to_config(site, None, backend="dense",
                                     noise="xorshift")
    dep = [w for w in rec if issubclass(w.category, DeprecationWarning)]
    assert len(dep) == 1 and site in str(dep[0].message)
    assert c1 == c2 == SolverConfig(backend="dense", noise="xorshift")


def test_shim_ignores_none_and_rejects_mixing():
    c = legacy_kwargs_to_config("tests.none-site", None, backend=None,
                                noise=None)
    assert c == SolverConfig()
    with pytest.raises(TypeError, match="not both"):
        legacy_kwargs_to_config("tests.mix-site", SolverConfig(),
                                backend="dense")


@pytest.mark.parametrize("legacy_kw,cfg", [
    (dict(backend="dense", noise="xorshift"),
     SolverConfig(backend="dense", noise="xorshift")),
    (dict(backend="sparse", noise="xorshift", storage_layout="packed"),
     SolverConfig(backend="sparse", noise="xorshift",
                  storage_layout="packed")),
    (dict(backend="pallas", noise="xorshift",
          backend_opts={"noise_mode": "streamed"}),
     SolverConfig(backend="pallas", noise="xorshift",
                  backend_opts={"noise_mode": "streamed"})),
])
def test_legacy_kwargs_bit_identical_to_config(legacy_kw, cfg):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        r_legacy = anneal(TORUS, HP, seed=3, track_energy=False, **legacy_kw)
    r_cfg = anneal(TORUS, HP, seed=3, track_energy=False, config=cfg)
    np.testing.assert_array_equal(r_legacy.best_energy, r_cfg.best_energy)
    np.testing.assert_array_equal(r_legacy.best_cut, r_cfg.best_cut)
    np.testing.assert_array_equal(r_legacy.best_m, r_cfg.best_m)


def test_legacy_default_noise_stays_threefry():
    """anneal()'s historical no-kwarg default (threefry) is preserved; the
    typed default (xorshift) applies only when a config is passed."""
    r_bare = anneal(TORUS, HP, seed=3, track_energy=False)
    r_tf = anneal(TORUS, HP, seed=3, track_energy=False,
                  config=SolverConfig(noise="threefry"))
    r_xs = anneal(TORUS, HP, seed=3, track_energy=False,
                  config=SolverConfig())
    np.testing.assert_array_equal(r_bare.best_energy, r_tf.best_energy)
    np.testing.assert_array_equal(r_bare.best_m, r_tf.best_m)
    assert not np.array_equal(r_bare.best_m, r_xs.best_m)


# ---------------------------------------------------------------------------
# Cache-key identity: config-built vs kwarg-built services
# ---------------------------------------------------------------------------
def test_service_cache_keys_identical_config_vs_legacy():
    reqs = lambda: [AnnealRequest(problem=TORUS, hp=HP, seed=7)]  # noqa: E731
    svc_kw = AnnealService(backend="dense", noise="xorshift", min_bucket=16)
    svc_cfg = AnnealService(
        config=SolverConfig(backend="dense", noise="xorshift"), min_bucket=16)
    r_kw = svc_kw.solve(reqs())
    r_cfg = svc_cfg.solve(reqs())
    np.testing.assert_array_equal(r_kw[0].result.best_m,
                                  r_cfg[0].result.best_m)
    keys_kw, keys_cfg = set(svc_kw._programs), set(svc_cfg._programs)
    assert keys_kw and keys_kw == keys_cfg


def test_per_request_config_signature_splits_groups():
    """Two same-shape requests whose configs demand different execution
    surfaces must not share a group (the config signature is in the key)."""
    hp = HP
    r1 = AnnealRequest(problem=TORUS, hp=hp, seed=7,
                       config=SolverConfig(backend="dense"))
    r2 = AnnealRequest(problem=TORUS, hp=hp, seed=7,
                       config=SolverConfig(backend="dense", j_mode="tiled"))
    svc = AnnealService(backend="sparse", min_bucket=16)
    k1, k2 = svc._group_key(r1, 64), svc._group_key(r2, 64)
    assert k1 != k2
    # and config-less requests key separately from config-carrying ones
    r3 = AnnealRequest(problem=TORUS, hp=hp, seed=7)
    assert svc._group_key(r3, 64) != k1
