"""The resident kernels compile for a TPU v5e chip (described, not attached).

Mosaic refuses what interpret mode runs happily: unsigned reductions,
lane-splitting reshapes, slices of loaded values, more VMEM than a kernel
may claim.  These cases compile each resident kernel of the served path
with ``interpret=False`` at the service buckets of the G11 class (1024) and
the K2000 class (2048), plus a stacked B=4 group, with and without the
per-lane live mask, and check that the program launches the kernel
(``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, so collecting this file
touches no TPU library; where it cannot be described the cases skip.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ssa_update as k

R = 16          # trials: the launcher's Table-II default
C = 100         # cycles per plateau (tau)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _streamed(s, B, N, live=False, n_replicas=0):
    nw = N // 32

    def fn(mp, it, J, h, rng, i0, bh, bmp, lv):
        return k.ssa_plateau_packed_batched(
            mp, it, J, h, rng, i0, bh, bmp, n_cycles=C, interpret=False,
            block_r=n_replicas or 8, jperp=3, n_replicas=n_replicas,
            live=lv if live else None)

    return jax.jit(fn).lower(
        s((B, R, nw), jnp.uint32), s((B, R, N), jnp.int32),
        s((B, N, N), jnp.float32), s((B, N), jnp.int32),
        s((B, 4, R, N), jnp.uint32), s((), jnp.int32), s((B, R), jnp.int32),
        s((B, R, nw), jnp.uint32), s((B,), jnp.int32))


def _popcount(s, B, N, live=False, n_replicas=0):
    nw = N // 32
    cyc = 6 * C  # one HA-SSA iteration: I0 1→32 is six plateaus

    def fn(mp, it, sg, mg, base, h, rng, i0s, folds, bh, bmp, jps, lv):
        return k.ssa_plateau_popcount_batched(
            mp, it, sg, mg, base, h, rng, i0s, folds, bh, bmp,
            block_r=n_replicas or 8, interpret=False,
            jperp_sched=jps if n_replicas else None, n_replicas=n_replicas,
            live=lv if live else None)

    return jax.jit(fn).lower(
        s((B, R, nw), jnp.uint32), s((B, R, N), jnp.int32),
        s((B, N, nw), jnp.uint32), s((B, 1, N, nw), jnp.uint32),
        s((B, N), jnp.int32), s((B, N), jnp.int32),
        s((B, 4, R, N), jnp.uint32), s((cyc,), jnp.int32),
        s((cyc + 1,), jnp.int32), s((B, R), jnp.int32),
        s((B, R, nw), jnp.uint32), s((cyc,), jnp.int32), s((B,), jnp.int32))


def _pregen(s, B, N, live=False):
    def fn(m, it, J, h, noise, i0, bh, bm, lv):
        return k.ssa_plateau_batched(m, it, J, h, noise, i0, bh, bm,
                                     interpret=False,
                                     live=lv if live else None)

    return jax.jit(fn).lower(
        s((B, R, N), jnp.float32), s((B, R, N), jnp.int32),
        s((B, N, N), jnp.float32), s((B, N), jnp.int32),
        s((B, C, R, N), jnp.int8), s((), jnp.int32), s((B, R), jnp.int32),
        s((B, R, N), jnp.int8), s((B,), jnp.int32))


CASES = {
    "streamed-1024": (_streamed, 1, 1024),
    "streamed-2048": (_streamed, 1, 2048),
    "streamed-B4-1024": (_streamed, 4, 1024),
    "popcount-1024": (_popcount, 1, 1024),
    "popcount-2048": (_popcount, 1, 2048),
    "popcount-B4-1024": (_popcount, 4, 1024),
    "pregen-2048": (_pregen, 1, 2048),
}
# The same kernels behind a (B,) live mask in SMEM (dead lanes skipped).
LIVE_CASES = {
    "streamed-B4-1024": (_streamed, 4, 1024),
    "popcount-B4-1024": (_popcount, 4, 1024),
    "popcount-B4-2048": (_popcount, 4, 2048),
    "pregen-B2-1024": (_pregen, 2, 1024),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_resident_kernel_compiles_for_v5e(one_chip, case):
    build, B, N = CASES[case]
    spec = lambda shape, dtype: _spec(one_chip, shape, dtype)  # noqa: E731
    text = build(spec, B, N).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("case", sorted(LIVE_CASES))
def test_masked_resident_kernel_compiles_for_v5e(one_chip, case):
    build, B, N = LIVE_CASES[case]
    spec = lambda shape, dtype: _spec(one_chip, shape, dtype)  # noqa: E731
    text = build(spec, B, N, live=True).compile().as_text()
    assert "tpu_custom_call" in text


def test_popcount_ssqa_ring_compiles_for_v5e(one_chip):
    spec = lambda shape, dtype: _spec(one_chip, shape, dtype)  # noqa: E731
    text = _popcount(spec, 1, 1024, n_replicas=8).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("build", [_popcount, _streamed],
                         ids=["popcount", "streamed"])
def test_masked_ssqa_ring_compiles_for_v5e(one_chip, build):
    spec = lambda shape, dtype: _spec(one_chip, shape, dtype)  # noqa: E731
    text = build(spec, 2, 1024, live=True, n_replicas=8).compile().as_text()
    assert "tpu_custom_call" in text
