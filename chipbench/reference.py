"""Plain reference of the annealer the benchmark checks: HA-SSA on Max-Cut.

Written from the paper's equations (arXiv 2601.18007, Sec. II-B and III)
and the service's stated contract, with no import from the program:

* Ising embedding of a Max-Cut instance: J = -w, h = 0, so
  H(m) = -(m . J m) / 2 and cut = (w_total - H) / 2.
* Noise: one Marsaglia xorshift128 lane per (trial, spin), seeded by a
  SplitMix64 avalanche of (request seed, flat lane index), one +/-1 draw per
  lane per cycle from the output word's top bit.  The first draw is the
  initial spin state; Itanh starts at 0 for +1 and -1 for -1.
* One cycle, exact int32 (Eq. 2a-2c):
  I = J m + n_rnd r + Itanh;  Itanh = clip(I, -I0, I0 - 1);  m = sign(Itanh).
* Schedule (Eq. 4): plateaus I0 = i0_min << (beta k) up to i0_max, each held
  tau cycles; one shot runs every plateau once.
* HA-SSA storage: the best state per trial is tracked only over states
  produced inside the I0 == i0_max plateau (strict improvement).
* The service reports after every shot; a request stops after the first
  shot whose best cut over trials reaches its ``target_cut``, else after
  ``m_shot`` shots.

A request's result is therefore a pure function of (instance, hyper-
parameters, seed, target).  The field is computed by dense int8 x int8 ->
int32 products for dense instances and by a neighbour-table gather for
sparse ones; both are exact.

``variant`` selects a variant put in the program's place.  The control
that must fail the comparison is ``"shared_noise"``: one noise lane per
spin for all trials of a request, breaking the stated guarantee of
independent trials.  No lower precision can serve as the control here: the
arithmetic is exact integer and every configuration's fields, Itanh and
currents fit in int16 (the G-set ones in int8).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .instances import Instance

BIG = 2 ** 30
VARIANTS = ("exact", "shared_noise")
_M64 = 0xFFFFFFFFFFFFFFFF


@dataclasses.dataclass(frozen=True)
class HyperParams:
    n_trials: int
    m_shot: int
    n_rnd: int
    i0_min: int
    i0_max: int
    tau: int
    beta_shift: int

    def plateaus(self) -> List[int]:
        out, v = [], self.i0_min
        while True:
            out.append(min(v, self.i0_max))
            if out[-1] >= self.i0_max:
                return out
            v <<= self.beta_shift

    @property
    def cycles_per_shot(self) -> int:
        return len(self.plateaus()) * self.tau


@dataclasses.dataclass
class Outcome:
    """A request's reference result at the chunk it stops at."""
    chunks: int               # shots run before the request stopped
    best_cut: np.ndarray      # (T,) best cut per trial
    best_m: np.ndarray        # (T, n) int8 spins of the best state
    trace: List[int]          # best cut over trials after each shot


def seed_lanes(seed: int, n_trials: int, n: int) -> np.ndarray:
    """(4, T, n) uint32 xorshift128 states for one request."""
    total = n_trials * n
    idx = np.arange(total, dtype=np.uint64)
    words = []
    with np.errstate(over="ignore"):
        for w in range(4):
            z = (np.uint64(seed & _M64)
                 + np.uint64(0x9E3779B97F4A7C15)
                 * (idx + np.uint64(1 + w * total)))
            z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            z = z ^ (z >> np.uint64(31))
            words.append((z & np.uint64(0xFFFFFFFF)).astype(np.uint32))
    st = np.stack(words)
    st[0] = np.where((st == 0).all(axis=0), np.uint32(0x1234567), st[0])
    return st.reshape(4, n_trials, n)


def _xorshift(st):
    x, y, z, w = st[0], st[1], st[2], st[3]
    t = x ^ (x << jnp.uint32(11))
    w_new = (w ^ (w >> jnp.uint32(19))) ^ (t ^ (t >> jnp.uint32(8)))
    r = jnp.where((w_new >> jnp.uint32(31)) == 1, 1, -1).astype(jnp.int32)
    return jnp.stack([y, z, w, w_new]), r


def _use_dense(inst: Instance) -> bool:
    return len(inst.edges) > 8 * inst.n


def coupling_arrays(inst: Instance):
    """Device arrays of J = -w: dense (n, n) int8, or (idx, w) (n, d)."""
    n = inst.n
    i, j = inst.edges[:, 0], inst.edges[:, 1]
    if _use_dense(inst):
        J = np.zeros((n, n), np.int32)
        np.add.at(J, (i, j), -inst.weights)
        np.add.at(J, (j, i), -inst.weights)
        if np.abs(J).max() > 127:
            raise ValueError("dense reference holds couplings as int8")
        return {"J": jnp.asarray(J.astype(np.int8))}
    src = np.concatenate([i, j])
    dst = np.concatenate([j, i])
    w = np.concatenate([-inst.weights, -inst.weights])
    order = np.argsort(src, kind="stable")
    src, dst, w = src[order], dst[order], w[order]
    deg = np.bincount(src, minlength=n)
    d = int(deg.max())
    slot = np.arange(len(src)) - np.repeat(np.cumsum(deg) - deg, deg)
    idx = np.zeros((n, d), np.int32)
    ww = np.zeros((n, d), np.int32)
    idx[src, slot] = dst
    ww[src, slot] = w
    return {"idx": jnp.asarray(idx), "w": jnp.asarray(ww)}


def _field(coup, m):
    """Local fields J m for spins m (R, T, n) int8 -> (R, T, n) int32."""
    if "J" in coup:
        return jax.lax.dot_general(
            m, coup["J"], (((2,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    g = jnp.take(m.astype(jnp.int32), coup["idx"], axis=2)  # (R,T,n,d)
    return jnp.sum(g * coup["w"], axis=-1)


def _energy(m, f):
    return -jnp.sum(m.astype(jnp.int32) * f, axis=-1) // 2


def _shot_fn(hp: HyperParams, variant: str):
    i0 = np.repeat(np.asarray(hp.plateaus(), np.int32), hp.tau)
    elig = (i0 == hp.i0_max).astype(np.int32)
    # fold[c]: the state entering cycle c was produced inside the i0_max
    # plateau (the first cycle of a shot holds the previous shot's state,
    # already folded at its end).
    fold = np.concatenate([[0], elig[:-1]]).astype(bool)
    shared = variant == "shared_noise"

    def shot(coup, st):
        def cycle(carry, x):
            ns, m, it, bh, bm = carry
            i0c, fc = x
            f = _field(coup, m)
            H = _energy(m, f)
            better = fc & (H < bh)
            bh = jnp.where(better, H, bh)
            bm = jnp.where(better[..., None], m, bm)
            ns, r = _xorshift(ns)
            if shared:
                r = jnp.broadcast_to(r[:, :1], m.shape)
            cur = f + hp.n_rnd * r + it
            it = jnp.clip(cur, -i0c, i0c - 1)
            m = jnp.where(it >= 0, 1, -1).astype(jnp.int8)
            return (ns, m, it, bh, bm), None

        st, _ = jax.lax.scan(cycle, st, (jnp.asarray(i0), jnp.asarray(fold)))
        ns, m, it, bh, bm = st
        if elig[-1]:
            H = _energy(m, _field(coup, m))
            better = H < bh
            bh = jnp.where(better, H, bh)
            bm = jnp.where(better[..., None], m, bm)
        return ns, m, it, bh, bm

    return jax.jit(shot)


def _init(hp: HyperParams, n: int, seeds: Sequence[int], variant: str):
    rows = 1 if variant == "shared_noise" else hp.n_trials
    lanes = np.stack([seed_lanes(int(s), rows, n) for s in seeds], axis=1)
    ns = jnp.asarray(lanes)                       # (4, R, rows, n)
    ns, r = _xorshift(ns)
    if variant == "shared_noise":
        r = jnp.broadcast_to(r, (len(seeds), hp.n_trials, n))
    m = r.astype(jnp.int8)
    it = jnp.where(m > 0, 0, -1).astype(jnp.int32)
    bh = jnp.full(m.shape[:2], BIG, jnp.int32)
    return ns, m, it, bh, m


def solve(inst: Instance, hp: HyperParams, seeds: Sequence[int],
          targets: Sequence[Optional[int]], *,
          variant: str = "exact",
          shot_fns: Optional[Dict] = None) -> List[Outcome]:
    """Reference outcomes of requests on one instance, run together.

    Each request stops after the first shot whose best cut reaches its
    target (``None``: run all ``m_shot`` shots).
    ``shot_fns`` caches the compiled shot per (hyperparameters, variant)
    across calls.
    """
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}; known: {VARIANTS}")
    if not seeds:
        return []
    key = (hp, variant)
    cache = shot_fns if shot_fns is not None else {}
    if key not in cache:
        cache[key] = _shot_fn(hp, variant)
    shot = cache[key]
    coup = coupling_arrays(inst)
    n_real = len(seeds)
    # Pad the group to a power of two so few shapes compile across runs.
    R = 1 << (n_real - 1).bit_length()
    seeds = list(seeds) + [seeds[0]] * (R - n_real)
    targets = list(targets) + [targets[0]] * (R - n_real)
    st = _init(hp, inst.n, seeds, variant)
    out: List[Optional[Outcome]] = [None] * R
    traces: List[List[int]] = [[] for _ in range(R)]
    for c in range(hp.m_shot):
        st = shot(coup, st)
        cuts = (inst.w_total - np.asarray(st[3]).astype(np.int64)) // 2
        newly = []
        for k in range(R):
            if out[k] is not None:
                continue
            best = int(cuts[k].max())
            traces[k].append(best)
            tgt = targets[k]
            if (tgt is not None and best >= tgt) or c + 1 >= hp.m_shot:
                newly.append(k)
        if newly:
            bm = np.asarray(st[4])
            for k in newly:
                out[k] = Outcome(c + 1, cuts[k].copy(), bm[k].copy(),
                                 traces[k])
        if all(o is not None for o in out):
            break
    return out[:n_real]  # type: ignore[return-value]
