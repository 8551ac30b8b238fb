"""The algorithmic work of one annealing chunk, from the instance and the
hyperparameters alone.

Whatever computes the field (a dense f32 matmul, an XNOR-popcount over
bitplanes, a sparse gather, one chip or four), one lane-chunk of HA-SSA has
to do the same work:

* operations: each live coupling J_ij (both directions of an edge) is
  multiplied by a spin and accumulated once per trial per cycle, so
  ``2 * 2E * trials * cycles`` integer operations, held against the int8 peak;
* bytes: the couplings once per chunk at their weight bits (enough bits
  for the distinct weight values: one for +/-1), plus the spin state read
  and written once per chunk: the spin and best-spin bits and the Itanh
  counter at its needed width.

The backend, the field mode and the padding never enter, so a change of
datapath is judged on the same yardstick.
"""
from __future__ import annotations

import math

import numpy as np

from .instances import Instance
from .reference import HyperParams


def lane_chunk_work(inst: Instance, hp: HyperParams) -> dict:
    """``{'ops', 'bytes'}`` of one request over one chunk (one shot)."""
    couplings = 2 * len(inst.edges)
    cycles = hp.cycles_per_shot
    ops = 2 * couplings * hp.n_trials * cycles
    levels = len(np.unique(inst.weights))
    weight_bits = max(1, math.ceil(math.log2(levels)))
    itanh_bits = math.ceil(math.log2(2 * hp.i0_max))
    state_bits = hp.n_trials * inst.n * (2 + itanh_bits)
    nbytes = couplings * weight_bits / 8 + 2 * state_bits / 8
    return {"ops": float(ops), "bytes": float(nbytes)}


def least_seconds(ops: float, nbytes: float, peaks: dict, chips: int):
    """``(seconds, bound)``: the roofline's least time on ``chips`` chips."""
    t_ops = ops / (chips * peaks["int8_ops"])
    t_bytes = nbytes / (chips * peaks["hbm_bytes_per_s"])
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


def config_work(cfg: dict) -> list:
    """Per-instance lane-chunk work of a configuration file's contents.

    Reads only the instances and the hyperparameters: the ``service``
    section (backend, field mode, partition) never enters.
    """
    from .instances import make

    hp = HyperParams(**cfg["hyperparams"])
    return [lane_chunk_work(make(s), hp) for s in cfg["instances"]]
