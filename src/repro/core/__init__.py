"""repro.core — the paper's contribution: HA-SSA / SSA / SA / PT annealers.

Public API:
  IsingModel, MaxCutProblem           — problem substrate (ising.py)
  gset.load                           — benchmark instances (gset.py)
  SSAHyperParams, anneal, solve_maxcut— SSA + HA-SSA (ssa.py)
  SSQAHyperParams, anneal_ssqa        — Trotter-replica SSQA (ssqa.py)
  SolverConfig                        — typed solver options (config.py)
  BatchedBackend, make_batched_backend— the plateau engine (engine.py);
                                        the drivers run it at B=1
  SAHyperParams, anneal_sa            — conventional SA baseline (sa.py)
  PTHyperParams, anneal_pt            — parallel-tempering baseline (pt.py)
  memory                              — Eq.(5)/(6) memory models
"""
from . import gset, memory  # noqa: F401
from .autotune import (  # noqa: F401
    AutotuneReport,
    autotune_hyperparams,
    resolve_hyperparams,
    sample_local_fields,
)
from .config import SolverConfig, legacy_kwargs_to_config  # noqa: F401
from .engine import (  # noqa: F401
    TILED_J_THRESHOLD,
    BaseResult,
    BatchedBackend,
    EngineState,
    PackedEngineState,
    Plateau,
    bucket_n,
    make_batched_backend,
    pack_state,
    pad_model,
    padded_noise_init,
    schedule_plateaus,
    unpack_state,
)
from .ising import IsingModel, MaxCutProblem, fig4_example, ising_energy  # noqa: F401
from .pt import (  # noqa: F401
    PTHyperParams,
    PTResult,
    PTSSAHyperParams,
    PTSSAResult,
    anneal_pt,
    anneal_pt_ssa,
)
from .sa import SAHyperParams, SAResult, anneal_sa  # noqa: F401
from .schedule import (  # noqa: F401
    Schedule,
    hassa_schedule,
    n_temp_steps,
    ssa_schedule,
    ssqa_schedule,
)
from .ssa import (  # noqa: F401
    AnnealResult,
    SSAHyperParams,
    anneal,
    pack_spins,
    solve_maxcut,
    ssa_cycle_update,
    unpack_spins,
)
from .ssqa import SSQAHyperParams, anneal_ssqa  # noqa: F401
