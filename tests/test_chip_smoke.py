"""chip_smoke.py off the chip: its refusals, its service, its control flow.

The script itself only runs on a TPU; here it must refuse (non-zero, no
result line), its service must let a pallas failure escape instead of
falling back, and its phases must run end to end at a tiny size with the
kernels in interpret mode — one device for the one-chip phases, four
virtual CPU devices (a child process) for the spin-sharded phase.
"""
import importlib.util
import os
import subprocess
import sys

import pytest

from repro.core import SSAHyperParams, gset
from repro.ft.faults import FaultInjector, InjectedCompileFailure
from repro.serve import AnnealRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HP = SSAHyperParams(n_trials=8, m_shot=2, n_rnd=2, i0_min=1, i0_max=8, tau=6)


def _smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_refuses_without_a_tpu(capsys):
    assert _smoke().main([]) == 1
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_pallas_compile_fault_escapes_the_smoke_service():
    """A failing pallas compile must surface, not rerun on XLA dense."""
    inj = FaultInjector()
    inj.arm("compile", backend="pallas")
    svc = _smoke().smoke_service("pallas", "dense", faults=inj)
    req = AnnealRequest(problem=gset.toroidal_grid(64, seed=1), hp=HP)
    with pytest.raises(InjectedCompileFailure):
        svc.solve([req])
    assert [p for p, _ in inj.log] == ["compile"]
    assert not any(k.startswith("fallback") for k in svc.stats)


def test_one_chip_phases_run_on_cpu(capsys):
    g = [gset.toroidal_grid(100, seed=s, name=f"T100-{s}") for s in (1, 2, 3)]
    k = [gset.complete_graph(60, seed=7, name="K60")]
    _smoke().run_one_chip(g, k, HP, HP)
    out = capsys.readouterr().out
    assert out.count("bit-identical to sparse: 4/4") == 2
    assert "bit-identical to one-shot: 3/3" in out
    assert "kernel=popcount" in out and "kernel=streamed" in out


def test_four_device_phase_runs_on_cpu():
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('chip_smoke', "
        f"{os.path.join(ROOT, 'chip_smoke.py')!r})\n"
        "cs = importlib.util.module_from_spec(spec); spec.loader.exec_module(cs)\n"
        "from repro.core import SSAHyperParams, gset\n"
        "hp = SSAHyperParams(n_trials=8, m_shot=2, i0_max=8, tau=6)\n"
        "cs.run_four_chip(gset.toroidal_grid(300, seed=81, name='T300'), hp)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join(
                   [os.path.join(ROOT, "src"), os.environ.get("PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "bit-identical to 1-device: yes" in out.stdout
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("spin/")]
    assert len(lines) == 3
