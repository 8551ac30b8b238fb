"""Roofline-term extraction from compiled XLA artifacts.

compute/memory terms come from ``compiled.cost_analysis()``; collective
bytes are NOT in cost_analysis, so we parse the optimized HLO text and sum
the operand sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op.

Hardware constants (TPU v5e, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

__all__ = [
    "HW",
    "collective_bytes",
    "count_hlo_ops",
    "roofline",
    "RooflineReport",
    "shape_bytes",
]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12      # bf16 FLOP/s per chip
    hbm_bw: float = 819e9           # bytes/s per chip
    link_bw: float = 50e9           # bytes/s per ICI link


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
}

# e.g. "bf16[256,4096,128]{2,1,0}" — capture dtype + dims
_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")

_COLLECTIVE_OPS = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)


def count_hlo_ops(hlo_text: str, op: str) -> int:
    """Count instruction occurrences of ``op`` in HLO or StableHLO text.

    Matches both the compiled-HLO form (``%x = f32[..] dot(...)``) and the
    StableHLO/MLIR form (``%5 = stablehlo.dot_general ...``).  Used by the
    contraction-count regression tests: a plateau's cycle loop must contain
    exactly one field contraction (dot for the dense backend, gather for the
    sparse one) — the seed's record='best' path evaluated it twice.
    """
    pat = rf"stablehlo\.{re.escape(op)}\b|(?<![\w.-]){re.escape(op)}\("
    return len(re.findall(pat, hlo_text))


def shape_bytes(dtype: str, dims_str: str) -> int:
    n = 1
    if dims_str:
        for d in dims_str.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _line_result_bytes(line: str) -> int:
    """Sum the bytes of the result shape(s) at the head of an HLO line.

    HLO line form: ``%name = <shape> <op>(<operands>)``.  For collectives,
    result bytes ≈ data moved per participating device (a good roofline
    proxy for all of AG/AR/RS/A2A/CP).
    """
    head = line.split(" = ", 1)
    if len(head) != 2:
        return 0
    result = head[1]
    # shapes before the op name — take the segment up to the op token
    m = re.search(r"\b(" + "|".join(_COLLECTIVE_OPS) + r")\b", result)
    if not m:
        return 0
    shapes_part = result[: m.start()]
    return sum(
        shape_bytes(d, s) for d, s in _SHAPE_RE.findall(shapes_part)
    )


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-type result bytes summed over the module.

    Includes '-start' variants (async collectives); '-done' lines carry the
    same tuple shape and are skipped to avoid double counting.
    """
    out = {k: 0 for k in _COLLECTIVE_OPS}
    out["total"] = 0
    for line in hlo_text.splitlines():
        ls = line.strip()
        if " = " not in ls:
            continue
        if "-done" in ls:
            continue
        for op in _COLLECTIVE_OPS:
            token = f" {op}"
            if f" {op}(" in ls or f" {op}-start(" in ls:
                b = _line_result_bytes(ls)
                out[op] += b
                out["total"] += b
                break
    return out


@dataclasses.dataclass
class RooflineReport:
    """All byte/FLOP numbers are PER-DEVICE (what cost_analysis reports for
    an SPMD-partitioned module — verified against a hand-sharded matmul).
    The prompt's form `HLO_FLOPs_global / (chips × peak)` equals
    `per_device / peak`."""

    flops: float
    hbm_bytes: float
    coll_bytes: float
    coll_breakdown: Dict[str, int]
    n_chips: int
    peak_memory_per_device: Optional[float]
    hw: HW = dataclasses.field(default_factory=HW)

    @property
    def t_compute(self) -> float:
        return self.flops / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.hw.link_bw

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def asdict(self) -> Dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.hbm_bytes,
            "coll_bytes_per_device": self.coll_bytes,
            "flops_global": self.flops * self.n_chips,
            "coll_breakdown": {k: int(v) for k, v in self.coll_breakdown.items()},
            "n_chips": self.n_chips,
            "peak_memory_per_device": self.peak_memory_per_device,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
        }


def roofline(compiled, n_chips: int, hlo_text: Optional[str] = None) -> RooflineReport:
    """Build a RooflineReport from a jax compiled artifact."""
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    hbm = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    peak = None
    try:
        ma = compiled.memory_analysis()
        peak = float(
            ma.temp_size_in_bytes + ma.argument_size_in_bytes + ma.output_size_in_bytes
        )
    except Exception:
        pass
    return RooflineReport(
        flops=flops,
        hbm_bytes=hbm,
        coll_bytes=float(coll["total"]),
        coll_breakdown=coll,
        n_chips=n_chips,
        peak_memory_per_device=peak,
    )


def model_flops(n_params_active: float, n_tokens: float, kind: str) -> float:
    """MODEL_FLOPS = 6·N·D (train) or 2·N·D (single forward / decode)."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params_active * n_tokens
