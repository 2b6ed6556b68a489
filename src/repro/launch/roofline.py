"""Roofline-term extraction from compiled XLA artifacts (deliverable g).

Hardware model (per assignment): TPU v5p-class chip with
  197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.

Terms (seconds, per training/serving step):

  compute    = FLOPs / (chips * PEAK_FLOPS)
  memory     = HBM bytes / (chips * HBM_BW)
  collective = collective bytes / (chips * ICI_BW)

``compiled.cost_analysis()`` reports the *partitioned per-device* module
(verified empirically in repro.launch.smoketest), so per-chip terms divide
by PEAK only; the global-FLOP roofline view multiplies back by chip count.
Collective bytes are parsed from the HLO text: the summed output bytes of
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
ops (async ``*-start`` variants counted once, ``*-done`` skipped).
"""

from __future__ import annotations

import dataclasses
import re

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "c64": 8, "s64": 8, "u64": 8, "f64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.  %all-gather.3 = f32[8,128]{1,0} all-gather(...)
#       ROOT %tuple = (f32[2]{0}, bf16[4,4]{1,0}) all-to-all(...)
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")(-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum output bytes per collective kind from HLO text."""
    out: dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shape_str)
    return out


@dataclasses.dataclass
class Roofline:
    name: str
    chips: int
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_bytes_per_device: float
    coll_breakdown: dict[str, int]
    peak_memory_per_device: float        # from memory_analysis
    model_flops: float                   # analytic 6*N*D (global)

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_device / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flop_ratio(self) -> float:
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def step_time_bound(self) -> float:
        """Lower bound on step time = max of the three terms."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def mfu_bound(self) -> float:
        """Model-FLOP utilization upper bound at the roofline step time."""
        denom = self.step_time_bound * PEAK_FLOPS * self.chips
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> dict:
        return {
            "name": self.name, "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "coll_breakdown": self.coll_breakdown,
            "peak_memory_per_device": self.peak_memory_per_device,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flop_ratio": self.useful_flop_ratio,
            "mfu_bound": self.mfu_bound,
        }


def analyze(name: str, compiled, chips: int, model_flops: float,
            hlo_text: str | None = None) -> Roofline:
    cost = compiled.cost_analysis() or {}
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    try:
        mem = compiled.memory_analysis()
        peak = float(getattr(mem, "temp_size_in_bytes", 0)
                     + getattr(mem, "argument_size_in_bytes", 0)
                     + getattr(mem, "output_size_in_bytes", 0)
                     - getattr(mem, "alias_size_in_bytes", 0))
    except Exception:
        peak = 0.0
    return Roofline(
        name=name, chips=chips, flops_per_device=flops,
        hbm_bytes_per_device=byts,
        collective_bytes_per_device=float(sum(coll.values())),
        coll_breakdown=coll, peak_memory_per_device=peak,
        model_flops=model_flops,
    )


def model_flops_train(n_params_active: float, n_tokens: float) -> float:
    """6 N D rule (fwd 2ND + bwd 4ND)."""
    return 6.0 * n_params_active * n_tokens


def model_flops_decode(n_params_active: float, n_tokens: float) -> float:
    """Forward-only: 2 N D."""
    return 2.0 * n_params_active * n_tokens
