"""HLO analysis: collective-traffic extraction + roofline terms.

``cost_analysis()`` gives HLO FLOPs/bytes but NOT collective bytes — those
are parsed from the compiled HLO text: we sum the output-operand sizes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction (per-device view, as GSPMD emits it).

Hardware model (TPU v5e per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # bytes/s / chip
ICI_BW = 50e9                # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

#: matches e.g. ``f32[128,1024]{1,0}`` or ``bf16[4096]``
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of every array literal in an HLO type string (handles
    tuples)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind byte totals from compiled (post-SPMD) HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        # instruction lines look like:  %x = f32[..] all-reduce(...)
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[^\s]+)\s+"
                     r"([\w\-]+)", s)
        if not m:
            continue
        type_str, op = m.group(1), m.group(2)
        for kind in _COLLECTIVES:
            if op == kind or op.startswith(kind + "-start"):
                out[kind] += _shape_bytes(type_str)
                break
    return out


@dataclasses.dataclass
class Roofline:
    """Three-term roofline for one (arch x shape x mesh) cell."""

    flops: float                 # HLO FLOPs (per device)
    hbm_bytes: float             # HLO bytes accessed (per device)
    coll_bytes: float            # collective bytes (per device)
    num_devices: int
    model_flops: float           # 6*N*D (analytic, GLOBAL)

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs x devices): remat/redundancy waste."""
        total = self.flops * self.num_devices
        return self.model_flops / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs utilization at the roofline step time."""
        denom = self.step_time * PEAK_FLOPS * self.num_devices
        return self.model_flops / denom if denom else 0.0

    def row(self) -> dict:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops_per_dev": self.flops,
            "useful_ratio": self.useful_flops_ratio,
            "roofline_mfu": self.mfu,
        }


def exact_param_counts(cfg) -> tuple[float, float]:
    """(total, active) param counts from the REAL spec (not the analytic
    estimate): MoE active = total - inactive routed expert fraction."""
    from repro.models import build
    from repro.models.module import param_count
    total = float(param_count(build(cfg).spec))
    active = total
    if cfg.family == "moe":
        n_moe = cfg.num_layers - cfg.first_k_dense
        routed = float(cfg.num_experts) * 3 * cfg.d_model * cfg.moe_d_ff * n_moe
        active_routed = routed * cfg.num_experts_per_tok / cfg.num_experts
        active = total - routed + active_routed
    return total, active


def model_flops_for_cell(cfg, shape) -> float:
    """6*N*D for training, 2*N*D for inference (N = active params, exact)."""
    _, n = exact_param_counts(cfg)
    d = shape.tokens_per_step
    if shape.kind == "train":
        return 6.0 * n * d
    return 2.0 * n * d            # prefill / decode (one token per sequence)
