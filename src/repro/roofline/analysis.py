"""Roofline-term extraction from compiled dry-run artifacts.

compute term    = HLO_FLOPs / (chips x 197 TFLOP/s bf16)
memory term     = HLO_bytes / (chips x 819 GB/s)
collective term = collective_bytes / (chips x 50 GB/s/link)

cost_analysis() on the SPMD-partitioned executable reports *per-device*
FLOPs/bytes (verified in tests/test_roofline.py); we scale by chip count
to report globals. Collective bytes are not in cost_analysis: we parse the
post-partitioning HLO (`compiled.as_text()`) and sum operand bytes of
every all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute, with ring-algorithm byte multipliers (all-reduce
moves ~2x its payload per device). Shapes in partitioned HLO are already
per-device, so `collective_bytes_per_chip / link_bw` is the term directly;
the table also reports the global `x chips` figure to match the formula in
the brief."""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

# bytes-on-the-wire multiplier per collective kind (ring algorithms)
_FACTORS = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
    "ragged-all-to-all": 1.0,
}

_OP_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+?))\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|ragged-all-to-all)"
    r"(?:-start|-done)?\(")
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")


def _tensor_bytes(shape_str: str) -> float:
    total = 0.0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_per_chip: float = 0.0
    counts: Dict[str, int] = field(default_factory=dict)
    bytes_by_kind: Dict[str, float] = field(default_factory=dict)


def parse_collectives(hlo_text: str) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        # async pairs appear as -start/-done; count the -start only
        if "-done(" in line:
            continue
        shape_str = m.group(1) if m.group(1) is not None else m.group(2)
        b = _tensor_bytes(shape_str) * _FACTORS[kind]
        stats.bytes_per_chip += b
        stats.counts[kind] = stats.counts.get(kind, 0) + 1
        stats.bytes_by_kind[kind] = stats.bytes_by_kind.get(kind, 0.0) + b
    return stats


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_chip: float
    bytes_per_chip: float
    collective_bytes_per_chip: float
    model_flops: float
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    dominant: str = ""
    flops_ratio: float = 0.0            # MODEL_FLOPS / global HLO flops
    collective_counts: Dict[str, int] = field(default_factory=dict)
    memory_per_chip: Dict[str, float] = field(default_factory=dict)

    def finalize(self, peak_flops=197e12, hbm_bw=819e9, link_bw=50e9):
        self.compute_s = self.flops_per_chip / peak_flops
        self.memory_s = self.bytes_per_chip / hbm_bw
        self.collective_s = self.collective_bytes_per_chip / link_bw
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.dominant = max(terms, key=terms.get)
        global_flops = self.flops_per_chip * self.chips
        self.flops_ratio = self.model_flops / global_flops if global_flops else 0.0
        return self

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def roofline_fraction(self) -> float:
        """How close the step is to the compute roofline: ideal compute
        time / achievable time (dominant term)."""
        ideal = self.model_flops / (self.chips * 197e12)
        return ideal / self.bound_s if self.bound_s else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "flops_per_chip": self.flops_per_chip,
            "bytes_per_chip": self.bytes_per_chip,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "model_flops": self.model_flops, "compute_s": self.compute_s,
            "memory_s": self.memory_s, "collective_s": self.collective_s,
            "dominant": self.dominant, "flops_ratio": self.flops_ratio,
            "roofline_fraction": self.roofline_fraction(),
            "collective_counts": self.collective_counts,
            "memory_per_chip": self.memory_per_chip,
        }


def analyze(compiled, *, arch: str, shape: str, mesh_name: str, chips: int,
            model_flops: float) -> RooflineReport:
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    stats = parse_collectives(compiled.as_text())
    mem = compiled.memory_analysis()
    mem_d = {
        "argument": float(getattr(mem, "argument_size_in_bytes", 0)),
        "output": float(getattr(mem, "output_size_in_bytes", 0)),
        "temp": float(getattr(mem, "temp_size_in_bytes", 0)),
        "generated_code": float(getattr(mem, "generated_code_size_in_bytes", 0)),
    }
    rep = RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_chip=flops, bytes_per_chip=bytes_acc,
        collective_bytes_per_chip=stats.bytes_per_chip,
        model_flops=model_flops, collective_counts=stats.counts,
        memory_per_chip=mem_d)
    return rep.finalize()


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (prefill) / 2*N_active per token
    (decode), N = active params (MoE counts routed experts only)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq
