"""Roofline analysis from compiled dry-run artifacts (deliverable g).

Three terms per (arch x shape x mesh), all in SECONDS:

  compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
  memory     = HLO_bytes_per_device / HBM_bandwidth
  collective = wire_bytes_per_device / ICI_link_bandwidth

Sources: ``compiled.cost_analysis()`` reports PER-DEVICE flops/bytes for
the partitioned module (verified empirically — a (16,64)@(64,128) matmul
over 8 devices reports 32768 = global/8 flops).  Collective bytes are NOT
in cost_analysis: we parse the optimized HLO, take each collective op's
per-device result-shard bytes, and convert to wire bytes with the standard
ring-algorithm factors.

Hardware peaks come from ``PEAKS``, keyed by the chip's ``device_kind``;
a device that is not in the table is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, List, Optional, Tuple

# Published per-chip peaks by ``jax.devices()[0].device_kind``.  TPU v5e
# ("TPU v5 lite"), Google Cloud documentation "TPU v5e": 197 TFLOP/s bf16,
# 819 GB/s HBM, 1,600 Gbit/s of chip-to-chip interconnect over its 4 ICI
# links (50 GB/s a link).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"   # the chip the dry-run meshes model


def peaks(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:\w+\[[\d,]*\]\S*))\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip()])
    return 2


# ring-algorithm wire factors, applied to the per-device RESULT bytes
def _wire_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op in ("all-gather", "reduce-scatter", "all-to-all"):
        return (n - 1) / n
    return 1.0  # collective-permute


@dataclasses.dataclass
class CollectiveStats:
    count: int = 0
    result_bytes: float = 0.0      # per-device shard bytes, summed over ops
    wire_bytes: float = 0.0        # ring-adjusted bytes on the wire

    def merge(self, other: "CollectiveStats") -> None:
        self.count += other.count
        self.result_bytes += other.result_bytes
        self.wire_bytes += other.wire_bytes


def parse_collectives(hlo_text: str) -> Dict[str, CollectiveStats]:
    """Per-op-kind collective statistics from optimized HLO text.

    ``-start`` ops are counted; their paired ``-done`` lines carry no shape
    of their own in the tuple position so double-count risk is low, but we
    also skip lines with ``-done(`` explicitly."""
    out: Dict[str, CollectiveStats] = {}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        shape_text, op = m.group(1), m.group(2)
        bytes_ = _shape_bytes(shape_text)
        n = _group_size(line)
        st = out.setdefault(op, CollectiveStats())
        st.count += 1
        st.result_bytes += bytes_
        st.wire_bytes += bytes_ * _wire_factor(op, n)
    return out


@dataclasses.dataclass
class Roofline:
    name: str
    device_kind: str                 # selects the PEAKS row
    chips: int
    flops_per_device: float
    bytes_per_device: float          # analytic TPU-fusion HBM traffic
    collective_wire_bytes: float     # per device
    collective_counts: Dict[str, int]
    memory_stats: Dict[str, float]
    model_flops: float = 0.0         # 6·N·D (train) or 2·N·D (inference)
    hlo_bytes_per_device: float = 0.0  # raw HLO-buffer bytes (cross-check;
    #                                    CPU fusion granularity inflates it)
    traffic_breakdown: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    def __post_init__(self):
        peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / peaks(self.device_kind)["flops"]

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / peaks(self.device_kind)["hbm_bw"]

    @property
    def collective_s(self) -> float:
        return (self.collective_wire_bytes
                / peaks(self.device_kind)["ici_bw"])

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_lower_bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (HLO flops x chips): how much compiled compute is
        'useful'.  <1 means remat/dispatch/padding overhead; >1 means the
        compiler found algebraic savings (rare) or the analytic model
        overcounts (e.g. SWA)."""
        total = self.flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    def to_dict(self) -> Dict:
        return {
            "name": self.name, "device_kind": self.device_kind,
            "chips": self.chips,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "hlo_bytes_per_device": self.hlo_bytes_per_device,
            "traffic_breakdown": self.traffic_breakdown,
            "collective_wire_bytes": self.collective_wire_bytes,
            "collective_counts": self.collective_counts,
            "memory_stats": self.memory_stats,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
            "useful_flops_ratio": self.useful_flops_ratio,
        }


def analyze_compiled(name: str, compiled, chips: int, device_kind: str, *,
                     model_flops: float = 0.0,
                     hlo_text: Optional[str] = None,
                     analytic_traffic=None) -> Roofline:
    from .hlo_cost import analyze_hlo_text
    text = hlo_text if hlo_text is not None else compiled.as_text()
    # trip-count-aware per-device costs (XLA's cost_analysis counts while
    # bodies once — see hlo_cost.py; kept in memory_stats as cross-ref)
    cost = analyze_hlo_text(text)
    flops = cost.flops
    hlo_bytes = cost.bytes
    bytes_ = analytic_traffic.total if analytic_traffic is not None \
        else cost.bytes
    wire = cost.coll_wire_bytes
    counts = {k: int(v) for k, v in cost.coll_counts.items()}
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    mem = {}
    for field in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
        mem[field] = float(getattr(ma, field, 0) or 0)
    mem["total_hbm_bytes"] = (mem["argument_size_in_bytes"]
                              + mem["output_size_in_bytes"]
                              + mem["temp_size_in_bytes"]
                              - mem["alias_size_in_bytes"])
    mem["xla_flops_once"] = float(ca.get("flops", 0.0))
    mem["xla_bytes_once"] = float(ca.get("bytes accessed", 0.0))
    return Roofline(name=name, device_kind=device_kind, chips=chips,
                    flops_per_device=flops,
                    bytes_per_device=bytes_, collective_wire_bytes=wire,
                    collective_counts=counts, memory_stats=mem,
                    model_flops=model_flops,
                    hlo_bytes_per_device=hlo_bytes,
                    traffic_breakdown=(analytic_traffic.to_dict()
                                       if analytic_traffic else {}))


def model_flops_estimate(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N_active·D for training, 2·N_active·D for inference
    (D = tokens processed; decode D = batch)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch     # decode: one token per sequence


def format_table(rows: List[Roofline]) -> str:
    hdr = (f"{'pair':42s} {'chips':>5s} {'compute_s':>10s} {'memory_s':>10s}"
           f" {'coll_s':>10s} {'dominant':>10s} {'useful':>7s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r.name:42s} {r.chips:5d} {r.compute_s:10.4g} "
            f"{r.memory_s:10.4g} {r.collective_s:10.4g} {r.dominant:>10s} "
            f"{r.useful_flops_ratio:7.3f}")
    return "\n".join(lines)
