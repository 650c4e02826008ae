"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch x shape x mesh), in seconds (DESIGN.md §8):

  compute    = HLO_FLOPs_per_device / peak_FLOPs
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_bytes_per_device / ICI_link_bw

with the peaks of the device the program targets, looked up by its
``device_kind`` in :data:`DEVICE_PEAKS`.  A kind not in the table is an
error, never a default.

``cost_analysis()`` on the compiled executable is per-partition (verified
empirically in tests/test_roofline.py), matching the formulas'
"/ chips" with global quantities.  Collective bytes are not in
cost_analysis: we parse the post-SPMD HLO and sum result-shape bytes of
every collective op, doubling all-reduce (reduce-scatter + all-gather
wire-equivalent).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""
    flops: float            # bf16 FLOP/s
    hbm_bw: float           # HBM bytes/s
    ici_bw: float           # chip-to-chip interconnect bytes/s, all links
    ici_links: int          # ICI links per chip
    source: str

    @property
    def link_bw(self) -> float:
        """Bytes/s of one ICI link: a collective between two neighbours
        (one hop of a ring, one pair of a 2x2 all-to-all) uses one."""
        return self.ici_bw / self.ici_links


#: keyed by ``jax.Device.device_kind``
DEVICE_PEAKS = {
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8, ici_links=4,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI per chip over '
               '4 links'),
}

#: the chip the planner's priors describe (``tuning/cost_model.py``)
TARGET_DEVICE_KIND = "TPU v5 lite"


def peaks_for(device_kind: str) -> DevicePeaks:
    """The published peaks of ``device_kind``; raises on an unknown kind."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# `bf16[8,128]{1,0}` or scalar `f32[]`
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\]{},\s]+?)\s*"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)"
    r"\(", re.MULTILINE)


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_stats(hlo_text: str) -> dict:
    """Per-collective-kind {count, bytes} from post-SPMD HLO text."""
    stats: dict = {}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        kind = op.replace("-start", "")
        b = _shape_bytes(shape_str)
        if kind == "all-reduce":
            b *= 2  # reduce-scatter + all-gather wire equivalent
        e = stats.setdefault(kind, {"count": 0, "bytes": 0})
        e["count"] += 1
        e["bytes"] += b
    return stats


@dataclasses.dataclass
class RooflineTerms:
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float
    n_devices: int
    device_kind: str
    model_flops: float = 0.0       # 6*N*D (train) / 2*N_active*tokens (serve)

    def __post_init__(self):
        self.peaks = peaks_for(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_bytes_per_device / self.peaks.link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Optimistic (perfect-overlap) model: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (HLO flops summed over devices) — remat/
        redundancy waste shows up here."""
        total = self.flops_per_device * self.n_devices
        return self.model_flops / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the modeled step time."""
        t = self.step_time_s
        if not t:
            return 0.0
        return self.model_flops / (self.n_devices * self.peaks.flops * t)

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
            "n_devices": self.n_devices,
            "device_kind": self.device_kind,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time_s,
            "useful_flops_fraction": self.useful_flops_fraction,
            "mfu": self.mfu,
        }


def terms_from_compiled(compiled, n_devices: int, device_kind: str,
                        model_flops: float = 0.0) -> tuple:
    """(RooflineTerms, collective_stats dict, memory dict) against the
    peaks of ``device_kind``.

    Uses the trip-count-aware HLO analyzer (hlo_cost.py): XLA's own
    cost_analysis counts scan bodies once, undercounting layer-scanned
    models by O(depth).  The raw cost_analysis numbers ride along in the
    memory dict for cross-checking.
    """
    from repro.launch import hlo_cost

    ca = compiled.cost_analysis()
    hlo = compiled.as_text()
    cost = hlo_cost.analyze(hlo)
    ma = compiled.memory_analysis()
    mem = {
        "argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
        "output_bytes": getattr(ma, "output_size_in_bytes", 0),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
        "xla_flops_unscaled": float(ca.get("flops", 0.0)),
        "xla_bytes_unscaled": float(ca.get("bytes accessed", 0.0)),
    }
    terms = RooflineTerms(
        flops_per_device=cost.flops, bytes_per_device=cost.bytes,
        collective_bytes_per_device=cost.collective_bytes,
        n_devices=n_devices, device_kind=device_kind,
        model_flops=model_flops)
    return terms, cost.collectives, mem
