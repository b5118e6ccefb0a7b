"""DAMOV Step 3 re-based onto compiled XLA artifacts (TPU adaptation).

The paper classifies functions by *where their data movement stalls* using
architecture-dependent metrics gathered from simulation.  On TPU the
compiled HLO module plays the role of the instrumented binary:

- ``compiled.cost_analysis()``  -> FLOPs + HBM bytes (compute/memory terms)
- ``lowered.as_text()``         -> collective operand bytes (interconnect
  term; XLA's cost model does not expose these, so we parse the IR)

From these we derive the three roofline terms per (arch × shape × mesh)
cell and assign a DAMOV-style bottleneck class:

=================  ==========================================================
TPU class          DAMOV analogue
=================  ==========================================================
``compute``        Class 2c (compute-bound: MXU roof dominates)
``hbm``            Class 1a (DRAM-bandwidth-bound: HBM roof dominates)
``collective``     off-chip-link bound (the paper's I/O-pin argument, §1) —
                   mitigated by compute-near-shard placement, the cluster-
                   scale analogue of NDP
``latency``        Class 1b (small grids: per-op dispatch/DMA latency, not
                   any throughput roof, dominates)
=================  ==========================================================

The module also reports the paper's "useful-compute" hygiene ratio
MODEL_FLOPS / HLO_FLOPs (catching remat/redundant recompute) and an HLO
**reuse ratio** — HBM bytes / operand bytes touched — the LFMR analogue: a
value near 1 means fusion/VMEM residency is not capturing any reuse.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

__all__ = [
    "TPU_V5E",
    "PEAKS",
    "device_spec",
    "HardwareSpec",
    "CollectiveStats",
    "RooflineTerms",
    "collective_stats",
    "dtype_bytes",
]


@dataclass(frozen=True)
class HardwareSpec:
    name: str
    peak_flops: float          # per chip, bf16
    hbm_bw: float              # bytes/s per chip
    ici_bw: float              # bytes/s per link
    vmem_bytes: int = 128 * 2**20
    dispatch_latency_s: float = 3e-6   # per executed HLO "step" floor


# Published per-chip peaks of one TPU v5e (Google Cloud documentation,
# "TPU v5e"): 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of
# chip-to-chip interconnect (~50 GB/s per link).
TPU_V5E = HardwareSpec(
    name="tpu_v5e",
    peak_flops=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
)

# The peak table, keyed by jax's ``Device.device_kind``.
PEAKS: dict[str, HardwareSpec] = {"TPU v5 lite": TPU_V5E}


def device_spec(kind: str | None = None) -> HardwareSpec:
    """Peaks of the chip ``kind`` names (default: the device jax runs on,
    ``jax.devices()[0].device_kind``).  A kind missing from :data:`PEAKS`
    is an error: no peak is ever assumed."""
    if kind is None:
        import jax

        kind = jax.devices()[0].device_kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no peak table entry for device kind {kind!r}; known kinds: "
            f"{sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# `%name = <shapes> op-name(` — shapes may be a tuple.
_OP_RE = re.compile(
    r"=\s*(?P<shapes>[^=]*?)\s+(?P<op>"
    + "|".join(_COLLECTIVES)
    + r")(?:-start|-done)?\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def dtype_bytes(dt: str) -> int:
    return _DTYPE_BYTES.get(dt, 4)


def _shape_bytes(segment: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(segment):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    total_bytes: int = 0
    by_op: dict[str, int] = field(default_factory=dict)
    count: int = 0

    def add(self, op: str, nbytes: int) -> None:
        self.total_bytes += nbytes
        self.by_op[op] = self.by_op.get(op, 0) + nbytes
        self.count += 1


def collective_stats(hlo_text: str) -> CollectiveStats:
    """Sum result-shape bytes of every collective op in an HLO module.

    ``-start``/``-done`` pairs are deduplicated (the ``-done`` op repeats
    the payload shape); result bytes are used as the per-chip traffic proxy
    for all collective kinds, which is exact for all-gather/all-reduce
    outputs and within 2x for reduce-scatter/all-to-all — adequate for a
    roofline *term* (we care about the dominant-term identification, and
    errors are consistent across candidate implementations).
    """
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # counted at -start
        m = _OP_RE.search(line)
        if not m:
            continue
        nbytes = _shape_bytes(m.group("shapes"))
        if nbytes:
            stats.add(m.group("op"), nbytes)
    return stats


@dataclass
class RooflineTerms:
    """Three-term roofline for one (arch x shape x mesh) cell."""

    name: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    model_flops: float = 0.0
    # the chip the terms are taken against; default: the device jax runs on
    hw: HardwareSpec = field(default_factory=device_spec)
    n_ops: int = 0

    # ---- the three terms, in seconds ------------------------------------
    @property
    def t_compute(self) -> float:
        return self.hlo_flops / (self.chips * self.hw.peak_flops)

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / (self.chips * self.hw.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * self.hw.ici_bw)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "hbm": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)  # type: ignore[arg-type]

    @property
    def bottleneck_class(self) -> str:
        """DAMOV-style class for the compiled program (see module docstring).

        ``latency``: the whole step finishes in < ~100 us — per-op dispatch
        and DMA issue latency, not any throughput roof, governs (decode
        steps of small models land here; DAMOV Class-1b analogue)."""
        if self.t_bound < 100e-6:
            return "latency"
        return self.dominant

    # ---- hygiene ratios ---------------------------------------------------
    @property
    def useful_compute_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (catches remat/redundancy waste)."""
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def arithmetic_intensity(self) -> float:
        """FLOPs per HBM byte (the paper's AI analogue)."""
        return self.hlo_flops / self.hlo_bytes if self.hlo_bytes else 0.0

    @property
    def mfu_bound(self) -> float:
        """Best-case MFU implied by the roofline (useful flops / peak at
        the binding term)."""
        if self.t_bound <= 0:
            return 0.0
        return (self.model_flops or self.hlo_flops) / (
            self.t_bound * self.chips * self.hw.peak_flops
        )

    @property
    def roofline_fraction(self) -> float:
        """Compute-term share of the bound: 1.0 = perfectly compute-bound
        (at roofline); < 1 means HBM or ICI dominates."""
        return self.t_compute / self.t_bound if self.t_bound > 0 else 0.0

    def summary(self) -> dict[str, object]:
        return {
            "name": self.name,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "class": self.bottleneck_class,
            "model_flops": self.model_flops,
            "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "useful_compute_ratio": self.useful_compute_ratio,
            "arithmetic_intensity": self.arithmetic_intensity,
            "mfu_bound": self.mfu_bound,
            "roofline_fraction": self.roofline_fraction,
        }
