"""Machine models and ``lpf_probe`` — the paper's (p, g, l) introspection.

The paper requires ``lpf_probe`` so immortal algorithms can parametrise
themselves in (p, g, l).  Here ``probe`` returns an :class:`LPFMachine` per
mesh-axis group, derived from a hardware table (offline benchmark, paper
S4.1) — a Theta(1) table lookup, as the paper allows.  ``probe_online``
measures (g, l) on the current backend by timing total exchanges (paper
Table 3 methodology) and is used by ``benchmarks/hrelation.py``.

All bandwidths are bytes/second, latencies seconds, compute flop/second.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Mapping

__all__ = [
    "LinkModel",
    "HardwareModel",
    "LPFMachine",
    "TPU_V5E",
    "TPU_V5P",
    "CPU_HOST",
    "hardware_for",
    "probe",
    "axis_kind_default",
]


@dataclasses.dataclass(frozen=True)
class LinkModel:
    """One interconnect class (ICI axis, DCN pod link, ...)."""

    bw: float        # per-chip injection bandwidth over this link class (B/s)
    latency: float   # per-superstep launch/sync latency (seconds)


@dataclasses.dataclass(frozen=True)
class HardwareModel:
    """Static description of one chip + its interconnects."""

    name: str
    peak_flops_bf16: float
    peak_flops_fp32: float
    hbm_bw: float                      # bytes/s
    hbm_bytes: float                   # capacity per chip
    vmem_bytes: float                  # on-chip vector memory
    links: Mapping[str, LinkModel]     # kind -> link model ("ici", "dcn", "host")

    def __hash__(self) -> int:
        # the links mapping hashes by its items: equal models hash alike
        return hash((self.name, self.peak_flops_bf16, self.peak_flops_fp32,
                     self.hbm_bw, self.hbm_bytes, self.vmem_bytes,
                     tuple(sorted(self.links.items()))))

    def link(self, kind: str) -> LinkModel:
        if kind not in self.links:
            raise KeyError(f"{self.name} has no link class {kind!r}")
        return self.links[kind]


#: TPU v5e — the target platform for the production mesh (spec constants:
#: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI).  DCN per-chip
#: bandwidth and latencies are engineering assumptions, recorded here so the
#: cost model is explicit about them.
TPU_V5E = HardwareModel(
    name="tpu_v5e",
    peak_flops_bf16=197e12,
    peak_flops_fp32=98.5e12,
    hbm_bw=819e9,
    hbm_bytes=16e9,
    vmem_bytes=128 * 2**20,
    links={
        "ici": LinkModel(bw=50e9, latency=1e-6),
        "dcn": LinkModel(bw=12.5e9, latency=50e-6),
    },
)

TPU_V5P = HardwareModel(
    name="tpu_v5p",
    peak_flops_bf16=459e12,
    peak_flops_fp32=229.5e12,
    hbm_bw=2765e9,
    hbm_bytes=95e9,
    vmem_bytes=128 * 2**20,
    links={
        "ici": LinkModel(bw=100e9, latency=1e-6),
        "dcn": LinkModel(bw=25e9, latency=50e-6),
    },
)

#: The CPU container this repo is *validated* on (not the deployment target).
CPU_HOST = HardwareModel(
    name="cpu_host",
    peak_flops_bf16=5e10,
    peak_flops_fp32=5e10,
    hbm_bw=2e10,
    hbm_bytes=32e9,
    vmem_bytes=32 * 2**20,
    links={
        "ici": LinkModel(bw=5e9, latency=5e-6),
        "dcn": LinkModel(bw=1e9, latency=1e-4),
    },
)


#: ``jax.Device.device_kind`` -> hardware model.  A device kind missing
#: here has no known constants, and :func:`hardware_for` refuses it.
_BY_DEVICE_KIND: Mapping[str, HardwareModel] = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
    "cpu": CPU_HOST,
}


def hardware_for(device_kind: str) -> HardwareModel:
    """The :class:`HardwareModel` of a device, by its ``device_kind``.
    Raises ``KeyError`` for a kind this table does not know."""
    try:
        return _BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise KeyError(f"no hardware model for device kind "
                       f"{device_kind!r}; known: "
                       f"{sorted(_BY_DEVICE_KIND)}") from None


@dataclasses.dataclass(frozen=True)
class LPFMachine:
    """What ``lpf_probe`` returns: the BSP machine (p, g, l) + compute rate.

    ``g`` is seconds per *byte* of h-relation; ``l`` is seconds per
    superstep.  ``r`` is seconds per flop so that (g, l) can be normalised
    as in paper Table 3 (g x r-relative, l in word-times).
    """

    p: int
    g: float
    l: float
    r: float
    hardware: HardwareModel = TPU_V5E

    def t_comm(self, h_bytes: float, supersteps: int = 1) -> float:
        """BSP cost of communicating an h-relation: h*g + l per superstep."""
        return h_bytes * self.g + supersteps * self.l

    def normalised(self, word_bytes: int = 8) -> tuple[float, float]:
        """(g, l) in the paper's Table-3 units: g relative to memcpy speed r
        for one word, l in units of words."""
        g_norm = (self.g * word_bytes) / (self.r * word_bytes)
        l_norm = self.l / (self.g * word_bytes)
        return g_norm, l_norm


def axis_kind_default(axis_name: str) -> str:
    """Map a mesh axis name to an interconnect class."""
    return "dcn" if axis_name in ("pod", "dcn", "slice") else "ici"


def probe(
    axis_sizes: Mapping[str, int],
    hardware: HardwareModel = TPU_V5E,
    axis_kinds: Mapping[str, str] | None = None,
) -> LPFMachine:
    """``lpf_probe``: the BSP machine for a context spanning ``axis_sizes``.

    For a context over several axes the effective ``g`` is dominated by the
    slowest link class involved and the latency is the sum of the per-axis
    latencies (hierarchical supersteps execute per level).  Total-exchange
    bandwidth over a torus axis of size ``p`` scales the per-chip injection
    bandwidth by ``p/(p-1)`` locality loss, which we fold in as the paper's
    measured-g does.
    """
    if not axis_sizes:
        # Sequential LPF_ROOT context: communication is memcpy.
        return LPFMachine(p=1, g=1.0 / hardware.hbm_bw, l=0.0,
                          r=1.0 / hardware.peak_flops_fp32, hardware=hardware)
    axis_kinds = axis_kinds or {}
    p = 1
    worst_g = 0.0
    total_l = 0.0
    for name, size in axis_sizes.items():
        p *= int(size)
        if int(size) == 1:
            continue
        link = hardware.link(axis_kinds.get(name, axis_kind_default(name)))
        frac = (size - 1) / size  # fraction of traffic leaving the chip
        worst_g = max(worst_g, frac / link.bw)
        total_l += link.latency * max(1.0, math.log2(size))
    if worst_g == 0.0:
        worst_g = 1.0 / hardware.hbm_bw
    return LPFMachine(
        p=p,
        g=worst_g,
        l=total_l,
        r=1.0 / hardware.peak_flops_fp32,
        hardware=hardware,
    )
