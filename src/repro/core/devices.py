"""Device peaks, keyed by ``device_kind`` — the one table the training chain
and the ``rotor:auto`` budget read.

A chain prices each stage as FLOPs over the peak FLOP/s of the device it is
planned for, and ``rotor:auto`` sizes its activation budget from that
device's memory.  Both look the device up here: a ``device_kind`` missing
from the table raises rather than borrowing another device's numbers.

The HBM size prefers what the backend itself reports
(``memory_stats()["bytes_limit"]``, the allocator's usable limit) and
falls back to the table's nominal capacity where the backend reports
nothing (the CPU, or a described-but-not-attached compile target).

Pure Python: importable without jax (the numpy-only ``core`` rule).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    flops_bf16: float      # dense bf16 FLOP/s per chip
    hbm_bw: float          # HBM bytes/s per chip
    hbm_bytes: int         # HBM capacity per chip (nominal)
    ici_bw: float          # inter-chip bytes/s per link
    source: str


#: The v5e's peaks; also the chip the production-mesh estimates target
#: (launch/roofline, launch/analytic, launch/dryrun).
V5E = DevicePeaks(
    flops_bf16=197e12, hbm_bw=819e9, hbm_bytes=16 * 1024 ** 3,
    # 1,600 Gbit/s of inter-chip interconnect per chip over 4 links
    ici_bw=50e9,
    source='Google Cloud documentation, "TPU v5e"')

#: ``device_kind`` (as JAX reports it) -> peaks.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": V5E,
    # The CPU backend plans with the v5e's numbers so that a CPU rehearsal
    # builds the chain the chip would run.
    "cpu": dataclasses.replace(
        V5E, source="nominal, for CPU tests; not a measurement"),
}


def peaks_for_kind(kind: str) -> DevicePeaks:
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(
            f"no peaks recorded for device_kind {kind!r}; add a row with its "
            f"source to repro.core.devices.DEVICE_PEAKS (known: "
            f"{sorted(DEVICE_PEAKS)})") from None


def device_peaks(device: Any) -> DevicePeaks:
    """Peaks of a ``jax.Device`` (looked up by its ``device_kind``)."""
    return peaks_for_kind(device.device_kind)


def hbm_bytes(device: Any) -> int:
    """Usable device memory: the backend's ``bytes_limit`` where it reports
    one, the table's nominal capacity otherwise."""
    try:
        stats = device.memory_stats()
    except RuntimeError:  # a described (compile-only) device: not queryable
        stats = None
    if stats and stats.get("bytes_limit"):
        return int(stats["bytes_limit"])
    return device_peaks(device).hbm_bytes
