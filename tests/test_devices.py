"""The device peaks table: rows keyed by ``device_kind``, a missing kind
raises, and the HBM size comes from the backend where it reports one."""

import pytest

from repro.core import devices


class FakeDevice:
    def __init__(self, kind, stats=None, queryable=True):
        self.device_kind = kind
        self._stats = stats
        self._queryable = queryable

    def memory_stats(self):
        if not self._queryable:
            raise RuntimeError("described device: memory_stats unavailable")
        return self._stats


def test_v5e_row_carries_its_source():
    p = devices.peaks_for_kind("TPU v5 lite")
    assert p is devices.V5E
    assert (p.flops_bf16, p.hbm_bw, p.hbm_bytes) == (197e12, 819e9, 16 << 30)
    assert "TPU v5e" in p.source


def test_cpu_row_is_marked_nominal():
    assert "not a measurement" in devices.peaks_for_kind("cpu").source


def test_missing_kind_raises():
    with pytest.raises(KeyError, match="'TPU v9'"):
        devices.device_peaks(FakeDevice("TPU v9"))


@pytest.mark.parametrize("stats,queryable,expected", [
    ({"bytes_limit": 16_909_336_064}, True, 16_909_336_064),
    ({}, True, 16 << 30),
    (None, True, 16 << 30),
    (None, False, 16 << 30),
], ids=["reported", "empty-stats", "no-stats", "described"])
def test_hbm_bytes_prefers_the_backend(stats, queryable, expected):
    dev = FakeDevice("TPU v5 lite", stats, queryable)
    assert devices.hbm_bytes(dev) == expected
