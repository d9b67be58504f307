"""Arithmetic shared by the per-layer readers."""

from __future__ import annotations

from bench.peaks import peaks_for


def idle_percent(run: dict):
    """Share of the traced window in which no operation ran on the device."""
    red = run.get("trace")
    return None if red is None else 100.0 * red["idle_frac"]


def mfu_percent(run: dict):
    """Model FLOPs of the window over the chips' peak for its length."""
    if not run.get("model_flops") or not run.get("window_s"):
        return None
    peak = peaks_for(run["device_kind"])["flops_bf16"]
    return 100.0 * run["model_flops"] / (run["window_s"] * run["chips"] * peak)
