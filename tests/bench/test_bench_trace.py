"""The trace reduction: busy and idle time, top device operations and
idle gaps named by what the host was doing."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"

# Two chips over a window of 100 ns.  Chip 0 runs a [10, 20) and b [15, 30)
# (overlapping: busy [10, 30), 20 ns), a again [50, 60) (10 ns) and c
# [95, 110), of which [95, 100) is inside the window (5 ns): busy 35 ns.
# Chip 1 runs d [0, 50): busy 50 ns.  Busy averaged over the chips is
# 42.5 ns, an idle share of 0.575.  Chip 0's idle gaps are [60, 95) (35 ns,
# the host in "PjitFunction(step)"), [30, 50) (20 ns, in the benchmark's
# step callback) and [0, 10) (10 ns, overlapped by nothing but the window).
HAND = {
    "devices": {
        "/device:TPU:0": [[10, 10, "a"], [15, 15, "b"], [50, 10, "a"],
                          [95, 15, "c"]],
        "/device:TPU:1": [[0, 50, "d"]],
    },
    "modules": {"/device:TPU:0": [[10, 20, "jit_step"], [50, 10, "jit_step"],
                                  [95, 15, "jit_step"]]},
    "host": [[0, 100, "bench.window"], [28, 24, "bench.step_callback"],
             [55, 41, "PjitFunction(step)"], [200, 5, "later"]],
}


def test_busy_share_top_ops_and_gaps_by_hand():
    red = trace.reduce(HAND)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(42.5e-9)
    assert red["idle_frac"] == pytest.approx(0.575)
    assert red["devices"] == 2
    ops = dict(red["device_ops"])
    # per-op time summed over both chips, divided by their number
    assert ops["d"] == pytest.approx(25e-9)
    assert ops["a"] == pytest.approx(10e-9)
    assert ops["b"] == pytest.approx(7.5e-9)
    assert ops["c"] == pytest.approx(2.5e-9)
    assert [g[0] for g in red["idle_gaps"]] == [
        "PjitFunction(step)", "bench.step_callback", "unattributed"]
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx(
        [35e-9, 20e-9, 10e-9])


def test_module_runs_inside_the_window_only():
    n, secs = trace.module_runs(HAND, lambda name: name == "jit_step")
    assert (n, secs) == (2, pytest.approx(30e-9))


def test_no_window_or_no_device_work_reads_nothing():
    assert trace.reduce({"devices": HAND["devices"], "host": []}) is None
    assert trace.reduce({"devices": {}, "host": HAND["host"]}) is None


def test_union():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_recorded_v5e_trace():
    """A trace recorded on a v5e: three runs of one jitted program inside
    the benchmark's window span, a 5 ms sleep after each, as ``extract``
    read it from the profiler's file (host file paths cut from the Python
    event names)."""
    ex = trace.align(json.loads((DATA / "tiny_v5e.trace.json").read_text()))
    # device clock shifted so that no program starts before its launch:
    # the first launch is 1,226,333 ns after the first program's raw start
    assert ex["shift_ns"] == 1_226_333
    red = trace.reduce(ex)
    # per run: copy-start, copy-done and the fusion, by hand from the
    # events: [0,13)+[13,16) and [17,90214) -> 90,213 ns; then 13 + 3 +
    # 90,195 = 90,211 ns; then 14 + 3 + 90,197 = 90,214 ns
    busy_ns = 90_213 + 90_211 + 90_214
    assert red["window_s"] == pytest.approx(19_701_000e-9)
    assert red["busy_s"] == pytest.approx(busy_ns * 1e-9)
    assert red["idle_frac"] == pytest.approx(1 - busy_ns / 19_701_000)
    assert red["device_ops"][0][0] == "fusion bf16[]"
    assert red["device_ops"][0][1] == pytest.approx((90_197 * 2 + 90_195) * 1e-9)
    # the three longest idle gaps are the sleeps between the runs
    assert [g[0] for g in red["idle_gaps"][:3]] == ["$time sleep"] * 3
    n, secs = trace.module_runs(ex, lambda name: name == "jit__lambda")
    assert n == 3 and secs == pytest.approx(3 * 90_218e-9)
