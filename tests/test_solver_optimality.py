"""The paper's Theorem 1: the DP computes the optimal *persistent* schedule.

Validated against exhaustive search (Dijkstra over the full Table-1 operation
space) on random small heterogeneous chains, with exact slot discretization.

The randomized half of the suite is property-based (``hypothesis``, a
declared dependency of the ``test`` extra and pinned in CI — always
exercised there): chain strategies draw heterogeneous integer-cost chains
(every DP quantity f32-exact) and assert, per drawn chain,

- two-tier DP optimality against brute force,
- offload-DP dominance (never slower than brute force at equal device
  budget) plus feasibility of the returned schedule under the simulator
  (device *and* host peaks within budget),
- band-exactness of the fused single-dispatch Pallas fill
  (``impl="pallas_fused"``) against the numpy banded fill, in interpret mode.

The hypothesis-driven tests carry ``@pytest.mark.slow`` — deselect locally
with ``-m "not slow"``; CI runs everything.  On an environment without
``hypothesis`` installed the property tests *skip visibly* (they never pass
vacuously) — install the ``test`` extra to run them.
"""

import math

import numpy as np
import pytest

from repro.core.bruteforce import optimal_time
from repro.core.chain import Chain, HostTransferModel
from repro.core.schedule import Schedule, simulate
from repro.core.solver import solve_min_memory, solve_optimal, tree_to_schedule

from helpers import random_chain

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover — CI always installs the test extra
    HAVE_HYPOTHESIS = False


def _check_chain(ch: Chain, fracs=(0.5, 0.75, 1.0)):
    sa = simulate(ch, Schedule.store_all(ch.length))
    assert sa.valid
    for frac in fracs:
        m = float(math.ceil(sa.peak_mem * frac))
        sol = solve_optimal(ch, m, num_slots=int(m))  # slot size exactly 1
        bf = optimal_time(ch, m + 1e-6, persistent_only=True)
        if not sol.feasible:
            assert not np.isfinite(bf), (
                f"DP infeasible but brute force found {bf}")
            continue
        res = simulate(ch, sol.schedule, m + 1e-6)
        assert res.valid, res.error
        # predicted time == simulated time (the model is exact)
        assert abs(res.time - sol.expected_time) < 1e-9
        # tree flattening reproduces the same schedule semantics
        res2 = simulate(ch, tree_to_schedule(sol.tree, ch.length), m + 1e-6)
        assert res2.valid and abs(res2.time - res.time) < 1e-9
        # optimality among persistent schedules
        assert abs(sol.expected_time - bf) < 1e-9, (
            f"DP={sol.expected_time} vs brute-force={bf} at m={m}")


@pytest.mark.parametrize("seed", range(12))
def test_dp_matches_bruteforce_random(seed):
    rng = np.random.default_rng(seed)
    _check_chain(random_chain(rng, max_len=4))


# ---------------------------------------------------------------------------
# property-based suite: randomized heterogeneous chains via hypothesis
# ---------------------------------------------------------------------------

if not HAVE_HYPOTHESIS:
    @pytest.mark.slow
    def test_property_suite_needs_hypothesis():
        pytest.importorskip("hypothesis")
else:
    @st.composite
    def chains(draw, max_len=4, max_cost=5, max_size=4):
        """A random heterogeneous chain with integer costs/sizes (f32-exact) —
        the same family the seeded tests use, but adversarially explored."""
        L = draw(st.integers(1, max_len))
        n = L + 1
        ints = lambda hi: st.lists(  # noqa: E731
            st.integers(1, hi), min_size=n, max_size=n)
        zeros = st.lists(st.integers(0, 1), min_size=n, max_size=n)
        return Chain.make(
            uf=draw(ints(max_cost)), ub=draw(ints(max_cost)),
            wa=draw(ints(max_size)), wabar=draw(ints(max_size + 2)),
            of=draw(zeros), ob=draw(zeros))


    @st.composite
    def hosts(draw):
        """Dyadic-rate host links so transfer times stay f32-exact."""
        return HostTransferModel(
            bandwidth_d2h=draw(st.sampled_from([0.25, 0.5, 1.0, 2.0, 4.0])),
            latency=draw(st.sampled_from([0.0, 0.25, 0.5])))


    @pytest.mark.slow
    @settings(max_examples=40, deadline=None)
    @given(ch=chains(), frac=st.sampled_from([0.4, 0.6, 0.8, 1.0]))
    def test_dp_matches_bruteforce_hypothesis(ch, frac):
        """Two-tier DP == brute force, plus simulator feasibility and
        tree/schedule agreement, on arbitrary drawn chains."""
        _check_chain(ch, fracs=(frac,))


    @pytest.mark.slow
    @settings(max_examples=30, deadline=None)
    @given(ch=chains(), host=hosts(), frac=st.sampled_from([0.5, 0.75, 1.0]))
    def test_offload_dp_dominates_bruteforce_hypothesis(ch, host, frac):
        """The offload DP is never slower than the *two-tier* brute-force
        optimum at equal device budget (extra tiers cannot hurt), and its
        schedule must simulate feasibly within both device and host budgets."""
        from repro.offload.solver import solve_optimal_offload

        hch = ch.with_host(host)
        sa = simulate(hch, Schedule.store_all(hch.length))
        m = float(math.ceil(sa.peak_mem * frac))
        sol = solve_optimal_offload(hch, m, num_slots=int(m))
        bf = optimal_time(ch, m + 1e-6, persistent_only=True)
        if not sol.feasible:
            # at equal device budget the offload DP dominates two-tier, so an
            # infeasible offload solve implies an infeasible two-tier problem
            assert not np.isfinite(bf)
            return
        assert sol.expected_time <= bf + 1e-9
        res = simulate(hch, sol.schedule, m + 1e-6,
                       host_mem_limit=float(np.inf))
        assert res.valid, res.error
        assert abs(res.time - sol.expected_time) < 1e-9


    @pytest.mark.slow
    @settings(max_examples=25, deadline=None)
    @given(ch=chains(max_len=5), frac=st.sampled_from([0.4, 0.7, 1.0]),
           allow_fall=st.booleans())
    def test_fused_fill_band_exact_hypothesis(ch, frac, allow_fall):
        """impl="pallas_fused" (interpret mode) is band-exact vs impl="banded"
        on any drawn f32-exact chain — the device-resident recursion as a
        hypothesis property, not just on seeded cases."""
        from repro.core import dp_kernels
        from repro.kernels.dp_fill import ops as dpo

        sa = simulate(ch, Schedule.store_all(ch.length))
        m = float(math.ceil(sa.peak_mem * frac))
        S = int(m)
        dchain = ch.discretize(m, S)
        band = dp_kernels.fill_two_tier(dchain, S, allow_fall=allow_fall)
        fused = dpo.fill_two_tier_fused(dchain, S, allow_fall=allow_fall)
        assert np.array_equal(band.data, fused.data, equal_nan=True)


    @pytest.mark.slow
    @settings(max_examples=15, deadline=None)
    @given(ch=chains(max_len=4), host=hosts(), allow_fall=st.booleans())
    def test_fused_offload_fill_band_exact_hypothesis(ch, host, allow_fall):
        from repro.core import dp_kernels
        from repro.kernels.dp_fill import ops as dpo

        hch = ch.with_host(host)
        sa = simulate(hch, Schedule.store_all(hch.length))
        S = int(math.ceil(sa.peak_mem * 0.7))
        dchain = hch.discretize(float(S), S)
        tb, te = dp_kernels.fill_offload(dchain, S, allow_fall=allow_fall)
        fb, fe = dpo.fill_offload_fused(dchain, S, allow_fall=allow_fall)
        assert np.array_equal(tb.data, fb.data, equal_nan=True)
        assert np.array_equal(te.data, fe.data, equal_nan=True)


def test_monotone_in_memory():
    """C_BP(1, L+1, m) is non-increasing in m."""
    rng = np.random.default_rng(3)
    ch = random_chain(rng, max_len=4)
    peak = simulate(ch, Schedule.store_all(ch.length)).peak_mem
    prev = np.inf
    for m in range(2, int(peak) + 2):
        sol = solve_optimal(ch, float(m), num_slots=m)
        if sol.feasible:
            assert sol.expected_time <= prev + 1e-9
            prev = sol.expected_time
    assert np.isfinite(prev)


def test_large_memory_recovers_store_all():
    ch = Chain.homogeneous(6)
    sol = solve_optimal(ch, 1000.0, num_slots=500)
    assert sol.feasible
    ideal = float(ch.uf.sum() + ch.ub.sum())
    assert abs(sol.expected_time - ideal) < 1e-9


def test_solve_min_memory():
    rng = np.random.default_rng(7)
    ch = random_chain(rng, max_len=4)
    sol = solve_min_memory(ch, num_slots=200)
    assert sol.feasible
    res = simulate(ch, sol.schedule, sol.mem_limit * (1 + 1e-6))
    assert res.valid, res.error
    # a budget meaningfully below the reported minimum must be infeasible
    slot = sol.mem_limit / sol.num_slots
    tight = solve_optimal(ch, sol.mem_limit - 3 * slot, num_slots=200)
    assert (not tight.feasible) or tight.expected_time >= sol.expected_time - 1e-9


def test_revolve_never_beats_optimal():
    rng = np.random.default_rng(11)
    for _ in range(8):
        ch = random_chain(rng, max_len=4)
        peak = simulate(ch, Schedule.store_all(ch.length)).peak_mem
        for frac in (0.6, 0.9):
            m = math.ceil(peak * frac)
            full = solve_optimal(ch, float(m), num_slots=int(m))
            rev = solve_optimal(ch, float(m), num_slots=int(m),
                                allow_fall=False)
            if rev.feasible:
                assert full.feasible
                assert full.expected_time <= rev.expected_time + 1e-9
