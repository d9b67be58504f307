"""``impl="pallas"`` / ``impl="pallas_fused"`` parity: both Pallas DP fills
(``repro.kernels.dp_fill``) must produce **band-identical** cost tables to
the numpy banded fill (``impl="banded"``) in interpret mode, on the same
f32-exact chains ``tests/test_dp_kernels.py`` uses (integer stage costs,
dyadic transfer times — every DP quantity exactly representable in float32,
so equality is bit-exact, not approximate).

Interpret mode (set for every test by ``conftest.py``) executes the kernel
bodies in Python on CPU, so this suite runs in CPU CI and kernel
regressions no longer need a TPU to surface.  The
fused impl additionally carries a *single-dispatch* contract: one
``pallas_call`` per fill, no per-band host loop — asserted below via a
counting shim on ``pallas_call``.
"""

import math

import jax
import numpy as np
import pytest

from repro.core import dp_kernels, solver_cache
from repro.core.chain import Chain, HostTransferModel
from repro.core.schedule import Schedule, simulate
from repro.core.solver import solve_min_memory, solve_optimal
from repro.kernels.dp_fill import autotune
from repro.kernels.dp_fill import kernel as dpk
from repro.kernels.dp_fill import ops as dpo
from repro.kernels.dp_fill import ref as dpr
from repro.offload.solver import solve_optimal_offload
from repro.plan import PlanRequest, build_plan

from helpers import random_chain


#: Both Pallas two-tier fills behind one parametrization knob.
TWO_TIER_FILLS = {"pallas": dpo.fill_two_tier, "pallas_fused": dpo.fill_two_tier_fused}
OFFLOAD_FILLS = {"pallas": dpo.fill_offload, "pallas_fused": dpo.fill_offload_fused}


def _dyadic_host(rng) -> HostTransferModel:
    return HostTransferModel(
        bandwidth_d2h=float(rng.choice([0.5, 1.0, 4.0])),
        latency=float(rng.choice([0.0, 0.25])))


def _budgets(ch, fracs):
    peak = simulate(ch, Schedule.store_all(ch.length)).peak_mem
    return [float(math.ceil(peak * f)) for f in fracs]


# ---------------------------------------------------------------------------
# kernel-level parity vs the pure-jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,ns,w", [(1, 1, 4), (3, 5, 17), (7, 300, 33)])
def test_band_min_two_tier_matches_oracle(d, ns, w):
    rng = np.random.default_rng(d * 100 + ns)
    r = rng.uniform(0, 8, (d, ns, w)).astype(np.float32)
    lm = rng.uniform(-4, 4, (d, ns, w)).astype(np.float32)
    r[rng.uniform(size=r.shape) < 0.3] = np.inf   # out-of-budget sentinels
    out = dpk.band_min_two_tier(r, lm, interpret=True)
    exp = dpr.band_min_two_tier(r, lm)
    assert np.array_equal(np.asarray(out), np.asarray(exp))


def test_band_min_two_tier_row_tiling():
    """ns above the block size exercises the padded multi-tile grid path."""
    rng = np.random.default_rng(0)
    r = rng.uniform(0, 8, (4, 37, 9)).astype(np.float32)
    lm = rng.uniform(-4, 4, (4, 37, 9)).astype(np.float32)
    out = dpk.band_min_two_tier(r, lm, block_rows=16, interpret=True)
    exp = dpr.band_min_two_tier(r, lm)
    assert np.array_equal(np.asarray(out), np.asarray(exp))


@pytest.mark.parametrize("d,ns,w", [(1, 1, 4), (4, 23, 11)])
def test_band_min_offload_matches_oracle(d, ns, w):
    rng = np.random.default_rng(d * 10 + ns)

    def plane(lo, hi):
        return rng.uniform(lo, hi, (d, ns, w)).astype(np.float32)

    r, r3 = plane(0, 8), plane(0, 8)
    r[rng.uniform(size=r.shape) < 0.3] = np.inf
    r3[rng.uniform(size=r3.shape) < 0.3] = np.inf
    lmb, lme, lmb3 = plane(-4, 4), plane(-4, 4), plane(-4, 4)
    toff = rng.uniform(0, 6, (ns, 1)).astype(np.float32)
    outs = dpk.band_min_offload(r, r3, lmb, lme, lmb3, toff, interpret=True)
    exps = dpr.band_min_offload(r, r3, lmb, lme, lmb3, toff)
    for o, e in zip(outs, exps):
        assert np.array_equal(np.asarray(o), np.asarray(e))


# ---------------------------------------------------------------------------
# band-exact table agreement with impl="banded" on f32-exact chains
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fill", sorted(TWO_TIER_FILLS))
@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("allow_fall", [True, False])
def test_two_tier_tables_band_exact(seed, allow_fall, fill):
    rng = np.random.default_rng(seed)
    ch = random_chain(rng, max_len=5)
    for m in _budgets(ch, (0.4, 0.7, 1.0)):
        S = int(m)
        dchain = ch.discretize(m, S)
        band = dp_kernels.fill_two_tier(dchain, S, allow_fall=allow_fall)
        pall = TWO_TIER_FILLS[fill](dchain, S, allow_fall=allow_fall)
        assert np.array_equal(band.data, pall.data, equal_nan=True)


@pytest.mark.parametrize("fill", sorted(OFFLOAD_FILLS))
@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("allow_fall", [True, False])
def test_offload_tables_band_exact(seed, allow_fall, fill):
    rng = np.random.default_rng(100 + seed)
    ch = random_chain(rng, max_len=4).with_host(_dyadic_host(rng))
    for m in _budgets(ch, (0.4, 1.0)):
        S = int(m)
        dchain = ch.discretize(m, S)
        tb, te = dp_kernels.fill_offload(dchain, S, allow_fall=allow_fall)
        pb, pe = OFFLOAD_FILLS[fill](dchain, S, allow_fall=allow_fall)
        assert np.array_equal(tb.data, pb.data, equal_nan=True)
        assert np.array_equal(te.data, pe.data, equal_nan=True)


@pytest.mark.parametrize("fill", sorted(OFFLOAD_FILLS))
def test_offload_gather_path_band_exact(fill):
    """An activation bigger than the whole budget forces the non-sliced C3
    gather path in every fill."""
    ch = Chain.make(uf=[1.0, 1.0, 0.0], ub=[1.0, 1.0, 0.0],
                    wa=[1.0, 40.0, 1.0], wabar=[2.0, 2.0, 0.0],
                    host=HostTransferModel(bandwidth_d2h=1.0))
    dchain = ch.discretize(8.0, 8)
    tb, te = dp_kernels.fill_offload(dchain, 8)
    pb, pe = OFFLOAD_FILLS[fill](dchain, 8)
    assert np.array_equal(tb.data, pb.data, equal_nan=True)
    assert np.array_equal(te.data, pe.data, equal_nan=True)


# ---------------------------------------------------------------------------
# fused-fill edge cases: tiling, tiny chains, saturation, dispatch count
# ---------------------------------------------------------------------------

def test_fused_block_rows_not_dividing_band():
    """L not divisible by block_rows exercises masked partial row tiles."""
    rng = np.random.default_rng(5)
    ch = random_chain(rng, max_len=7)
    m = _budgets(ch, (0.6,))[0]
    S = int(m)
    dchain = ch.discretize(m, S)
    band = dp_kernels.fill_two_tier(dchain, S)
    for br in (1, 2, 3, 64):
        fus = dpo.fill_two_tier_fused(dchain, S, block_rows=br)
        assert np.array_equal(band.data, fus.data, equal_nan=True), br


def test_fused_single_stage_chain():
    """d = 1 is the smallest grid the fused recursion can run (L = 1)."""
    rng = np.random.default_rng(8)
    ch = random_chain(rng, max_len=1)
    assert ch.length == 1
    for S in (3, 12):
        dchain = ch.discretize(float(S), S)
        band = dp_kernels.fill_two_tier(dchain, S)
        fus = dpo.fill_two_tier_fused(dchain, S)
        assert np.array_equal(band.data, fus.data, equal_nan=True)
        tbb, teb = dp_kernels.fill_offload(dchain, S)
        tbf, tef = dpo.fill_offload_fused(dchain, S)
        assert np.array_equal(tbb.data, tbf.data, equal_nan=True)
        assert np.array_equal(teb.data, tef.data, equal_nan=True)


def test_fused_saturated_tails():
    """A budget far above every threshold saturates cap_d well below S: the
    fused fill computes the capped width and the host broadcasts a wide
    tail — bit-identical to banded with pruning on *and* off."""
    rng = np.random.default_rng(13)
    ch = random_chain(rng, max_len=4)
    S = 96  # weights in random chains are <= 5, so caps sit far below S
    dchain = ch.discretize(float(S), S)
    caps = dp_kernels.saturation_caps(dp_kernels._views(dchain), S)
    assert caps[-1] < S, "budget not saturating — test premise broken"
    band = dp_kernels.fill_two_tier(dchain, S)
    fus = dpo.fill_two_tier_fused(dchain, S)
    nop = dp_kernels.fill_two_tier(dchain, S, prune=False)
    assert np.array_equal(band.data, fus.data, equal_nan=True)
    assert np.array_equal(nop.data, fus.data, equal_nan=True)
    fus_nop = dpo.fill_two_tier_fused(dchain, S, prune=False)
    assert np.array_equal(nop.data, fus_nop.data, equal_nan=True)


@pytest.fixture
def dispatch_counter(monkeypatch):
    calls = []
    orig = dpk.pl.pallas_call

    def counting(*args, **kwargs):
        calls.append(1)
        return orig(*args, **kwargs)

    monkeypatch.setattr(dpk.pl, "pallas_call", counting)
    return calls


def test_fused_fill_is_single_dispatch(dispatch_counter):
    """The fused impl's contract: ONE pallas_call per fill — the per-band
    impl costs O(L) dispatches on the same problem."""
    rng = np.random.default_rng(21)
    ch = random_chain(rng, max_len=5)
    m = _budgets(ch, (0.6,))[0]
    S = int(m)
    dchain = ch.discretize(m, S)
    dpo.fill_two_tier_fused(dchain, S)
    assert len(dispatch_counter) == 1
    del dispatch_counter[:]
    dpo.fill_offload_fused(dchain, S)
    assert len(dispatch_counter) == 1
    del dispatch_counter[:]
    dpo.fill_two_tier(dchain, S)          # per-band: one launch per length
    assert len(dispatch_counter) == ch.length


def test_fused_solver_is_single_dispatch(dispatch_counter):
    """End to end through solve_optimal: the whole plan costs one device
    dispatch with impl="pallas_fused"."""
    rng = np.random.default_rng(22)
    ch = random_chain(rng, max_len=4)
    m = _budgets(ch, (0.7,))[0]
    sol = solve_optimal(ch, m, num_slots=int(m), impl="pallas_fused",
                        cache=False)
    assert sol.feasible
    assert len(dispatch_counter) == 1


# ---------------------------------------------------------------------------
# block_rows autotuner: persisted choice round-trip, corruption semantics
# ---------------------------------------------------------------------------

@pytest.fixture
def disk_cache(tmp_path):
    solver_cache.configure(directory=tmp_path)
    autotune._memo.clear()
    yield solver_cache.get_cache()
    autotune._memo.clear()
    solver_cache.reset()


def test_autotune_persists_choice(disk_cache):
    key = autotune.cache_key(10, 24, True)
    br = autotune.autotune_block_rows(10, 24, interpret=True,
                                      candidates=(2, 4))
    assert br in (2, 4)
    assert (disk_cache.directory / f"{key}.pkl").is_file()
    # second resolve is served from the store (no re-measure): poison
    # measure() and expect the cached answer
    import repro.kernels.dp_fill.autotune as at

    def boom(*a, **k):
        raise AssertionError("measured despite a persisted choice")

    orig = at.measure
    at.measure = boom
    try:
        assert autotune.autotune_block_rows(10, 24, interpret=True,
                                            candidates=(2, 4)) == br
    finally:
        at.measure = orig


def test_autotune_recalibrates_on_corrupted_entry(disk_cache):
    key = autotune.cache_key(10, 24, True)
    br = autotune.autotune_block_rows(10, 24, interpret=True,
                                      candidates=(2, 4))
    path = disk_cache.directory / f"{key}.pkl"
    path.write_bytes(b"\x00garbage, not a pickle")
    solver_cache.configure(directory=disk_cache.directory)  # drop the LRU
    autotune._memo.clear()                                  # fresh process
    br2 = autotune.autotune_block_rows(10, 24, interpret=True,
                                       candidates=(2, 4))
    assert br2 in (2, 4)
    # the corrupted entry was replaced by a readable one
    assert autotune._valid_entry(solver_cache.get_cache().get(key))


def test_autotune_rejects_wrong_shaped_entry(disk_cache):
    """A decodable pickle with the wrong shape (version skew) must also
    recalibrate — mirroring solver_cache's header semantics."""
    key = autotune.cache_key(10, 24, True)
    disk_cache.put(key, {"version": -1, "block_rows": "huge"})
    br = autotune.autotune_block_rows(10, 24, interpret=True,
                                      candidates=(2, 4))
    assert br in (2, 4)


def test_resolve_block_rows_env_pin(monkeypatch):
    monkeypatch.setenv("REPRO_DP_BLOCK_ROWS", "7")
    assert autotune.resolve_block_rows(100, 100, interpret=True) == 7
    monkeypatch.delenv("REPRO_DP_BLOCK_ROWS")
    monkeypatch.delenv("REPRO_DP_AUTOTUNE", raising=False)
    assert (autotune.resolve_block_rows(100, 100, interpret=True)
            == dpk.DEFAULT_BLOCK_ROWS)


def test_resolve_block_rows_rejects_garbage_pin(monkeypatch):
    """A mistyped pin must raise, not silently fall back to the default
    (matching the repo's strict size/budget parsing)."""
    monkeypatch.setenv("REPRO_DP_BLOCK_ROWS", "8x")
    with pytest.raises(ValueError, match="REPRO_DP_BLOCK_ROWS"):
        autotune.resolve_block_rows(100, 100, interpret=True)


def test_measure_dedupes_clamped_candidates():
    """Candidates above the calibration length collapse to one effective
    tile height — they must be measured once, and the stored winner must be
    a height that was actually run."""
    result = autotune.measure(10, 24, True, candidates=(2, 64, 128, 256))
    assert set(result["timings"]) <= {2, 10}   # effective heights only
    assert result["block_rows"] in result["timings"]


# ---------------------------------------------------------------------------
# solver / plan surface threading
# ---------------------------------------------------------------------------

PALLAS_IMPLS = ("pallas", "pallas_fused")


@pytest.mark.parametrize("impl", PALLAS_IMPLS)
@pytest.mark.parametrize("seed", range(3))
def test_solutions_match_banded(seed, impl):
    rng = np.random.default_rng(200 + seed)
    ch = random_chain(rng, max_len=5)
    for m in _budgets(ch, (0.5, 1.0)):
        S = int(m)
        b = solve_optimal(ch, m, num_slots=S, cache=False)
        p = solve_optimal(ch, m, num_slots=S, impl=impl, cache=False)
        assert b.feasible == p.feasible
        if not b.feasible:
            continue
        assert b.expected_time == p.expected_time
        res = simulate(ch, p.schedule, m + 1e-6)
        assert res.valid, res.error


@pytest.mark.parametrize("impl", PALLAS_IMPLS)
def test_min_memory_matches_banded(impl):
    rng = np.random.default_rng(42)
    ch = random_chain(rng, max_len=5)
    b = solve_min_memory(ch, num_slots=60, cache=False)
    p = solve_min_memory(ch, num_slots=60, impl=impl, cache=False)
    assert b.feasible == p.feasible
    if b.feasible:
        assert b.slots_used == p.slots_used
        assert b.expected_time == p.expected_time


@pytest.mark.parametrize("impl", PALLAS_IMPLS)
def test_offload_solution_matches_banded(impl):
    rng = np.random.default_rng(77)
    ch = random_chain(rng, max_len=4).with_host(_dyadic_host(rng))
    m = _budgets(ch, (0.6,))[0]
    S = int(m)
    b = solve_optimal_offload(ch, m, num_slots=S, cache=False)
    p = solve_optimal_offload(ch, m, num_slots=S, impl=impl, cache=False)
    assert b.feasible == p.feasible
    if b.feasible:
        assert b.expected_time == p.expected_time


@pytest.mark.parametrize("impl", PALLAS_IMPLS)
def test_plan_request_accepts_pallas(impl):
    rng = np.random.default_rng(9)
    ch = random_chain(rng, max_len=4)
    from repro.plan import Budget
    plan_b = build_plan(PlanRequest(strategy="optimal",
                                    budget=Budget.fraction(0.8),
                                    num_slots=40), ch)
    plan_p = build_plan(PlanRequest(strategy="optimal",
                                    budget=Budget.fraction(0.8),
                                    num_slots=40, impl=impl), ch)
    assert plan_p.expected_time == plan_b.expected_time
    assert plan_p.schedule.ops == plan_b.schedule.ops


def test_plan_request_rejects_unknown_impl():
    with pytest.raises(ValueError, match="unknown DP impl"):
        PlanRequest(strategy="optimal", impl="cuda")


def test_interpret_dispatch_default_is_backend_based():
    """The default is compiled dispatch, which raises off a TPU and names
    the backend: interpret mode is asked for, never chosen by backend."""
    assert jax.default_backend() != "tpu"
    dpo.set_interpret(False)
    try:
        with pytest.raises(RuntimeError,
                           match=f"backend is '{jax.default_backend()}'"):
            dpo.interpret_mode()
        ch = random_chain(np.random.default_rng(1), max_len=3)
        with pytest.raises(RuntimeError, match="set_interpret"):
            solve_optimal(ch, _budgets(ch, (0.8,))[0], num_slots=20,
                          impl="pallas_fused", cache=False)
    finally:
        dpo.set_interpret(True)
    assert dpo.interpret_mode() is True
