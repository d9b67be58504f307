"""The `repro.plan` planning API and its policy-string back-compat shim.

Covers the redesign's acceptance criteria: every documented policy string
resolves through `repro.plan` to a bit-identical schedule and expected_time
as the pre-redesign resolution (inlined here as the reference), `MemoryPlan`
round-trips through disk and refuses a mismatched chain, budget parsing
rejects the garbage the old regex accepted, the offload-plan-as-tree error
has exactly one resolution path, and `num_slots`/`impl` thread uniformly
from every entry point."""

import dataclasses
import math

import numpy as np
import pytest

from repro.core.chain import Chain, HostTransferModel
from repro.core.policies import (make_policy_plan, make_policy_tree,
                                 parse_budget, policy_to_request)
from repro.core.schedule import Schedule, simulate
from repro.core.solver import solve_min_memory, solve_optimal, tree_to_schedule
from repro.plan import (Budget, DEFAULT_NUM_SLOTS, InfeasiblePlanError,
                        MemoryPlan, PlanRequest, StalePlanError, build_plan,
                        min_memory_plan, parse_size, register_solver, solver_for,
                        sweep, two_tier_fallback)

from helpers import make_mlp_chain, random_chain, tree_allclose


# ---------------------------------------------------------------------------
# budget / size parsing (satellite: harden _parse_size / parse_budget)
# ---------------------------------------------------------------------------

def test_parse_size_documented_forms():
    assert parse_size("1.5G") == 1.5e9
    assert parse_size("800M") == 8e8
    assert parse_size("2e9") == 2e9
    assert parse_size("1.5e9") == 1.5e9
    assert parse_size("123") == 123.0
    assert parse_size("0") == 0.0
    assert parse_size(".5K") == 500.0
    assert parse_size(" 4G ") == 4e9  # stray whitespace tolerated


@pytest.mark.parametrize("garbage", ["1e", "--5G", "", "G", "1..5", "x",
                                     "e9", "+5G", "-5G", "1.5GG", "nan",
                                     "inf", "0x10", "1,5G"])
def test_parse_size_rejects_garbage(garbage):
    """The old ``[\\d.eE+-]+`` regex accepted these and blew up in float()
    with a confusing message; now they fail fast with a clear one."""
    with pytest.raises(ValueError, match="expected a number|cannot parse"):
        parse_size(garbage)


def test_parse_budget_forms_and_errors():
    ch = Chain.homogeneous(4)
    peak = simulate(ch, Schedule.store_all(4)).peak_mem
    assert parse_budget("1.5G", None) == 1.5e9
    assert parse_budget("x0.5", ch) == 0.5 * peak
    assert parse_budget("0", None) == 0.0
    with pytest.raises(ValueError, match="profiled chain"):
        parse_budget("x0.5", None)
    with pytest.raises(ValueError, match="'x' followed by a number"):
        parse_budget("x", ch)
    with pytest.raises(ValueError, match="'x' followed by a number"):
        parse_budget("x--5", ch)
    with pytest.raises(ValueError, match="auto"):
        parse_budget("auto", ch)  # resolvable only through the launch path


def test_budget_dataclass():
    assert Budget.parse("x0.25") == Budget.fraction(0.25)
    assert Budget.parse("8G") == Budget.bytes(8e9)
    assert Budget.parse("auto") == Budget.auto()
    assert Budget.bytes(10).resolve() == 10.0
    assert Budget.fraction(0.5).resolve(store_all_peak=100.0) == 50.0
    assert Budget.auto().resolve(auto_budget=7.0) == 7.0
    assert Budget.auto().resolve(auto_budget=lambda: 9.0) == 9.0
    with pytest.raises(ValueError):
        Budget("parsecs", 1.0)
    with pytest.raises(ValueError):
        Budget.bytes(-1).resolve()


# ---------------------------------------------------------------------------
# back-compat: documented policy strings == pre-redesign resolution, bitwise
# ---------------------------------------------------------------------------

def _legacy_resolve(policy, chain, num_slots=500):
    """The pre-redesign ``core/policies.py`` resolution, inlined verbatim as
    the reference: returns ``(ops, expected_time | None, uses_offload)``."""
    from repro.core.rematerialize import (full_remat_tree, periodic_tree,
                                          sequential_tree)
    L = chain.length
    if policy == "none":
        return tree_to_schedule(sequential_tree(L), L).ops, None, False
    if policy == "full":
        return tree_to_schedule(full_remat_tree(L), L).ops, None, False
    if policy.startswith("periodic:"):
        t = periodic_tree(L, int(policy.split(":", 1)[1]))
        return tree_to_schedule(t, L).ops, None, False
    if policy.startswith(("rotor:", "revolve:")):
        kind, spec = policy.split(":", 1)
        if spec.startswith("x"):
            peak = simulate(chain, Schedule.store_all(L)).peak_mem
            budget = float(spec[1:]) * peak
        else:
            budget = parse_size(spec)
        sol = solve_optimal(chain, budget, num_slots=num_slots,
                            allow_fall=(kind == "rotor"))
        assert sol.feasible
        return tree_to_schedule(sol.tree, L).ops, sol.expected_time, False
    assert policy.startswith("optimal_offload")
    from repro.offload.solver import solve_optimal_offload, tree_uses_offload
    parts = policy.split(":")
    if parts[1].startswith("x"):
        peak = simulate(chain, Schedule.store_all(L)).peak_mem
        budget = float(parts[1][1:]) * peak
    else:
        budget = parse_size(parts[1])
    host = chain.host
    if len(parts) >= 3:
        bw = parse_size(parts[2])
        host = HostTransferModel(bandwidth_d2h=bw) if bw > 0 else None
    elif host is None:
        host = HostTransferModel.pcie_gen3()
    if host is None or not host.enabled:
        sol = solve_optimal(chain, budget, num_slots=num_slots)
        assert sol.feasible
        return sol.schedule.ops, sol.expected_time, False
    sol = solve_optimal_offload(chain.with_host(host), budget,
                                num_slots=num_slots)
    assert sol.feasible
    return sol.schedule.ops, sol.expected_time, tree_uses_offload(sol.tree)


def _compat_chain(seed):
    rng = np.random.default_rng(seed)
    ch = random_chain(rng, max_len=6)
    return ch.with_host(HostTransferModel(bandwidth_d2h=50.0, latency=0.1))


@pytest.mark.parametrize("policy", [
    "none", "full", "periodic:2", "periodic:3",
    "rotor:x0.8", "rotor:x1.0", "revolve:x1.0",
    "optimal_offload:x0.8", "optimal_offload:x0.8:100", "optimal_offload:x1.0:0",
])
@pytest.mark.parametrize("seed", [0, 3])
def test_policy_strings_bit_identical_to_legacy(policy, seed):
    """Acceptance criterion: every documented policy form resolves through
    `repro.plan` to exactly the schedule and makespan of the pre-redesign
    string path."""
    chain = _compat_chain(seed)
    ref_ops, ref_time, ref_off = _legacy_resolve(policy, chain)
    plan = make_policy_plan(policy, chain)
    assert plan.schedule.ops == ref_ops
    assert plan.uses_offload == ref_off
    if ref_time is not None:
        assert plan.solution.expected_time == ref_time  # bitwise
    # the underlying MemoryPlan agrees with itself
    mp = plan.plan
    assert mp.policy == policy
    assert mp.schedule.ops == ref_ops
    if not ref_off:
        # tree path produces the same ops through the same resolution
        tree = make_policy_tree(policy, chain)
        assert tree_to_schedule(tree, chain.length).ops == ref_ops


def test_rotor_infeasible_still_memoryerror():
    ch = _compat_chain(1)
    with pytest.raises(MemoryError):
        make_policy_tree("rotor:1", ch)  # 1 byte: infeasible
    with pytest.raises(InfeasiblePlanError):
        make_policy_plan("rotor:1", ch)  # the new exception IS a MemoryError


def test_unknown_policy_and_bad_segments():
    with pytest.raises(ValueError, match="unknown remat policy"):
        make_policy_tree("magic:1", None, length=4)
    with pytest.raises(ValueError, match="integer segment"):
        policy_to_request("periodic:x")


# ---------------------------------------------------------------------------
# offload-plan-as-tree: one resolution path, one error (satellite)
# ---------------------------------------------------------------------------

def _offload_bearing_chain():
    """A chain + budget whose three-tier optimum genuinely uses the host."""
    for seed in range(20):
        rng = np.random.default_rng(300 + seed)
        ch = random_chain(rng, max_len=5).with_host(
            HostTransferModel(bandwidth_d2h=1000.0))
        f2 = solve_min_memory(ch, num_slots=200)
        f3 = min_memory_plan(ch, tiers=("device", "host"), num_slots=200)
        if f3.budget_bytes < f2.mem_limit - 1e-9:
            return ch, 0.5 * (f3.budget_bytes + f2.mem_limit)
    raise AssertionError("no offload-bearing test chain found")


def test_offload_plan_requested_as_tree_raises():
    ch, budget = _offload_bearing_chain()
    policy = f"optimal_offload:{budget:.6e}"
    plan = make_policy_plan(policy, ch, num_slots=200)
    assert plan.uses_offload
    with pytest.raises(ValueError, match="nested remat cannot express"):
        make_policy_tree(policy, ch, num_slots=200)


def test_two_tier_fallback_degrades_offload_plan():
    ch, budget = _offload_bearing_chain()
    plan = build_plan(PlanRequest(strategy="optimal", budget=Budget.bytes(budget),
                                  tiers=("device", "host"), num_slots=200), ch)
    assert plan.uses_offload
    fb = two_tier_fallback(plan, ch)
    assert not fb.uses_offload and fb.remat_expressible
    # budget between the floors is two-tier-infeasible -> min-memory fallback
    assert fb.solution.feasible


# ---------------------------------------------------------------------------
# MemoryPlan: introspection, round-trip, stale-chain rejection
# ---------------------------------------------------------------------------

def test_plan_save_load_roundtrip(tmp_path):
    rng = np.random.default_rng(7)
    ch = random_chain(rng, max_len=6)
    peak = simulate(ch, Schedule.store_all(ch.length)).peak_mem
    plan = build_plan(PlanRequest(strategy="optimal",
                                  budget=Budget.bytes(peak), num_slots=100), ch)
    p = str(tmp_path / "plan.pkl")
    plan.save(p)
    loaded = MemoryPlan.load(p, chain=ch)
    assert loaded.schedule.ops == plan.schedule.ops
    assert loaded.expected_time == plan.expected_time
    assert loaded.chain_hash == plan.chain_hash
    assert loaded.request == plan.request
    # loading without a chain skips validation
    assert MemoryPlan.load(p).schedule.ops == plan.schedule.ops


def test_plan_load_rejects_stale_chain(tmp_path):
    rng = np.random.default_rng(8)
    ch = random_chain(rng, max_len=6)
    peak = simulate(ch, Schedule.store_all(ch.length)).peak_mem
    plan = build_plan(PlanRequest(strategy="optimal",
                                  budget=Budget.bytes(peak), num_slots=100), ch)
    p = str(tmp_path / "plan.pkl")
    plan.save(p)
    # any content change invalidates: a stage got slower
    uf2 = ch.uf.copy(); uf2[0] += 1.0
    changed = dataclasses.replace(ch, uf=uf2)
    with pytest.raises(StalePlanError, match="re-plan"):
        MemoryPlan.load(p, chain=changed)
    # ...or the host link changed
    hosted = ch.with_host(HostTransferModel(bandwidth_d2h=1.0))
    with pytest.raises(StalePlanError):
        MemoryPlan.load(p, chain=hosted)
    with pytest.raises(ValueError, match="not a saved MemoryPlan"):
        bad = str(tmp_path / "bad.pkl")
        import pickle
        with open(bad, "wb") as f:
            pickle.dump({"not": "a plan"}, f)
        MemoryPlan.load(bad)


def test_plan_summary_and_timeline():
    rng = np.random.default_rng(9)
    ch = random_chain(rng, max_len=6)
    peak = simulate(ch, Schedule.store_all(ch.length)).peak_mem
    plan = build_plan(PlanRequest(strategy="optimal",
                                  budget=Budget.bytes(0.8 * peak),
                                  num_slots=200), ch)
    s = plan.summary()
    assert "MemoryPlan" in s and "predicted" in s and "executor" in s
    tl = plan.timeline()
    assert len(tl) == len(plan.schedule.ops)
    assert tl[0]["t_start"] == 0.0
    assert abs(tl[-1]["t_end"] - plan.expected_time) < 1e-12
    assert all(r["t_end"] >= r["t_start"] for r in tl)
    stats = plan.stats()
    assert stats["executor"] == "jit-nested-remat"
    import json
    json.dumps(stats)  # JSON-serializable for dry-run artifacts


def test_structural_plans_without_chain():
    plan = build_plan(PlanRequest(strategy="periodic", segments=3), length=6)
    assert plan.chain is None and plan.chain_hash is None
    assert math.isnan(plan.expected_time)
    assert plan.remat_expressible
    with pytest.raises(ValueError, match="timeline"):
        plan.timeline()
    with pytest.raises(ValueError, match="need chain or length"):
        build_plan(PlanRequest(strategy="store_all"))
    with pytest.raises(ValueError, match="needs a profiled chain"):
        build_plan(PlanRequest(strategy="optimal", budget=Budget.bytes(1e9)))
    with pytest.raises(ValueError, match="needs a budget"):
        build_plan(PlanRequest(strategy="optimal"), Chain.homogeneous(3))


# ---------------------------------------------------------------------------
# sweep: the time-vs-budget frontier
# ---------------------------------------------------------------------------

def test_sweep_frontier_monotone():
    rng = np.random.default_rng(11)
    ch = random_chain(rng, max_len=6)
    # 1.1: ceil-discretization can make the exact store-all peak infeasible
    # (§5.2's 1+1/S overestimation) — grant the usual slack at the top point
    fracs = (0.3, 0.5, 0.7, 0.85, 1.1)
    pts = sweep(ch, fracs, PlanRequest(strategy="optimal", num_slots=200))
    assert [p.fraction for p in pts] == list(fracs)
    assert pts[-1].feasible  # with slack, store-all always admits a schedule
    times = [p.plan.expected_time for p in pts if p.feasible]
    assert all(a >= b - 1e-12 for a, b in zip(times, times[1:])), \
        "more memory can never make the optimum slower"
    # infeasible points are reported, not raised
    floor = min_memory_plan(ch, num_slots=200)
    tiny = sweep(ch, (0.001,), PlanRequest(strategy="optimal", num_slots=200))
    if floor.budget_bytes > 0.001 * simulate(
            ch, Schedule.store_all(ch.length)).peak_mem:
        assert not tiny[0].feasible


def test_sweep_offload_dominates_two_tier():
    ch = _compat_chain(5)
    fracs = (0.5, 0.75, 1.0)
    two = sweep(ch, fracs, PlanRequest(strategy="optimal", num_slots=200))
    three = sweep(ch, fracs, PlanRequest(strategy="optimal",
                                         tiers=("device", "host"),
                                         num_slots=200))
    for p2, p3 in zip(two, three):
        if p2.feasible:
            assert p3.feasible
            assert (p3.plan.expected_time
                    <= p2.plan.expected_time + 1e-9)


# ---------------------------------------------------------------------------
# num_slots / impl threading (satellite)
# ---------------------------------------------------------------------------

def test_num_slots_and_impl_thread_through_request():
    rng = np.random.default_rng(12)
    ch = random_chain(rng, max_len=5)
    peak = simulate(ch, Schedule.store_all(ch.length)).peak_mem
    plan = build_plan(PlanRequest(strategy="optimal",
                                  budget=Budget.bytes(peak),
                                  num_slots=123, impl="reference"), ch)
    assert plan.solution.num_slots == 123
    assert plan.request.resolved_num_slots == 123
    # default resolves to the single shared constant
    assert PlanRequest(strategy="optimal").resolved_num_slots \
        == DEFAULT_NUM_SLOTS
    # the shim threads it too (the old surface hard-coded 500)
    pp = make_policy_plan("rotor:x1.0", ch, num_slots=77)
    assert pp.solution.num_slots == 77
    # banded and reference kernels agree through the API
    ref = build_plan(PlanRequest(strategy="optimal", budget=Budget.bytes(peak),
                                 num_slots=123, impl="banded"), ch)
    assert ref.schedule.ops == plan.schedule.ops


def test_num_slots_threads_through_launch_planner():
    """launch/steps + TrainLoopConfig expose one knob that reaches the DP."""
    import jax
    from repro.configs import smoke_config
    from repro.configs.shapes import ShapeSpec, input_specs
    from repro.distributed.sharding import DEFAULT_RULES, axis_rules
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import plan_training
    from repro.models.lm import StagedLM
    from repro.runtime.train_loop import TrainLoopConfig

    cfg = smoke_config("qwen1.5-4b", num_layers=4, layer_kinds=("dense",) * 4,
                       n_chunks=4)
    model = StagedLM(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    shape = ShapeSpec("train", "train", 16, 2)
    with axis_rules(mesh, DEFAULT_RULES):
        batch_specs = input_specs(cfg, shape)
        plan, chain = plan_training(model, batch_specs, mesh, DEFAULT_RULES,
                                    "rotor:x0.9", num_slots=111)
    assert plan.solution.num_slots == 111
    # TrainLoopConfig carries the same knobs the loop hands to plan_training
    loop = TrainLoopConfig(num_slots=111, solver_impl="reference")
    assert loop.num_slots == 111 and loop.solver_impl == "reference"


# ---------------------------------------------------------------------------
# registry: the tier -> solver extension point
# ---------------------------------------------------------------------------

def test_registry_known_and_unknown_tiers():
    assert solver_for(("device",)).key == "device"
    assert solver_for(("device", "host")).key == "device+host"
    with pytest.raises(ValueError, match="no solver registered"):
        solver_for(("device", "nvme"))
    with pytest.raises(ValueError, match="already registered"):
        register_solver("device", lambda *a, **k: None,
                        lambda *a, **k: None)


def test_registry_custom_tier_plugs_in():
    """A new storage tier only needs a registry entry — build_plan picks it
    up with no other code changes."""
    calls = {}

    def fake_solve(chain, budget, *, num_slots, allow_fall, impl):
        calls["solve"] = (budget, num_slots, allow_fall, impl)
        return solve_optimal(chain, budget, num_slots=num_slots,
                             allow_fall=allow_fall, impl=impl)

    import repro.plan.registry as reg
    key = "device+nvme-test"
    try:
        register_solver(key, fake_solve, lambda *a, **k: None)
        entry = solver_for(("device", "nvme-test"))
        ch = Chain.homogeneous(4)
        peak = simulate(ch, Schedule.store_all(4)).peak_mem
        plan = build_plan(PlanRequest(strategy="optimal",
                                      budget=Budget.bytes(peak),
                                      tiers=("device", "nvme-test"),
                                      num_slots=50), ch)
        assert calls["solve"] == (peak, 50, True, None)
        assert plan.solution.feasible
    finally:
        reg._REGISTRY.pop(key, None)


# ---------------------------------------------------------------------------
# uniform executor binding
# ---------------------------------------------------------------------------

def test_bind_jit_remat_matches_reference():
    import jax
    from repro.core import profile_stages_measured, reference_grads

    stages, params, x = make_mlp_chain(5)
    chain = profile_stages_measured(stages, params, x, repeats=1)
    peak = simulate(chain, Schedule.store_all(5)).peak_mem
    plan = build_plan(PlanRequest(strategy="optimal",
                                  budget=Budget.bytes(0.6 * peak),
                                  num_slots=300), chain)
    bound = plan.bind(stages)
    assert bound.jittable
    out_ref, g_ref, dx_ref = reference_grads(stages, params, x)
    out, g, dx = bound.value_and_grad(params, x)
    tree_allclose(g, g_ref)
    tree_allclose(dx, dx_ref)
    # forward is a pure jit-able function on this path
    np.testing.assert_allclose(float(jax.jit(bound.forward)(params, x)),
                               float(out_ref), rtol=1e-6)
    # plan.execute always runs the faithful eager op sequence
    out2, g2, dx2 = plan.execute(stages, params, x)
    tree_allclose(g2, g_ref)


def test_bind_offload_eager_matches_reference():
    from repro.core import profile_stages_measured, reference_grads

    L = 6
    stages, params, x = make_mlp_chain(L)
    chain = profile_stages_measured(stages, params, x, repeats=1)
    bw = sum(chain.wa) / max(float(chain.uf.sum()), 1e-9)
    chain = chain.with_host(HostTransferModel(bandwidth_d2h=bw))
    peak = simulate(chain, Schedule.store_all(L)).peak_mem
    plan = build_plan(PlanRequest(strategy="optimal",
                                  budget=Budget.bytes(0.35 * peak),
                                  tiers=("device", "host"),
                                  num_slots=300), chain)
    assert plan.uses_offload and not plan.remat_expressible
    bound = plan.bind(stages)
    assert not bound.jittable
    out_ref, g_ref, dx_ref = reference_grads(stages, params, x)
    out, g, dx = bound.value_and_grad(params, x)
    tree_allclose(g, g_ref)
    tree_allclose(dx, dx_ref)
    np.testing.assert_allclose(float(bound.forward(params, x)),
                               float(out_ref), rtol=1e-6)


# ---------------------------------------------------------------------------
# MemoryPlan persistence: URI targets + component-named staleness
# ---------------------------------------------------------------------------


def _roundtrip_plan(seed=21):
    rng = np.random.default_rng(seed)
    ch = random_chain(rng, max_len=6)
    peak = simulate(ch, Schedule.store_all(ch.length)).peak_mem
    plan = build_plan(
        PlanRequest(
            strategy="optimal", budget=Budget.bytes(peak), num_slots=100
        ),
        ch,
    )
    return ch, plan


def test_plan_save_load_file_uri(tmp_path):
    ch, plan = _roundtrip_plan()
    uri = f"file://{tmp_path}/plan.bin"
    plan.save(uri)
    loaded = MemoryPlan.load(uri, chain=ch)
    assert loaded.schedule.ops == plan.schedule.ops
    assert loaded.expected_time == plan.expected_time


def test_plan_save_load_store_uri():
    from repro.store import config as store_config

    ch, plan = _roundtrip_plan()
    store_config.configure("memory://")
    try:
        uri = "store://plans/api-roundtrip"
        plan.save(uri)
        loaded = MemoryPlan.load(uri, chain=ch)
        assert loaded.schedule.ops == plan.schedule.ops
        with pytest.raises(FileNotFoundError):
            MemoryPlan.load("store://plans/never-written")
    finally:
        store_config.reset()


def test_stale_plan_error_names_diverged_component(tmp_path, monkeypatch):
    ch, plan = _roundtrip_plan()
    p = str(tmp_path / "plan.bin")
    plan.save(p)
    # chain divergence is named
    uf2 = ch.uf.copy()
    uf2[0] += 1.0
    with pytest.raises(StalePlanError, match="chain"):
        MemoryPlan.load(p, chain=dataclasses.replace(ch, uf=uf2))
    # request divergence is named
    other_req = dataclasses.replace(
        plan.request, budget=Budget.bytes(plan.budget_bytes * 0.5)
    )
    with pytest.raises(StalePlanError, match="request"):
        MemoryPlan.load(p, chain=ch, request=other_req)
    # code divergence (a solver edit since the save) is named
    from repro.core import solver_cache

    monkeypatch.setattr(solver_cache, "_code_fingerprint", "f" * 64)
    with pytest.raises(StalePlanError, match="code"):
        MemoryPlan.load(p, chain=ch)
