"""The one span API (``repro.obs.trace.span``): profiler annotations with
an optional tracer record, the chain stages' and the optimizer's scopes in
the lowered step's HLO, and the planner's and the train step's gauges."""

import glob
import math
import time
from collections import Counter

import jax
import jax.numpy as jnp
import pytest

from repro.obs import metrics
from repro.obs.trace import Tracer, category_of, span

from helpers import make_mlp_chain


def _host_event_names(trace_dir) -> Counter:
    """Names of the host events with a duration in a profiler trace."""
    path = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(path)
    return Counter(e.name for plane in pd.planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events
                   if e.duration_ns > 0)


def test_span_without_tracer_times_the_block():
    with span("unit.block", step=3) as sp:
        time.sleep(0.002)
    assert sp.seconds >= 0.002


def test_span_records_into_an_enabled_tracer_only():
    tr = Tracer(name="t")
    with span("train.sync", tr, step=7) as sp:
        sp.note(bytes=12)
    with span("Fall", tr, arg=2, step=7):
        pass
    with span("ignored", Tracer(enabled=False)):
        pass
    assert [(s.op, s.arg, s.bytes) for s in tr.spans] == [
        ("train.sync", 7, 12), ("Fall", 2, None)]
    first = tr.spans[0]
    assert first.t_end - first.t_start == pytest.approx(sp.seconds)
    assert 0 <= first.t_start <= first.t_end <= tr.now()
    # the tracer's own method is the same helper
    with tr.span("B", 1, bytes=5):
        pass
    assert (tr.spans[-1].op, tr.spans[-1].arg, tr.spans[-1].bytes) == ("B", 1, 5)
    assert category_of("train.step") == "step"


def test_step_marker_span_keeps_its_name(tmp_path):
    tr = Tracer(name="t")
    with jax.profiler.trace(str(tmp_path)):
        for k in range(2):
            with span("train.step", tr, step=k, marks_step=True):
                with span("train.dispatch", step=k):
                    jnp.ones(4).block_until_ready()
    names = _host_event_names(tmp_path)
    assert names["train.step"] == 2 and names["train.dispatch"] == 2
    assert [s.arg for s in tr.spans] == [0, 1]


def test_span_costs_microseconds_with_no_profiler_session():
    span("warm").__enter__().__exit__(None, None, None)
    n = 5000
    t = time.perf_counter()
    for k in range(n):
        with span("train.dispatch", step=k):
            pass
    per_span = (time.perf_counter() - t) / n
    assert per_span < 100e-6, per_span


def test_tracer_spans_of_the_offload_walker_land_in_a_profiler_trace(tmp_path):
    from repro.core import profile_stages_measured
    from repro.plan import Budget, PlanRequest, build_plan

    stages, params, x = make_mlp_chain(4, seed=0)
    chain = profile_stages_measured(stages, params, x, repeats=1)
    plan = build_plan(PlanRequest(strategy="optimal",
                                  budget=Budget.fraction(0.6),
                                  num_slots=200), chain)
    tr = Tracer(name="test")
    with jax.profiler.trace(str(tmp_path)):
        plan.execute(stages, params, x, tracer=tr)
    kinds = Counter(k for k, _ in plan.schedule.ops)
    names = _host_event_names(tmp_path)
    assert {k: names[k] for k in kinds} == dict(kinds)
    assert Counter(s.op for s in tr.spans) == kinds


# ---------------------------------------------------------------------------
# the train step: scopes in its HLO, gauges of its plan and its compile
# ---------------------------------------------------------------------------

TINY = dict(num_layers=2, n_chunks=2, d_model=64, n_heads=4, n_kv_heads=2,
            d_ff=128, vocab_size=256, qkv_bias=True, mlp_kind="swiglu",
            dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
            scan_layer_remat="full", logits_chunk=64)


def _tiny_cfg():
    from repro.models.lm import ModelConfig

    return ModelConfig(name="tiny", **TINY)


@pytest.mark.parametrize("policy", ["none", "full"])
def test_lowered_train_step_names_stages_and_optimizer(policy):
    from repro.configs.shapes import ShapeSpec, input_specs
    from repro.distributed.sharding import DEFAULT_RULES, axis_rules
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_train_step, plan_training
    from repro.models.lm import StagedLM
    from repro.optim.adamw import AdamWConfig, adamw_init

    cfg = _tiny_cfg()
    model = StagedLM(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    with axis_rules(mesh, DEFAULT_RULES):
        specs = input_specs(cfg, ShapeSpec("train", "train", 32, 4))
        plan, _ = plan_training(model, specs, mesh, DEFAULT_RULES, policy)
        tree = plan.tree if plan is not None else None
        p = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        o = jax.eval_shape(adamw_init, p)
        step = jax.jit(make_train_step(model, AdamWConfig(), tree))
        hlo = step.lower(p, o, specs, jnp.int32(0)).as_text(debug_info=True)
    for scope in ("stage.embed", "stage.chunk0", "stage.chunk1",
                  "stage.head", "optimizer.adamw"):
        assert scope in hlo, scope


def test_gauges_after_a_tiny_run(monkeypatch):
    from repro.launch.steps import state_bytes
    from repro.models.lm import StagedLM
    from repro.runtime import train_loop
    from repro.runtime.train_loop import TrainLoopConfig, run_training

    compiled, backend_compiles = [], []
    record = train_loop._record_step_bytes

    def record_and_count(c):
        compiled.append(c)
        backend_compiles.clear()  # count from the ahead-of-time compile on
        record(c)

    monkeypatch.setattr(train_loop, "_record_step_bytes", record_and_count)
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k: backend_compiles.append(name)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    metrics.reset()
    cfg = _tiny_cfg()
    B, S = 4, 32
    out = run_training(cfg, TrainLoopConfig(steps=3, global_batch=B,
                                            seq_len=S, policy="rotor:auto",
                                            log_every=100),
                       log_fn=lambda _: None)
    assert len(compiled) == 1  # one compile, ahead of the first step
    # the jitted calls found that executable: nothing compiled after it
    assert backend_compiles == []
    ma = compiled[0].memory_analysis()
    assert metrics.value("train.step_bytes") == (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes) > 0
    plan = out["plan"]
    params_spec = jax.eval_shape(StagedLM(cfg).init, jax.random.PRNGKey(0))
    assert metrics.value("plan.planned_bytes") == pytest.approx(
        plan.peak_device_mem + state_bytes(params_spec, 1))
    assert metrics.value("plan.predicted_step_s") == plan.expected_time
    assert metrics.value("plan.chain_s") > 0
    assert metrics.value("plan.solve_s") > 0
    # the head's logits and dW, each made once (no replay of the logits)
    assert metrics.value("train.vocab_dots") == 2
    for name in ("plan.chain_s", "plan.solve_s", "plan.predicted_step_s",
                 "plan.planned_bytes", "train.step_bytes",
                 "train.vocab_dots"):
        assert metrics.registry().get(name).updates == 1, name
    # throughput of the steps after the first, over the time from the end
    # of the first to the end of the last: no faster than their own step
    # times allow, and finite
    tps = out["tokens_per_s"]
    assert math.isfinite(tps) and tps > 0
    assert tps <= B * S * 2 / sum(out["step_seconds"][1:]) * (1 + 1e-9)
    metrics.reset()


def test_one_step_run_reports_no_throughput():
    from repro.runtime.train_loop import TrainLoopConfig, run_training

    out = run_training(_tiny_cfg(), TrainLoopConfig(steps=1, global_batch=2,
                                                    seq_len=16,
                                                    policy="none"),
                       log_fn=lambda _: None)
    assert len(out["losses"]) == 1 and math.isnan(out["tokens_per_s"])


def test_offload_drift_reads_the_last_steps_schedule_ops(monkeypatch):
    """With the loop's own ``train.*`` spans in the same tracer, the drift
    report of a traced offload run is built from the last step's schedule
    ops alone, all of which lie inside that step's ``train.dispatch``."""
    from repro.configs import smoke_config
    from repro.obs import drift
    from repro.runtime.train_loop import TrainLoopConfig, run_training

    seen = []
    compare = drift.compare
    monkeypatch.setattr(drift, "compare",
                        lambda plan, tr: (seen.append(list(tr.spans)),
                                          compare(plan, tr))[1])
    cfg = smoke_config("qwen1.5-4b", num_layers=8,
                       layer_kinds=("dense",) * 8, n_chunks=8,
                       scan_layer_remat="full")
    tr = Tracer(name="train")
    out = run_training(cfg, TrainLoopConfig(
        steps=3, global_batch=2, seq_len=16,
        policy="optimal_offload:x0.6:1e15", log_every=100),
        log_fn=lambda _: None, tracer=tr)
    schedule = out["plan"].schedule
    assert out["plan"].uses_offload
    (used,) = seen
    assert out["drift"].span_count == len(schedule) == len(used)
    assert [(s.op, s.arg) for s in used] == [(k, int(l)) for k, l in schedule]
    last = [s for s in tr.spans if s.op == "train.dispatch"][-1]
    assert last.arg == 2
    assert all(last.t_start <= s.t_start <= s.t_end <= last.t_end
               for s in used)
    assert out["drift"].measured_makespan <= last.duration
