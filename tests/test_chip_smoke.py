"""``chip_smoke.py`` rehearsed on the CPU: its training and decode phases
end to end at the smoke config (with their own checks), its refusal to run
without a TPU, the sharded path on four virtual CPU devices (healthy, and
with faults its check must catch), and the compile-cache placement rule its
launchers share."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import smoke_config
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_and_decode_phases_at_smoke_size(chip_smoke):
    cfg = smoke_config("qwen1.5-4b")
    mesh = make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])
    loop = chip_smoke.train_loop_config(seq_len=64)
    out = chip_smoke.train_phase(cfg, mesh, loop)
    assert len(out["losses"]) == chip_smoke.STEPS
    assert len(out["step_seconds"]) == chip_smoke.STEPS
    assert out["plan"] is not None and out["plan"].budget_bytes > 0
    res = chip_smoke.decode_phase(cfg, out["params"], prompt_len=32,
                                  new_tokens=6)
    assert res["generations"].shape == (chip_smoke.PROMPTS, 6)
    # f32 smoke weights: the decode path matches the forward pass closely
    assert max(res["logit_rel_err"].values()) < 1e-4


def test_decode_check_catches_a_wrong_cache(chip_smoke, monkeypatch):
    """The decode-vs-forward check fails when the cached path is wrong."""
    from repro.models.lm import StagedLM

    cfg = smoke_config("qwen1.5-4b")
    params = StagedLM(cfg).init(jax.random.PRNGKey(0))
    orig = StagedLM.decode_step

    def lost_cache(self, params, cache, tokens):
        chunks = jax.tree.map(lambda x: x * 0, cache["chunks"])
        return orig(self, params, dict(cache, chunks=chunks), tokens)

    monkeypatch.setattr(StagedLM, "decode_step", lost_cache)
    with pytest.raises(chip_smoke.CheckFailed, match="first decode step"):
        chip_smoke.decode_phase(cfg, params, prompt_len=16, new_tokens=3)


def test_main_refuses_the_cpu(chip_smoke):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert "platform 'cpu'" in str(exc.value.code)


def test_script_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, SCRIPT], capture_output=True,
                         text=True, timeout=120, env=env, cwd=REPO)
    assert out.returncode != 0
    assert "platform 'cpu'" in out.stderr
    assert '"ok"' not in out.stdout


_LOAD_SCRIPT = f"""
import importlib.util, jax
import repro.launch.steps as steps
from repro.configs import smoke_config
spec = importlib.util.spec_from_file_location("cs", {SCRIPT!r})
cs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
assert len(jax.devices()) == 4
"""

_RUN_SHARDED = """
cs.sharded(jax.devices(), cfg=smoke_config("qwen1.5-4b"),
           loop=cs.train_loop_config(seq_len=32))
print("SHARDED_OK")
"""

#: Faults of the sharded path that its check must catch: the 2x2 mesh's
#: optimizer step is replaced (the one-chip mesh keeps the real one).
_FAULTS = {
    # the update is computed but never applied
    "update-dropped": """
def faulty(cfg, grads, opt, params, lr):
    _, new_opt, metrics = adamw(cfg, grads, opt, params, lr)
    return params, new_opt, metrics
""",
    # the gradient is reduced as a mean over 2 shards where a sum was due;
    # Adam's step is nearly scale-free, so the gradient norm must show it
    "gradient-halved": """
def faulty(cfg, grads, opt, params, lr):
    return adamw(cfg, jax.tree.map(lambda g: g / 2, grads), opt, params, lr)
""",
}

_INJECT = """
adamw, phase = steps.adamw_update, cs.train_phase
def train_phase(cfg, mesh, loop, tag="train"):
    steps.adamw_update = faulty if mesh.size == 4 else adamw
    return phase(cfg, mesh, loop, tag)
cs.train_phase = train_phase
"""


def _run_on_four_virtual_devices(code):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=560, env=env, cwd=REPO)


def test_sharded_path_on_four_virtual_devices():
    out = _run_on_four_virtual_devices(_LOAD_SCRIPT + _RUN_SHARDED)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    assert "SHARDED_OK" in out.stdout
    assert "[train 2x2] params on" in out.stdout


@pytest.mark.parametrize("fault", sorted(_FAULTS))
def test_sharded_check_catches(fault):
    out = _run_on_four_virtual_devices(
        _LOAD_SCRIPT + _FAULTS[fault] + _INJECT + _RUN_SHARDED)
    assert out.returncode != 0, out.stdout
    assert "sharded and one-chip training part" in out.stderr, out.stderr
    assert "SHARDED_OK" not in out.stdout


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing is set over it in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    expected = os.path.join(REPO, ".jax_cache")
    assert compile_cache.compile_cache_dir() == expected
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == expected
        assert jax.config.jax_compilation_cache_dir == expected
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_last_line_contract_is_json(chip_smoke, capsys, monkeypatch):
    """With the device check and the phases stubbed, ``main`` prints the
    one-line JSON result last."""

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(chip_smoke, "require_tpu", lambda n: [FakeTpu()] * n)
    monkeypatch.setattr(chip_smoke, "one_chip", lambda devices: None)
    monkeypatch.setattr(chip_smoke, "watch_compiles",
                        lambda: {"compile_s": 0.0, "cache_hits": 0})
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "x")
    monkeypatch.setattr(jax, "devices", lambda: [FakeTpu()])
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
