"""``BENCHMARK.json`` against the rules of its format, and the harness's
refusal to run without a chip."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert SPEC["command"][1] == "bench/run.py"


def test_names_units_and_sources():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def test_every_cell_finds_its_files_and_reports_enough():
    from bench.run import load_cell

    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    for cell in SPEC["workloads"]:
        assert cell["chips"] in (1, 4)
        loaded = load_cell(cell["name"], ROOT)
        kind = loaded["traffic"]["kind"]
        assert (ROOT / "bench" / "drivers" / f"{kind}.py").is_file()
        reported = {m["name"] for m in loaded["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert loaded["per_layer"], cell["name"]
        for m in loaded["per_layer"]:
            assert m["moves"] in reported
            assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e


def test_config_files_state_their_cut():
    for conf in SPEC["configs"]:
        f = json.loads((ROOT / conf["file"]).read_text())
        assert f["reduced"] == conf["reduced"]
        assert f["source"].startswith(conf["source"])
        for key in conf["reduced"]:
            assert key in f["published"]
        assert f["model"]["num_layers"] == f["num_hidden_layers"]


def test_no_tpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = SPEC["workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", name,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, 2**40 + 1])
def test_program_seed_is_31_bit_and_distinct(seed):
    from bench.run import program_seed

    s = program_seed(seed)
    assert 0 <= s < 2**31 and s == program_seed(seed)
    assert s != program_seed(seed + 2**32)
