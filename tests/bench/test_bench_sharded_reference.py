"""The harness on a cell of four chips, on four virtual CPU devices: the
float32 reference with its state split over them agrees with the one on
one device, a traffic file's ``mesh`` lays the chips out, and a training
cell laid out as data=2 x model=2 comes out correct.  Each case runs in a
process of its own (``four_devices.py``).  Also: a configuration's
``reference`` key names the module that checks it."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT))

from bench.reference import compare  # noqa: E402

#: float32 at ``highest`` on one device and on four: the same products,
#: with sums split over the devices in another order.
REL = 1e-6


def four_devices(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=4"))
    p = subprocess.run([sys.executable, str(HERE / "four_devices.py"), case],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def sharded():
    return four_devices("reference")


def test_sharded_reference_matches_one_device(sharded):
    one, four = sharded["one"], sharded["four"]
    np.testing.assert_allclose(four["losses"], one["losses"], rtol=REL, atol=0)
    # norms against the reference's leaf or median leaf, as the check
    # compares them; the change over the leaves the gradient moves
    assert compare.leaf_gap(four["grad0"], one["grad0"])[0] <= REL
    keep = compare.moved_leaves(one["grad0"])
    assert compare.leaf_gap(four["delta"], one["delta"], keep)[0] <= REL


def test_sharded_reference_starts_from_the_same_weights_on_the_host(sharded):
    assert sharded["init_equal"]


@pytest.mark.parametrize("state", ["params", "zeros", "grads", "stepped"])
def test_sharded_reference_state_is_split_over_four_devices(sharded, state):
    leaves = sharded[state]
    assert leaves and all(split for divisible, split in leaves if divisible)


def test_traffic_mesh_lays_out_the_cells_chips():
    got = four_devices("mesh")
    assert got["2x2"] == {"data": 2, "model": 2}
    assert got["default"] == {"data": 4, "model": 1}
    assert "[3, 1]" in got["3x1"] and "4 chips" in got["3x1"]


def test_training_cell_on_a_2x2_mesh_is_correct():
    res = four_devices("cell")
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["count"] == 4


def test_configuration_names_its_reference():
    from bench.drivers.train import reference_module
    from bench.reference import dense

    assert reference_module({}) is dense
    assert reference_module({"reference": "dense"}) is dense
    for name in ("Reference", "follow", "lr_at", "train_flops_per_token"):
        assert callable(getattr(dense, name))
    with pytest.raises(FileNotFoundError,
                       match=r"bench/reference/no_such_model\.py"):
        reference_module({"reference": "no_such_model"})


def test_every_cell_finds_its_reference_and_lays_out_its_chips():
    from bench.drivers.train import reference_module
    from bench.run import load_cell

    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in spec["workloads"]:
        loaded = load_cell(cell["name"], ROOT)
        if loaded["traffic"]["kind"] == "train":
            assert callable(reference_module(loaded["config"]).follow)
        data, model = loaded["traffic"].get("mesh", [cell["chips"], 1])
        assert data * model == cell["chips"], cell["name"]
