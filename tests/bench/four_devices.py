"""Cases that need four devices, each run in a process of its own with
``--xla_force_host_platform_device_count=4`` (the test process keeps its
one CPU device):

    python tests/bench/four_devices.py <reference|mesh|cell>

Each prints one JSON line with what it read."""

import json
import sys
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE), str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

MODEL = {"name": "tiny", "num_layers": 2, "n_chunks": 2, "d_model": 128,
         "n_heads": 4, "n_kv_heads": 2, "d_ff": 256, "vocab_size": 512,
         "qkv_bias": True, "mlp_kind": "swiglu", "rope_theta": 10000.0,
         "dtype": "bfloat16", "param_dtype": "bfloat16",
         "scan_layer_remat": "none", "logits_chunk": 0}


def _split(x) -> bool:
    """``x`` lies in four parts, one on each device."""
    sh = x.sharding
    return (len(sh.device_set) == 4
            and sh.shard_shape(x.shape) != tuple(x.shape))


def _placement(tree) -> list:
    """Per leaf: (has a dimension that 4 divides, split over 4 devices)."""
    import jax

    return [[any(s % 4 == 0 for s in x.shape), _split(x)]
            for x in jax.tree.leaves(tree)]


def reference() -> dict:
    """``follow`` over one device and over four, and the sharded state of
    a step of the four-device ``Trainer``."""
    import jax
    import numpy as np

    from bench.reference.data import batch_at
    from bench.reference.dense import Reference, Trainer, follow
    from tiny_cells import OPT

    key = jax.random.PRNGKey(5)
    bs = [batch_at(MODEL["vocab_size"], 4, 48, 9, k) for k in range(3)]
    lrs = [3e-4] * 3
    # small blocks, so that the query-block and token-block paths run
    ref = Reference(MODEL, q_block=16, tok_block=32)
    one = follow(ref, OPT, key, bs, lrs, devices=jax.devices()[:1])
    four = follow(ref, OPT, key, bs, lrs, devices=jax.devices()[:4])
    init_equal = all(
        isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
        and a.dtype == b.dtype and np.array_equal(a, b)
        for a, b in zip(jax.tree.leaves(one["init"]),
                        jax.tree.leaves(four["init"])))

    tr = Trainer(ref, OPT, devices=jax.devices()[:4])
    _, params = tr.init_state(key)
    zeros = tr._zeros(ref.split(params))  # the accumulators a step starts from
    _, grads = tr.loss_and_grad(params, bs[0])
    moments = [(np.zeros(x.shape, np.float32), np.zeros(x.shape, np.float32))
               for x in jax.tree.leaves(params)]
    stepped = tr.apply(params, grads, moments, 0, 3e-4)
    out = {"init_equal": init_equal,
           "params": _placement(params), "zeros": _placement(zeros),
           "grads": _placement(grads),
           "stepped": _placement(stepped)}
    for name, r in (("one", one), ("four", four)):
        out[name] = {"losses": [float(x) for x in r["losses"]],
                     "grad0": r["grad0"].tolist(),
                     "delta": r["delta"].tolist()}
    return out


def mesh() -> dict:
    """The mesh a four-chip cell's traffic file lays out."""
    import jax

    from bench.run import Context

    args = types.SimpleNamespace(seed=1, seconds=1.0, trace=0)

    def axes(traffic):
        loaded = {"cell": {"name": "c", "chips": 4}, "config": {"model": {}},
                  "traffic": traffic, "limits": {}}
        ctx = Context(loaded, args, jax.devices()[:4], None)
        try:
            return dict(ctx.mesh().shape)
        except ValueError as e:
            return str(e)

    return {"2x2": axes({"mesh": [2, 2]}), "default": axes({}),
            "3x1": axes({"mesh": [3, 1]})}


def cell() -> dict:
    """The tiny training cell on four chips laid out as data=2 x model=2,
    through the harness past its look for a chip."""
    import jax

    import tiny_cells
    from bench.run import run_cell

    loaded = tiny_cells.train()
    loaded["cell"]["chips"] = 4
    loaded["traffic"]["mesh"] = [2, 2]
    args = types.SimpleNamespace(seed=2**31 + 7, seconds=0.5, trace=0)
    res = run_cell(loaded, args, jax.devices()[:4])
    return {k: res[k] for k in ("correct", "attempted", "failed", "checks",
                                "device")}


if __name__ == "__main__":
    print(json.dumps({"reference": reference, "mesh": mesh,
                      "cell": cell}[sys.argv[1]]()))
