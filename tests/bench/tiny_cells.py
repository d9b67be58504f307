"""Tiny stand-ins of the benchmark's cells for CPU tests: the cells'
drivers, comparisons and limits, at widths a test run can hold."""

import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

MODEL = {"name": "tiny", "num_layers": 2, "n_chunks": 2, "d_model": 64,
         "n_heads": 4, "n_kv_heads": 2, "d_ff": 128, "vocab_size": 256,
         "qkv_bias": True, "mlp_kind": "swiglu", "rope_theta": 10000.0,
         "dtype": "bfloat16", "param_dtype": "bfloat16",
         "scan_layer_remat": "full", "logits_chunk": 64}
OPT = {"lr": 3e-4, "warmup": 0, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
       "weight_decay": 0.1, "clip_norm": 1.0}


def limits(cell: str) -> dict:
    return json.loads((ROOT / "bench" / "limits" / f"{cell}.json").read_text())


def train(cell: str = "qwen1.5-4b.train.fullmem") -> dict:
    return {"cell": {"name": cell, "chips": 1},
            "config": {"model": MODEL, "optimizer": OPT},
            "traffic": {"kind": "train", "seq_len": 32, "global_batch": 4,
                        "policy": "rotor:auto", "warm_steps": 4,
                        "check_steps": 3},
            "limits": limits(cell),
            "end_to_end": [{"name": "train_tokens_per_s", "unit": "tokens/s"},
                           {"name": "setup_s", "unit": "s"}],
            "per_layer": []}


def run(loaded: dict, seed: int = 2**31 + 7, seconds: float = 0.5) -> dict:
    """One run through the harness, past its look for a chip."""
    import jax

    from bench.run import run_cell

    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    return run_cell(loaded, args, jax.devices()[:1])
