"""Readings that a cell's correctness limits are set from: the control and
the planted faults, at the cell's own size, on the chip.

    python3 bench/controls.py --workload <name> --seeds <n> [<n> ...]

For a training cell, on each seed: the float32 reference follows the
cell's checked steps, and so do (a) the control, the same reference with
every matrix product in float8 (the precision below the configuration's
bfloat16), and (b) the reference with half of each batch left out of the
loss (the mean taken over the rest).  Each is compared with the float32
reference by the numbers of ``bench/reference/compare.py``, as the
program is.  A step that returns its state unchanged reads 1 on
``grad`` and ``update`` by construction and needs no run.

One JSON line per seed and reading goes to standard output.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def train_readings(loaded: dict, seed: int) -> list:
    import jax

    from bench.drivers.train import MAX_STEPS
    from bench.reference import compare
    from bench.reference.data import batch_at
    from bench.reference.dense import Reference, follow, lr_at
    from bench.run import program_seed

    m, opt, t = (loaded["config"]["model"], loaded["config"]["optimizer"],
                 loaded["traffic"])
    ps = program_seed(seed)
    B, S, n = int(t["global_batch"]), int(t["seq_len"]), int(t["check_steps"])
    batches = [batch_at(m["vocab_size"], B, S, ps, k) for k in range(n)]
    lrs = [lr_at(k, opt["lr"], opt["warmup"], MAX_STEPS) for k in range(n)]
    key = jax.random.PRNGKey(ps)

    def run(ref, bs):
        r = follow(ref, opt, key, bs, lrs)
        del r["init"]
        return r

    base = run(Reference(m), batches)
    half = []
    for b in batches:
        mask = b["loss_mask"].copy()
        mask[B // 2:] = 0.0
        half.append(dict(b, loss_mask=mask))
    out = []
    for what, r in (("control_fp8", run(Reference(m, quant="fp8"), batches)),
                    ("fault_half_batch", run(Reference(m), half))):
        nums = compare.train_numbers(r, base)
        out.append({"seed": seed, "reading": what,
                    **{k: nums[k] for k in ("loss", "grad", "update")},
                    "where": nums["where"]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.run import CACHE_DIR, load_cell, require_tpu

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    loaded = load_cell(args.workload)
    require_tpu(loaded["cell"]["chips"])
    for seed in args.seeds:
        for row in train_readings(loaded, seed):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
