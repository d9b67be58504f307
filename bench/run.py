"""Run one benchmark cell on the chips JAX finds, and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything
about it is found by name: the configuration's file (``configs``, whose
``reference`` names its plain reference ``bench/reference/<name>.py``), the
traffic mix ``bench/traffic/<traffic>.json`` (whose ``kind`` names the
driver ``bench/drivers/<kind>.py``, and whose ``mesh`` lays the cell's
chips out as ``[data, model]``), the limits of its correctness check
``bench/limits/<workload>.json``, and each per-layer metric's reader
``bench/metrics/<metric>.py``.  A new cell, configuration, mix or metric
is new files and new entries; no file here changes.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
same window under the profiler, for a shorter time, and prints the
per-layer metrics and a breakdown of the trace.  The last line of standard
output is one JSON object; the numbers of the correctness check are also
the last lines of standard error.  With no TPU, or fewer chips than the
cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
#: JAX's persistent compilation cache: a fixed directory in the checkout
#: (the path is part of the cache key), set before JAX is imported.
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
#: Length of the traced window of a ``--trace 1`` run: a few steps, not
#: the whole measured window (traces are large and tracing slows the host).
TRACE_SECONDS = 4.0

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything a run of workload ``name`` needs, read from the files
    ``BENCHMARK.json`` names."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    # a per-layer metric without a cell list goes with its end-to-end metric
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return {
        "cell": cell,
        "config": json.loads((root / conf["file"]).read_text()),
        "traffic": json.loads((root / "bench" / "traffic"
                               / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((root / "bench" / "limits"
                              / f"{name}.json").read_text()),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def require_tpu(chips: int):
    """The first ``chips`` TPU devices; exits when there are none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, but JAX's first device is on "
                         f"platform {devices[0].platform!r}; there is no CPU "
                         f"fallback")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def program_seed(seed: int) -> int:
    """A 31-bit seed for the program's and the reference's PRNG keys and
    data, drawn from ``--seed`` (which may exceed 32 bits, whose high bits
    ``jax.random.PRNGKey`` would drop)."""
    import numpy as np

    return int(np.random.SeedSequence(seed).generate_state(1)[0] & 0x7FFFFFFF)


def model_config(model: dict):
    """The program's ``ModelConfig`` for a configuration's ``model`` section."""
    import jax.numpy as jnp

    from repro.models.lm import ModelConfig

    kw = dict(model)
    for k in ("dtype", "param_dtype"):
        kw[k] = getattr(jnp, kw[k])
    return ModelConfig(**kw)


class Compiles:
    """Backend compiles (persistent-cache hits included) as
    ``(perf_counter at the end, seconds)`` pairs, from JAX's monitoring
    events, for as long as the object is open."""

    def __init__(self):
        self.events = []

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), float(secs)))

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)

    def seconds(self) -> float:
        return sum(s for _, s in self.events)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.events if t0 < t <= t1)


class Context:
    """What a driver gets: the cell's files, the run's arguments, the
    devices, and the clock, trace and compile hooks of the harness."""

    def __init__(self, loaded: dict, args, devices, compiles: Compiles,
                 t0: float = T0):
        self.cell = loaded["cell"]
        self.name = self.cell["name"]
        self.config = loaded["config"]
        self.model = self.config["model"]
        self.traffic = loaded["traffic"]
        self.limits = loaded["limits"]
        self.prog_seed = program_seed(args.seed)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.devices = devices
        self.compiles = compiles
        self.t0 = t0
        self.trace_path = None
        self._window_span = None

    @property
    def window_seconds(self) -> float:
        return min(self.seconds, TRACE_SECONDS) if self.trace else self.seconds

    def model_config(self):
        return model_config(self.model)

    def mesh(self):
        """The cell's ``data`` x ``model`` mesh: the traffic file's
        ``"mesh": [data, model]``, else every chip on ``data``."""
        from repro.launch.mesh import make_mesh

        chips = self.cell["chips"]
        shape = tuple(self.traffic.get("mesh", (chips, 1)))
        if len(shape) != 2 or shape[0] * shape[1] != chips:
            raise ValueError(f"the traffic's mesh {list(shape)} is not "
                             f"[data, model] over the cell's {chips} chips")
        return make_mesh(shape, ("data", "model"), devices=self.devices)

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip, as the allocator reports
        it (0 on a backend that reports nothing, as the CPU in tests)."""
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in self.devices)

    def span(self, name: str):
        """A host span in the profiler's trace (a no-op when not tracing)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def window_open(self) -> float:
        """Start the window (and the trace); returns its start time."""
        if self.trace:
            import jax

            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            jax.profiler.start_trace(str(TRACE_DIR))
            self._window_span = jax.profiler.TraceAnnotation("bench.window")
            self._window_span.__enter__()
        return time.perf_counter()

    def window_close(self) -> float:
        """End the window (the caller has waited for its work); returns the
        end time, taken before the trace is written."""
        t = time.perf_counter()
        if self.trace and self._window_span is not None:
            import jax

            self._window_span.__exit__(None, None, None)
            self._window_span = None
            jax.profiler.stop_trace()
            found = glob.glob(str(TRACE_DIR / "**" / "*.xplane.pb"),
                              recursive=True)
            self.trace_path = max(found, key=os.path.getmtime) if found else None
        return t


def _metric_reader(name: str):
    """``read`` of ``bench/metrics/<name>.py`` (names hold dots, so the
    file is loaded by its path)."""
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(loaded: dict, args, devices) -> dict:
    """Drive one run and build its result line (the harness without its
    look for a chip)."""
    from bench import trace as tr

    driver = importlib.import_module(f"bench.drivers.{loaded['traffic']['kind']}")
    with Compiles() as compiles:
        ctx = Context(loaded, args, devices, compiles)
        out = driver.run(ctx)
    gc.collect()

    e2e_units = {m["name"]: m["unit"] for m in loaded["end_to_end"]}
    metrics = {}
    breakdown = None
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    if ctx.trace:
        ex = tr.extract(ctx.trace_path) if ctx.trace_path else None
        red = tr.reduce(ex) if ex else None
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        out["trace"], out["trace_events"] = red, ex
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"],
                         "idle_gaps": red["idle_gaps"]}
        else:
            device["busy_s"] = 0.0
            device["window_s"] = out["window_s"]
        out["compile_s"] = compiles.seconds()
        for m in loaded["per_layer"]:
            value = _metric_reader(m["name"])(out)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for name, unit in e2e_units.items():
            metrics[name] = {"value": out["e2e"][name], "unit": unit}
    checks = out["checks"]
    correct = (out["failed"] == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if out.get("worst_leaves"):
        result["worst_leaves"] = out["worst_leaves"]
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    loaded = load_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import repro  # the program under test, from this checkout

    where = [str(Path(p).resolve()) for p in repro.__path__]
    if where != [str(ROOT / "src" / "repro")]:
        raise SystemExit(f"bench: imported the program from {where}, not "
                         f"from this checkout")
    devices = require_tpu(loaded["cell"]["chips"])
    result = run_cell(loaded, args, devices)
    for name, c in result["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
