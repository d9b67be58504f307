"""Roofline-term extraction from a compiled dry-run artifact (§Roofline).

- compute term     = per-device HLO FLOPs / 197 TFLOP/s (bf16, v5e)
- memory term      = per-device HLO bytes-accessed / 819 GB/s
- collective term  = per-device collective operand bytes / 50 GB/s per link

``cost_analysis`` gives FLOPs/bytes of the per-device SPMD module directly;
collective bytes are not in cost_analysis, so we parse the compiled HLO text
and sum operand sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute (including the async ``-start`` forms).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from ..core.devices import V5E

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute", "ragged-all-to-all")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)\s+"
    r"(" + "|".join(_COLLECTIVES) + r")(?:-start)?\(")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _group_size(line: str) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return max(1, m.group(1).count(",") + 1)
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(1, int(m.group(2)))  # [n_groups, group_size]<=[...]
    return 1


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Sum of operand bytes per collective kind (per-device module).

    Compiled-HLO operands are printed without inline shapes, so operand sizes
    are derived from the *result* shape: all-reduce / all-to-all /
    collective-permute results equal their operands; an all-gather result is
    ``group_size ×`` its operand; a reduce-scatter result is ``1/group_size``
    of its operand.
    """
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        result_bytes = 0
        for sm in _SHAPE_RE.finditer(line[m.start():m.end()]):
            result_bytes += _shape_bytes(sm.group(1), sm.group(2))
        g = _group_size(line)
        if kind == "all-gather":
            operand = result_bytes / g
        elif kind == "reduce-scatter":
            operand = result_bytes * g
        else:
            operand = result_bytes
        out[kind] += float(operand)
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


_DOT_RE = re.compile(
    r"=\s*[a-z0-9]+\[([0-9,]*)\][^ ]*\s+(?:dot|convolution)\(")


def dot_shapes_from_hlo(hlo_text: str) -> List[Tuple[int, ...]]:
    """Result shape of every ``dot``/``convolution`` instruction in the
    compiled HLO text, fused computations included (a loop body's matmul
    counts once, however many times the loop runs)."""
    return [tuple(int(d) for d in m.group(1).split(",") if d)
            for m in _DOT_RE.finditer(hlo_text)]


@dataclasses.dataclass
class Roofline:
    flops: float                 # per-device
    bytes_accessed: float        # per-device
    collective_bytes: float      # per-device
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float           # 6·N_active·D (global)
    useful_ratio: float          # MODEL_FLOPS / (HLO_FLOPs · chips)
    per_device_peak_bytes: Optional[float] = None
    collective_breakdown: Optional[Dict[str, float]] = None

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def analyze(compiled, n_devices: int, model_flops: float,
            hlo_text: Optional[str] = None) -> Roofline:
    ca = compiled.cost_analysis() or {}
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes_from_hlo(text)
    compute_s = flops / V5E.flops_bf16
    memory_s = byts / V5E.hbm_bw
    collective_s = coll["total"] / V5E.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    useful = model_flops / max(flops * n_devices, 1.0)
    peak = None
    try:
        ma = compiled.memory_analysis()
        peak = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        pass
    return Roofline(flops=flops, bytes_accessed=byts,
                    collective_bytes=coll["total"], compute_s=compute_s,
                    memory_s=memory_s, collective_s=collective_s,
                    dominant=dominant, model_flops=model_flops,
                    useful_ratio=useful, per_device_peak_bytes=peak,
                    collective_breakdown=coll)
