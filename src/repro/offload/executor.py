"""Eager execution of offload schedules against real JAX arrays.

Mirrors ``core/executor.py`` (the paper-faithful op walker) and adds the two
host-tier ops:

- ``F_off^i``    → copy the live ``a^i`` into the host pool.  On an
  accelerator backend this is ``jax.device_put`` onto the CPU device (an
  async D2H DMA under JAX's effect ordering); on a CPU-only backend it is an
  explicit ``np.asarray`` materialization, so the copy is real either way.
  The device array is left untouched — it is consumed by the following
  ``F_∅``/``B`` exactly as the schedule says.
- ``Prefetch^i`` → pop the host copy and ``jax.device_put`` it back, donating
  the host buffer (its bytes are released from the pool on the spot).

The host pool is a :class:`repro.offload.host_buffer.HostBuffer`; pass one in
to bound host memory or read back byte-exact peak accounting.
"""

from __future__ import annotations

from contextlib import nullcontext
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.schedule import BWD, F_ALL, F_CK, F_NONE, F_OFF, PREFETCH, Schedule
from ..obs import metrics
from ..obs.trace import Tracer
from .host_buffer import HostBuffer


def _tree_bytes(tree: Any) -> int:
    total = 0
    for leaf in jax.tree.leaves(tree):
        nb = getattr(leaf, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


def default_host_device():
    """The CPU device to park offloaded copies on, or ``None`` when the
    default backend *is* the CPU (then host copies are numpy arrays, which
    live outside the device allocator and are still genuine copies)."""
    try:
        cpus = jax.devices("cpu")
    except RuntimeError:
        return None
    if jax.default_backend() == "cpu":
        return None
    return cpus[0]


def _to_host(value: Any, host_device):
    if host_device is not None:
        return jax.tree.map(lambda a: jax.device_put(a, host_device), value)
    # np.asarray may alias the device buffer on CPU backends; force a copy so
    # the "host tier" is genuinely distinct storage
    return jax.tree.map(lambda a: np.array(a, copy=True), value)


def _to_device(value: Any, device, donate: bool):
    if device is not None:
        return jax.tree.map(
            lambda a: jax.device_put(a, device, donate=donate), value)
    return jax.tree.map(jnp.asarray, value)


def execute_offload_schedule(
    schedule: Schedule,
    stages: Sequence[Any],
    params: Sequence[Any],
    x: Any,
    loss_cotangent: Any = None,
    track_live_bytes: bool = False,
    host_buffer: Optional[HostBuffer] = None,
    host_device=None,
    device=None,
    tracer: Optional[Tracer] = None,
) -> Tuple[Any, List[Any], Any]:
    """Run forward+backward per an offload-bearing ``schedule``.

    Same contract as ``core.executor.execute_schedule`` — returns
    ``(loss_output, param_grads, input_grad)`` plus, with
    ``track_live_bytes=True``, the empirical peak of the *device-side*
    saved-set in bytes.  Host-side bytes are accounted by ``host_buffer``
    (``host_buffer.peak_bytes`` after the run).

    ``tracer`` (opt-in) records one :class:`~repro.obs.trace.Span` per op —
    kind, op index, bytes produced/moved, wall time — fencing each op with
    ``jax.block_until_ready`` when ``tracer.sync`` so spans cover real
    device work; the untraced path is untouched.  Prefetch wall time (the
    schedule's synchronous stall) also lands in the
    ``offload.prefetch_stall_seconds`` metric.
    """
    L = schedule.length
    if host_buffer is None:
        host_buffer = HostBuffer()
    if host_device is None:
        host_device = default_host_device()
    if device is None and host_device is not None:
        device = jax.devices()[0]

    acts: Dict[int, Any] = {0: x}          # bare a^i values
    vjps: Dict[int, Any] = {}              # ā^l  (vjp closures)
    outs: Dict[int, Any] = {}              # stage outputs recorded by F_all
    deltas: Dict[int, Any] = {}
    grads: List[Any] = [None] * (L + 1)
    final_out = None
    peak_live = 0

    def get_act(i: int):
        if i in acts:
            return acts[i]
        if i in outs:  # a^i readable from ā^i (Table 1, second line)
            return outs[i]
        raise RuntimeError(f"a^{i} not available — invalid schedule")

    rec = tracer is not None and tracer.enabled
    for kind, l in schedule.ops:
        with (tracer.span(kind, int(l)) if rec else nullcontext()) as sp:
            produced = None     # value fenced before a traced span closes
            moved: Optional[int] = None
            if kind == F_OFF:
                i = int(l)
                if i not in acts:
                    raise RuntimeError(
                        f"Foff: a^{i} not live as a bare activation")
                host_copy = _to_host(acts[i], host_device)
                nbytes = _tree_bytes(host_copy)
                host_buffer.put(i, host_copy, nbytes=nbytes)
                if rec:
                    produced, moved = host_copy, nbytes
            elif kind == PREFETCH:
                i = int(l)
                if i in acts:
                    raise RuntimeError(f"Prefetch: a^{i} already on device")
                acts[i] = _to_device(host_buffer.pop(i), device, donate=True)
                if rec:
                    produced = acts[i]
                    moved = _tree_bytes(produced)
            elif kind in (F_NONE, F_CK, F_ALL):
                a_in = get_act(l - 1)
                if kind == F_ALL:
                    out, vjp_fn = jax.vjp(stages[l - 1], params[l - 1], a_in)
                    vjps[l] = vjp_fn
                    outs[l] = out
                    if l == L + 1:
                        final_out = out
                else:
                    out = stages[l - 1](params[l - 1], a_in)
                    acts[l] = out
                    if l == L + 1:
                        final_out = out
                if kind == F_NONE:
                    acts.pop(l - 1, None)
                if rec:
                    produced = out
                    moved = _tree_bytes(out)
            elif kind == BWD:
                if l == L + 1:
                    out = outs[l]
                    if loss_cotangent is not None:
                        delta = loss_cotangent
                    else:
                        delta = jax.tree.map(lambda o: jnp.ones_like(o), out)
                else:
                    delta = deltas.pop(l)
                dparams, da = vjps.pop(l)(delta)
                outs.pop(l, None)
                grads[l - 1] = (dparams if grads[l - 1] is None else
                                jax.tree.map(jnp.add, grads[l - 1], dparams))
                deltas[l - 1] = da
                acts.pop(l - 1, None)  # B^l consumes a^{l-1}
                if rec:
                    produced = (dparams, da)
            else:
                raise ValueError(f"offload executor cannot run op kind {kind}")
            live = None
            if track_live_bytes:
                live = (_tree_bytes(acts) + _tree_bytes(vjps)
                        + _tree_bytes(outs) + _tree_bytes(deltas))
                peak_live = max(peak_live, live)
            if rec:
                tracer.fence(produced)
                sp.note(bytes=moved,
                        host_mem=(float(host_buffer.bytes_in_use)
                                  if kind in (F_OFF, PREFETCH) else None),
                        device_mem=(float(live) if live is not None
                                    else None))
        if rec and kind == PREFETCH:
            # the prefetch is synchronous: its whole wall time is stall
            metrics.histogram(
                "offload.prefetch_stall_seconds").observe(sp.seconds)

    if 0 not in deltas:
        raise RuntimeError("schedule did not produce δ^0")
    if track_live_bytes:
        return final_out, grads, deltas[0], peak_live
    return final_out, grads, deltas[0]
