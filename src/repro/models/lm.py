"""Unified staged decoder-LM covering all assigned architecture families.

The model is organized as a **chain of stages** — [embed] + [layer-chunks] +
[head+loss] — which is exactly the structure the paper's checkpointing DP
consumes.  Each chunk is a ``lax.scan`` over its (stacked) layer parameters,
so compile size stays O(n_chunks) regardless of depth; rotor's remat tree is
applied *across* chunks (DESIGN.md §4).

Families are selected per-layer via ``layer_kinds``:
- ``dense``  — pre-norm attention (GQA/MQA/MLA per cfg) + MLP,
- ``moe``    — attention + shared/routed MoE,
- ``mamba``  — Mamba2 (SSD) mixer,
- ``zamba``  — Mamba2 layer; chunks aligned to ``hybrid_period`` also invoke
               the *shared* attention block (Zamba2) at chunk start.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import constrain
from . import attention as attn
from . import mamba2 as m2
from . import mlp as mlp_mod
from .common import (dense_apply, dense_init, rms_norm, rms_norm_init,
                     sinusoidal_positions, softmax_cross_entropy,
                     truncated_normal_init)

Params = Dict[str, Any]


def _scoped(name: str, fn):
    """``fn`` with the operations it traces under ``jax.named_scope(name)``."""

    def stage(*args):
        with jax.named_scope(name):
            return fn(*args)

    return stage


@dataclasses.dataclass(frozen=True)
class CacheLayout:
    """Byte layout of a decode cache (see :meth:`StagedLM.cache_layout`).

    - ``block_bytes[j]`` — allocated bytes of model layer ``j``'s cache
      slice: its KV block padded to ``max_len`` (attention layers) or its
      recurrent state (SSM layers); the Zamba2 shared-attention KV is
      attributed evenly to the period-start layers that invoke it.
    - ``token_bytes`` — bytes logically appended per decoded token across
      all attention layers (the cache's logical growth rate).
    - ``static_bytes`` — position-independent bytes (SSM conv/ssm states,
      the ``pos`` scalar).
    - ``allocated_bytes`` — total preallocated bytes; equals
      ``static_bytes + token_bytes * max_len`` exactly.
    """

    block_bytes: Tuple[int, ...]
    token_bytes: int
    static_bytes: int
    allocated_bytes: int
    max_len: int

    def logical_bytes(self, pos: int) -> int:
        """Bytes logically resident with ``pos`` tokens in the cache."""
        return self.static_bytes + int(pos) * self.token_bytes


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    num_layers: int = 4
    d_model: int = 256
    n_heads: int = 4
    n_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 1024
    head_dim: Optional[int] = None
    # attention
    attention_kind: str = "gqa"          # gqa | mla
    qkv_bias: bool = False
    use_rope: bool = True
    rope_theta: float = 10000.0
    query_scale: Optional[float] = None
    sliding_window: Optional[int] = None  # windowed attention (long-context)
    # mlp
    mlp_kind: str = "swiglu"             # swiglu | geglu | gelu
    # block pattern
    layer_kinds: Optional[Tuple[str, ...]] = None   # default: all "dense"
    # MoE
    num_experts: int = 0
    moe_top_k: int = 2
    moe_d_ff: int = 0
    num_shared_experts: int = 0
    moe_capacity_factor: float = 1.25
    moe_aux_loss: float = 0.01
    moe_norm_topk: bool = True
    # MLA
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # SSM (Mamba2)
    ssm_expand: int = 2
    ssm_state: int = 128
    ssm_head_dim: int = 64
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 128
    # hybrid (Zamba2)
    hybrid_period: int = 0               # shared attn block every N layers
    # modality
    modality: str = "text"               # text | audio_embed | vlm
    prefix_len: int = 0                  # VLM image-token prefix (bidirectional)
    embed_scale: bool = False            # Gemma: embeddings * sqrt(d)
    # numerics / execution
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.bfloat16
    n_chunks: int = 8
    scan_layer_remat: str = "none"       # none | full  (inner per-layer remat)
    remat_policy: str = "none"           # none|full|periodic:K|rotor:B|revolve:B
    use_flash_attention: bool = False
    use_ssd_kernel: bool = False
    logits_chunk: int = 0                # token-chunked xent if > 0
    z_loss: float = 0.0
    attn_block_q: int = 512              # q-block size of chunked attention
    kv_cache_dtype: Any = None           # e.g. jnp.float8_e4m3fn (serving)

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        if self.layer_kinds is None:
            object.__setattr__(self, "layer_kinds",
                               ("dense",) * self.num_layers)
        assert len(self.layer_kinds) == self.num_layers

    @property
    def kind_runs(self) -> List[Tuple[str, int, int]]:
        """Contiguous (kind, start, length) runs of identical layer kinds."""
        runs = []
        start = 0
        for i in range(1, self.num_layers + 1):
            if i == self.num_layers or self.layer_kinds[i] != self.layer_kinds[start]:
                runs.append((self.layer_kinds[start], start, i - start))
                start = i
        return runs

    @property
    def layer_slices(self) -> List[Tuple[int, int]]:
        """Per global layer ``j``: ``(chunk index, offset)`` into the stacked
        per-chunk parameter / decode-cache pytrees."""
        out: List[Tuple[int, int]] = []
        for ci, (kind, start, length) in enumerate(self.chunks):
            out.extend((ci, off) for off in range(length))
        return out

    @property
    def chunks(self) -> List[Tuple[str, int, int]]:
        """(kind, start, length) chunks — the rotor chain's interior stages.

        Chunks never cross kind boundaries; for Zamba2 they align with
        ``hybrid_period`` so each chunk owns at most one shared-attn call."""
        runs = self.kind_runs
        total = self.num_layers
        out: List[Tuple[str, int, int]] = []
        budget = max(self.n_chunks, len(runs))
        for kind, start, length in runs:
            if kind == "zamba" and self.hybrid_period:
                per = self.hybrid_period
                n = max(1, length // per)
            else:
                n = max(1, round(budget * length / total))
            n = min(n, length)
            base, extra = divmod(length, n)
            pos = start
            for j in range(n):
                size = base + (1 if j < extra else 0)
                out.append((kind, pos, size))
                pos += size
        return out

    def active_params(self) -> int:
        """Approximate active (per-token) parameter count, for 6ND math."""
        return _param_count(self, active_only=True)

    def total_params(self) -> int:
        return _param_count(self, active_only=False)


def _attn_params(cfg) -> int:
    if cfg.attention_kind == "mla":
        d, H = cfg.d_model, cfg.n_heads
        qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return (d * H * qd + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
                + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
                + H * cfg.v_head_dim * d)
    d, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return d * H * Dh + 2 * d * K * Dh + H * Dh * d


def _mlp_params(cfg, d_ff) -> int:
    mult = 3 if cfg.mlp_kind in ("swiglu", "geglu") else 2
    return mult * cfg.d_model * d_ff


def _mamba_params(cfg) -> int:
    d_inner = cfg.ssm_expand * cfg.d_model
    gn = cfg.ssm_groups * cfg.ssm_state
    d_proj = 2 * d_inner + 2 * gn + d_inner // cfg.ssm_head_dim
    return cfg.d_model * d_proj + d_inner * cfg.d_model

def _param_count(cfg, active_only: bool) -> int:
    total = 2 * cfg.vocab_size * cfg.d_model  # embed + head
    shared_attn = 0
    for kind in cfg.layer_kinds:
        if kind == "dense":
            total += _attn_params(cfg) + _mlp_params(cfg, cfg.d_ff)
        elif kind == "moe":
            ek = cfg.moe_top_k if active_only else cfg.num_experts
            total += _attn_params(cfg)
            total += ek * 3 * cfg.d_model * cfg.moe_d_ff
            total += cfg.num_shared_experts * 3 * cfg.d_model * cfg.moe_d_ff
        elif kind in ("mamba", "zamba"):
            total += _mamba_params(cfg)
    if cfg.hybrid_period and "zamba" in cfg.layer_kinds:
        shared_attn = _attn_params(cfg) + _mlp_params(cfg, cfg.d_ff)
        total += shared_attn  # shared params counted once ...
        if active_only:
            pass  # ... but applied every period; active == stored here
    return total


# ---------------------------------------------------------------------------
# per-layer blocks
# ---------------------------------------------------------------------------

def _block_init(key, cfg, kind: str) -> Params:
    dt = cfg.param_dtype
    ks = jax.random.split(key, 4)
    if kind == "dense":
        a_init = attn.mla_init if cfg.attention_kind == "mla" else attn.gqa_init
        return {"ln1": rms_norm_init(cfg.d_model, dt),
                "attn": a_init(ks[0], cfg, dt),
                "ln2": rms_norm_init(cfg.d_model, dt),
                "mlp": mlp_mod.mlp_init(ks[1], cfg.d_model, cfg.d_ff, dt,
                                        cfg.mlp_kind, cfg.num_layers)}
    if kind == "moe":
        a_init = attn.mla_init if cfg.attention_kind == "mla" else attn.gqa_init
        return {"ln1": rms_norm_init(cfg.d_model, dt),
                "attn": a_init(ks[0], cfg, dt),
                "ln2": rms_norm_init(cfg.d_model, dt),
                "moe": mlp_mod.moe_init(ks[1], cfg, dt)}
    if kind in ("mamba", "zamba"):
        return {"ln": rms_norm_init(cfg.d_model, dt),
                "mixer": m2.mamba2_init(ks[0], cfg, dt)}
    raise ValueError(kind)


def _block_axes(cfg, kind: str) -> Params:
    a_axes = (attn.mla_param_axes(cfg) if cfg.attention_kind == "mla"
              else attn.gqa_param_axes(cfg))
    if kind == "dense":
        return {"ln1": {"scale": (None,)}, "attn": a_axes,
                "ln2": {"scale": (None,)},
                "mlp": mlp_mod.mlp_param_axes(cfg.mlp_kind)}
    if kind == "moe":
        return {"ln1": {"scale": (None,)}, "attn": a_axes,
                "ln2": {"scale": (None,)},
                "moe": mlp_mod.moe_param_axes(cfg)}
    if kind in ("mamba", "zamba"):
        return {"ln": {"scale": (None,)}, "mixer": m2.mamba2_param_axes(cfg)}
    raise ValueError(kind)


def _positions(B: int, S: int, offset: int = 0) -> jax.Array:
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S)) + offset


def _train_mask(cfg, S: int) -> attn.MaskSpec:
    return attn.MaskSpec(causal=True, prefix_len=cfg.prefix_len,
                         window=cfg.sliding_window)


def _apply_block(p: Params, h: jax.Array, cfg, kind: str, mask, positions
                 ) -> Tuple[jax.Array, jax.Array]:
    """Returns (h, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("dense", "moe"):
        a_apply = attn.mla_apply if cfg.attention_kind == "mla" else attn.gqa_apply
        h = h + a_apply(p["attn"], cfg, rms_norm(p["ln1"], h), positions, mask)
        if kind == "dense":
            h = h + mlp_mod.mlp_apply(p["mlp"], rms_norm(p["ln2"], h), cfg.mlp_kind)
        else:
            y, aux = mlp_mod.moe_apply(p["moe"], cfg, rms_norm(p["ln2"], h))
            h = h + y
    else:  # mamba / zamba
        h = h + m2.mamba2_apply(p["mixer"], cfg, rms_norm(p["ln"], h))
    return h, aux


def _shared_attn_block(p: Params, cfg, h, mask, positions) -> jax.Array:
    h = h + attn.gqa_apply(p["attn"], cfg, rms_norm(p["ln1"], h), positions, mask)
    h = h + mlp_mod.mlp_apply(p["mlp"], rms_norm(p["ln2"], h), cfg.mlp_kind)
    return h


# ---------------------------------------------------------------------------
# the staged model
# ---------------------------------------------------------------------------

class StagedLM:
    """init/apply bundle; stages line up with the rotor chain."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- parameters -------------------------------------------------------

    def init(self, key) -> Params:
        cfg = self.cfg
        dt = cfg.param_dtype
        keys = jax.random.split(key, len(cfg.chunks) + 4)
        params: Params = {}
        if cfg.modality in ("text", "vlm"):
            params["embed"] = {"table": truncated_normal_init(
                keys[0], (cfg.vocab_size, cfg.d_model), dt, 1.0)}
        else:
            params["embed"] = {}  # audio stub delivers embeddings directly
        chunks = []
        for i, (kind, start, length) in enumerate(cfg.chunks):
            lk = jax.random.split(keys[i + 1], length)
            stacked = jax.vmap(lambda k: _block_init(k, cfg, kind))(lk)
            chunks.append(stacked)
        params["chunks"] = chunks
        if cfg.hybrid_period and any(k == "zamba" for k in cfg.layer_kinds):
            sk = jax.random.split(keys[-3], 2)
            params["shared_attn"] = {
                "ln1": rms_norm_init(cfg.d_model, dt),
                "attn": attn.gqa_init(sk[0], cfg, dt),
                "ln2": rms_norm_init(cfg.d_model, dt),
                "mlp": mlp_mod.mlp_init(sk[1], cfg.d_model, cfg.d_ff, dt,
                                        cfg.mlp_kind, cfg.num_layers)}
        params["final_norm"] = rms_norm_init(cfg.d_model, dt)
        params["head"] = dense_init(keys[-1], cfg.d_model, cfg.vocab_size, dt)
        return params

    def param_axes(self) -> Params:
        cfg = self.cfg
        axes: Params = {}
        if cfg.modality in ("text", "vlm"):
            axes["embed"] = {"table": ("vocab", "embed")}
        else:
            axes["embed"] = {}
        chs = []
        for kind, start, length in cfg.chunks:
            block = _block_axes(cfg, kind)
            chs.append(jax.tree.map(lambda ax: ("stack",) + tuple(ax), block,
                                    is_leaf=lambda x: isinstance(x, tuple)))
        axes["chunks"] = chs
        if cfg.hybrid_period and any(k == "zamba" for k in cfg.layer_kinds):
            axes["shared_attn"] = {
                "ln1": {"scale": (None,)}, "attn": attn.gqa_param_axes(cfg),
                "ln2": {"scale": (None,)},
                "mlp": mlp_mod.mlp_param_axes(cfg.mlp_kind)}
        axes["final_norm"] = {"scale": (None,)}
        axes["head"] = {"kernel": ("embed", "vocab")}
        return axes

    # -- stage functions (the rotor chain) ---------------------------------

    def n_stages(self) -> int:
        return len(self.cfg.chunks) + 2

    def stage_params(self, params: Params) -> List[Any]:
        cfg = self.cfg
        shared = params.get("shared_attn")
        sp: List[Any] = [params["embed"]]
        for i, _ in enumerate(cfg.chunks):
            if shared is not None:
                sp.append({"chunk": params["chunks"][i], "shared": shared})
            else:
                sp.append({"chunk": params["chunks"][i]})
        sp.append({"final_norm": params["final_norm"], "head": params["head"]})
        return sp

    def combine_stage_grads(self, stage_grads: List[Any]) -> Params:
        """Inverse of stage_params: rebuild a params-shaped gradient tree
        (summing the shared-attn contributions across chunks)."""
        cfg = self.cfg
        out: Params = {"embed": stage_grads[0]}
        chunk_grads, shared_sum = [], None
        for g in stage_grads[1:-1]:
            chunk_grads.append(g["chunk"])
            if "shared" in g:
                shared_sum = g["shared"] if shared_sum is None else jax.tree.map(
                    jnp.add, shared_sum, g["shared"])
        out["chunks"] = chunk_grads
        if shared_sum is not None:
            out["shared_attn"] = shared_sum
        out["final_norm"] = stage_grads[-1]["final_norm"]
        out["head"] = stage_grads[-1]["head"]
        return out

    def _embed_stage(self, p: Params, batch: Dict[str, jax.Array]) -> Dict:
        cfg = self.cfg
        if cfg.modality == "text":
            h = p["table"][batch["tokens"]].astype(cfg.dtype)
        elif cfg.modality == "audio_embed":
            emb = batch["embeds"].astype(cfg.dtype)
            S = emb.shape[1]
            h = emb + sinusoidal_positions(S, cfg.d_model).astype(cfg.dtype)[None]
        else:  # vlm: [image prefix] + [text tokens]
            img = batch["image_embeds"].astype(cfg.dtype)
            txt = p["table"][batch["tokens"]].astype(cfg.dtype)
            h = jnp.concatenate([img, txt], axis=1)
        if cfg.embed_scale:
            h = h * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
        h = constrain(h, "act_batch", "act_seq", "act_embed")
        return {"h": h, "aux": jnp.zeros((), jnp.float32),
                "labels": batch["labels"], "mask": batch.get("loss_mask")}

    def _chunk_stage(self, chunk_idx: int, p: Params, a: Dict) -> Dict:
        cfg = self.cfg
        kind, start, length = cfg.chunks[chunk_idx]
        h, aux = a["h"], a["aux"]
        B, S = h.shape[:2]
        mask = _train_mask(cfg, S)
        positions = _positions(B, S)

        if ("shared" in p and cfg.hybrid_period
                and start % cfg.hybrid_period == 0):
            h = _shared_attn_block(p["shared"], cfg, h, mask, positions)

        fn = functools.partial(_apply_block, cfg=cfg, kind=kind,
                               mask=mask, positions=positions)
        if cfg.scan_layer_remat == "full":
            fn = jax.checkpoint(fn)
        elif cfg.scan_layer_remat == "save_moe":
            # per-layer remat that pins the EP output: the backward replays
            # local compute but never re-runs the MoE all-to-alls
            fn = jax.checkpoint(
                fn, policy=jax.checkpoint_policies.save_only_these_names(
                    "moe_out"))

        def body(carry, lp):
            h, aux = carry
            h2, aux2 = fn(lp, h)
            return (h2, aux + aux2), None

        (h, aux), _ = jax.lax.scan(body, (h, aux), p["chunk"])
        h = constrain(h, "act_batch", "act_seq", "act_embed")
        return {"h": h, "aux": aux, "labels": a["labels"], "mask": a["mask"]}

    def _head_stage(self, p: Params, a: Dict) -> jax.Array:
        cfg = self.cfg
        h = rms_norm(p["final_norm"], a["h"])
        labels, mask = a["labels"], a["mask"]
        if cfg.modality == "vlm" and cfg.prefix_len:
            h = h[:, cfg.prefix_len:]
        if cfg.logits_chunk:
            from ..kernels.xent import ops as xent_ops
            loss = xent_ops.token_chunked_xent(h, p["head"]["kernel"], labels,
                                               mask, block=cfg.logits_chunk,
                                               z_loss=cfg.z_loss)
        else:
            logits = dense_apply(p["head"], h)
            logits = constrain(logits, "act_batch", "act_seq", "act_vocab")
            loss = softmax_cross_entropy(logits, labels, mask, cfg.z_loss)
        return loss + a["aux"]

    def stage_fns(self) -> List[Any]:
        """The chain's stage functions, each under a ``jax.named_scope``
        (``stage.embed``, ``stage.chunk<i>``, ``stage.head``) that names its
        operations, recomputed ones included, on the device."""
        fns: List[Any] = [_scoped("stage.embed", self._embed_stage)]
        for i in range(len(self.cfg.chunks)):
            fns.append(_scoped(f"stage.chunk{i}",
                               functools.partial(self._chunk_stage, i)))
        fns.append(_scoped("stage.head", self._head_stage))
        return fns

    # -- plain & rotor forward ---------------------------------------------

    def loss_fn(self, params: Params, batch: Dict, tree=None) -> jax.Array:
        """Full train loss; if ``tree`` (a rotor/remat schedule tree) is
        given, execute through the nested-checkpoint structure."""
        sp = self.stage_params(params)
        fns = self.stage_fns()
        if tree is None:
            a = batch
            for fn, p in zip(fns, sp):
                a = fn(p, a)
            return a
        from ..core.rematerialize import build_remat_fn
        f = build_remat_fn(tree, fns)
        return f(sp, batch)

    # -- logits forward (eval / serving prefill) ----------------------------

    def forward_logits(self, params: Params, batch: Dict) -> jax.Array:
        cfg = self.cfg
        a = self._embed_stage_nolabel(params["embed"], batch)
        sp = self.stage_params(params)
        for i in range(len(cfg.chunks)):
            a = self._chunk_stage(i, sp[i + 1], a)
        h = rms_norm(params["final_norm"], a["h"])
        return dense_apply(params["head"], h)

    def _embed_stage_nolabel(self, p, batch):
        b2 = dict(batch)
        B = (batch.get("tokens") if "tokens" in batch else batch["embeds"]).shape[0]
        b2.setdefault("labels", jnp.zeros((B, 1), jnp.int32))
        b2.setdefault("loss_mask", None)
        return self._embed_stage(p, b2)

    def prefill(self, params: Params, batch: Dict, max_len: Optional[int] = None
                ) -> Tuple[jax.Array, Dict]:
        """Process a full prompt; returns (last-position logits, decode cache)."""
        cfg = self.cfg
        a = self._embed_stage_nolabel(params["embed"], batch)
        h = a["h"]
        B, S = h.shape[:2]
        max_len = max_len or S
        mask = _train_mask(cfg, S)
        positions = _positions(B, S)

        def pad_kv(x):  # (B, S, ...) -> (B, max_len, ...), cache storage dtype
            x = x.astype(cache_dt)
            if max_len == S:
                return x
            pad = [(0, 0), (0, max_len - S)] + [(0, 0)] * (x.ndim - 2)
            return jnp.pad(x, pad)

        cache_dt = cfg.kv_cache_dtype or cfg.dtype
        cache: Dict = {"pos": jnp.asarray(S, jnp.int32), "chunks": []}
        shared_kvs = []
        for ci, (kind, start, length) in enumerate(cfg.chunks):
            pstack = params["chunks"][ci]
            if ("shared_attn" in params and cfg.hybrid_period
                    and kind == "zamba" and start % cfg.hybrid_period == 0):
                sp = params["shared_attn"]
                y, kv = attn.gqa_prefill(sp["attn"], cfg,
                                         rms_norm(sp["ln1"], h), positions, mask)
                h = h + y
                h = h + mlp_mod.mlp_apply(sp["mlp"], rms_norm(sp["ln2"], h),
                                          cfg.mlp_kind)
                shared_kvs.append(jax.tree.map(pad_kv, kv))

            def body(h, lp):
                if kind in ("dense", "moe"):
                    hn = rms_norm(lp["ln1"], h)
                    pf = attn.mla_prefill if cfg.attention_kind == "mla" else attn.gqa_prefill
                    y, kv = pf(lp["attn"], cfg, hn, positions, mask)
                    h = h + y
                    if kind == "dense":
                        h = h + mlp_mod.mlp_apply(lp["mlp"], rms_norm(lp["ln2"], h), cfg.mlp_kind)
                    else:
                        y2, _ = mlp_mod.moe_apply(lp["moe"], cfg, rms_norm(lp["ln2"], h))
                        h = h + y2
                    return h, jax.tree.map(pad_kv, kv)
                y, c = m2.mamba2_prefill(lp["mixer"], cfg, rms_norm(lp["ln"], h))
                return h + y, c

            h, cstack = jax.lax.scan(body, h, pstack)
            cache["chunks"].append(cstack)
        if shared_kvs:
            cache["shared"] = jax.tree.map(lambda *xs: jnp.stack(xs), *shared_kvs)
        h = rms_norm(params["final_norm"], h[:, -1:])
        logits = dense_apply(params["head"], h)
        return logits, cache

    # -- decode path --------------------------------------------------------

    def init_cache(self, batch: int, max_len: int) -> Dict:
        cfg = self.cfg
        cdt = cfg.kv_cache_dtype or cfg.dtype
        caches = []
        for kind, start, length in cfg.chunks:
            if kind in ("dense", "moe"):
                if cfg.attention_kind == "mla":
                    one = {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cdt),
                           "k_rope": jnp.zeros((batch, max_len, 1, cfg.qk_rope_head_dim), cdt)}
                else:
                    one = {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), cdt),
                           "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim), cdt)}
            else:
                one = m2.mamba2_init_cache(cfg, batch, cfg.dtype)
            caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (length,) + x.shape), one))
        out = {"chunks": caches, "pos": jnp.zeros((), jnp.int32)}
        if cfg.hybrid_period and any(k == "zamba" for k in cfg.layer_kinds):
            n_inv = sum(1 for kind, start, _ in cfg.chunks
                        if kind == "zamba" and start % cfg.hybrid_period == 0)
            out["shared"] = {
                "k": jnp.zeros((n_inv, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cfg.dtype),
                "v": jnp.zeros((n_inv, batch, max_len, cfg.n_kv_heads, cfg.head_dim), cfg.dtype)}
        return out

    def cache_layout(self, batch: int, max_len: int) -> "CacheLayout":
        """Byte layout of the decode cache, sized by ``jax.eval_shape`` over
        :meth:`init_cache` at the configured ``kv_cache_dtype`` (nothing is
        allocated).  This is the measurement base for the serve loop's KV
        telemetry and the sizing base for the KV-residency planner
        (:mod:`repro.plan.serving`)."""
        cfg = self.cfg
        spec = jax.eval_shape(lambda: self.init_cache(batch, max_len))

        def nbytes(tree) -> int:
            return int(sum(math.prod(leaf.shape) * leaf.dtype.itemsize
                           for leaf in jax.tree.leaves(tree)))

        blocks = [0] * cfg.num_layers
        token_bytes = 0
        static_bytes = nbytes(spec["pos"])
        for ci, (kind, start, length) in enumerate(cfg.chunks):
            chunk_bytes = nbytes(spec["chunks"][ci])
            per_layer = chunk_bytes // length
            for j in range(start, start + length):
                blocks[j] += per_layer
            if kind in ("dense", "moe"):
                token_bytes += chunk_bytes // max_len
            else:
                static_bytes += chunk_bytes  # recurrent state: no seq axis
        if "shared" in spec:
            shared_bytes = nbytes(spec["shared"])
            starts = [start for kind, start, _ in cfg.chunks
                      if kind == "zamba" and start % cfg.hybrid_period == 0]
            for s in starts:
                blocks[s] += shared_bytes // len(starts)
            token_bytes += shared_bytes // max_len
        return CacheLayout(block_bytes=tuple(blocks),
                           token_bytes=token_bytes,
                           static_bytes=static_bytes,
                           allocated_bytes=nbytes(spec),
                           max_len=max_len)

    def cache_axes(self) -> Dict:
        """Logical sharding axes for the decode cache (mirrors init_cache)."""
        cfg = self.cfg
        caches = []
        for kind, start, length in cfg.chunks:
            if kind in ("dense", "moe"):
                if cfg.attention_kind == "mla":
                    one = {"c_kv": ("act_batch", "act_kv_seq", None),
                           "k_rope": ("act_batch", "act_kv_seq", None, None)}
                else:
                    one = {"k": ("act_batch", "act_kv_seq", "act_kv", None),
                           "v": ("act_batch", "act_kv_seq", "act_kv", None)}
            else:
                one = {"conv": ("act_batch", None, "act_mlp"),
                       "ssm": ("act_batch", "act_ssm_heads", None, None)}
            caches.append(jax.tree.map(lambda ax: ("stack",) + tuple(ax), one,
                                       is_leaf=lambda x: isinstance(x, tuple)))
        out = {"chunks": caches, "pos": ()}
        if cfg.hybrid_period and any(k == "zamba" for k in cfg.layer_kinds):
            out["shared"] = {
                "k": ("stack", "act_batch", "act_kv_seq", "act_kv", None),
                "v": ("stack", "act_batch", "act_kv_seq", "act_kv", None)}
        return out

    def decode_step(self, params: Params, cache: Dict, tokens: jax.Array
                    ) -> Tuple[jax.Array, Dict]:
        """One greedy decode step. tokens: (B, 1) int32 (or embeds (B,1,d) for
        audio).  Returns (logits (B, 1, V), new cache)."""
        cfg = self.cfg
        pos = cache["pos"]
        B = tokens.shape[0]
        if cfg.modality == "audio_embed":
            # caller passes an embedding frame; add the sinusoidal positional
            # code for the (dynamic) current position — matches prefill
            h = tokens.astype(cfg.dtype)
            div = jnp.exp(jnp.arange(0, cfg.d_model, 2, dtype=jnp.float32)
                          * (-math.log(10000.0) / cfg.d_model))
            ang = pos.astype(jnp.float32) * div
            row = jnp.zeros((cfg.d_model,), jnp.float32)
            row = row.at[0::2].set(jnp.sin(ang)).at[1::2].set(jnp.cos(ang))
            h = h + row.astype(cfg.dtype)[None, None, :]
        else:
            h = params["embed"]["table"][tokens].astype(cfg.dtype)
        if cfg.embed_scale:
            h = h * jnp.asarray(math.sqrt(cfg.d_model), cfg.dtype)
        h = constrain(h, "act_batch", None, "act_embed")
        new_cache: Dict = {"pos": pos + 1, "chunks": []}
        shared_i = 0
        for ci, (kind, start, length) in enumerate(cfg.chunks):
            pstack = params["chunks"][ci]
            cstack = cache["chunks"][ci]
            if ("shared_attn" in params and cfg.hybrid_period
                    and kind == "zamba" and start % cfg.hybrid_period == 0):
                sc = {"k": cache["shared"]["k"][shared_i],
                      "v": cache["shared"]["v"][shared_i]}
                sp = params["shared_attn"]
                y, sc2 = attn.gqa_decode(sp["attn"], cfg,
                                         rms_norm(sp["ln1"], h), sc, pos)
                h = h + y
                h = h + mlp_mod.mlp_apply(sp["mlp"], rms_norm(sp["ln2"], h),
                                          cfg.mlp_kind)
                if "shared" not in new_cache:
                    new_cache["shared"] = jax.tree.map(jnp.copy, cache["shared"])
                new_cache["shared"] = jax.tree.map(
                    lambda full, upd, i=shared_i: full.at[i].set(upd),
                    new_cache["shared"], sc2)
                shared_i += 1

            def body(h, scanned):
                lp, lc = scanned
                if kind in ("dense", "moe"):
                    hn = rms_norm(lp["ln1"], h)
                    dec = attn.mla_decode if cfg.attention_kind == "mla" else attn.gqa_decode
                    y, lc2 = dec(lp["attn"], cfg, hn, lc, pos)
                    h = h + y
                    if kind == "dense":
                        h = h + mlp_mod.mlp_apply(lp["mlp"], rms_norm(lp["ln2"], h), cfg.mlp_kind)
                    else:
                        y2, _ = mlp_mod.moe_apply(lp["moe"], cfg, rms_norm(lp["ln2"], h))
                        h = h + y2
                else:
                    y, lc2 = m2.mamba2_decode(lp["mixer"], cfg,
                                              rms_norm(lp["ln"], h), lc)
                    h = h + y
                return h, lc2

            h, cstack2 = jax.lax.scan(body, h, (pstack, cstack))
            new_cache["chunks"].append(cstack2)
        if "shared" in cache and "shared" not in new_cache:
            new_cache["shared"] = cache["shared"]
        h = rms_norm(params["final_norm"], h)
        logits = dense_apply(params["head"], h)
        return logits, new_cache
