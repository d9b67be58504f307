"""Plain float32 reference of the dense decoder the training cells run:
pre-norm RMSNorm, GQA attention with RoPE (halves rotated) and optional
QKV bias, a SwiGLU or tanh-GELU MLP, an untied head, and a token mean of
the cross-entropy.  Every matrix product runs at ``highest``
precision on float32 operands.

It imports nothing of the program.  Its weights come from the seed by a
copy of the program's initialisation rule (``StagedLM.init``: the same key
splits, truncated normals and scales, stored in the configuration's
parameter dtype), so that both sides start from the same numbers without
the reference taking any array the program made.

The gradient is computed stage by stage (embedding, each chunk of layers,
final norm and head) and one row of the batch at a time, with the
attention's query blocks and the head's token blocks recomputed in the
backward pass, so that a step at the cells' sizes fits one chip beside
float32 parameters and gradients.  Adam's moments live on the host and are
updated one leaf at a time on the device.

``quant="fp8"`` builds the control: every matrix product takes operands
(and, in the backward pass, cotangents) rounded to float8 e4m3 with one
scale per tensor, the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
NEG = -1e30
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
FP8_MAX = 240.0  # largest finite float8 e4m3 (IEEE layout: bias 7, no 448)


# -- rounding to a narrower type ---------------------------------------------
# Always by ``reduce_precision``, never by a round trip through ``astype``:
# inside one program XLA may drop such a round trip and keep the wider
# value (the TPU's compiler does), while it keeps every reduce_precision.

def _round_to(x, dtype):
    """``x`` (float32) rounded to the nearest value of ``dtype``, kept in
    float32."""
    fi = jnp.finfo(dtype)
    if fi.nmant >= jnp.finfo(F32).nmant:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST, preferred_element_type=F32)


def _fp8(x):
    """Round to float8 e4m3 with one scale per tensor (amax to 240);
    ``reduce_precision`` flushes its subnormals to zero."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return jax.lax.reduce_precision(x / s, exponent_bits=4,
                                    mantissa_bits=3) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(eq, a, b):
    return _einsum(eq, _fp8(a), _fp8(b))


def _einsum_fp8_fwd(eq, a, b):
    return _einsum_fp8(eq, a, b), (a, b)


def _einsum_fp8_bwd(eq, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: _einsum(eq, x, y), _fp8(a), _fp8(b))
    return vjp(_fp8(g))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


# -- the configuration ---------------------------------------------------------

def chunk_lengths(m: dict) -> List[int]:
    """Layers per chunk (stacked parameter groups), as the program splits
    an all-dense stack of ``num_layers`` into ``n_chunks``."""
    L = m["num_layers"]
    n = min(max(1, m["n_chunks"]), L)
    base, extra = divmod(L, n)
    return [base + (1 if j < extra else 0) for j in range(n)]


class Reference:
    """The model of one configuration's ``model`` section."""

    def __init__(self, m: dict, quant: Optional[str] = None,
                 q_block: int = 512, tok_block: int = 512):
        kinds = m.get("layer_kinds")
        if kinds is not None and set(kinds) != {"dense"}:
            raise ValueError("the reference covers dense layers only")
        if m.get("attention_kind", "gqa") != "gqa" or m.get("modality", "text") != "text":
            raise ValueError("the reference covers text GQA models only")
        self.m = m
        self.d = m["d_model"]
        self.H, self.K = m["n_heads"], m["n_kv_heads"]
        self.Dh = m.get("head_dim") or self.d // self.H
        self.V = m["vocab_size"]
        self.L = m["num_layers"]
        self.mlp_kind = m["mlp_kind"]
        self.theta = float(m.get("rope_theta", 10000.0))
        self.pdt = DTYPES[m["param_dtype"]]
        self.chunks = chunk_lengths(m)
        self.q_block = q_block
        self.tok_block = tok_block
        self.mm = _einsum if quant is None else _einsum_fp8
        if quant not in (None, "fp8"):
            raise ValueError(f"unknown quant {quant!r}")

    # -- initialisation (a copy of the program's rule) ------------------------

    def _tn(self, key, shape, scale):
        return scale * jax.random.truncated_normal(
            key, -2.0, 2.0, shape, F32).astype(self.pdt)

    def _dense(self, key, din, dout, bias=False, scale=None):
        dout = (dout,) if isinstance(dout, int) else tuple(dout)
        scale = scale if scale is not None else 1.0 / math.sqrt(din)
        p = {"kernel": self._tn(key, (din,) + dout, scale)}
        if bias:
            p["bias"] = jnp.zeros(dout, self.pdt)
        return p

    def _layer_init(self, key):
        d, H, K, Dh, pdt = self.d, self.H, self.K, self.Dh, self.pdt
        ks = jax.random.split(key, 4)
        ka = jax.random.split(ks[0], 4)
        bias = bool(self.m.get("qkv_bias", False))
        attn = {
            "wq": self._dense(ka[0], d, (H, Dh), bias),
            "wk": self._dense(ka[1], d, (K, Dh), bias),
            "wv": self._dense(ka[2], d, (K, Dh), bias),
            "wo": self._dense(ka[3], H * Dh, d,
                              scale=1.0 / math.sqrt(H * Dh * max(self.L, 1))),
        }
        km = jax.random.split(ks[1], 3)
        dff = self.m["d_ff"]
        out_scale = 1.0 / math.sqrt(dff * max(self.L, 1))
        if self.mlp_kind in ("swiglu", "geglu"):
            mlp = {"wi_gate": self._dense(km[0], d, dff),
                   "wi_up": self._dense(km[1], d, dff),
                   "wo": self._dense(km[2], dff, d, scale=out_scale)}
        else:
            mlp = {"wi": self._dense(km[0], d, dff),
                   "wo": self._dense(km[1], dff, d, scale=out_scale)}
        return {"ln1": {"scale": jnp.ones((d,), pdt)}, "attn": attn,
                "ln2": {"scale": jnp.ones((d,), pdt)}, "mlp": mlp}

    def init(self, key) -> dict:
        """Parameters in the configuration's dtype, from ``key`` (jit me)."""
        keys = jax.random.split(key, len(self.chunks) + 4)
        params = {"embed": {"table": self._tn(keys[0], (self.V, self.d), 1.0)}}
        params["chunks"] = [
            jax.vmap(self._layer_init)(jax.random.split(keys[i + 1], n))
            for i, n in enumerate(self.chunks)]
        params["final_norm"] = {"scale": jnp.ones((self.d,), self.pdt)}
        params["head"] = self._dense(keys[-1], self.d, self.V)
        return params

    # -- the forward pass, in float32 ----------------------------------------

    @staticmethod
    def _rms(scale, x):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + 1e-6) * scale.astype(F32)

    def _rope(self, x, pos):
        """x: (b, S, h, Dh) float32, pos: (S,)."""
        freqs = 1.0 / (self.theta ** (jnp.arange(0, self.Dh, 2, dtype=F32) / self.Dh))
        ang = pos[:, None].astype(F32) * freqs                  # (S, Dh/2)
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def _proj(self, p, x, eq):
        y = self.mm(eq, x, p["kernel"].astype(F32))
        if "bias" in p:
            y = y + p["bias"].astype(F32)
        return y

    def _attention(self, q, k, v):
        """Causal attention; q: (b,S,H,Dh), k/v: (b,S,K,Dh), query blocks
        recomputed in the backward pass."""
        b, S = q.shape[:2]
        g = self.H // self.K
        bq = min(self.q_block, S)
        nb = -(-S // bq)
        pad = nb * bq - S
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qb = qp.reshape(b, nb, bq, self.K, g, self.Dh).transpose(1, 0, 2, 3, 4, 5)
        k_pos = jnp.arange(S)

        @jax.checkpoint
        def block(args):
            qblk, i = args
            s = self.mm("bqkgd,bskd->bkgqs", qblk, k) / math.sqrt(self.Dh)
            q_pos = i * bq + jnp.arange(bq)
            s = jnp.where((k_pos[None, :] <= q_pos[:, None])[None, None, None],
                          s, NEG)
            pr = jax.nn.softmax(s, axis=-1)
            return self.mm("bkgqs,bskd->bqkgd", pr, v)

        out = jax.lax.map(block, (qb, jnp.arange(nb)))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, nb * bq, self.H * self.Dh)
        return out[:, :S]

    def layer(self, lp, h):
        """One dense layer; h: (b, S, d) float32."""
        b, S, _ = h.shape
        pos = jnp.arange(S)
        x = self._rms(lp["ln1"]["scale"], h)
        a = lp["attn"]
        q = self._rope(self._proj(a["wq"], x, "bsd,dhe->bshe"), pos)
        k = self._rope(self._proj(a["wk"], x, "bsd,dhe->bshe"), pos)
        v = self._proj(a["wv"], x, "bsd,dhe->bshe")
        o = self._attention(q, k, v)
        h = h + self.mm("bsf,fd->bsd", o, a["wo"]["kernel"].astype(F32))
        x = self._rms(lp["ln2"]["scale"], h)
        p = lp["mlp"]
        if self.mlp_kind == "swiglu":
            u = (jax.nn.silu(self._proj(p["wi_gate"], x, "bsd,df->bsf"))
                 * self._proj(p["wi_up"], x, "bsd,df->bsf"))
        elif self.mlp_kind == "geglu":
            u = (jax.nn.gelu(self._proj(p["wi_gate"], x, "bsd,df->bsf"),
                             approximate=True)
                 * self._proj(p["wi_up"], x, "bsd,df->bsf"))
        else:
            u = jax.nn.gelu(self._proj(p["wi"], x, "bsd,df->bsf"),
                            approximate=True)
        return h + self._proj(p["wo"], u, "bsf,fd->bsd")

    def chunk(self, cp, h):
        """A chunk's stacked layers (residuals of one row block at a time
        are kept for the backward pass)."""
        def body(h, lp):
            return self.layer(lp, h), None
        h, _ = jax.lax.scan(body, h, cp)
        return h

    def embed(self, table, tokens):
        return table[tokens].astype(F32)

    def head_loss_sum(self, hp, h, labels, mask):
        """Sum over tokens of the masked cross-entropy, in token blocks."""
        x = self._rms(hp["final_norm"]["scale"], h)
        T = x.shape[0] * x.shape[1]
        x = x.reshape(T, self.d)
        lab, msk = labels.reshape(T), mask.reshape(T).astype(F32)
        tb = min(self.tok_block, T)
        nb = -(-T // tb)
        pad = nb * tb - T
        x = jnp.pad(x, ((0, pad), (0, 0))).reshape(nb, tb, self.d)
        lab = jnp.pad(lab, (0, pad)).reshape(nb, tb)
        msk = jnp.pad(msk, (0, pad)).reshape(nb, tb)
        w = hp["head"]["kernel"].astype(F32)

        @jax.checkpoint
        def block(carry, args):
            xb, lb, mb = args
            z = self.mm("td,dv->tv", xb, w)
            lse = jax.nn.logsumexp(z, axis=-1)
            gold = jnp.take_along_axis(z, lb[:, None], axis=-1)[:, 0]
            return carry + jnp.sum((lse - gold) * mb), None

        total, _ = jax.lax.scan(block, jnp.zeros((), F32), (x, lab, msk))
        return total

    # -- pieces of a training step, one row block at a time -------------------

    def split(self, params) -> list:
        """Stage parameter groups: embedding, each chunk, final norm+head."""
        return ([params["embed"]] + list(params["chunks"])
                + [{"final_norm": params["final_norm"], "head": params["head"]}])

    def join(self, stages) -> dict:
        return {"embed": stages[0], "chunks": list(stages[1:-1]),
                "final_norm": stages[-1]["final_norm"],
                "head": stages[-1]["head"]}


class Trainer:
    """Follows the program's first training steps in float32: loss and
    gradient of each step, then AdamW as the configuration states it
    (global-norm clipping, bias correction, decoupled decay of matrices;
    parameters stored back in the configuration's dtype)."""

    def __init__(self, ref: Reference, opt: dict):
        self.ref = ref
        self.opt = opt
        r = ref

        @functools.partial(jax.jit, donate_argnums=(1,))
        def head_bwd(hp, gacc, h, labels, mask, inv_denom):
            def f(hp, h):
                return r.head_loss_sum(hp, h, labels, mask) * inv_denom
            loss, vjp = jax.vjp(f, hp, h)
            ghp, dh = vjp(jnp.ones((), F32))
            return loss, dh, jax.tree.map(jnp.add, gacc, ghp)

        @functools.partial(jax.jit, donate_argnums=(1,))
        def chunk_bwd(cp, gacc, h, dh):
            _, vjp = jax.vjp(r.chunk, cp, h)
            gcp, dh_in = vjp(dh)
            return dh_in, jax.tree.map(jnp.add, gacc, gcp)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def embed_bwd(gacc, tokens, dh):
            return {"table": gacc["table"].at[tokens].add(dh)}

        self._head_bwd = head_bwd
        self._chunk_bwd = chunk_bwd
        self._embed_bwd = embed_bwd
        self._chunk_fwd = jax.jit(r.chunk)
        self._embed_fwd = jax.jit(r.embed)
        self._zeros = jax.jit(lambda p: jax.tree.map(
            lambda x: jnp.zeros(x.shape, F32), p))
        self._sq = jax.jit(lambda t: [jnp.sum(jnp.square(x.astype(F32)))
                                      for x in jax.tree.leaves(t)])

        b1, b2, eps, wd = opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"]
        pdt = r.pdt

        @functools.partial(jax.jit, static_argnums=(6,), donate_argnums=(0, 2, 3))
        def adam_leaf(p, g, m, v, lr, scale_count, decay):
            scale, count = scale_count[0], scale_count[1]
            g = g * scale
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            step = (m / (1 - b1 ** count)) / (jnp.sqrt(v / (1 - b2 ** count)) + eps)
            if decay:
                step = step + wd * p
            # stored in the configuration's parameter dtype
            return _round_to(p - lr * step, pdt), m, v

        self._adam_leaf = adam_leaf

    def loss_and_grad(self, params, batch) -> tuple:
        """Mean loss and float32 gradient tree of one full batch.
        ``params`` holds float32 leaves."""
        r = self.ref
        stages = r.split(params)
        gacc = self._zeros(stages)
        tokens, labels, mask = (batch["tokens"], batch["labels"],
                                batch["loss_mask"])
        inv_denom = jnp.asarray(1.0 / max(float(mask.sum()), 1.0), F32)
        loss = 0.0
        for row in range(tokens.shape[0]):
            sl = slice(row, row + 1)
            tok = jnp.asarray(tokens[sl])
            hs = [self._embed_fwd(stages[0]["table"], tok)]
            for ci in range(len(r.chunks)):
                hs.append(self._chunk_fwd(stages[1 + ci], hs[-1]))
            l, dh, gacc[-1] = self._head_bwd(
                stages[-1], gacc[-1], hs[-1], jnp.asarray(labels[sl]),
                jnp.asarray(mask[sl]), inv_denom)
            loss += float(l)
            for ci in reversed(range(len(r.chunks))):
                dh, gacc[1 + ci] = self._chunk_bwd(stages[1 + ci], gacc[1 + ci],
                                                   hs[ci], dh)
            gacc[0] = self._embed_bwd(gacc[0], tok, dh)
            del hs, dh
        return loss, r.join(gacc)

    def leaf_norms(self, tree) -> np.ndarray:
        return np.sqrt(np.asarray(jax.device_get(self._sq(tree)), np.float64))

    def apply(self, params, grads, moments, step: int, lr: float) -> dict:
        """One AdamW update.  ``moments`` is a host list of (m, v) numpy
        pairs, one per leaf, updated in place."""
        opt = self.opt
        gsq = np.asarray(jax.device_get(self._sq(grads)), np.float64)
        gnorm = float(np.sqrt(gsq.sum()))
        scale = 1.0
        if opt.get("clip_norm") is not None:
            scale = min(1.0, opt["clip_norm"] / max(gnorm, 1e-12))
        sc = jnp.asarray([scale, float(step + 1)], F32)
        flat_p, tdef = jax.tree.flatten(params)
        flat_g = jax.tree.leaves(grads)
        paths = [jax.tree_util.keystr(k) for k, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0]]
        out = []
        for i, (p, g) in enumerate(zip(flat_p, flat_g)):
            m, v = moments[i]
            decay = bool(opt["weight_decay"]) and _is_matrix(paths[i], p.ndim)
            p2, m2, v2 = self._adam_leaf(p, g, jnp.asarray(m), jnp.asarray(v),
                                         jnp.asarray(lr, F32), sc, decay)
            moments[i] = (np.asarray(m2), np.asarray(v2))
            out.append(p2)
        return jax.tree.unflatten(tdef, out)


def _is_matrix(path: str, ndim: int) -> bool:
    """Decay matrices, not norms or biases (chunk leaves carry a leading
    layer axis)."""
    per_layer = ndim - 1 if path.startswith("['chunks']") else ndim
    return per_layer >= 2 and not path.endswith("['bias']")


def lr_at(step: int, base: float, warmup: int, total: int,
          min_frac: float = 0.1) -> float:
    """Linear warmup then cosine to ``min_frac`` of ``base`` (the schedule
    the configuration's optimizer states), in float32 as the program
    evaluates it."""
    s = np.float32(step)
    if s < warmup:
        return float(np.float32(base) * s / np.float32(max(warmup, 1)))
    span = max(total - warmup, 1)
    t = np.minimum(np.float32(step - warmup), np.float32(span)) / np.float32(span)
    return float(np.float32(base) * (np.float32(min_frac) + np.float32(1 - min_frac)
                                     * np.float32(0.5) * (1 + np.cos(np.float32(np.pi) * t))))


def follow(ref: Reference, opt: dict, seed_key, batches: List[dict],
           lrs: List[float]) -> Dict[str, Any]:
    """Run the reference through ``len(batches)`` steps from the seed.

    Returns the loss of each step, the per-leaf norms of the first
    gradient (before clipping), and the per-leaf norms of the parameters'
    change after the last step, with the leaf paths."""
    tr = Trainer(ref, opt)
    p0 = jax.jit(ref.init)(seed_key)
    paths = [jax.tree_util.keystr(k) for k, _ in
             jax.tree_util.tree_flatten_with_path(p0)[0]]
    params = jax.tree.map(lambda x: x.astype(F32), p0)
    moments = [(np.zeros(x.shape, np.float32), np.zeros(x.shape, np.float32))
               for x in jax.tree.leaves(p0)]
    losses, g0 = [], None
    for step, (batch, lr) in enumerate(zip(batches, lrs)):
        loss, grads = tr.loss_and_grad(params, batch)
        losses.append(loss)
        if step == 0:
            g0 = tr.leaf_norms(grads)
        params = tr.apply(params, grads, moments, step, lr)
        del grads
    delta = jax.jit(lambda a, b: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(F32) - y.astype(F32)))) for x, y in zip(
            jax.tree.leaves(a), jax.tree.leaves(b))])(params, p0)
    return {"losses": losses, "grad0": g0, "paths": paths,
            "delta": np.asarray(jax.device_get(delta), np.float64),
            "init": p0}
