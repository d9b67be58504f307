"""Operations the dense decoder's passes require, from the
configuration's sizes alone.

Convention: a multiply-add is 2 FLOPs; only matrix multiplications count
(norms, rotary embeddings, softmax and the optimizer are left out); the
embedding is a gather and costs nothing; attention is causal, so a query
at position ``i`` meets ``i + 1`` keys.  Recomputation is never counted:
these are the operations the passes require, not the ones a remat plan
chooses to repeat.
"""

from __future__ import annotations


def _sizes(m: dict):
    d, H, K = m["d_model"], m["n_heads"], m["n_kv_heads"]
    Dh = m.get("head_dim") or d // H
    mult = 3 if m["mlp_kind"] in ("swiglu", "geglu") else 2
    return d, H, K, Dh, mult


def layer_matmul_params(m: dict) -> int:
    """Matmul weights of one dense layer (q, k, v, o and the MLP)."""
    d, H, K, Dh, mult = _sizes(m)
    return d * (H * Dh + 2 * K * Dh) + H * Dh * d + mult * d * m["d_ff"]


def matmul_params(m: dict) -> int:
    """Matmul weights a token passes through: every layer and the head."""
    return m["num_layers"] * layer_matmul_params(m) + m["d_model"] * m["vocab_size"]


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per trained token at ``seq_len``: 6 per
    matmul weight, plus causal attention (scores and values, 2 FLOPs per
    multiply-add each, over ``(seq_len + 1) / 2`` keys on average), three
    times over for the two backward products."""
    d, H, K, Dh, _ = _sizes(m)
    attn_fwd = 2 * 2 * H * Dh * (seq_len + 1) / 2
    return 6 * matmul_params(m) + 3 * m["num_layers"] * attn_fwd
