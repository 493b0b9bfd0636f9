"""Operations and bytes the algorithms need, from shapes alone.

Each count is what the mathematics requires, not what an implementation
does: recomputed activations, padding and copies are not counted, so a
share of a peak computed from these never credits waste. ``c`` is a
configuration in the published config.json's key names.
"""
from __future__ import annotations


def layer_matmul_params(c: dict) -> int:
    """Weights one decoder layer multiplies each token by."""
    D, H, KV, hd, F = (c["hidden_size"], c["num_attention_heads"],
                       c["num_key_value_heads"], c["head_dim"],
                       c["intermediate_size"])
    return D * H * hd + D * 2 * KV * hd + H * hd * D + 3 * D * F


def matmul_params(c: dict) -> int:
    """All weights multiplied per token: the layers and the vocabulary
    head (the embedding lookup is a gather, not a matmul)."""
    return (c["num_hidden_layers"] * layer_matmul_params(c)
            + c["hidden_size"] * c["vocab_size"])


def keys_seen(pos: int, c: dict) -> int:
    """Keys the query at position ``pos`` attends to (causal, windowed)."""
    w = c.get("sliding_window") or 0
    return min(pos + 1, w) if w > 0 else pos + 1


def attention_flops_fwd(c: dict, seq: int) -> int:
    """Forward q.k and p.v over one causal sequence of ``seq`` tokens, all
    layers: 2 matmuls x 2 flops x heads x head_dim per visible key."""
    per_key = 4 * c["num_attention_heads"] * c["head_dim"]
    keys = sum(keys_seen(p, c) for p in range(seq))
    return c["num_hidden_layers"] * per_key * keys


def train_flops_per_token(c: dict, seq: int) -> float:
    """Forward and backward (3x forward), no recompute, per trained
    token of rows of ``seq`` tokens."""
    fwd = 2 * matmul_params(c) * seq + attention_flops_fwd(c, seq)
    return 3 * fwd / seq


def decode_step_flops(c: dict, ctx_lens) -> int:
    """One decode step over lanes whose new token sits at context length
    ``ctx_lens`` (keys it sees, itself included)."""
    per_key = 4 * c["num_attention_heads"] * c["head_dim"]
    return sum(2 * matmul_params(c)
               + c["num_hidden_layers"] * per_key * min(
                   n, c.get("sliding_window") or n)
               for n in ctx_lens)


def decode_step_bytes(c: dict, ctx_lens, *, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> int:
    """Bytes one decode step must move: each weight matrix once (the
    head too; a tied head is the embedding, read once), the embedding rows
    of the lanes gathered, the norms, and the keys and values of every
    lane's earlier context read and its new ones written."""
    D, L = c["hidden_size"], c["num_hidden_layers"]
    n = len(ctx_lens)
    weights = matmul_params(c) + L * 2 * D + D
    if c.get("qk_norm"):
        weights += L * 2 * c["head_dim"]
    gathered = n * D
    kv_per_pos = L * 2 * c["num_key_value_heads"] * c["head_dim"]
    kv = sum(min(m, c.get("sliding_window") or m) for m in ctx_lens)
    return (weight_bytes * (weights + gathered)
            + kv_bytes * kv_per_pos * kv)


def bus_bytes(primitive: str, group: int, in_bytes: int,
              out_bytes: int) -> float:
    """Bytes each member of a group of ``group`` must send for one call
    (the usual bus-bandwidth convention): all-reduce 2(g-1)/g of the
    message, reduce-scatter and all-gather (g-1)/g of the larger side,
    all-to-all (g-1)/g of the message."""
    f = (group - 1) / group
    if primitive == "all_reduce":
        return 2 * f * in_bytes
    if primitive in ("reduce_scatter", "all_gather"):
        return f * max(in_bytes, out_bytes)
    if primitive == "all_to_all":
        return f * in_bytes
    raise ValueError(f"no bus bytes for {primitive!r}")
