"""The work a step needs, counted from the model's shapes and never from the
implementation: matrix-product FLOPs (2 per multiply-add) and the bytes a
decode step must move. So the yardstick stays when a kernel replaces the
gather, and no share of a peak can read above what the chip allows.

`spec` is a configuration's `model` block (dense attention + SwiGLU).
"""
from __future__ import annotations

ACT_BYTES = 2          # bfloat16 weights and KV as the program computes


def layer_params(spec: dict) -> int:
    D, F = spec["d_model"], spec["d_ff"]
    H, KV, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def head_params(spec: dict) -> int:
    return spec["d_model"] * spec["vocab_size"]


def param_count(spec: dict) -> int:
    """Every parameter: embedding, layers with their two norms, final norm,
    and an untied head."""
    D, V, L = spec["d_model"], spec["vocab_size"], spec["n_layers"]
    n = V * D + L * (layer_params(spec) + 2 * D) + D
    if not spec.get("tie_embeddings", False):
        n += D * V
    return n


def matmul_params(spec: dict) -> int:
    """Parameters that take part in a matrix product per token: every layer
    and the head (the tied embedding counts once, as the head)."""
    return spec["n_layers"] * layer_params(spec) + head_params(spec)


def attn_flops(spec: dict, ctx: int) -> int:
    """Scores and weighted values of one query over `ctx` keys, all layers."""
    return 4 * spec["n_layers"] * spec["n_heads"] * spec["head_dim"] * ctx


def kv_bytes_per_token(spec: dict) -> int:
    return 2 * spec["n_layers"] * spec["n_kv_heads"] * spec["head_dim"] \
        * ACT_BYTES


def prefill_flops(spec: dict, start: int, n: int) -> int:
    """Forward FLOPs of prefilling positions start .. start+n-1 of one
    prompt: every token through the layers, causal attention, and the head
    at the last position only (the one whose logits are used)."""
    body = 2 * spec["n_layers"] * layer_params(spec) * n
    keys = n * start + n * (n + 1) // 2      # sum of (position + 1)
    return body + attn_flops(spec, 1) * keys + 2 * head_params(spec)


def decode_flops(spec: dict, positions) -> int:
    """Forward FLOPs of one decode step: one token per live lane at its
    position, attending to position + 1 keys."""
    n = len(positions)
    keys = sum(p + 1 for p in positions)
    return 2 * matmul_params(spec) * n + attn_flops(spec, 1) * keys


def decode_bytes(spec: dict, positions) -> int:
    """Bytes one decode step must move: the weights once at the compute
    dtype, the live lanes' cached keys and values, and the new rows."""
    kv = kv_bytes_per_token(spec)
    return ACT_BYTES * matmul_params(spec) + kv * sum(positions) \
        + kv * len(positions)


def decode_least_s(spec: dict, positions, peak: dict) -> float:
    """The least time the chip could take for one decode step."""
    return max(decode_flops(spec, positions) / peak["bf16_flops"],
               decode_bytes(spec, positions) / peak["hbm_bytes_per_s"])


def train_flops_per_token(spec: dict, seq_len: int) -> int:
    """Forward and backward FLOPs per token of one pass: 6 per matrix
    parameter, plus causal attention three times over (forward, and the
    two products of its backward), at the mean context (seq_len + 1) / 2.
    Recomputation (remat) does not count."""
    mean_keys = (seq_len + 1) / 2
    return 6 * matmul_params(spec) + 3 * attn_flops(spec, 1) * mean_keys
