"""The work a step needs, counted from the model's shapes and never from the
implementation: matrix-product FLOPs (2 per multiply-add) and the bytes a
decode step must move. So the yardstick stays when a kernel replaces the
gather, and no share of a peak can read above what the chip allows.

`spec` is a configuration's `model` block. The counts are its
architecture's (`arch/<name>.py`, found by `common.arch`); this file holds
the entry points the readers call and the least time of a decode step.
"""
from __future__ import annotations

import common


def param_count(spec: dict) -> int:
    """Every parameter of the model."""
    return common.arch(spec).param_count(spec)


def matmul_params(spec: dict) -> int:
    """Parameters that take part in a matrix product per token."""
    return common.arch(spec).matmul_params(spec)


def kv_bytes_per_token(spec: dict) -> int:
    """Bytes of cached state one token adds, all layers."""
    return common.arch(spec).kv_bytes_per_token(spec)


def prefill_flops(spec: dict, start: int, n: int) -> int:
    """Forward FLOPs of prefilling positions start .. start+n-1 of one
    prompt."""
    return common.arch(spec).prefill_flops(spec, start, n)


def decode_flops(spec: dict, positions) -> int:
    """Forward FLOPs of one decode step, one token per live lane at its
    position."""
    return common.arch(spec).decode_flops(spec, positions)


def decode_bytes(spec: dict, positions) -> int:
    """Bytes one decode step must move."""
    return common.arch(spec).decode_bytes(spec, positions)


def decode_least_s(spec: dict, positions, peak: dict) -> float:
    """The least time the chip could take for one decode step."""
    return max(decode_flops(spec, positions) / peak["bf16_flops"],
               decode_bytes(spec, positions) / peak["hbm_bytes_per_s"])


def train_flops_per_token(spec: dict, seq_len: int) -> int:
    """Forward and backward FLOPs per token of one pass over sequences of
    `seq_len`; recomputation (remat) does not count."""
    return common.arch(spec).train_flops_per_token(spec, seq_len)
