"""The dense decoder block: every layer attention + SwiGLU, RMSNorm with
gains (1 + g), rotary embedding over the full head width. The weight tree,
the plain float32 forward and the work counts of this one architecture;
`common.arch` finds this file for a spec whose `arch` is "dense" or absent.

Weights: the layout `models/lm.py` takes for `block_pattern == ("attn",)`:
layers stacked on a leading axis under blocks/p0, norms as (1 + scale).

Forward, written from the model's equations and importing nothing of the
program:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * (1 + g1)
                q, k, v = h Wq, h Wk, h Wv      (GQA: H query, KV key heads)
                q, k = rope(q), rope(k)         (split-half rotation, full
                                                 head width, base theta)
                x = x + softmax(q k^T / sqrt(hd), causal) v Wo
                h = rmsnorm(x) * (1 + g2)
                x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rmsnorm(x) * (1 + gf)) E^T        (tied) or ... Whead

Counts: matrix-product FLOPs (2 per multiply-add) and the bytes a decode
step must move, from the shapes alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ops import ACT_BYTES, attention, f32, mm

# ----------------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------------


def shapes(spec: dict) -> dict:
    L, D, F, V = (spec["n_layers"], spec["d_model"], spec["d_ff"],
                  spec["vocab_size"])
    H, KV, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    tree = {
        "embed": (V, D),
        "prefix": [],
        "blocks": {"p0": {
            "norm1": (L, D), "norm2": (L, D),
            "attn": {"wq": (L, D, H * hd), "wk": (L, D, KV * hd),
                     "wv": (L, D, KV * hd), "wo": (L, H * hd, D)},
            "mlp": {"w_gate": (L, D, F), "w_up": (L, D, F),
                    "w_down": (L, F, D)},
        }},
        "final_norm": (D,),
    }
    if not spec.get("tie_embeddings", False):
        tree["head"] = (D, V)
    return tree


def scale(path: str, shape: tuple) -> float:
    if path == "embed":
        return 0.02
    if "norm" in path:
        return 0.1                 # (1 + scale): gains of 1 +- 0.1
    return shape[-2] ** -0.5       # fan-in of a matrix

# ----------------------------------------------------------------------------
# the plain forward
# ----------------------------------------------------------------------------


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _layer(x, lw, spec, pos, quant):
    S = x.shape[0]
    H, KV, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    eps, theta = spec["norm_eps"], spec["rope_theta"]
    h = _rms(x, lw["norm1"], eps)
    q = mm(h, lw["attn"]["wq"], quant).reshape(S, H, hd)
    k = mm(h, lw["attn"]["wk"], quant).reshape(S, KV, hd)
    v = mm(h, lw["attn"]["wv"], quant).reshape(S, KV, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    o = attention(q.reshape(S, KV, H // KV, hd), k, v, quant)
    x = x + mm(o, lw["attn"]["wo"], quant)
    h = _rms(x, lw["norm2"], eps)
    m = lw["mlp"]
    act = jax.nn.silu(mm(h, m["w_gate"], quant)) * mm(h, m["w_up"], quant)
    return x + mm(act, m["w_down"], quant)


def hidden(w, spec, tokens, quant=None, remat=False):
    """Final normed hidden states (S, D) of one sequence. `remat` keeps
    only each layer's input for a backward pass (same arithmetic)."""
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)

    def body(x, lw):
        return _layer(x, f32(lw), spec, pos, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["blocks"]["p0"])
    return _rms(x, f32(w["final_norm"]), spec["norm_eps"])


def logits(w, spec, tokens, quant=None, remat=False):
    """(S, V) float32 logits of one sequence of token ids."""
    h = hidden(w, spec, tokens, quant, remat)
    table = w["embed"].T if spec.get("tie_embeddings") else w["head"]
    return mm(h, f32(table), quant)

# ----------------------------------------------------------------------------
# work counts
# ----------------------------------------------------------------------------


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


def train_flops_per_token(spec: dict, seq_len: int) -> int:
    """Forward and backward FLOPs per token of one pass: 6 per matrix
    parameter, plus causal attention three times over (forward, and the
    two products of its backward), at the mean context (seq_len + 1) / 2.
    Recomputation (remat) does not count."""
    mean_keys = (seq_len + 1) / 2
    return 6 * matmul_params(spec) + 3 * attn_flops(spec, 1) * mean_keys
