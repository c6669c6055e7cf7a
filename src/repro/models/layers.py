"""Shared neural-net building blocks (pure functions over param pytrees).

Conventions:
  * params are plain dicts of jnp arrays; layer-stacked leaves carry a leading
    `n_super` axis consumed by lax.scan in lm.py.
  * activations run in cfg.dtype (bf16 by default), softmax/norms in fp32.
  * no framework dependency (flax/haiku) — keeps sharding rules transparent.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def init_dense(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    return (jax.random.normal(key, (d_in, d_out)) * scale).astype(dtype)


# ----------------------------------------------------------------------------
# Rotary position embeddings
# ----------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                     / head_dim)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention-temperature factor (DeepSeek's yarn_get_mscale)."""
    if factor <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(dim: int, theta: float, orig_max_pos: int,
                          beta_fast: float, beta_slow: float):
    """(low, high) frequency-pair indices between which YaRN blends the
    interpolated and the original frequencies: the pairs that turn
    beta_fast and beta_slow times over `orig_max_pos` positions."""
    def pair(rotations):
        return (dim * math.log(orig_max_pos / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = math.floor(pair(beta_fast))
    high = math.ceil(pair(beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_freqs(dim: int, cfg):
    """YaRN's blended inverse frequencies (dim/2,) for a `dim`-wide rotary
    slice: pairs below the correction range keep their original
    frequency, pairs above it are divided by the factor, and a linear
    ramp blends the pairs within it. The rotated values' scale and the
    attention scale are yarn_rope_scale and attention.mla_scale."""
    extra = rope_freqs(dim, cfg.rope_theta)
    low, high = yarn_correction_range(dim, cfg.rope_theta,
                                      cfg.yarn_original_max_pos,
                                      cfg.yarn_beta_fast, cfg.yarn_beta_slow)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return extra / cfg.yarn_factor * (1.0 - keep) + extra * keep


def yarn_rope_scale(cfg) -> float:
    """YaRN's scale of the rotated values (DeepSeek's cos/sin `_mscale`):
    1.0 whenever mscale equals mscale_all_dim, as in DeepSeek-V2."""
    return (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def apply_rope(x, positions, theta: float = 10000.0, freqs=None):
    """x: (..., S, H, hd); positions: (..., S) int32. `freqs` (hd/2,)
    replaces the plain inverse frequencies (YaRN)."""
    hd = x.shape[-1]
    if freqs is None:
        freqs = rope_freqs(hd, theta)                   # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]                       # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# MLPs
# ----------------------------------------------------------------------------

def init_mlp(key, d_model, d_ff, dtype, kind: str = "swiglu"):
    k1, k2, k3 = jax.random.split(key, 3)
    if kind in ("swiglu", "geglu"):
        return {"w_gate": init_dense(k1, d_model, d_ff, dtype),
                "w_up": init_dense(k2, d_model, d_ff, dtype),
                "w_down": init_dense(k3, d_ff, d_model, dtype)}
    if kind == "gelu":
        return {"w_up": init_dense(k1, d_model, d_ff, dtype),
                "w_down": init_dense(k2, d_ff, d_model, dtype)}
    raise ValueError(kind)


def mlp(params, x, kind: str = "swiglu"):
    if kind == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    elif kind == "geglu":
        h = jax.nn.gelu(x @ params["w_gate"]) * (x @ params["w_up"])
    elif kind == "gelu":
        h = jax.nn.gelu(x @ params["w_up"])
    else:
        raise ValueError(kind)
    return h @ params["w_down"]


# ----------------------------------------------------------------------------
# Embedding / unembedding
# ----------------------------------------------------------------------------

def init_embed(key, vocab, d_model, dtype):
    return (jax.random.normal(key, (vocab, d_model)) * 0.02).astype(dtype)


def softmax_xent(logits, targets, z_loss: float = 0.0):
    """Stable cross-entropy in fp32. logits (..., V), targets (...) int."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    loss = lse - gold
    if z_loss > 0.0:
        loss = loss + z_loss * lse**2
    return loss
