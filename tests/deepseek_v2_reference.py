"""A plain float32 forward of DeepSeek-V2's published block, written from
the equations of arXiv:2405.04434 and the model's `modeling_deepseek.py`,
importing nothing of `repro.models`. Tests compare the program with it.

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * (1 + g1)
                q = h Wq -> per head [q_nope (128) | q_pe (64)]
                [c | k_pe] = h Wkv_a;  c = rmsnorm(c) * (1 + g_kv)
                [k_nope | v] = c Wkv_b, per head
                q_pe, k_pe = yarn_rope(q_pe), yarn_rope(k_pe)  (k_pe shared)
                k = [k_nope | k_pe]
                x = x + softmax(q k^T * scale, causal) v Wo
                    scale = (128 + 64)^-1/2 * yarn_mscale(40, 0.707)^2
                h = rmsnorm(x) * (1 + g2)
                layer 0:  x = x + swiglu(h)            (width 10944)
                others:   p = softmax(h Wr)            (64 experts, f32)
                          top-6 of p, gates p (no renormalisation) * 1
                          x = x + sum_top6 gate_e swiglu_e(h)
                                + swiglu_shared(h)     (2 x 1408 wide)
    logits = (rmsnorm(x) * (1 + gf)) Whead              (untied)

Departures from the checkpoint, each a fixed reparametrisation of the
same weights:
  * norm gains are stored as (1 + g);
  * the rotary pairs of q_pe and k_pe are taken split-half (dims i and
    i + 32), where the checkpoint interleaves them (dims 2i and 2i + 1)
    and `modeling_deepseek.py` de-interleaves them before rotating: a
    permutation of the rotary columns of Wq and Wkv_a.
The experts are computed densely, each over every token and weighted by
its gate (zero for an expert a token did not choose): plain, and small
enough at test sizes.

`params` is the program's parameter tree (`models/lm.py`'s layout):
leading dense layers under "prefix", the MoE layers stacked under
blocks/p0. `cfg` is a `ModelConfig` (read for its sizes only).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tree):
    return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + g)


def yarn_get_mscale(scale, mscale):
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_find_correction_range(low_rot, high_rot, dim, base, max_pos):
    def corr(n_rot):
        return (dim * math.log(max_pos / (n_rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = math.floor(corr(low_rot))
    high = math.ceil(corr(high_rot))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(cfg):
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies (float64) and
    its cos/sin scale `_mscale`."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    pos_freqs = base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not cfg.yarn_factor:
        return 1.0 / pos_freqs, 1.0
    f = cfg.yarn_factor
    freq_extra = 1.0 / pos_freqs
    freq_inter = 1.0 / (f * pos_freqs)
    low, high = yarn_find_correction_range(
        cfg.yarn_beta_fast, cfg.yarn_beta_slow, dim, base,
        cfg.yarn_original_max_pos)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    m = (yarn_get_mscale(f, cfg.yarn_mscale)
         / yarn_get_mscale(f, cfg.yarn_mscale_all_dim))
    return inv_freq, m


def softmax_scale(cfg):
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor:
        m = yarn_get_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        s = s * m * m
    return s


def _rope(x, pos, inv_freq, m):
    """x (S, ..., R) rotated split-half at positions pos (S,)."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq,
                                                         jnp.float32)
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    c, s = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _mla(x, a, cfg, pos):
    S = x.shape[0]
    H, L = cfg.n_heads, cfg.kv_lora_rank
    N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    inv_freq, m = yarn_inv_freq(cfg)
    q = (x @ a["wq"]).reshape(S, H, N + R)
    kva = x @ a["wkv_a"]
    c = _rms(kva[:, :L], a["kv_norm"], cfg.norm_eps)
    k_pe = _rope(kva[:, L:], pos, inv_freq, m)               # (S, R)
    q_pe = _rope(q[..., N:], pos, inv_freq, m)               # (S, H, R)
    kv = (c @ a["wkv_b"]).reshape(S, H, N + V)
    k = jnp.concatenate(
        [kv[..., :N], jnp.broadcast_to(k_pe[:, None], (S, H, R))], -1)
    qf = jnp.concatenate([q[..., :N], q_pe], -1)
    s = jnp.einsum("qhd,khd->hqk", qf, k) * softmax_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), kv[..., N:])
    return o.reshape(S, H * V) @ a["wo"]


def _swiglu(x, w):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def route(x, router, cfg):
    """Gates (S, E): the softmax probability of each of a token's top-k
    experts (norm_topk_prob false, routed_scaling_factor 1), 0
    elsewhere."""
    p = jax.nn.softmax(x @ router, -1)
    kth = jnp.sort(p, -1)[:, -cfg.experts_per_token][:, None]
    return jnp.where(p >= kth, p, 0.0)


def _moe(x, w, cfg):
    gates = route(x, w["router"], cfg)
    h = jax.nn.silu(jnp.einsum("sd,edf->esf", x, w["w_gate"])) \
        * jnp.einsum("sd,edf->esf", x, w["w_up"])
    y = jnp.einsum("esf,efd,se->sd", h, w["w_down"], gates)
    if "shared" in w:
        y = y + _swiglu(x, w["shared"])
    return y


def _layer(x, lw, cfg, pos):
    x = x + _mla(_rms(x, lw["norm1"], cfg.norm_eps), lw["attn"], cfg, pos)
    h = _rms(x, lw["norm2"], cfg.norm_eps)
    return x + (_moe(h, lw["moe"], cfg) if "moe" in lw
                else _swiglu(h, lw["mlp"]))


def logits(params, cfg, tokens):
    """(S, V) float32 logits of one sequence of token ids."""
    with jax.default_matmul_precision("highest"):
        w = _f32(params)
        pos = jnp.arange(tokens.shape[0])
        x = w["embed"][tokens]
        for lw in w["prefix"]:
            x = _layer(x, lw, cfg, pos)
        stack = w["blocks"]["p0"]
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            x = _layer(x, jax.tree.map(lambda a: a[i], stack), cfg, pos)
        return _rms(x, w["final_norm"], cfg.norm_eps) @ w["head"]
