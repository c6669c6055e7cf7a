"""The plain reference: a float32 forward pass of a dense decoder whose
every layer is attention + SwiGLU, written from the model's equations and
importing nothing of the program.

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * (1 + g1)
                q, k, v = h Wq, h Wk, h Wv      (GQA: H query, KV key heads)
                q, k = rope(q), rope(k)         (split-half rotation, full
                                                 head width, base theta)
                x = x + softmax(q k^T / sqrt(hd), causal) v Wo
                h = rmsnorm(x) * (1 + g2)
                x = x + (silu(h Wg) * (h Wu)) Wd
    logits = (rmsnorm(x) * (1 + gf)) E^T        (tied) or ... Whead

Every matrix product runs at `Precision.HIGHEST`, so a TPU computes it in
float32 and not in one bfloat16 pass. Attention is taken in blocks of
query rows so that long contexts fit.

`quant="fp8"` is the control: the same pass with both operands of every
matrix product rounded to float8 (e4m3, one max-abs scale per tensor), the
precision below the program's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512


def _qdq(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(quant)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, b, quant):
    return jnp.matmul(_qdq(a, quant), _qdq(b, quant), precision=HI)


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


def _attention(q, k, v, quant):
    """q (S, KV, G, hd), k/v (S, KV, hd) -> (S, KV*G*hd), causal."""
    S, KV, G, hd = q.shape
    kpos = jnp.arange(S)
    kq, vq = _qdq(k, quant), _qdq(v, quant)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        n = qb.shape[0]
        s = jnp.einsum("qkgh,skh->kgqs", _qdq(qb, quant), kq,
                       precision=HI) * hd ** -0.5
        mask = kpos[None, :] <= (lo + jnp.arange(n))[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skh->qkgh", _qdq(p, quant), vq, precision=HI)
        outs.append(o.reshape(n, KV * G * hd))
    return jnp.concatenate(outs, axis=0)


def _layer(x, lw, spec, pos, quant):
    S = x.shape[0]
    H, KV, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    eps, theta = spec["norm_eps"], spec["rope_theta"]
    h = _rms(x, lw["norm1"], eps)
    q = _mm(h, lw["attn"]["wq"], quant).reshape(S, H, hd)
    k = _mm(h, lw["attn"]["wk"], quant).reshape(S, KV, hd)
    v = _mm(h, lw["attn"]["wv"], quant).reshape(S, KV, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    o = _attention(q.reshape(S, KV, H // KV, hd), k, v, quant)
    x = x + _mm(o, lw["attn"]["wo"], quant)
    h = _rms(x, lw["norm2"], eps)
    m = lw["mlp"]
    act = jax.nn.silu(_mm(h, m["w_gate"], quant)) * _mm(h, m["w_up"], quant)
    return x + _mm(act, m["w_down"], quant)


def hidden(w, spec, tokens, quant=None, remat=False):
    """Final normed hidden states (S, D) of one sequence. `remat` keeps
    only each layer's input for a backward pass (same arithmetic)."""
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)

    def body(x, lw):
        return _layer(x, lw, spec, pos, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["blocks"]["p0"])
    return _rms(x, w["final_norm"], spec["norm_eps"])


def logits(w, spec, tokens, quant=None, remat=False):
    """(S, V) float32 logits of one sequence of token ids."""
    h = hidden(w, spec, tokens, quant, remat)
    table = w["embed"].T if spec.get("tie_embeddings") else w["head"]
    return _mm(h, table, quant)


@functools.partial(jax.jit, static_argnames=("spec_items", "control"))
def _readings(w, tokens, spec_items, control):
    spec = dict(spec_items)
    ref = jax.nn.log_softmax(logits(w, spec, tokens), axis=-1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    best = ref.max(axis=-1)
    served = jnp.take_along_axis(ref, nxt[:, None], axis=1)[:, 0]
    out = {"ref_lp": served, "gap": best - served}
    if control:
        ctl = jax.nn.log_softmax(logits(w, spec, tokens, "fp8"), axis=-1)
        first = jnp.argmax(ctl, axis=-1)
        out["control_gap"] = best - jnp.take_along_axis(
            ref, first[:, None], axis=1)[:, 0]
        out["control_lp"] = jnp.take_along_axis(ctl, nxt[:, None],
                                                axis=1)[:, 0]
    return out


def readings(w, spec: dict, tokens, control: bool = False) -> dict:
    """Per position t of one padded sequence, about the token at t + 1:
    `ref_lp`, its log-probability under the reference, and `gap`, how far
    that lies below the reference's best. With `control`, also
    `control_gap`, the gap of the token the fp8 pass puts first, and
    `control_lp`, the fp8 pass's log-probability of the token at t + 1.
    Compiled once per padded length."""
    import numpy as np
    items = tuple(sorted((k, v) for k, v in spec.items()
                         if isinstance(v, (int, float, bool, str))))
    out = _readings(w, jnp.asarray(tokens, jnp.int32), items, control)
    return {k: np.asarray(v, np.float64) for k, v in out.items()}
