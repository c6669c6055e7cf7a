"""What every architecture module's forward and counts share: the float32
matrix product with the fp8 control's rounding, blocked causal attention,
and the bytes of one element as the program computes. `arch/<name>.py`
imports these; `reference.py` and `flops.py` import the architecture module
and nothing of it imports them back.

Every matrix product runs at `Precision.HIGHEST`, so a TPU computes it in
float32 and not in one bfloat16 pass. Attention is taken in blocks of query
rows so that long contexts fit.

`quant="fp8"` is the control: both operands of every matrix product rounded
to float8 (e4m3, one max-abs scale per tensor), the precision below the
program's bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
ACT_BYTES = 2          # bfloat16 weights and KV as the program computes


def f32(tree):
    """Weights as the reference reads them: float32, whatever type they are
    stored in (a no-op on float32 leaves). Cast where each is used, so a
    bfloat16 model never holds a whole float32 copy."""
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


def qdq(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(quant)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a, b, quant):
    return jnp.matmul(qdq(a, quant), qdq(b, quant), precision=HI)


def attention(q, k, v, quant):
    """q (S, KV, G, hd), k/v (S, KV, hd) -> (S, KV*G*hd), causal."""
    S, KV, G, hd = q.shape
    kpos = jnp.arange(S)
    kq, vq = qdq(k, quant), qdq(v, quant)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        n = qb.shape[0]
        s = jnp.einsum("qkgh,skh->kgqs", qdq(qb, quant), kq,
                       precision=HI) * hd ** -0.5
        mask = kpos[None, :] <= (lo + jnp.arange(n))[:, None]
        s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skh->qkgh", qdq(p, quant), vq, precision=HI)
        outs.append(o.reshape(n, KV * G * hd))
    return jnp.concatenate(outs, axis=0)
