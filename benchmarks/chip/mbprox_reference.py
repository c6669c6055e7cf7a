"""The plain reference of the training cell: the paper's MBProx outer step
in its `local` form with the DANE correction (Algorithm 2 of arXiv
1702.06269, App. D), written out shard by shard in float32 on the plain
forward pass of `reference.py`. It imports nothing of the program.

One outer step over K shards, each holding n_micro microbatches, from the
anchor a (the parameters) and the momentum m:

    g_k   = grad L(a; shard k, microbatch 0)            for each k
    g     = mean_k g_k                                  (the DANE exchange)
    per shard k, from p = a, v = m, for j = 0 .. n_micro-1:
        d = grad L(p; shard k, microbatch j) + gamma (p - a) + (g - g_k)
        v = mu v + d ;  p = p - lr v
    a' = mean_k p_k ;  m' = mean_k v_k                  (the averaging)
    loss = mean over k and j of L at each inner step

L is the mean next-token cross-entropy over the microbatch's tokens. At
j = 0, p = a and the step's gradient is g itself, which is reused.

Each shard runs on its own device (`devices[k % len(devices)]`) and the
exchanges go through the first device. Options plant the faults a checked
step must catch: `half_batch` drops the second half of every microbatch's
rows; `no_exchange` leaves out every exchange between shards and returns
shard 0's result; `quant="fp8"` is the control's precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import reference


def _seq_loss(w, spec, quant, toks, tgts):
    lg = reference.logits(w, spec, toks, quant, remat=True)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tgts[:, None], axis=1)[:, 0]
    return jnp.mean(lse - gold)


def _items(spec):
    return tuple(sorted((k, v) for k, v in spec.items()
                        if isinstance(v, (int, float, bool, str))))


@functools.partial(jax.jit, static_argnames=("spec_items", "quant"))
def micro_grad(w, toks, tgts, spec_items, quant):
    """Loss and gradient of one microbatch (rows, S), one row at a time so
    that only one row's activations live at once."""
    spec = dict(spec_items)
    vg = jax.value_and_grad(_seq_loss)

    def row(acc, xs):
        lo, g = vg(w, spec, quant, *xs)
        return (acc[0] + lo, jax.tree.map(jnp.add, acc[1], g)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
    (lo, g), _ = jax.lax.scan(row, zero, (toks, tgts))
    n = toks.shape[0]
    return lo / n, jax.tree.map(lambda x: x / n, g)


@functools.partial(jax.jit, static_argnames=("spec_items", "quant"),
                   donate_argnames=("p", "v"))
def inner_step(p, v, a, corr, toks, tgts, lr, gamma, mu, spec_items, quant):
    lo, g = micro_grad(p, toks, tgts, spec_items, quant)
    d = jax.tree.map(lambda gi, pi, ai, ci: gi + gamma * (pi - ai) + ci,
                     g, p, a, corr)
    v = jax.tree.map(lambda vi, di: mu * vi + di, v, d)
    p = jax.tree.map(lambda pi, vi: pi - lr * vi, p, v)
    return p, v, lo


@jax.jit
def _first_update(a, v, g, lr, mu):
    v = jax.tree.map(lambda vi, gi: mu * vi + gi, v, g)
    return jax.tree.map(lambda ai, vi: ai - lr * vi, a, v), v


def _mean(trees, dev):
    trees = [jax.device_put(t, dev) for t in trees]
    n = len(trees)
    return jax.tree.map(lambda *xs: sum(xs) / n, *trees)


def outer_step(a, m, shards, spec, hp, devices, quant=None,
               half_batch=False, no_exchange=False):
    """One outer step. shards: K of (toks, tgts), each (n_micro, rows, S).
    hp: gamma, mu (momentum), lr. Returns (a', m', loss, g) with g the
    exchanged anchor gradient (shard 0's own under `no_exchange`)."""
    items = _items(spec)
    K = len(shards)
    dev = [devices[k % len(devices)] for k in range(K)]
    if half_batch:
        shards = [(t[:, :t.shape[1] // 2], y[:, :y.shape[1] // 2])
                  for t, y in shards]
    sh = [(jax.device_put(t, dev[k]), jax.device_put(y, dev[k]))
          for k, (t, y) in enumerate(shards)]
    a_k = [jax.device_put(a, d) for d in dev]
    m_k = [jax.device_put(m, d) for d in dev]
    first = [micro_grad(a_k[k], sh[k][0][0], sh[k][1][0], items, quant)
             for k in range(K)]
    g_loc = [g for _, g in first]
    if no_exchange:
        g_glob = g_loc
    else:
        g = _mean(g_loc, dev[0])
        g_glob = [jax.device_put(g, d) for d in dev]
    lr, gamma, mu = hp["lr"], hp["gamma"], hp["mu"]
    out_p, out_v, losses = [], [], []
    for k in range(K):
        corr = jax.tree.map(jnp.subtract, g_glob[k], g_loc[k])
        p, v = _first_update(a_k[k], m_k[k], g_glob[k], lr, mu)
        ls = [first[k][0]]
        for j in range(1, sh[k][0].shape[0]):
            p, v, lo = inner_step(p, v, a_k[k], corr, sh[k][0][j],
                                  sh[k][1][j], lr, gamma, mu, items, quant)
            ls.append(lo)
        out_p.append(p)
        out_v.append(v)
        losses.append(ls)
    loss = sum(float(x) for ls in losses for x in ls) / sum(
        len(ls) for ls in losses)
    if no_exchange:
        return out_p[0], out_v[0], loss, g_loc[0]
    return (_mean(out_p, dev[0]), _mean(out_v, dev[0]), loss,
            jax.device_put(g_glob[0], dev[0]))
