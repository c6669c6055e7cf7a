"""Fused variance-reduced prox update — the paper's inner-loop hot spot.

    x <- x - eta * (g_x - g_z + mu + gamma * (x - w_anchor))

Unfused, this is 5 HBM reads + 1 write with 4 intermediate round-trips;
fused it is a single pass (memory-bound, ~6x traffic reduction). Executed
n(eps)/m * log n(eps) times per training run, on parameter-sized vectors.

TPU mapping: 1D vectors are viewed as (rows, 256)-shaped tiles (lane width
aligned); BlockSpec streams (BLOCK_ROWS, 256) tiles HBM->VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 256
BLOCK_ROWS = 512  # (512, 256) f32 tile = 512 KB/operand; 6 operands ~ 3 MB


def _kernel(x_ref, gx_ref, gz_ref, mu_ref, w_ref, eta_ref, gamma_ref,
            out_ref):
    eta = eta_ref[0]
    gamma = gamma_ref[0]
    x = x_ref[...]
    g = (gx_ref[...] - gz_ref[...] + mu_ref[...]
         + gamma * (x - w_ref[...]))
    out_ref[...] = x - eta * g


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def svrg_update(x, g_x, g_z, mu, w_anchor, eta, gamma, *,
                interpret: bool, block_rows: int = BLOCK_ROWS):
    """All array args are 1-D of equal length; eta/gamma scalars."""
    (n,) = x.shape
    pad = (-n) % LANES
    def prep(a):
        a = jnp.pad(a, (0, pad))
        return a.reshape(-1, LANES)
    xs = [prep(a) for a in (x, g_x, g_z, mu, w_anchor)]
    rows = xs[0].shape[0]
    br = min(block_rows, rows)
    grid = (pl.cdiv(rows, br),)
    spec = pl.BlockSpec((br, LANES), lambda i: (i, 0))
    scalar_spec = pl.BlockSpec(memory_space=pl.ANY)

    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec] * 5 + [scalar_spec] * 2,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(xs[0].shape, x.dtype),
        interpret=interpret,
    )(*xs, jnp.asarray(eta, x.dtype).reshape(1),
      jnp.asarray(gamma, x.dtype).reshape(1))
    return out.reshape(-1)[:n]
