"""RG-LRU gated linear recurrence kernel:  h_t = a_t * h_{t-1} + x_t.

Grid (B, C/Cb, T/Tc) with the chunk axis sequential; the (Cb,) hidden state
stays in VMEM scratch across chunks. Channels are independent, so the
channel axis is freely parallel/shardable. Same state-residency argument as
rwkv6_scan: the jnp lax.scan round-trips h (B, C) through HBM per token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, x_ref, h0_ref, y_ref, hout_ref, h_ref, *, tc):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    def body(t, _):
        at = a_ref[0, t].astype(jnp.float32)
        xt = x_ref[0, t].astype(jnp.float32)
        h = at * h_ref[...] + xt
        h_ref[...] = h
        y_ref[0, t] = h.astype(y_ref.dtype)
        return ()

    jax.lax.fori_loop(0, tc, body, ())

    @pl.when(ci == nc - 1)
    def _emit():
        hout_ref[0] = h_ref[...]


@functools.partial(jax.jit, static_argnames=("tc", "cb", "interpret"))
def rglru_scan(a, x, h0=None, *, tc: int = 128, cb: int = 256,
               interpret: bool):
    """a, x: (B, T, C); h0: (B, C) or None. Returns (h_seq, h_final)."""
    B, T, C = a.shape
    if h0 is None:
        h0 = jnp.zeros((B, C), jnp.float32)
    tc = min(tc, T)
    cb = min(cb, C)
    grid = (B, pl.cdiv(C, cb), pl.cdiv(T, tc))
    x_spec = pl.BlockSpec((1, tc, cb), lambda b, cj, ci: (b, ci, cj))
    h_spec = pl.BlockSpec((1, cb), lambda b, cj, ci: (b, cj))

    kernel = functools.partial(_kernel, tc=tc)
    y, h_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, x_spec, h_spec],
        out_specs=(x_spec, h_spec),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((B, C), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((cb,), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, x, h0)
    return y, h_fin
