"""RWKV6 (Finch) recurrence kernel: chunked state-resident scan.

    S_t = diag(w_t) S_{t-1} + k_t^T v_t ;   y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)

Grid (B, H, T/Tc) with the chunk axis sequential ("arbitrary"): the (N, N)
state lives in VMEM scratch across chunks — zero HBM state traffic, and
r/k/v/w stream through VMEM in (Tc, N) tiles. The jnp reference scans over
single tokens with the state in HBM every step; per token the kernel removes
2 * N*N * 4B of state traffic (N=64: 32 KB/token/head) — the dominant term
at decode/training for attention-free archs (see EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref, s_ref, *,
            tc):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)                    # (N,)

    def body(t, _):
        rt = r_ref[0, 0, t].astype(jnp.float32)         # (N,)
        kt = k_ref[0, 0, t].astype(jnp.float32)
        vt = v_ref[0, 0, t].astype(jnp.float32)
        wt = w_ref[0, 0, t].astype(jnp.float32)
        S = s_ref[...]
        kv = kt[:, None] * vt[None, :]                  # (N, N)
        y = rt @ (S + u[:, None] * kv)                  # (N,)
        y_ref[0, 0, t] = y.astype(y_ref.dtype)
        s_ref[...] = wt[:, None] * S + kv
        return ()

    jax.lax.fori_loop(0, tc, body, ())

    @pl.when(ci == nc - 1)
    def _emit_state():
        sout_ref[0, 0] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("tc", "interpret"))
def rwkv6_scan(r, k, v, w, u, *, tc: int = 64, interpret: bool):
    """r/k/v/w: (B, H, T, N); u: (H, N). Returns (y, final_state)."""
    B, H, T, N = r.shape
    tc = min(tc, T)
    grid = (B, H, pl.cdiv(T, tc))
    x_spec = pl.BlockSpec((1, 1, tc, N), lambda b, h, c: (b, h, c, 0))
    u_spec = pl.BlockSpec((1, N), lambda b, h, c: (h, 0))
    s_spec = pl.BlockSpec((1, 1, N, N), lambda b, h, c: (b, h, 0, 0))

    kernel = functools.partial(_kernel, tc=tc)
    y, s_fin = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, x_spec, x_spec, x_spec, u_spec],
        out_specs=(x_spec, s_spec),
        out_shape=(jax.ShapeDtypeStruct(r.shape, r.dtype),
                   jax.ShapeDtypeStruct((B, H, N, N), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((N, N), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(r, k, v, w, u)
    return y, s_fin
