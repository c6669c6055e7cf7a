"""Paged decode attention (block-table gather), TPU Pallas.

Single-token decode where each sequence's KV cache lives in fixed-size
physical blocks of a shared pool; a per-sequence block table maps logical
block j to its physical block id. The block table and per-sequence context
lengths ride in as scalar-prefetch operands so the K/V BlockSpec index maps
can gather physical blocks directly — no head-expansion or cache
defragmentation copies ever touch HBM.

Grid: (B, M) with the logical-block axis innermost ("arbitrary"
semantics — sequential per sequence, carrying online-softmax stats in
VMEM scratch). Blocks at or past the context length are skipped entirely,
so decode attention reads ceil(ctx/bs) blocks per sequence, not the
allocation bound M.

GQA: queries are laid out (B, KV, group, hd). Each grid step gathers one
whole (bs, KV, hd) physical block — a block spec whose trailing dims equal
the pool's, which is what the TPU tiling accepts at any KV and head_dim —
and loops over the KV heads inside the kernel, contracting each head's
query group against its (bs, hd) K/V slice.

Quantized pools (serving/kv_cache.py kv_dtype "int8"/"fp8") pass their
per-(token slot, kv head) scale side-tables as extra operands; the kernel
dequantizes each gathered block in-register against a (1, bs, KV) scale
tile indexed by the same block-table map, so HBM still only ever moves the
narrow pool elements.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, cl_ref, q_ref, *refs, scale, bs, quant):
    if quant:
        k_ref, v_ref, ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)
    n_kv, group = q_ref.shape[1], q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = cl_ref[b]

    @pl.when(j * bs < ctx)
    def _compute():
        kpos = j * bs + jax.lax.broadcasted_iota(jnp.int32, (group, bs), 1)
        if quant:
            ks = ks_ref[0].astype(jnp.float32)           # (bs, KV) scales
            vs = vs_ref[0].astype(jnp.float32)
        for h in range(n_kv):        # static loop over the block's KV heads
            q = q_ref[0, h].astype(jnp.float32)          # (group, hd)
            k = k_ref[0, :, h].astype(jnp.float32)       # (bs, hd)
            v = v_ref[0, :, h].astype(jnp.float32)
            if quant:
                k = k * ks[:, h:h + 1]
                v = v * vs[:, h:h + 1]
            s = (q @ k.T) * scale                        # (group, bs)
            s = jnp.where(kpos < ctx, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + p @ v
            m_ref[h] = m_new

    @pl.when(j == nb - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pool, v_pool, block_tables, ctx_lens, *,
                    k_scale=None, v_scale=None, interpret: bool):
    """q: (B, H, hd); k_pool/v_pool: (N, bs, KV, hd);
    block_tables: (B, M) int32; ctx_lens: (B,) int32 valid-token counts
    (rows with ctx_lens == 0 return zeros); k_scale/v_scale (optional):
    (N, bs, KV) float32 side-tables of a quantized pool — when given the
    kernel dequantizes gathered blocks in-register. Returns (B, H, hd)."""
    B, H, hd = q.shape
    _, bs, KV, _ = k_pool.shape
    group = H // KV
    M = block_tables.shape[1]
    qg = q.reshape(B, KV, group, hd)
    quant = k_scale is not None

    def q_map(b, j, bt_ref, cl_ref):
        return (b, 0, 0, 0)

    def kv_map(b, j, bt_ref, cl_ref):
        return (bt_ref[b, j], 0, 0, 0)

    def sc_map(b, j, bt_ref, cl_ref):
        return (bt_ref[b, j], 0, 0)

    # every block spec spans the trailing dims whole, as the TPU tiling
    # asks: a grid step gathers one physical block for all KV heads
    in_specs = [
        pl.BlockSpec((1, KV, group, hd), q_map),
        pl.BlockSpec((1, bs, KV, hd), kv_map),
        pl.BlockSpec((1, bs, KV, hd), kv_map),
    ]
    operands = [qg, k_pool, v_pool]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, KV), sc_map),
                     pl.BlockSpec((1, bs, KV), sc_map)]
        operands += [k_scale, v_scale]

    kernel = functools.partial(_kernel, scale=hd**-0.5, bs=bs, quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, M),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KV, group, hd), q_map),
            scratch_shapes=[
                # per-KV-head m, l, acc live in VMEM across the
                # logical-block sweep
                pltpu.VMEM((KV, group, 1), jnp.float32),
                pltpu.VMEM((KV, group, 1), jnp.float32),
                pltpu.VMEM((KV, group, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, group, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(block_tables, ctx_lens, *operands)
    return out.reshape(B, H, hd)
