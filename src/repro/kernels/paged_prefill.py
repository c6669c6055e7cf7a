"""Chunk-parallel paged suffix-prefill attention (online softmax), TPU Pallas.

Suffix prefill for serving: each row's queries are a bucket-padded prompt
suffix whose KV cache prefix lives in fixed-size physical blocks of a
shared pool, mapped through a per-row block table. Queries attend to the
cached prefix (pool positions < starts[n]) plus the fresh suffix causally
— exactly `attention.streamed_paged_attention`, which is this kernel's
interpret-mode oracle.

Grid: (N, Ls/bq, M + Ls/bs) with the key axis innermost ("arbitrary"
semantics — sequential per (row, q-tile), carrying online-softmax stats
in VMEM scratch). The first M key steps stream physical pool blocks
gathered through the scalar-prefetched block table (skipped once past the
cached prefix); the remaining Ls/bs steps stream the fresh suffix K/V
tiles (skipped strictly above the causal diagonal). Only a
(group*bq, bs) score tile ever materializes — peak score memory is
independent of both the prompt length and the block-table bound M.

GQA: queries are laid out (N, KV, group, Ls, hd). Each step moves one
whole (bs, KV, hd) K/V tile — trailing dims equal to the arrays', as the
TPU tiling asks — and loops over the KV heads inside the kernel,
contracting each head's (group, bq) query tile against its (bs, hd)
slice, like kernels/paged_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(bt_ref, st_ref, ln_ref, q_ref, kp_ref, vp_ref, ks_ref, vs_ref,
            *refs, scale, bs, bq, M, window, quant):
    if quant:
        kps_ref, vps_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    n = pl.program_id(0)
    qi = pl.program_id(1)
    j = pl.program_id(2)
    nj = pl.num_programs(2)
    n_kv, G = m_ref.shape[0], m_ref.shape[1]     # G = group * bq rows

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    st = st_ref[n]
    ln = ln_ref[n]
    js = j - M                       # suffix tile index when j >= M
    qrow = jax.lax.broadcasted_iota(jnp.int32, (G, bs), 0) % bq
    qpos = st + qi * bq + qrow                               # absolute
    c = jax.lax.broadcasted_iota(jnp.int32, (G, bs), 1)

    def attend(kpos, valid, load_kv):
        """Online-softmax update of every KV head against one key tile;
        load_kv(h) gives head h's (bs, hd) K and V in float32."""
        if window > 0:
            valid = jnp.logical_and(valid, kpos > qpos - window)
        for h in range(n_kv):        # static loop over the tile's KV heads
            q = q_ref[0, h].astype(jnp.float32).reshape(G, -1)  # (g*bq, hd)
            k, v = load_kv(h)
            s = (q @ k.T) * scale                            # (g*bq, bs)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = alpha * l_ref[h] + p.sum(-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + p @ v
            m_ref[h] = m_new

    # pool blocks, skipped once past the cached prefix
    @pl.when(jnp.logical_and(j < M, j * bs < st))
    def _pool():
        if quant:
            kps = kps_ref[0].astype(jnp.float32)             # (bs, KV)
            vps = vps_ref[0].astype(jnp.float32)

        def load_kv(h):
            k = kp_ref[0, :, h].astype(jnp.float32)          # (bs, hd)
            v = vp_ref[0, :, h].astype(jnp.float32)
            if quant:                # dequantize the pool side in-register
                k = k * kps[:, h:h + 1]
                v = v * vps[:, h:h + 1]
            return k, v

        kpos = j * bs + c
        attend(kpos, kpos < st, load_kv)

    # fresh suffix tiles, skipped strictly above the causal diagonal
    @pl.when(jnp.logical_and(j >= M, js * bs <= qi * bq + bq - 1))
    def _suffix():
        def load_kv(h):
            return (ks_ref[0, :, h].astype(jnp.float32),
                    vs_ref[0, :, h].astype(jnp.float32))

        kpos = st + js * bs + c
        attend(kpos, jnp.logical_and(kpos <= qpos, js * bs + c < ln - st),
               load_kv)

    @pl.when(j == nj - 1)
    def _finalize():
        for h in range(n_kv):
            acc = acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)
            o_ref[0, h] = acc.reshape(o_ref.shape[2:]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bq", "interpret"))
def paged_prefill_attention(q, k_suf, v_suf, k_pool, v_pool, block_tables,
                            starts, lengths, *, k_scale=None, v_scale=None,
                            window: int = 0, bq: int = 128,
                            interpret: bool):
    """q: (N, Ls, H, hd) rope'd suffix queries; k_suf/v_suf: (N, Ls, KV, hd)
    fresh suffix K/V (not yet scattered into the pools); k_pool/v_pool:
    (P, bs, KV, hd) physical block pools; block_tables: (N, M) int32;
    starts/lengths: (N,) int32 (rows with lengths == 0 return garbage —
    mask downstream); k_scale/v_scale (optional): (P, bs, KV) float32
    side-tables of a quantized pool, dequantized in-kernel (the fresh
    suffix K/V stays full-precision). Returns (N, Ls, H, hd) in q.dtype."""
    N, Ls, H, hd = q.shape
    _, bs, KV, _ = k_pool.shape
    group = H // KV
    M = block_tables.shape[1]
    bq = min(bq, Ls)
    nq = pl.cdiv(Ls, bq)
    ns = pl.cdiv(Ls, bs)
    qg = q.reshape(N, Ls, KV, group, hd).transpose(0, 2, 3, 1, 4)
    quant = k_scale is not None

    def q_map(n, qi, j, bt_ref, st_ref, ln_ref):
        return (n, 0, 0, qi, 0)

    def pool_map(n, qi, j, bt_ref, st_ref, ln_ref):
        return (bt_ref[n, jnp.minimum(j, M - 1)], 0, 0, 0)

    def suf_map(n, qi, j, bt_ref, st_ref, ln_ref):
        return (n, jnp.clip(j - M, 0, ns - 1), 0, 0)

    def sc_map(n, qi, j, bt_ref, st_ref, ln_ref):
        return (bt_ref[n, jnp.minimum(j, M - 1)], 0, 0)

    # K/V and scale specs span the trailing (KV, hd) / KV dims whole, as
    # the TPU tiling asks: a grid step moves one block for all KV heads
    in_specs = [
        pl.BlockSpec((1, KV, group, bq, hd), q_map),
        pl.BlockSpec((1, bs, KV, hd), pool_map),
        pl.BlockSpec((1, bs, KV, hd), pool_map),
        pl.BlockSpec((1, bs, KV, hd), suf_map),
        pl.BlockSpec((1, bs, KV, hd), suf_map),
    ]
    operands = [qg, k_pool, v_pool, k_suf, v_suf]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, KV), sc_map),
                     pl.BlockSpec((1, bs, KV), sc_map)]
        operands += [k_scale, v_scale]

    kernel = functools.partial(_kernel, scale=hd**-0.5, bs=bs, bq=bq,
                               M=M, window=window, quant=quant)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(N, nq, M + ns),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, KV, group, bq, hd), q_map),
            scratch_shapes=[
                # per-KV-head m, l, acc live in VMEM across the key sweep
                pltpu.VMEM((KV, group * bq, 1), jnp.float32),
                pltpu.VMEM((KV, group * bq, 1), jnp.float32),
                pltpu.VMEM((KV, group * bq, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((N, KV, group, Ls, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(block_tables, starts, lengths, *operands)
    return out.transpose(0, 3, 1, 2, 4).reshape(N, Ls, H, hd)
