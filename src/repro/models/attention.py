"""GQA attention: chunked-causal (memory-safe long prefill), local-windowed,
and single-token KV-cache decode.

The chunked implementation scans over query chunks so peak score memory is
O(B * H * chunk * S) instead of O(B * H * S^2) — required for the 32k prefill
cells. On TPU the Pallas flash kernel (repro.kernels.flash_attention) replaces
the inner chunk computation; the jnp path here doubles as its oracle.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.models.layers import (apply_rope, init_dense, rms_norm,
                                 yarn_freqs, yarn_mscale, yarn_rope_scale)

NEG_INF = -1e30

# ----------------------------------------------------------------------------
# Quantized paged KV pools. Scales are per-(token slot, kv head) max-abs
# over head_dim — the optim/compression.py quantizer shape, localized per
# pool slot so each write (prefill/decode/verify) quantizes independently
# and copying a block's (q, scale) pair verbatim is an exact round-trip.
# Quantized pool layer dicts carry a "kv_scale" side-table (see the row
# form below); consumers detect it by key presence, which is shape-static
# under jit.
# ----------------------------------------------------------------------------

_POOL_QMAX = {jnp.dtype(jnp.int8): 127.0}
_FP8 = getattr(jnp, "float8_e4m3fn", None)
if _FP8 is not None:
    _POOL_QMAX[jnp.dtype(_FP8)] = 448.0


def pool_qmax(dtype) -> float:
    """Max representable magnitude targeted by quantize_kv for a pool."""
    return _POOL_QMAX[jnp.dtype(dtype)]


def quantize_kv(x, dtype):
    """x: (..., KV, hd) -> (q: same shape in `dtype`, scale: (..., KV) f32).

    scale = max|x| / qmax over head_dim; q = x / max(scale, eps), rounded
    and clipped for integer pools (int8 uses the symmetric [-127, 127]
    range). All-zero slots get scale 0, so dequantize returns exact zeros.
    """
    qmax = pool_qmax(dtype)
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1) / qmax
    q = xf / jnp.maximum(scale, 1e-12)[..., None]
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    return q.astype(dtype), scale


def dequantize_kv(q, scale):
    """Inverse of quantize_kv: (..., KV, hd) pool values -> float32."""
    return q.astype(jnp.float32) * scale[..., None]


def init_attention(key, d_model, n_heads, n_kv_heads, head_dim, dtype):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_dense(kq, d_model, n_heads * head_dim, dtype),
        "wk": init_dense(kk, d_model, n_kv_heads * head_dim, dtype),
        "wv": init_dense(kv, d_model, n_kv_heads * head_dim, dtype),
        "wo": init_dense(ko, n_heads * head_dim, d_model, dtype),
    }


def _qkv(params, x, n_heads, n_kv_heads, head_dim, positions, rope_theta):
    B, S, _ = x.shape
    q = (x @ params["wq"]).reshape(B, S, n_heads, head_dim)
    k = (x @ params["wk"]).reshape(B, S, n_kv_heads, head_dim)
    v = (x @ params["wv"]).reshape(B, S, n_kv_heads, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def _gqa_scores(q, k):
    """q: (B, Sq, H, hd), k: (B, Sk, KV, hd) -> (B, H, Sq, Sk)."""
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    group = H // KV
    q = q.reshape(B, Sq, KV, group, hd)
    s = jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                   preferred_element_type=jnp.float32)
    return s.reshape(B, KV * group, Sq, k.shape[1])


def _gqa_out(p, v):
    """p: (B, H, Sq, Sk), v: (B, Sk, KV, hd) -> (B, Sq, H, hd).

    Probabilities are cast DOWN to the value dtype (not v up to f32 — that
    would materialize an f32 copy of the whole KV cache at decode); the
    matmul accumulates in f32 via preferred_element_type.
    """
    B, H, Sq, Sk = p.shape
    KV = v.shape[2]
    group = H // KV
    p = p.reshape(B, KV, group, Sq, Sk).astype(v.dtype)
    o = jnp.einsum("bkgqs,bskh->bqkgh", p, v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H, -1)


def chunked_causal_attention(q, k, v, *, chunk: int = 512, window: int = 0,
                             prefix_len: int = 0, scale=None):
    """Exact causal attention, scanned over query chunks.

    window > 0 => local attention (each query sees the last `window` keys).
    prefix_len > 0 => the first prefix_len positions attend bidirectionally
    (prefix-LM for the VLM arch).

    This is the training/single-shot-prefill path (attention_block's
    default; `cfg.attn_impl == "bisect"` swaps in bisect_causal_attention
    for long even sequences). The same `cfg.attn_chunk` knob also sets
    the KV band size of the serving-side streamed paged prefill
    (streamed_paged_attention / kernels/paged_prefill.py), so one config
    value bounds score-tile memory on both paths. `scale` defaults to
    hd ** -0.5; values may be narrower than queries and keys (MLA).
    """
    B, S, H, hd = q.shape
    scale = hd ** -0.5 if scale is None else scale
    chunk = min(chunk, S)
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qc = q.reshape(B, n_chunks, chunk, H, hd).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(S)

    def one_chunk(ci, qi):
        qpos = ci * chunk + jnp.arange(chunk)
        s = _gqa_scores(qi, k) * scale                   # (B,H,chunk,S) fp32
        causal = kpos[None, :] <= qpos[:, None]
        if prefix_len > 0:
            in_prefix = jnp.logical_and(qpos[:, None] < prefix_len,
                                        kpos[None, :] < prefix_len)
            causal = jnp.logical_or(causal, in_prefix)
        if window > 0:
            causal = jnp.logical_and(causal,
                                     kpos[None, :] > qpos[:, None] - window)
        s = jnp.where(causal[None, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        return _gqa_out(p, v)                            # (B,chunk,H,hd)

    out = lax.map(lambda args: one_chunk(*args),
                  (jnp.arange(n_chunks), qc))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, n_chunks * chunk, H, -1)
    return out[:, :S].astype(v.dtype)


# ----------------------------------------------------------------------------
# Bisection-causal attention: static-shape causal decomposition that skips
# the strictly-upper-triangular work.  causal(S) = [causal(S/2) on A;
# merge(full(B->A), causal(S/2) on B)], recursed `depth` levels: FLOPs drop
# from S^2 to (1/2 + 1/2^{depth+1}) S^2 — the HLO-measurable analogue of the
# flash kernel's block skipping (EXPERIMENTS.md §Perf).
# ----------------------------------------------------------------------------

def _attn_stats(q, k, v, scale, causal, mask=None):
    """Unnormalized flash stats. q: (B,Sq,H,hd), k/v: (B,Sk,KV,hd).
    `mask` (optional) is boolean, broadcastable against (B, 1, Sq, Sk)
    after the head axis is inserted — True keeps a score.
    Returns m (B,H,Sq), l (B,H,Sq), acc (B,Sq,H,hd) fp32."""
    s = _gqa_scores(q, k) * scale                       # (B,H,Sq,Sk) fp32
    if causal:
        Sq, Sk = s.shape[-2], s.shape[-1]
        cmask = jnp.arange(Sk)[None, :] <= jnp.arange(Sq)[:, None]
        s = jnp.where(cmask[None, None], s, NEG_INF)
    if mask is not None:
        s = jnp.where(mask[:, None], s, NEG_INF)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    acc = _gqa_out(p, v).astype(jnp.float32)
    return m, l, acc


def _merge_stats(a, b):
    m1, l1, acc1 = a
    m2, l2, acc2 = b
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    l = a1 * l1 + a2 * l2
    # alphas are (B,H,Sq); accs are (B,Sq,H,hd)
    w1 = a1.transpose(0, 2, 1)[..., None]
    w2 = a2.transpose(0, 2, 1)[..., None]
    return m, l, w1 * acc1 + w2 * acc2


def _bisect_stats(q, k, v, scale, depth):
    S = q.shape[1]
    if depth <= 0 or S % 2 or S < 256:
        return _attn_stats(q, k, v, scale, causal=True)
    h = S // 2
    sa = _bisect_stats(q[:, :h], k[:, :h], v[:, :h], scale, depth - 1)
    sbd = _bisect_stats(q[:, h:], k[:, h:], v[:, h:], scale, depth - 1)
    sbr = _attn_stats(q[:, h:], k[:, :h], v[:, :h], scale, causal=False)
    sb = _merge_stats(sbd, sbr)
    m = jnp.concatenate([sa[0], sb[0]], axis=-1)
    l = jnp.concatenate([sa[1], sb[1]], axis=-1)
    acc = jnp.concatenate([sa[2], sb[2]], axis=1)
    return m, l, acc


def bisect_causal_attention(q, k, v, *, depth: int = 3):
    """Exact causal attention with ~(0.5 + 2^-(depth+1)) S^2 FLOPs."""
    hd = q.shape[-1]
    m, l, acc = _bisect_stats(q, k, v, hd**-0.5, depth)
    out = acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(v.dtype)


def attention_block(params, x, positions, cfg, *, window: int = 0,
                    prefix_len: int = 0, return_kv: bool = False):
    """Full attention sub-layer (projections + chunked attention).

    return_kv=True also returns the rope'd (k, v) — exactly what the decode
    cache stores — so single-shot prefill can seed serving KV caches."""
    B, S, D = x.shape
    q, k, v = _qkv(params, x, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                   positions, cfg.rope_theta)
    if (cfg.attn_impl == "bisect" and window == 0 and prefix_len == 0
            and S % 2 == 0 and S >= 512):
        o = bisect_causal_attention(q, k, v)
    else:
        o = chunked_causal_attention(q, k, v, chunk=cfg.attn_chunk,
                                     window=window, prefix_len=prefix_len)
    out = o.reshape(B, S, -1) @ params["wo"]
    if return_kv:
        return out, (k, v)
    return out


# ----------------------------------------------------------------------------
# KV-cache decode
# ----------------------------------------------------------------------------

def init_kv_cache(batch, max_len, n_kv_heads, head_dim, dtype, n_super=None):
    shape = (batch, max_len, n_kv_heads, head_dim)
    if n_super is not None:
        shape = (n_super,) + shape
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attention_block(params, x, cache, pos, cfg, *, window: int = 0):
    """One-token decode. x: (B, 1, D); cache k/v: (B, S_max, KV, hd);
    pos: scalar int32 current position. Returns (out, new_cache).

    For window > 0 the cache is a rolling buffer of size `window`.
    """
    B, _, D = x.shape
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q, k, v = _qkv(params, x, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                   positions, cfg.rope_theta)
    S_max = cache["k"].shape[1]
    slot = jnp.where(window > 0, pos % jnp.maximum(window, 1), pos)
    slot = jnp.asarray(slot, jnp.int32)
    ck = lax.dynamic_update_slice(cache["k"], k, (0, slot, 0, 0))
    cv = lax.dynamic_update_slice(cache["v"], v, (0, slot, 0, 0))

    s = _gqa_scores(q, ck) * (cfg.head_dim ** -0.5)      # (B,H,1,S_max)
    kpos = jnp.arange(S_max)
    if window > 0:
        # rolling buffer: valid slots are those already written
        written = jnp.minimum(pos + 1, S_max)
        valid = kpos < written
    else:
        valid = kpos <= pos
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = _gqa_out(p, cv).reshape(B, 1, -1)
    return (o @ params["wo"]).astype(x.dtype), {"k": ck, "v": cv}


# ----------------------------------------------------------------------------
# Paged KV pools in row form
#
# A paged attention layer keeps its KV in one array of token rows,
# cache["kv"]: (num_blocks * block_size, 2 * KV * hd). Row
# block * block_size + offset holds one token: its K in the first KV * hd
# columns, its V in the last. The scanned layer stack carries one such
# array per pattern position with a leading n_super axis through the
# layer loop, and each layer reads and writes it at [layer, rows], so no
# program slices, relayouts or rebuilds the pool to touch one layer
# (`layer` is None for an unstacked pool). Quantized pools add
# cache["kv_scale"]: (..., num_blocks * block_size, 2 * KV) float32, the
# K heads' scales then the V heads'.
#
# On TPU the default layout of a row pool is row-major with no padding
# when the row width is a multiple of 128 lanes, as it is for every
# published config (384 for smollm-135m, 5120 for stablelm-3b). A row
# width that is not (a 192-wide pool, compiled for a v5e) gets a
# transposed default layout and whole-pool copies at each program's entry
# and exit; such a config would need the pool's layout pinned
# (jax.experimental.layout.Format) in every program that takes it.
# tests/test_tpu_compile.py checks the 384-wide case.
# ----------------------------------------------------------------------------

def _gather_blocks(pool, layer, block_tables, block_size):
    """The rows of every block in `block_tables` (..., M), read in place
    from `pool` (rows, C) or, with `layer`, from the stacked (n_super,
    rows, C). Returns (..., M * block_size, C).

    A block's rows are contiguous, so this gathers whole (block_size, C)
    slices through a (.., blocks, block_size, C) view of the pool (on a
    v5e a 30-layer decode-shaped loop took 10.3 ms so, 33.6 ms gathering
    the same rows one by one). The view is free (a bitcast) while
    block_size is a multiple of the TPU's 8-row tile, as the served
    block size of 16 is."""
    view = pool.reshape(*pool.shape[:-2], -1, block_size, pool.shape[-1])
    g = view.at[(block_tables,) if layer is None else (layer, block_tables)
                ].get(mode="clip")
    return g.reshape(*block_tables.shape[:-1], -1, pool.shape[-1])


def read_kv(cache, layer, block_tables, block_size, head_dim):
    """K and V of every token in the blocks of `block_tables` (..., M):
    two (..., M * block_size, KV, hd) arrays, in the pool dtype, or
    dequantized to float32 for a quantized pool."""
    kv = _gather_blocks(cache["kv"], layer, block_tables, block_size)
    kv = kv.reshape(*kv.shape[:-1], -1, head_dim)        # (..., S, 2KV, hd)
    if "kv_scale" in cache:
        kv = dequantize_kv(kv, _gather_blocks(cache["kv_scale"], layer,
                                              block_tables, block_size))
    n_kv = kv.shape[-2] // 2
    return kv[..., :n_kv, :], kv[..., n_kv:, :]


def write_kv(cache, layer, rows, k, v):
    """Land tokens' K and V (each (..., KV, hd)) at pool rows `rows` (...),
    quantizing on landing for a quantized pool. Returns the new cache."""
    kv = jnp.concatenate([k, v], axis=-2)                # (..., 2KV, hd)
    at = (rows,) if layer is None else (layer, rows)
    new_cache = dict(cache)
    if "kv_scale" in cache:
        kv, scale = quantize_kv(kv, cache["kv"].dtype)
        new_cache["kv_scale"] = cache["kv_scale"].at[at].set(scale)
    new_cache["kv"] = cache["kv"].at[at].set(
        kv.reshape(*kv.shape[:-2], -1).astype(cache["kv"].dtype))
    return new_cache


# ----------------------------------------------------------------------------
# Paged KV-cache prefill (bucketed batched admission over cached prefixes)
# ----------------------------------------------------------------------------

def streamed_paged_attention(q, k, v, cache, block_tables, positions,
                             starts, lengths, *, scale, attn_chunk,
                             block_size, window: int = 0, layer=None,
                             expand=None):
    """Online-softmax suffix-prefill attention over a paged KV cache.

    q: (N, Ls, H, hd) rope'd suffix queries; k/v: (N, Ls, KV, hd) rope'd
    suffix keys/values (NOT yet written to the pool); cache: a row-form
    pool (see above; `layer` picks a layer of a stacked one) holding the
    cached prefix; block_tables: (N, M); positions: (N, Ls) absolute
    query positions; starts/lengths: (N,) — queries attend to pool
    positions < starts and causally within the suffix (suffix index <
    lengths - starts).

    The pool is streamed in bands of ceil(attn_chunk / bs) blocks via
    lax.scan, folding each band into flash running stats (_attn_stats /
    _merge_stats), so peak score memory is O(N*H*Ls*(attn_chunk + Ls))
    — never the full O(N*H*Ls*(M*bs + Ls)) dense tensor. Doubles as the
    interpret-mode oracle for kernels/paged_prefill.py.

    `expand` (MLA) turns a band's gathered pool rows (N, cb*bs, C) into
    its keys and values; without it the rows are K|V rows (read_kv).

    Returns the normalized attention output (N, Ls, H, hd_v) float32.
    """
    N, Ls, H, hd = q.shape
    bs = block_size
    M = block_tables.shape[1]

    # suffix: fresh q vs fresh k/v, causal within the suffix window
    i = jnp.arange(Ls)
    causal = (i[None, :] <= i[:, None])[None]                # (1, Ls, Ls)
    in_suffix = (i[None, None, :]
                 < (lengths - starts)[:, None, None])        # (N, 1, Ls)
    valid_suf = jnp.logical_and(causal, in_suffix)           # (N, Ls, Ls)
    if window > 0:
        valid_suf = jnp.logical_and(
            valid_suf, positions[:, None, :]
            > positions[:, :, None] - window)
    suf = _attn_stats(q, k, v, scale, causal=False, mask=valid_suf)

    # cached prefix: stream the block table in fixed-size bands
    cb = max(1, -(-min(attn_chunk, M * bs) // bs))           # blocks/band
    nb = -(-M // cb)
    bt = block_tables
    if nb * cb > M:   # pad with null blocks (masked: kpos >= starts)
        bt = jnp.pad(bt, ((0, 0), (0, nb * cb - M)))
    bt = bt.reshape(N, nb, cb).transpose(1, 0, 2)            # (nb, N, cb)

    def band(stats, inp):
        bi, btc = inp                                        # btc: (N, cb)
        if expand is None:
            gk, gv = read_kv(cache, layer, btc, bs, hd)      # (N, cb*bs, ..)
        else:
            gk, gv = expand(_gather_blocks(cache["kv"], layer, btc, bs))
        kpos = bi * cb * bs + jnp.arange(cb * bs)
        m = (kpos[None, None, :] < starts[:, None, None])    # (N, 1, cb*bs)
        if window > 0:
            m = jnp.logical_and(
                m, kpos[None, None, :] > positions[:, :, None] - window)
        st = _attn_stats(q, gk, gv, scale, causal=False, mask=m)
        return _merge_stats(stats, st), None

    init = (jnp.full((N, H, Ls), NEG_INF, jnp.float32),
            jnp.zeros((N, H, Ls), jnp.float32),
            jnp.zeros((N, Ls, H, v.shape[-1]), jnp.float32))
    pre, _ = lax.scan(band, init, (jnp.arange(nb), bt))
    m, l, acc = _merge_stats(pre, suf)
    return acc / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]


def paged_prefill_attention_block(params, x, cache, positions, block_tables,
                                  starts, lengths, cached_lens, cfg, *,
                                  block_size, window: int = 0, layer=None):
    """Suffix prefill for a batch of sequences straight into paged KV.

    x: (N, Ls, D) — each row is one sequence's prompt SUFFIX (tokens from
    `starts[n]` on), right-padded to the bucket length Ls;
    positions: (N, Ls) absolute token positions (= starts[:, None] + i);
    starts: (N,) first computed position (cached prefix skipped, capped
    at lengths-1 so at least one token is always computed);
    lengths: (N,) true prompt lengths; cached_lens: (N,) tokens whose KV
    already sits in the sequence's blocks (scatter skips them);
    block_tables: (N, max_blocks); cache: a row-form pool, at `layer`.

    Queries attend to the cached prefix (streamed through the block
    table, masked to kpos < starts) plus the suffix causally; the
    suffix's rope'd K/V lands at row table[p // bs] * bs + p % bs for
    cached_lens <= p < lengths — padded and already-cached positions are
    redirected to the null block.

    The cached prefix is NOT gathered densely: a lax.scan walks the
    block table in bands of ceil(attn_chunk / bs) blocks, folding each
    band into flash-style online-softmax running stats (_attn_stats /
    _merge_stats — the same machinery bisect_causal_attention uses), so
    peak score memory is O(N * H * Ls * (attn_chunk + Ls)) instead of
    O(N * H * Ls * (M*bs + Ls)). This is the interpret-mode oracle for
    kernels/paged_prefill.py. Returns (out, new_cache).
    """
    N, Ls, D = x.shape
    q, k, v = _qkv(params, x, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                   positions, cfg.rope_theta)
    bs = block_size
    M = block_tables.shape[1]
    o = streamed_paged_attention(q, k, v, cache, block_tables, positions,
                                 starts, lengths, scale=cfg.head_dim ** -0.5,
                                 attn_chunk=cfg.attn_chunk, block_size=bs,
                                 window=window, layer=layer)
    out = (o.reshape(N, Ls, -1) @ params["wo"]).astype(x.dtype)

    write = jnp.logical_and(positions >= cached_lens[:, None],
                            positions < lengths[:, None])    # (N, Ls)
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(positions // bs, 0, M - 1), axis=1)
    blk = jnp.where(write, blk, 0)               # null-sink the rest
    rows = blk * bs + positions % bs
    return out, write_kv(cache, layer, rows, k, v)


# ----------------------------------------------------------------------------
# Paged KV-cache decode (continuous-batching serving)
# ----------------------------------------------------------------------------

def paged_decode_attention_block(params, x, cache, positions, block_tables,
                                 cfg, *, block_size, window: int = 0,
                                 layer=None):
    """One-token decode through a paged KV cache (serving/kv_cache.py).

    x: (B, 1, D) — one token per slot, B = number of decode slots;
    cache: a row-form pool shared by all slots (at `layer` of a stacked
    one); positions: (B,) int32 per-slot token positions (ragged — each
    slot is at its own depth); block_tables: (B, max_blocks) int32.

    The current token's K/V lands at row block_tables[b, p//bs] * bs +
    p % bs; keys and values are gathered back through the table. Slots
    whose table rows point at the reserved null block write garbage there
    and mask it out — inactive slots cost nothing but the batch lane.

    window > 0 masks to the trailing `window` positions (local attention
    keeps the full paged history; the mask, not a rolling buffer, bounds
    the receptive field). This is the pure-jnp oracle for
    kernels/paged_attention.py. Returns (out, new_cache).
    """
    B, _, D = x.shape
    q, k, v = _qkv(params, x, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                   positions[:, None], cfg.rope_theta)
    bs = block_size
    rows = block_tables[jnp.arange(B), positions // bs] * bs + positions % bs
    new_cache = write_kv(cache, layer, rows, k[:, 0], v[:, 0])
    gk, gv = read_kv(new_cache, layer, block_tables, bs, cfg.head_dim)
    s = _gqa_scores(q, gk) * (cfg.head_dim ** -0.5)      # (B, H, 1, M*bs)
    kpos = jnp.arange(gk.shape[1])
    valid = kpos[None, :] <= positions[:, None]
    if window > 0:
        valid = jnp.logical_and(valid,
                                kpos[None, :] > positions[:, None] - window)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = _gqa_out(p, gv).reshape(B, 1, -1)
    return (o @ params["wo"]).astype(x.dtype), new_cache


# ----------------------------------------------------------------------------
# Multi-head latent attention (MLA, DeepSeek-V2)
#
# Per token, x W_kv_a gives a kv_lora_rank-wide latent c (RMS-normed) and
# one qk_rope-wide rotary key k_pe shared by every head; W_kv_b expands c
# into each head's non-rotary key and its value. The cache holds one
# latent row per token, [c | k_pe], kv_lora_rank + qk_rope values (576 in
# DeepSeek-V2), in a pool row padded to whole 128-lane tiles
# (mla_pool_width: 640) so that the pool keeps its row-major layout on
# TPU. Queries are [q_nope | q_pe].
#
# Two forms of one attention:
#   expanded  each key and value is expanded per head from its latent
#             (the sequence forward and paged prefill, where a chunk's
#             queries share each expanded key);
#   absorbed  W_kv_b's key half is folded into the query and its value
#             half applied after attention, so decode reads each cached
#             latent row once and never expands per-head keys or values.
# ----------------------------------------------------------------------------

def init_mla(key, cfg, dtype):
    D, H, L = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    N, R, V = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    kq, ka, kb, ko = jax.random.split(key, 4)
    return {"wq": init_dense(kq, D, H * (N + R), dtype),
            "wkv_a": init_dense(ka, D, L + R, dtype),
            "kv_norm": jnp.zeros((L,), dtype),
            "wkv_b": init_dense(kb, L, H * (N + V), dtype),
            "wo": init_dense(ko, H * V, D, dtype)}


def mla_row_width(cfg) -> int:
    """Values one token's latent row holds: [c | k_pe]."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def mla_pool_width(cfg) -> int:
    """Columns of a pool row: the latent row padded to 128-lane tiles."""
    return -(-mla_row_width(cfg) // 128) * 128


def mla_scale(cfg) -> float:
    """Softmax scale: (qk_nope + qk_rope)^-1/2 times YaRN's mscale^2."""
    m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _mla_rope(x, positions, cfg):
    freqs = yarn_freqs(x.shape[-1], cfg) if cfg.yarn_factor else None
    out = apply_rope(x, positions, cfg.rope_theta, freqs)
    m = yarn_rope_scale(cfg) if cfg.yarn_factor else 1.0
    return out if m == 1.0 else (out * m).astype(out.dtype)


def mla_project(params, x, positions, cfg):
    """x (B, S, D) -> q_nope (B, S, H, N), rope'd q_pe (B, S, H, R) and
    the tokens' latent rows (B, S, L + R): the normed c, then rope'd
    k_pe."""
    B, S, _ = x.shape
    H, L = cfg.n_heads, cfg.kv_lora_rank
    N, R = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ params["wq"]).reshape(B, S, H, N + R)
    q_pe = _mla_rope(q[..., N:], positions, cfg)
    kva = x @ params["wkv_a"]
    c = rms_norm(kva[..., :L], params["kv_norm"], cfg.norm_eps)
    k_pe = _mla_rope(kva[..., None, L:], positions, cfg)[..., 0, :]
    return q[..., :N], q_pe, jnp.concatenate([c, k_pe], axis=-1)


def mla_expand(params, rows, cfg):
    """Latent rows (..., S, >= L + R) -> per-head keys (..., S, H, N + R)
    and values (..., S, H, V)."""
    H, L = cfg.n_heads, cfg.kv_lora_rank
    N, R = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    with jax.named_scope("mla_expand"):
        kv = rows[..., :L] @ params["wkv_b"].astype(rows.dtype)
        kv = kv.reshape(*kv.shape[:-1], H, N + cfg.v_head_dim)
        k_pe = jnp.broadcast_to(rows[..., None, L:L + R],
                                kv.shape[:-1] + (R,))
        return jnp.concatenate([kv[..., :N], k_pe], axis=-1), kv[..., N:]


def mla_absorbed(params, q_nope, q_pe, rows, valid, cfg):
    """Absorbed attention over latent rows. q_nope (B, Sq, H, N), q_pe
    (B, Sq, H, R); rows (B, Sk, C) in the cache dtype, C >= L + R, with
    finite columns past L + R; valid (B, Sq, Sk) bool. Returns
    (B, Sq, H * V) float32.

    Scores are [q_nope W_kb | q_pe] . [c | k_pe], over whole rows (the
    query zero-padded to C, so the rows are read as stored); the output
    is (p . c) W_vb."""
    B, Sq, H, N = q_nope.shape
    L, V = cfg.kv_lora_rank, cfg.v_head_dim
    C, dt = rows.shape[-1], rows.dtype
    with jax.named_scope("mla_absorb"):
        w = params["wkv_b"].reshape(L, H, N + V)
        q_lat = jnp.einsum("bqhn,lhn->bqhl", q_nope, w[..., :N],
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_lat.astype(dt), q_pe.astype(dt)], axis=-1)
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, C - q.shape[-1]),))
        s = jnp.einsum("bqhc,bsc->bhqs", q, rows,
                       preferred_element_type=jnp.float32) * mla_scale(cfg)
        s = jnp.where(valid[:, None], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhqs,bsc->bqhc", p.astype(dt), rows,
                           preferred_element_type=jnp.float32)[..., :L]
        o = jnp.einsum("bqhl,lhv->bqhv", o_lat.astype(w.dtype), w[..., N:],
                       preferred_element_type=jnp.float32)
    return o.reshape(B, Sq, H * V)


def mla_block(params, x, positions, cfg, *, return_kv: bool = False):
    """Sequence form (train / single-shot prefill): expanded, causal.
    return_kv=True also returns the latent rows (B, S, L + R), what the
    decode cache stores."""
    B, S, _ = x.shape
    q_nope, q_pe, latent = mla_project(params, x, positions, cfg)
    k, v = mla_expand(params, latent, cfg)
    o = chunked_causal_attention(jnp.concatenate([q_nope, q_pe], axis=-1),
                                 k, v, chunk=cfg.attn_chunk,
                                 scale=mla_scale(cfg))
    out = o.reshape(B, S, -1) @ params["wo"]
    if return_kv:
        return out, latent
    return out


def init_mla_cache(batch, max_len, cfg, dtype):
    return {"kv": jnp.zeros((batch, max_len, mla_row_width(cfg)), dtype)}


def mla_decode_block(params, x, cache, pos, cfg):
    """One-token decode against a dense latent cache {"kv": (B, S_max,
    L + R)}; pos: scalar position. Absorbed. Returns (out, new_cache)."""
    B = x.shape[0]
    positions = jnp.full((B, 1), pos, dtype=jnp.int32)
    q_nope, q_pe, latent = mla_project(params, x, positions, cfg)
    rows = lax.dynamic_update_slice(
        cache["kv"], latent.astype(cache["kv"].dtype),
        (0, jnp.asarray(pos, jnp.int32), 0))
    valid = jnp.broadcast_to(jnp.arange(rows.shape[1]) <= pos,
                             (B, 1, rows.shape[1]))
    o = mla_absorbed(params, q_nope, q_pe, rows, valid, cfg)
    return (o.astype(x.dtype) @ params["wo"]).astype(x.dtype), {"kv": rows}


def write_rows(cache, layer, rows, vals):
    """Land latent rows `vals` (..., L + R) at pool rows `rows` (...),
    zero-padded to the pool's width. Returns the new cache."""
    pool = cache["kv"]
    vals = vals.astype(pool.dtype)
    pad = pool.shape[-1] - vals.shape[-1]
    if pad:
        vals = jnp.pad(vals, ((0, 0),) * (vals.ndim - 1) + ((0, pad),))
    at = (rows,) if layer is None else (layer, rows)
    return {**cache, "kv": pool.at[at].set(vals)}


def paged_mla_prefill_block(params, x, cache, positions, block_tables,
                            starts, lengths, cached_lens, cfg, *,
                            block_size, layer=None):
    """MLA suffix prefill straight into a latent pool, with the arguments
    and masking of paged_prefill_attention_block. Expanded: each band of
    cached latent rows is expanded to per-head keys and values inside the
    streamed scan, once per dispatch, and the chunk's queries share it.
    Returns (out, new_cache)."""
    N, Ls, _ = x.shape
    bs = block_size
    M = block_tables.shape[1]
    q_nope, q_pe, latent = mla_project(params, x, positions, cfg)
    k, v = mla_expand(params, latent, cfg)
    o = streamed_paged_attention(
        jnp.concatenate([q_nope, q_pe], axis=-1), k, v, cache, block_tables,
        positions, starts, lengths, scale=mla_scale(cfg),
        attn_chunk=cfg.attn_chunk, block_size=bs, layer=layer,
        expand=lambda rows: mla_expand(params, rows, cfg))
    out = (o.reshape(N, Ls, -1).astype(x.dtype) @ params["wo"]).astype(
        x.dtype)
    write = jnp.logical_and(positions >= cached_lens[:, None],
                            positions < lengths[:, None])    # (N, Ls)
    blk = jnp.take_along_axis(block_tables,
                              jnp.clip(positions // bs, 0, M - 1), axis=1)
    blk = jnp.where(write, blk, 0)               # null-sink the rest
    return out, write_rows(cache, layer, blk * bs + positions % bs, latent)


def paged_mla_decode_block(params, x, cache, positions, block_tables, cfg,
                           *, block_size, layer=None):
    """One-token MLA decode through a latent pool, with the arguments of
    paged_decode_attention_block. Absorbed: each lane's cached latent
    rows are read once, as stored. Returns (out, new_cache)."""
    B = x.shape[0]
    bs = block_size
    q_nope, q_pe, latent = mla_project(params, x, positions[:, None], cfg)
    rows = block_tables[jnp.arange(B), positions // bs] * bs + positions % bs
    new_cache = write_rows(cache, layer, rows, latent[:, 0])
    g = _gather_blocks(new_cache["kv"], layer, block_tables, bs)
    valid = jnp.arange(g.shape[1])[None, :] <= positions[:, None]
    o = mla_absorbed(params, q_nope, q_pe, g, valid[:, None, :], cfg)
    return (o.astype(x.dtype) @ params["wo"]).astype(x.dtype), new_cache
