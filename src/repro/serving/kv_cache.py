"""Paged KV cache: fixed-size physical blocks + per-sequence block tables.

The seed decode path allocates (batch, max_len, KV, hd) per layer — memory
scales with the worst case whether or not tokens exist. Here every layer's
cache is a pool of `num_blocks` blocks of `block_size` tokens; a sequence
occupying `n` tokens holds ceil(n / block_size) blocks, found through its
block-table row. Memory scales with LIVE tokens across all slots — the
serving-side analogue of the paper's hold-a-minibatch memory accounting
(cache capacity is a token budget, not a batch x max_len rectangle).

Block 0 is reserved as the null sink: inactive decode slots point their
table rows at it, so the always-full-batch decode step has somewhere
harmless to write. The allocator never hands it out.

Layer-state layout (mirrors models/lm.init_decode_state):
  attention layers   {"k","v"}: (num_blocks, block_size, KV, hd) pools,
                     stacked layers carry a leading n_super axis;
  recurrent layers   slot-indexed dense state, (num_slots, ...) per leaf —
                     O(num_slots), no paging needed.

Quantized pools (`kv_dtype` "int8" / "fp8") shrink the per-token pool
footprint 2-4x: attention layer dicts gain float32 "k_scale"/"v_scale"
side-tables of shape (num_blocks, block_size, KV) — one max-abs scale per
(token slot, kv head) over head_dim, the `optim/compression.py` quantizer
shape localized per pool slot. Per-slot scales mean every write
(prefill/decode/verify) quantizes independently — no lossy requantization
on incremental decode — and copying a block's (q, scale) pair verbatim is
an exact round-trip (the property the host spill tier relies on). The
default "fp16" maps to cfg.act_dtype, keeping the unquantized path
bit-identical to the pre-quantization layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, lm
# BlockAllocator grew refcounts + the prefix-cache index and moved to its
# own layer; re-exported here for backward compatibility.
from repro.serving.block_manager import NULL_BLOCK, BlockAllocator  # noqa: F401

# block kinds whose KV lives in the paged pools (canonical set —
# the engine's prefix-cache gate and copy_block both key off it)
ATTN_KINDS = ("attn", "attn_local", "moe")

# pool precisions: "fp16" is the activation dtype (bit-identical default);
# the quantized modes carry per-slot scale side-tables.
KV_DTYPES = ("fp16", "int8", "fp8")


def pool_dtype(cfg: ModelConfig, kv_dtype: str = "fp16") -> jnp.dtype:
    """Element dtype of the K/V pools for a kv_dtype knob."""
    if kv_dtype == "fp16":
        return cfg.act_dtype
    if kv_dtype == "int8":
        return jnp.dtype(jnp.int8)
    if kv_dtype == "fp8":
        return jnp.dtype(jnp.float8_e4m3fn)
    raise ValueError(f"unknown kv_dtype {kv_dtype!r}; expected {KV_DTYPES}")


def quantized(kv_dtype: str) -> bool:
    return kv_dtype != "fp16"


def init_paged_state(cfg: ModelConfig, num_slots: int, num_blocks: int,
                     block_size: int, kv_dtype: str = "fp16"):
    """Paged decode-state pytree (same layer tree as init_decode_state)."""
    dt = cfg.act_dtype
    pool_dt = pool_dtype(cfg, kv_dtype)

    def layer_state(kind):
        if kind in ATTN_KINDS:
            shape = (num_blocks, block_size, cfg.n_kv_heads, cfg.head_dim)
            st = {"k": jnp.zeros(shape, pool_dt),
                  "v": jnp.zeros(shape, pool_dt)}
            if quantized(kv_dtype):
                st["k_scale"] = jnp.zeros(shape[:3], jnp.float32)
                st["v_scale"] = jnp.zeros(shape[:3], jnp.float32)
            return st
        return lm._init_block_state(cfg, kind, num_slots, 0, dt)

    state = {"prefix": [layer_state(k) for k in cfg.prefix_pattern]}
    blocks = {}
    for pi, kind in enumerate(cfg.block_pattern):
        one = layer_state(kind)
        blocks[f"p{pi}"] = jax.tree.map(
            lambda x: jnp.zeros((cfg.n_super,) + x.shape, x.dtype), one)
    state["blocks"] = blocks
    return state


def _n_attn_layers(cfg: ModelConfig) -> int:
    return (sum(k in ATTN_KINDS for k in cfg.prefix_pattern)
            + cfg.n_super * sum(k in ATTN_KINDS for k in cfg.block_pattern))


def paged_bytes(cfg: ModelConfig, num_blocks: int, block_size: int,
                kv_dtype: str = "fp16") -> int:
    """Attention-cache bytes of the pool (the memory the paging bounds),
    computed from the actual pool dtype plus the scale side-tables."""
    per_tok = 2 * cfg.n_kv_heads * cfg.head_dim * pool_dtype(cfg,
                                                             kv_dtype).itemsize
    if quantized(kv_dtype):
        per_tok += 2 * cfg.n_kv_heads * 4      # f32 scale per (slot, head)
    return _n_attn_layers(cfg) * num_blocks * block_size * per_tok


def block_bytes(cfg: ModelConfig, block_size: int,
                kv_dtype: str = "fp16") -> int:
    """Bytes one physical block occupies across all attention pools (the
    host-tier payload size per demoted block)."""
    return paged_bytes(cfg, 1, block_size, kv_dtype)


def copy_block(cfg: ModelConfig, state, src, dst):
    """Copy one physical block in every attention pool (src/dst are
    traced int32 block ids, so one jitted instance serves all copies).
    The copy-on-write primitive: a sequence that must write into a shared
    prompt block gets a private copy first (see serving/scheduler.py).
    Every pool leaf is copied — quantized pools carry their scale tables
    with the payload, so a COW copy round-trips exactly.
    Recurrent slot state is untouched — it is per-slot, never shared."""

    def copy_layer(kind, st, stacked):
        if kind not in ATTN_KINDS:
            return st
        if stacked:
            return {n: a.at[:, dst].set(a[:, src]) for n, a in st.items()}
        return {n: a.at[dst].set(a[src]) for n, a in st.items()}

    new_prefix = [copy_layer(kind, st, False)
                  for kind, st in zip(cfg.prefix_pattern, state["prefix"])]
    new_blocks = {f"p{pi}": copy_layer(kind, state["blocks"][f"p{pi}"], True)
                  for pi, kind in enumerate(cfg.block_pattern)}
    return {"prefix": new_prefix, "blocks": new_blocks}


def load_prefill(cfg: ModelConfig, state, cache, slot, table_row,
                 block_size: int):
    """Scatter one sequence's prefill cache (lm.prefill, batch=1) into the
    paged slot state.

    The engine's admission path fuses prefill and this scatter in
    `lm.prefill_paged`; this standalone per-sequence loader is the
    reference oracle it is tested against (tests/test_serving.py) and
    the library route for seeding paged state outside the engine.

    `slot` (int32 scalar) and `table_row` ((max_blocks,) int32) are traced,
    so one jitted instance serves every slot; the prompt length is static
    from `cache` leaf shapes. Attention K/V of prompt position p lands in
    physical block table_row[p // block_size], offset p % block_size;
    recurrent final states land at the slot index. Quantized pools
    quantize on landing, scattering (q, scale) per token slot.
    """
    def attn_positions(n_tok):
        pos = jnp.arange(n_tok)
        return table_row[pos // block_size], pos % block_size

    def load_layer(kind, st, ca, stacked):
        if kind in ATTN_KINDS:
            # ca k/v: (B=1, P, KV, hd), stacked: (n_super, 1, P, KV, hd)
            n_tok = ca["k"].shape[2] if stacked else ca["k"].shape[1]
            blk, off = attn_positions(n_tok)
            k, v = ca["k"], ca["v"]
            if "k_scale" in st:
                k, sk = attention.quantize_kv(k, st["k"].dtype)
                v, sv = attention.quantize_kv(v, st["v"].dtype)
            if stacked:
                out = {"k": st["k"].at[:, blk, off].set(k[:, 0]),
                       "v": st["v"].at[:, blk, off].set(v[:, 0])}
                if "k_scale" in st:
                    out["k_scale"] = st["k_scale"].at[:, blk, off].set(
                        sk[:, 0])
                    out["v_scale"] = st["v_scale"].at[:, blk, off].set(
                        sv[:, 0])
                return out
            out = {"k": st["k"].at[blk, off].set(k[0]),
                   "v": st["v"].at[blk, off].set(v[0])}
            if "k_scale" in st:
                out["k_scale"] = st["k_scale"].at[blk, off].set(sk[0])
                out["v_scale"] = st["v_scale"].at[blk, off].set(sv[0])
            return out
        if stacked:
            return jax.tree.map(lambda s, c: s.at[:, slot].set(c[:, 0]),
                                st, ca)
        return jax.tree.map(lambda s, c: s.at[slot].set(c[0]), st, ca)

    new_prefix = [load_layer(kind, st, ca, False)
                  for kind, st, ca in zip(cfg.prefix_pattern,
                                          state["prefix"], cache["prefix"])]
    new_blocks = {}
    for pi, kind in enumerate(cfg.block_pattern):
        key = f"p{pi}"
        new_blocks[key] = load_layer(kind, state["blocks"][key],
                                     cache["blocks"][key], True)
    return {"prefix": new_prefix, "blocks": new_blocks}


# ----------------------------------------------------------------------------
# host-tier payload movement: gather blocks out of / scatter back into the
# attention pools. Payload leaves all carry the block-width axis FIRST
# (stacked layers are transposed to (W, n_super, bs, KV, hd)) so host-side
# batching is a uniform axis-0 concatenate regardless of layer structure.
# ----------------------------------------------------------------------------

def gather_blocks(cfg: ModelConfig, state, ids):
    """Gather physical blocks `ids` ((W,) int32, traced) from every
    attention pool. Returns a pytree of (W, ...) leaves; recurrent layers
    contribute empty subtrees (their state is per-slot, never demoted)."""

    def g(kind, st, stacked):
        if kind not in ATTN_KINDS:
            return {}
        if stacked:
            return {n: jnp.moveaxis(a[:, ids], 1, 0) for n, a in st.items()}
        return {n: a[ids] for n, a in st.items()}

    prefix = [g(kind, st, False)
              for kind, st in zip(cfg.prefix_pattern, state["prefix"])]
    blocks = {f"p{pi}": g(kind, state["blocks"][f"p{pi}"], True)
              for pi, kind in enumerate(cfg.block_pattern)}
    return {"prefix": prefix, "blocks": blocks}


def scatter_blocks(cfg: ModelConfig, state, ids, payload):
    """Scatter a gather_blocks-shaped payload back into the pools at
    `ids`. Padded entries may target NULL_BLOCK (the null sink)."""

    def s(kind, st, pa, stacked):
        if kind not in ATTN_KINDS:
            return st
        if stacked:
            return {n: st[n].at[:, ids].set(
                jnp.moveaxis(pa[n], 0, 1).astype(st[n].dtype)) for n in st}
        return {n: st[n].at[ids].set(pa[n].astype(st[n].dtype)) for n in st}

    new_prefix = [s(kind, st, pa, False)
                  for kind, st, pa in zip(cfg.prefix_pattern,
                                          state["prefix"], payload["prefix"])]
    new_blocks = {f"p{pi}": s(kind, state["blocks"][f"p{pi}"],
                              payload["blocks"][f"p{pi}"], True)
                  for pi, kind in enumerate(cfg.block_pattern)}
    return {"prefix": new_prefix, "blocks": new_blocks}
