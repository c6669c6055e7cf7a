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

Layer-state layout (the layer tree of models/lm.init_decode_state):
  attention layers   {"kv"}: one pool of token rows, (num_blocks *
                     block_size, 2 * KV * hd) — row block * block_size +
                     offset holds one token, K in the first KV * hd
                     columns and V in the last (models/attention.py's
                     row form); stacked layers carry a leading n_super
                     axis, and the layer loop carries that array and
                     updates it in place. An MLA layer's row is its
                     token's latent [c | k_pe], padded to 128-lane
                     tiles; a MoE layer's state also holds "routed",
                     its per-expert routed-token counters;
  recurrent layers   slot-indexed dense state, (num_slots, ...) per leaf —
                     O(num_slots), no paging needed.

Quantized pools (`kv_dtype` "int8" / "fp8") shrink the per-token pool
footprint 2-4x: attention layer dicts gain a float32 "kv_scale"
side-table of shape (num_blocks * block_size, 2 * KV) — one max-abs scale
per (token slot, kv head) over head_dim, K heads then V heads, the
`optim/compression.py` quantizer shape localized per pool slot. Per-slot
scales mean every write (prefill/decode/verify) quantizes independently —
no lossy requantization on incremental decode — and copying a block's
(q, scale) pair verbatim is an exact round-trip (the property the host
spill tier relies on). The default "fp16" maps to cfg.act_dtype, keeping
the unquantized path bit-identical to the pre-quantization layout.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import MOE_KINDS, ModelConfig
from repro.models import attention, lm
# BlockAllocator grew refcounts + the prefix-cache index and moved to its
# own layer; re-exported here for backward compatibility.
from repro.serving.block_manager import NULL_BLOCK, BlockAllocator  # noqa: F401

# block kinds whose KV lives in the paged pools (canonical set —
# the engine's prefix-cache gate and copy_block both key off it)
ATTN_KINDS = lm.ATTN_KINDS

# the leaves of an attention layer's state that are pool rows (a MoE
# layer's state also holds its expert counters, "routed": (E,) int32)
POOL_LEAVES = ("kv", "kv_scale")

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
    rows = num_blocks * block_size

    if quantized(kv_dtype) and cfg.kv_lora_rank:
        raise ValueError(
            f"kv_dtype {kv_dtype!r}: a latent (MLA) pool has no quantized "
            f"form yet (int8/fp8 pools keep one scale per KV head, and a "
            f"latent row has no heads); serve MLA models with kv_dtype "
            f"'fp16'")

    def layer_state(kind, lead=()):
        if kind in ATTN_KINDS:
            st = {"kv": jnp.zeros(lead + (rows, _row_width(cfg)), pool_dt)}
            if quantized(kv_dtype):
                st["kv_scale"] = jnp.zeros(lead + (rows, 2 * cfg.n_kv_heads),
                                           jnp.float32)
            if kind in MOE_KINDS:
                st["routed"] = jnp.zeros(lead + (cfg.n_experts,), jnp.int32)
            return st
        one = lm._init_block_state(cfg, kind, num_slots, 0, dt)
        return jax.tree.map(lambda x: jnp.zeros(lead + x.shape, x.dtype),
                            one)

    return {"prefix": [layer_state(k) for k in cfg.prefix_pattern],
            "blocks": {f"p{pi}": layer_state(kind, (cfg.n_super,))
                       for pi, kind in enumerate(cfg.block_pattern)}}


def _row_width(cfg: ModelConfig) -> int:
    """Columns of one token's pool row: its K and V heads side by side,
    or an MLA model's latent row padded to 128-lane tiles."""
    if cfg.kv_lora_rank:
        return attention.mla_pool_width(cfg)
    return 2 * cfg.n_kv_heads * cfg.head_dim


def _n_attn_layers(cfg: ModelConfig) -> int:
    return (sum(k in ATTN_KINDS for k in cfg.prefix_pattern)
            + cfg.n_super * sum(k in ATTN_KINDS for k in cfg.block_pattern))


def paged_bytes(cfg: ModelConfig, num_blocks: int, block_size: int,
                kv_dtype: str = "fp16") -> int:
    """Attention-cache bytes of the pool (the memory the paging bounds):
    one row per token as stored (2 * KV * hd elements, or a padded latent
    row) in the pool dtype, plus the f32 scale row of a quantized pool."""
    per_tok = _row_width(cfg) * pool_dtype(cfg, kv_dtype).itemsize
    if quantized(kv_dtype):
        per_tok += 2 * cfg.n_kv_heads * 4      # f32 scale per (slot, head)
    return _n_attn_layers(cfg) * num_blocks * block_size * per_tok


def block_bytes(cfg: ModelConfig, block_size: int,
                kv_dtype: str = "fp16") -> int:
    """Bytes one physical block occupies across all attention pools (the
    host-tier payload size per demoted block)."""
    return paged_bytes(cfg, 1, block_size, kv_dtype)


def _block_rows(ids, block_size: int):
    """Pool rows of blocks `ids` (...): (..., block_size) int32."""
    return ids[..., None] * block_size + jnp.arange(block_size,
                                                    dtype=jnp.int32)


def _at_rows(a, rows, stacked: bool):
    """Index of `rows` in pool leaf `a`, in every layer of a stacked one.
    The layer rides in the index, not in a slice window, so the scatter
    and gather take the pool in its row-major layout (a window over the
    layer axis makes XLA relayout the whole stack on TPU)."""
    if not stacked:
        return (rows,)
    layers = jnp.arange(a.shape[0], dtype=jnp.int32)
    return (layers.reshape((-1,) + (1,) * rows.ndim), rows[None])


def copy_block(cfg: ModelConfig, state, src, dst, block_size: int):
    """Copy one physical block in every attention pool (src/dst are
    traced int32 block ids, so one jitted instance serves all copies).
    The copy-on-write primitive: a sequence that must write into a shared
    prompt block gets a private copy first (see serving/scheduler.py).
    Every pool leaf is copied — quantized pools carry their scale tables
    with the payload, so a COW copy round-trips exactly.
    Recurrent slot state is untouched — it is per-slot, never shared."""
    src_rows = _block_rows(src, block_size)
    dst_rows = _block_rows(dst, block_size)

    def copy_layer(kind, st, stacked):
        if kind not in ATTN_KINDS:
            return st
        return {**st, **{n: a.at[_at_rows(a, dst_rows, stacked)].set(
            a[_at_rows(a, src_rows, stacked)]) for n, a in st.items()
            if n in POOL_LEAVES}}

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
    row table_row[p // block_size] * block_size + p % block_size;
    recurrent final states land at the slot index. Quantized pools
    quantize on landing, scattering (q, scale) per token slot.
    """
    def load_layer(kind, st, ca, stacked):
        if kind in ATTN_KINDS and "kv" in ca:
            # an MLA layer's latent rows: (B=1, P, L + R), stacked
            # (n_super, 1, P, L + R)
            lat = ca["kv"][:, 0] if stacked else ca["kv"][0]
            pos = jnp.arange(lat.shape[-2])
            rows = table_row[pos // block_size] * block_size + (
                pos % block_size)
            if not stacked:
                return attention.write_rows(st, None, rows, lat)
            for layer in range(lat.shape[0]):
                st = attention.write_rows(st, layer, rows, lat[layer])
            return st
        if kind in ATTN_KINDS:
            # ca k/v: (B=1, P, KV, hd), stacked: (n_super, 1, P, KV, hd)
            k, v = (ca["k"][:, 0], ca["v"][:, 0]) if stacked else (
                ca["k"][0], ca["v"][0])
            pos = jnp.arange(k.shape[-3])
            rows = table_row[pos // block_size] * block_size + (
                pos % block_size)
            if not stacked:
                return attention.write_kv(st, None, rows, k, v)
            for layer in range(k.shape[0]):
                st = attention.write_kv(st, layer, rows, k[layer], v[layer])
            return st
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
# attention pools. Payload leaves all carry the block-width axis FIRST —
# (W, block_size, C), stacked layers (W, n_super, block_size, C) — so
# host-side batching is a uniform axis-0 concatenate regardless of layer
# structure.
# ----------------------------------------------------------------------------

def gather_blocks(cfg: ModelConfig, state, ids, block_size: int):
    """Gather physical blocks `ids` ((W,) int32, traced) from every
    attention pool. Returns a pytree of (W, ...) leaves; recurrent layers
    contribute empty subtrees (their state is per-slot, never demoted)."""
    rows = _block_rows(ids, block_size)                  # (W, bs)

    def g(kind, st, stacked):
        if kind not in ATTN_KINDS:
            return {}
        pool = {n: a for n, a in st.items() if n in POOL_LEAVES}
        if stacked:
            return {n: jnp.moveaxis(a[_at_rows(a, rows, True)], 1, 0)
                    for n, a in pool.items()}
        return {n: a[rows] for n, a in pool.items()}

    prefix = [g(kind, st, False)
              for kind, st in zip(cfg.prefix_pattern, state["prefix"])]
    blocks = {f"p{pi}": g(kind, state["blocks"][f"p{pi}"], True)
              for pi, kind in enumerate(cfg.block_pattern)}
    return {"prefix": prefix, "blocks": blocks}


def scatter_blocks(cfg: ModelConfig, state, ids, payload, block_size: int):
    """Scatter a gather_blocks-shaped payload back into the pools at
    `ids`. Padded entries may target NULL_BLOCK (the null sink)."""
    rows = _block_rows(ids, block_size)

    def s(kind, st, pa, stacked):
        if kind not in ATTN_KINDS:
            return st
        if stacked:
            return {**st, **{n: a.at[_at_rows(a, rows, True)].set(
                jnp.moveaxis(pa[n], 0, 1).astype(a.dtype))
                for n, a in st.items() if n in POOL_LEAVES}}
        return {**st, **{n: a.at[rows].set(pa[n].astype(a.dtype))
                         for n, a in st.items() if n in POOL_LEAVES}}

    new_prefix = [s(kind, st, pa, False)
                  for kind, st, pa in zip(cfg.prefix_pattern,
                                          state["prefix"], payload["prefix"])]
    new_blocks = {f"p{pi}": s(kind, state["blocks"][f"p{pi}"],
                              payload["blocks"][f"p{pi}"], True)
                  for pi, kind in enumerate(cfg.block_pattern)}
    return {"prefix": new_prefix, "blocks": new_blocks}
