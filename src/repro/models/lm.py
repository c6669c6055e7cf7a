"""Decoder LM supporting every assigned architecture via block patterns.

Layer stacking: `cfg.block_pattern` defines a *superblock* (e.g. ('rec','rec',
'attn_local') for recurrentgemma, ('attn','moe') for llama4); the model is
`prefix_pattern` unrolled layers followed by `lax.scan` over `n_super`
stacked superblocks (keeps HLO size O(1) in depth — essential for the 512-
device dry-run compiles) with `jax.checkpoint` rematerialization.

Entry points:
  init_params(key, cfg)
  train_loss(params, cfg, batch)              -> loss, metrics
  forward(params, cfg, batch)                 -> logits            (prefill)
  prefill(params, cfg, batch)                 -> logits, kv cache  (serving;
         batch may carry "lengths" for right-padded mixed-length rows)
  init_decode_state(cfg, batch, max_len)      -> state pytree
  decode_step(params, cfg, state, tokens, pos)-> logits, new state (decode)
  decode_step_paged(params, cfg, state, tokens, positions, block_tables,
                    block_size)
      -> logits, new state    (continuous-batching decode over paged KV;
         see serving/ for slot scheduling and block allocation)
  prefill_paged(params, cfg, state, tokens, lengths, cached_lens,
                block_tables, slots, block_size)
      -> last_logits, new state   (bucketed batched prefill straight into
         paged state, skipping prefix-cached tokens; see serving/runner)
  decode_verify_paged(params, cfg, state, tokens, positions, counts,
                      block_tables, block_size)
      -> per-position logits, new state, recurrent snapshots
         (batched K-token verify forward for speculative decoding;
         commit_decode_state(cfg, state, snapshots, idx) accepts/rolls
         back recurrent slot state at each lane's accepted length)
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import MOE_KINDS, ModelConfig
from repro.distributed import context as dctx
from repro.models import attention, frontends, moe as moe_lib, recurrent
from repro.models.layers import (init_embed, init_mlp, mlp, rms_norm,
                                 softmax_xent)


def _pin_block(block_params):
    """Apply in-loop sharding constraints to sliced layer weights (see
    distributed/context.py). No-op when no specs are registered."""
    specs = dctx.get_inloop_specs()
    if specs is None:
        return block_params
    return jax.lax.with_sharding_constraint(block_params, specs)


def _pin_act(h):
    """Pin activations to batch-over-data (see distributed/context.py)."""
    spec = dctx.get_activation_spec()
    if spec is None:
        return h
    return jax.lax.with_sharding_constraint(h, spec)


# block kinds with multi-head latent attention
MLA_KINDS = ("mla", "mla_moe")


# ----------------------------------------------------------------------------
# Block init / apply
# ----------------------------------------------------------------------------

def _init_block(key, cfg: ModelConfig, kind: str):
    k1, k2 = jax.random.split(key)
    D, dt = cfg.d_model, cfg.p_dtype
    norms = {"norm1": jnp.zeros((D,), dt), "norm2": jnp.zeros((D,), dt)}
    if kind == "mla":
        return {**norms, "attn": attention.init_mla(k1, cfg, dt),
                "mlp": init_mlp(k2, D, cfg.d_ff, dt, cfg.mlp_kind)}
    if kind == "mla_moe":
        return {**norms, "attn": attention.init_mla(k1, cfg, dt),
                "moe": moe_lib.init_moe(k2, D, cfg.expert_d_ff,
                                        cfg.n_experts, dt, cfg.mlp_kind,
                                        cfg.n_shared_experts)}
    if kind in ("attn", "attn_local"):
        return {**norms,
                "attn": attention.init_attention(
                    k1, D, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dt),
                "mlp": init_mlp(k2, D, cfg.d_ff, dt, cfg.mlp_kind)}
    if kind == "moe":
        return {**norms,
                "attn": attention.init_attention(
                    k1, D, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dt),
                "moe": moe_lib.init_moe(k2, D, cfg.expert_d_ff,
                                        cfg.n_experts, dt, cfg.mlp_kind,
                                        cfg.n_shared_experts)}
    if kind == "rwkv":
        return {**norms,
                "tmix": recurrent.init_rwkv(k1, D, cfg.n_heads, cfg.head_dim,
                                            dt),
                "cmix": recurrent.init_rwkv_channel_mix(k2, D, cfg.d_ff, dt)}
    if kind == "rec":
        return {**norms,
                "rec": recurrent.init_rglru_block(k1, D, cfg.rnn_width,
                                                  cfg.conv_width, dt),
                "mlp": init_mlp(k2, D, cfg.d_ff, dt, cfg.mlp_kind)}
    raise ValueError(kind)


def _ffn(params, kind: str, h, cfg: ModelConfig, *, valid=None,
         layer=None):
    """The block's feed-forward on normed h: the MLP, or the experts,
    routed dropless where the config says so (`cfg.dropless`) and else
    through the capacity dispatch. With `layer`, the routed experts are
    the whole layer stack (see _paged_stack). Returns (out, aux,
    routed-or-None)."""
    if kind not in MOE_KINDS:
        return mlp(params["mlp"], h, cfg.mlp_kind), {}, None
    if cfg.dropless:
        return moe_lib.moe_dropless(params["moe"], h, cfg,
                                    kind=cfg.mlp_kind, valid=valid,
                                    layer=layer)
    o, aux = moe_lib.moe_block(params["moe"], h, cfg, kind=cfg.mlp_kind)
    return o, aux, None


def _apply_block_seq(params, kind: str, x, positions, cfg: ModelConfig,
                     state=None, prefix_len: int = 0,
                     collect_kv: bool = False, lengths=None):
    """Sequence form (train / prefill). Returns (x, new_state, aux).

    collect_kv=True makes attention layers return their rope'd K/V as
    new_state (the decode-cache contents) so `prefill` can seed serving
    caches in one pass; recurrent layers already return final states.

    lengths: optional (B,) true lengths for right-padded batched
    prefill. Attention needs no masking (trailing pads are causally
    invisible to valid queries); recurrent layers freeze their state
    past each row's length so final states are exact (see recurrent.py).
    """
    aux = {}
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    new_state = state
    if kind in MLA_KINDS:
        o = attention.mla_block(params["attn"], h, positions, cfg,
                                return_kv=collect_kv)
        if collect_kv:
            o, latent = o
            new_state = {"kv": latent}
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        o2, aux, _ = _ffn(params, kind, h2, cfg)
        x = x + o2
    elif kind in ("attn", "attn_local"):
        window = cfg.window if kind == "attn_local" else 0
        o = attention.attention_block(params["attn"], h, positions, cfg,
                                      window=window, prefix_len=prefix_len,
                                      return_kv=collect_kv)
        if collect_kv:
            o, (k_seq, v_seq) = o
            new_state = {"k": k_seq, "v": v_seq}
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = x + mlp(params["mlp"], h2, cfg.mlp_kind)
    elif kind == "moe":
        o = attention.attention_block(params["attn"], h, positions, cfg,
                                      prefix_len=prefix_len,
                                      return_kv=collect_kv)
        if collect_kv:
            o, (k_seq, v_seq) = o
            new_state = {"k": k_seq, "v": v_seq}
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        o2, aux, _ = _ffn(params, kind, h2, cfg)
        x = x + o2
    elif kind == "rwkv":
        st_t = None if state is None else state["tmix"]
        o, st_t = recurrent.rwkv_seq(params["tmix"], h, cfg, st_t,
                                     lengths=lengths)
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        st_c = None if state is None else state["cmix"]
        o2, shift = recurrent.rwkv_channel_mix(params["cmix"], h2, st_c,
                                               lengths=lengths)
        x = x + o2
        new_state = {"tmix": st_t, "cmix": shift}
    elif kind == "rec":
        st = None if state is None else state["rec"]
        o, st = recurrent.rglru_block_seq(params["rec"], h, cfg, st,
                                          lengths=lengths)
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = x + mlp(params["mlp"], h2, cfg.mlp_kind)
        new_state = {"rec": st}
    else:
        raise ValueError(kind)
    return x, new_state, aux


# ----------------------------------------------------------------------------
# Model init
# ----------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig):
    keys = jax.random.split(key, 8)
    D, V, dt = cfg.d_model, cfg.vocab_size, cfg.p_dtype
    params = {}
    if cfg.frontend == "audio":
        params["embed"] = frontends.init_audio_embed(
            keys[0], cfg.n_codebooks, V, D, dt)
    else:
        params["embed"] = init_embed(keys[0], V, D, dt)
    if cfg.frontend == "vision":
        params["vision"] = frontends.init_vision_frontend(
            keys[1], cfg.vision_dim, D, dt)

    # prefix (remainder) layers: unrolled, small
    prefix = []
    for i, kind in enumerate(cfg.prefix_pattern):
        prefix.append(_init_block(jax.random.fold_in(keys[2], i), cfg, kind))
    params["prefix"] = prefix

    # stacked superblocks: one stacked pytree per pattern position
    blocks = {}
    for pi, kind in enumerate(cfg.block_pattern):
        layer_keys = jax.random.split(
            jax.random.fold_in(keys[3], pi), cfg.n_super)
        blocks[f"p{pi}"] = jax.vmap(
            lambda k: _init_block(k, cfg, kind))(layer_keys)
    params["blocks"] = blocks

    params["final_norm"] = jnp.zeros((D,), dt)
    if not cfg.tie_embeddings:
        if cfg.frontend == "audio":
            params["head"] = (jax.random.normal(
                keys[4], (D, cfg.n_codebooks * V)) * D**-0.5).astype(dt)
        else:
            params["head"] = (jax.random.normal(keys[4], (D, V))
                              * D**-0.5).astype(dt)
    return params


# ----------------------------------------------------------------------------
# Sequence forward (train / prefill)
# ----------------------------------------------------------------------------

def cast_params(params, cfg: ModelConfig):
    """Cast float params to the compute dtype (single cast at step entry;
    master copies stay in cfg.param_dtype — standard mixed precision)."""
    dt = cfg.act_dtype

    def cast(p):
        if jnp.issubdtype(p.dtype, jnp.floating):
            return p.astype(dt)
        return p

    return jax.tree.map(cast, params)


def _embed_inputs(params, cfg: ModelConfig, batch):
    """Returns (h (B,S,D), positions (B,S), prefix_len)."""
    if cfg.frontend == "vision":
        tok_emb = jnp.take(params["embed"], batch["tokens"], axis=0)
        vis_emb = frontends.vision_embed(params["vision"],
                                         batch["vision_emb"]
                                         .astype(cfg.act_dtype))
        h = jnp.concatenate([vis_emb.astype(cfg.act_dtype),
                             tok_emb.astype(cfg.act_dtype)], axis=1)
        prefix_len = vis_emb.shape[1]
    elif cfg.frontend == "audio":
        h = frontends.audio_embed(params["embed"],
                                  batch["tokens"]).astype(cfg.act_dtype)
        prefix_len = 0
    else:
        h = jnp.take(params["embed"], batch["tokens"],
                     axis=0).astype(cfg.act_dtype)
        prefix_len = 0
    B, S = h.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return h, positions, prefix_len


def _run_blocks_seq(params, cfg: ModelConfig, h, positions, prefix_len,
                    remat: bool = True, collect_kv: bool = False,
                    lengths=None):
    """Runs prefix layers + the superblock scan. Returns (h, aux, states);
    states is the per-layer decode cache (see _apply_block_seq collect_kv)
    when collect_kv=True, else None — the scan carry/ys stay identical to
    the train path in that case."""
    aux_acc = {"moe_aux": 0.0, "moe_zloss": 0.0}

    prefix_states = []
    for p, kind in zip(params["prefix"], cfg.prefix_pattern):
        h, st, aux = _apply_block_seq(p, kind, h, positions, cfg,
                                      prefix_len=prefix_len,
                                      collect_kv=collect_kv,
                                      lengths=lengths)
        prefix_states.append(st)
        for k in aux:
            aux_acc[k] = aux_acc[k] + aux[k]

    def superblock(h, block_params):
        block_params = _pin_block(block_params)
        h = _pin_act(h)
        aux_s = {"moe_aux": jnp.zeros((), jnp.float32),
                 "moe_zloss": jnp.zeros((), jnp.float32)}
        states = {}
        for pi, kind in enumerate(cfg.block_pattern):
            h, st, aux = _apply_block_seq(block_params[f"p{pi}"], kind, h,
                                          positions, cfg,
                                          prefix_len=prefix_len,
                                          collect_kv=collect_kv,
                                          lengths=lengths)
            if collect_kv:
                states[f"p{pi}"] = st
            for k in aux:
                aux_s[k] = aux_s[k] + aux[k]
        return h, ((aux_s, states) if collect_kv else aux_s)

    if remat:
        # 'dots' saves matmul outputs so backward skips the re-forward —
        # but only dots WITHOUT batch dims (saving the (B,H,S,S) attention
        # score dots costs ~18 GB/device at 4k; measured, §Perf)
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots" else None)
        fn = jax.checkpoint(superblock, policy=policy)
    else:
        fn = superblock
    h, ys = lax.scan(lambda c, p: fn(c, p), h, params["blocks"])
    auxs, block_states = ys if collect_kv else (ys, None)
    for k in aux_acc:
        aux_acc[k] = aux_acc[k] + (auxs[k].sum() if k in auxs else 0.0)
    states = ({"prefix": prefix_states, "blocks": block_states}
              if collect_kv else None)
    return h, aux_acc, states


def forward(params, cfg: ModelConfig, batch, remat: bool = False):
    """Full-sequence logits (prefill). For vision inputs, logits cover the
    text region only."""
    params = cast_params(params, cfg)
    h, positions, prefix_len = _embed_inputs(params, cfg, batch)
    h, aux, _ = _run_blocks_seq(params, cfg, h, positions, prefix_len,
                                remat=remat)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if cfg.frontend == "vision":
        h = h[:, prefix_len:]
    logits = _head(params, cfg, h)
    return logits, aux


def _head(params, cfg: ModelConfig, h):
    if cfg.tie_embeddings:
        table = params["embed"]
        if cfg.frontend == "audio":
            # (n_cb, Vc, D) -> logits (B,S,n_cb,Vc)
            return jnp.einsum("bsd,cvd->bscv", h, table)
        return h @ table.T
    head = params["head"]
    if cfg.frontend == "audio":
        B, S, D = h.shape
        return (h @ head).reshape(B, S, cfg.n_codebooks, cfg.vocab_size)
    return h @ head


def prefill(params, cfg: ModelConfig, batch):
    """Single-shot chunked prefill: one jit call over the whole prompt.

    Returns (logits (B, S, V), cache) where `cache` mirrors the
    init_decode_state layer tree: attention layers hold their rope'd
    {"k","v"} of shape (B, S, KV, hd) (stacked layers carry a leading
    n_super axis from the scan), recurrent layers hold their final states.
    serving/kv_cache.load_prefill scatters this into paged slot state.

    batch may carry "lengths" ((B,) int32 true lengths) for right-padded
    mixed-length batches: attention is exact under trailing padding
    (causal masking), recurrent layers freeze past each row's length, so
    per-row cache states and logits[b, lengths[b]-1] match an unpadded
    run. KV at padded positions is garbage — consumers must slice or
    mask by length (prefill_paged's scatter does).

    Replaces the seed's token-by-token cache priming loop: S sequential
    decode_step dispatches (each a (B,1,D) matmul) collapse into one
    chunked-causal forward with MXU-shaped matmuls.
    """
    params = cast_params(params, cfg)
    h, positions, prefix_len = _embed_inputs(params, cfg, batch)
    h, _, cache = _run_blocks_seq(params, cfg, h, positions, prefix_len,
                                  remat=False, collect_kv=True,
                                  lengths=batch.get("lengths"))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if cfg.frontend == "vision":
        h = h[:, prefix_len:]
    logits = _head(params, cfg, h)
    return logits, cache


def train_loss(params, cfg: ModelConfig, batch, remat: bool = True):
    logits, aux = forward(params, cfg, batch, remat=remat)
    targets = batch["targets"]
    loss = softmax_xent(logits, targets).mean()
    total = loss + 0.01 * aux["moe_aux"] + 1e-4 * aux["moe_zloss"]
    return total, {"xent": loss, **aux}


# ----------------------------------------------------------------------------
# Decode (single-token step with per-layer state)
# ----------------------------------------------------------------------------

def _init_block_state(cfg: ModelConfig, kind: str, batch: int, max_len: int,
                      dtype):
    if kind == "attn":
        return attention.init_kv_cache(batch, max_len, cfg.n_kv_heads,
                                       cfg.head_dim, dtype)
    if kind == "attn_local":
        return attention.init_kv_cache(batch, min(cfg.window, max_len),
                                       cfg.n_kv_heads, cfg.head_dim, dtype)
    if kind == "moe":
        return attention.init_kv_cache(batch, max_len, cfg.n_kv_heads,
                                       cfg.head_dim, dtype)
    if kind in MLA_KINDS:
        return attention.init_mla_cache(batch, max_len, cfg, dtype)
    if kind == "rwkv":
        return {"tmix": {"shift": jnp.zeros((batch, cfg.d_model), dtype),
                         "S": jnp.zeros((batch, cfg.n_heads, cfg.head_dim,
                                         cfg.head_dim), jnp.float32)},
                "cmix": jnp.zeros((batch, cfg.d_model), dtype)}
    if kind == "rec":
        return {"rec": {"h": jnp.zeros((batch, cfg.rnn_width), jnp.float32),
                        "conv": jnp.zeros((batch, cfg.conv_width - 1,
                                           cfg.rnn_width), dtype)}}
    raise ValueError(kind)


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int):
    dt = cfg.act_dtype
    state = {"prefix": [
        _init_block_state(cfg, kind, batch, max_len, dt)
        for kind in cfg.prefix_pattern]}
    blocks = {}
    for pi, kind in enumerate(cfg.block_pattern):
        one = _init_block_state(cfg, kind, batch, max_len, dt)
        blocks[f"p{pi}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.n_super,) + x.shape),
            one)
    state["blocks"] = blocks
    return state


def _apply_block_step(params, kind: str, x, pos, cfg: ModelConfig, state):
    """One-token form. x: (B,1,D)."""
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if kind in ATTN_KINDS:
        if kind in MLA_KINDS:
            o, new_cache = attention.mla_decode_block(params["attn"], h,
                                                      state, pos, cfg)
        else:
            window = cfg.window if kind == "attn_local" else 0
            o, new_cache = attention.decode_attention_block(
                params["attn"], h, state, pos, cfg, window=window)
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        o2, _, _ = _ffn(params, kind, h2, cfg)
        return x + o2, new_cache
    if kind == "rwkv":
        o, st_t = recurrent.rwkv_seq(params["tmix"], h, cfg, state["tmix"])
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        o2, shift = recurrent.rwkv_channel_mix(params["cmix"], h2,
                                               state["cmix"])
        x = x + o2
        return x, {"tmix": st_t, "cmix": shift}
    if kind == "rec":
        o, st = recurrent.rglru_block_seq(params["rec"], h, cfg,
                                          state["rec"])
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = x + mlp(params["mlp"], h2, cfg.mlp_kind)
        return x, {"rec": st}
    raise ValueError(kind)


def decode_step(params, cfg: ModelConfig, state, tokens, pos):
    """tokens: (B,) int32 (or (B, n_cb) for audio); pos: scalar int32.
    Returns (logits, new_state)."""
    params = cast_params(params, cfg)
    if cfg.frontend == "audio":
        h = frontends.audio_embed(params["embed"],
                                  tokens[:, None, :]).astype(cfg.act_dtype)
    else:
        h = jnp.take(params["embed"], tokens[:, None],
                     axis=0).astype(cfg.act_dtype)

    new_prefix = []
    for p, kind, st in zip(params["prefix"], cfg.prefix_pattern,
                           state["prefix"]):
        h, st_new = _apply_block_step(p, kind, h, pos, cfg, st)
        new_prefix.append(st_new)

    def superblock(h, xs):
        block_params, block_state = xs
        block_params = _pin_block(block_params)
        h = _pin_act(h)
        new_state = {}
        for pi, kind in enumerate(cfg.block_pattern):
            h, st = _apply_block_step(block_params[f"p{pi}"], kind, h, pos,
                                      cfg, block_state[f"p{pi}"])
            new_state[f"p{pi}"] = st
        return h, new_state

    h, new_blocks = lax.scan(superblock, h,
                             (params["blocks"], state["blocks"]))
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(params, cfg, h)[:, 0]
    return logits, {"prefix": new_prefix, "blocks": new_blocks}


# ----------------------------------------------------------------------------
# Paged decode (continuous-batching serving: per-slot ragged positions)
# ----------------------------------------------------------------------------

# block kinds whose KV lives in the paged pools
ATTN_KINDS = ("attn", "attn_local", "moe", "mla", "mla_moe")


def _count_routes(state, layer, routed):
    """Add a dispatch's routed-token counts (E,) to a MoE layer's device
    counter (its paged state's "routed", (n_super, E) when stacked); the
    counters are read back only when the engine is asked for them."""
    if routed is None:
        return state
    at = () if layer is None else (layer,)
    return {**state, "routed": state["routed"].at[at].add(routed)}


def _paged_stack(params, cfg: ModelConfig, state, h, apply):
    """Runs the prefix layers and the superblock scan over paged state.

    apply(block_params, kind, h, layer_state, layer) -> (h, new_state,
    snapshot-or-None) is one layer; `layer` is the scan index for a
    stacked attention pool and None otherwise. The stacked attention
    pools (see attention.py's row form) ride in the scan CARRY, so each
    layer updates its rows in place and nothing copies a pool; recurrent
    slot state, small and per layer, stays in xs/ys. Returns (h,
    new_state, snapshots) with snapshots mirroring the state tree."""
    new_prefix, prefix_snaps = [], []
    for p, kind, st in zip(params["prefix"], cfg.prefix_pattern,
                           state["prefix"]):
        h, st, snap = apply(p, kind, h, st, None)
        new_prefix.append(st)
        prefix_snaps.append(snap)

    pooled = {f"p{pi}" for pi, kind in enumerate(cfg.block_pattern)
              if kind in ATTN_KINDS}
    pools = {k: v for k, v in state["blocks"].items() if k in pooled}
    slot_state = {k: v for k, v in state["blocks"].items()
                  if k not in pooled}
    # a stacked MoE layer's routed experts stay out of the scan's xs and
    # each layer reads them whole, at its index (moe.split_stacked_experts)
    blocks, experts = dict(params["blocks"]), {}
    for pi, kind in enumerate(cfg.block_pattern):
        if kind in MOE_KINDS:
            if not cfg.dropless:
                raise ValueError(
                    "the paged serving paths route MoE dropless: set "
                    "ModelConfig.dropless (ServingEngine does)")
            key = f"p{pi}"
            rest, experts[key] = moe_lib.split_stacked_experts(
                blocks[key]["moe"])
            blocks[key] = {**blocks[key], "moe": rest}

    def superblock(carry, xs):
        h, pools = carry
        block_params, slot_state, layer = xs
        block_params = {k: ({**p, "moe": {**p["moe"], **experts[k]}}
                            if k in experts else p)
                        for k, p in block_params.items()}
        block_params = _pin_block(block_params)
        h = _pin_act(h)
        pools = dict(pools)
        new_state, snaps = {}, {}
        for pi, kind in enumerate(cfg.block_pattern):
            key = f"p{pi}"
            if key in pools:
                h, pools[key], _ = apply(block_params[key], kind, h,
                                         pools[key], layer)
            else:
                h, new_state[key], snaps[key] = apply(
                    block_params[key], kind, h, slot_state[key], None)
        return (h, pools), (new_state, snaps)

    (h, pools), (new_slot, block_snaps) = lax.scan(
        superblock, (h, pools),
        (blocks, slot_state, jnp.arange(cfg.n_super, dtype=jnp.int32)))
    return (h, {"prefix": new_prefix, "blocks": {**new_slot, **pools}},
            {"prefix": prefix_snaps, "blocks": block_snaps})


def _apply_block_step_paged(params, kind: str, x, positions,
                            cfg: ModelConfig, state, block_tables,
                            block_size: int, layer=None):
    """One-token step against paged attention state. x: (B,1,D);
    positions: (B,) per-slot. Non-attention layers keep slot-indexed dense
    state (O(B) per layer) and ignore positions. Idle lanes (an all-null
    table row) are computed and not counted in the expert counters."""
    if kind in ATTN_KINDS:
        h = rms_norm(x, params["norm1"], cfg.norm_eps)
        if kind in MLA_KINDS:
            o, new_cache = attention.paged_mla_decode_block(
                params["attn"], h, state, positions, block_tables, cfg,
                block_size=block_size, layer=layer)
        else:
            window = cfg.window if kind == "attn_local" else 0
            o, new_cache = attention.paged_decode_attention_block(
                params["attn"], h, state, positions, block_tables, cfg,
                block_size=block_size, window=window, layer=layer)
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        valid = (block_tables[:, :1] != 0) if kind in MOE_KINDS else None
        o2, _, routed = _ffn(params, kind, h2, cfg, valid=valid,
                             layer=layer)
        return x + o2, _count_routes(new_cache, layer, routed)
    # rwkv / rec: position-independent recurrences; reuse the dense step
    return _apply_block_step(params, kind, x, 0, cfg, state)


def _apply_block_prefill_paged(params, kind: str, x, positions,
                               cfg: ModelConfig, state, block_tables,
                               block_size: int, starts, lengths,
                               cached_lens, slots, resume: bool = False,
                               layer=None):
    """Batched suffix-prefill against paged state. x: (N, Ls, D).

    Attention layers attend to their cached prefix through the block
    table and scatter the suffix K/V into the pools; recurrent layers
    run the length-masked sequence form and scatter final states at the
    slot indices (out-of-range slots, used for padding rows, drop).
    resume=False starts recurrent layers fresh (recurrent archs cannot
    resume from block-structured caches — the engine forces
    cached_lens = 0 for them); resume=True (a chunked-prefill
    continuation) gathers each row's initial recurrent state from its
    slot, where the previous chunk's dispatch scattered it."""
    if kind in ATTN_KINDS:
        h = rms_norm(x, params["norm1"], cfg.norm_eps)
        if kind in MLA_KINDS:
            o, new_cache = attention.paged_mla_prefill_block(
                params["attn"], h, state, positions, block_tables, starts,
                lengths, cached_lens, cfg, block_size=block_size,
                layer=layer)
        else:
            window = cfg.window if kind == "attn_local" else 0
            o, new_cache = attention.paged_prefill_attention_block(
                params["attn"], h, state, positions, block_tables, starts,
                lengths, cached_lens, cfg, block_size=block_size,
                window=window, layer=layer)
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        valid = (positions < lengths[:, None]) if kind in MOE_KINDS \
            else None
        o2, _, routed = _ffn(params, kind, h2, cfg, valid=valid,
                             layer=layer)
        return x + o2, _count_routes(new_cache, layer, routed)
    # rwkv / rec: run over the chunk, freeze past length
    init = None
    if resume:
        num_slots = jax.tree.leaves(state)[0].shape[0]
        idx = jnp.clip(slots, 0, num_slots - 1)
        init = jax.tree.map(lambda s: s[idx], state)
    x, fin, _ = _apply_block_seq(params, kind, x, positions, cfg,
                                 state=init, lengths=lengths - starts)
    new_state = jax.tree.map(
        lambda s, c: s.at[slots].set(c.astype(s.dtype), mode="drop"),
        state, fin)
    return x, new_state


def prefill_paged(params, cfg: ModelConfig, state, tokens, lengths,
                  cached_lens, block_tables, slots, block_size: int,
                  resume: bool = False):
    """Bucketed batched prefill straight into the paged serving state.

    tokens: (N, Ls) int32 — row n holds the prompt SUFFIX starting at
    min(cached_lens[n], lengths[n]-1), right-padded to the bucket length
    Ls; lengths: (N,) true prompt lengths; cached_lens: (N,) tokens
    already present in the row's blocks (prefix-cache hits — their
    compute AND their KV writes are skipped, except the last prompt
    token which is always recomputed so first-token logits exist);
    block_tables: (N, max_blocks) int32; slots: (N,) decode-slot index
    per row (recurrent dense state lands there; pass num_slots to drop,
    e.g. for batch-padding rows, which should also use lengths = 0 and
    all-null table rows); block_size: tokens per block of the pools.
    resume=True marks a chunked-prefill continuation: recurrent layers
    pick their initial state up from the slot instead of starting fresh
    (attention layers resume through cached_lens either way). Must be a
    static jit argument.

    One jitted instance serves every batch whose (N, Ls) matches — the
    scheduler buckets suffix lengths into powers of two precisely so the
    number of prefill compilations is bounded by the bucket count, not
    by the number of distinct prompt lengths in the workload.

    Returns (last_logits (N, V) at each row's true last prompt token,
    new_state).
    """
    params = cast_params(params, cfg)
    N, Ls = tokens.shape
    starts = jnp.minimum(cached_lens, jnp.maximum(lengths - 1, 0))
    positions = starts[:, None] + jnp.arange(Ls, dtype=jnp.int32)[None, :]
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.act_dtype)

    def apply(p, kind, h, st, layer):
        h, st = _apply_block_prefill_paged(
            p, kind, h, positions, cfg, st, block_tables, block_size,
            starts, lengths, cached_lens, slots, resume=resume, layer=layer)
        return h, st, None

    h, new_state, _ = _paged_stack(params, cfg, state, h, apply)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(params, cfg, h)                         # (N, Ls, V)
    idx = jnp.clip(lengths - 1 - starts, 0, Ls - 1)
    last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
    return last, new_state


def _apply_block_verify_paged(params, kind: str, x, positions,
                              cfg: ModelConfig, state, block_tables,
                              block_size: int, starts, counts, layer=None):
    """Batched K-token verify step against paged state. x: (B, T, D);
    row b holds `counts[b]` draft-chain tokens starting at absolute
    position starts[b], right-padded to the bucket length T.

    Attention layers reuse the suffix-prefill path (attend to the
    committed history through the block table + causally within the
    chain; scatter the chain's K/V — rollback is free because stale
    writes past the accepted point are position-masked and overwritten
    when those positions are re-fed). Recurrent layers resume from the
    live per-slot state, freeze past counts, and return PER-STEP state
    snapshots instead of committing: the slot state is committed later
    by commit_decode_state at each lane's accepted length. Returns
    (x, new_state, snapshots-or-None)."""
    if kind in ATTN_KINDS:
        x, new_state = _apply_block_prefill_paged(
            params, kind, x, positions, cfg, state, block_tables,
            block_size, starts, starts + counts, starts, None, layer=layer)
        return x, new_state, None
    h = rms_norm(x, params["norm1"], cfg.norm_eps)
    if kind == "rwkv":
        o, _, snap_t = recurrent.rwkv_seq(params["tmix"], h, cfg,
                                          state["tmix"], lengths=counts,
                                          return_states=True)
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        o2, _, snap_c = recurrent.rwkv_channel_mix(
            params["cmix"], h2, state["cmix"], lengths=counts,
            return_states=True)
        x = x + o2
        return x, state, {"tmix": snap_t, "cmix": snap_c}
    if kind == "rec":
        o, _, snap = recurrent.rglru_block_seq(params["rec"], h, cfg,
                                               state["rec"],
                                               lengths=counts,
                                               return_states=True)
        x = x + o
        h2 = rms_norm(x, params["norm2"], cfg.norm_eps)
        x = x + mlp(params["mlp"], h2, cfg.mlp_kind)
        return x, state, {"rec": snap}
    raise ValueError(kind)


def decode_verify_paged(params, cfg: ModelConfig, state, tokens, positions,
                        counts, block_tables, block_size: int):
    """Batched K-token verify forward through the paged cache — the
    verify half of the propose/verify speculative-decode pipeline.

    tokens: (B, T) int32 — row b is the draft chain [pending token,
    draft_1, ..., draft_{k}] right-padded to the bucket length T;
    positions: (B,) int32 absolute position of each row's first token;
    counts: (B,) int32 true chain lengths (0 = inactive lane: nothing
    is computed or written for it); block_tables: (B, max_blocks);
    block_size: tokens per block of the pools.

    Returns (logits (B, T, V) — logits[b, i] are the next-token logits
    after consuming chain token i, exactly what decode_step_paged would
    have produced feeding the chain one token at a time —, new_state,
    snapshots). Attention K/V of all `counts` chain positions is
    scattered eagerly (stale entries from a later-rejected suffix are
    position-masked until overwritten — attention rollback is just not
    advancing the position). Recurrent slot state is NOT advanced:
    `snapshots` holds the recurrent layers of the state tree with
    per-step (T+1, B, ...) stacks (leading n_super axis for scanned
    blocks); commit_decode_state gathers index a+1 per lane to accept
    a draft prefix of length a, or 0 to roll back entirely.
    """
    params = cast_params(params, cfg)
    B, T = tokens.shape
    pos_grid = positions[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    h = jnp.take(params["embed"], tokens, axis=0).astype(cfg.act_dtype)

    def apply(p, kind, h, st, layer):
        return _apply_block_verify_paged(p, kind, h, pos_grid, cfg, st,
                                         block_tables, block_size,
                                         positions, counts, layer=layer)

    h, new_state, snaps = _paged_stack(params, cfg, state, h, apply)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(params, cfg, h)                          # (B, T, V)
    return logits, new_state, snaps


def commit_decode_state(cfg: ModelConfig, state, snapshots, idx):
    """Commit per-slot recurrent state after a verify step.

    snapshots: the per-step state stacks from decode_verify_paged;
    idx: (B,) int32 — tokens of lane b's chain to accept (a+1 for an
    accepted draft prefix of length a, 0 to keep the pre-verify state,
    e.g. for lanes that sat out the dispatch). Attention state needs no
    commit (positions are the rollback) and passes through untouched;
    recurrent leaves are gathered at their lane's accepted snapshot.
    Returns the committed state."""
    B = idx.shape[0]
    lanes = jnp.arange(B)

    def gather(snap_leaf, stacked):
        if stacked:                     # (n_super, T+1, B, ...)
            return snap_leaf[:, idx, lanes]
        return snap_leaf[idx, lanes]    # (T+1, B, ...)

    new_prefix = []
    for kind, st, snap in zip(cfg.prefix_pattern, state["prefix"],
                              snapshots["prefix"]):
        if snap is None:
            new_prefix.append(st)
        else:
            new_prefix.append(jax.tree.map(
                lambda s: gather(s, False), snap))
    new_blocks = {}
    for pi, kind in enumerate(cfg.block_pattern):
        key = f"p{pi}"
        snap = snapshots["blocks"].get(key)
        if snap is None:
            new_blocks[key] = state["blocks"][key]
        else:
            new_blocks[key] = jax.tree.map(
                lambda s: gather(s, True), snap)
    return {"prefix": new_prefix, "blocks": new_blocks}


def decode_step_paged(params, cfg: ModelConfig, state, tokens, positions,
                      block_tables, block_size: int):
    """One decode iteration for a slot batch. tokens: (B,) int32;
    positions: (B,) int32 per-slot token positions (ragged — slots decode
    independently); block_tables: (B, max_blocks) int32; block_size:
    tokens per block of the pools.
    Returns (logits (B, V), new_state)."""
    params = cast_params(params, cfg)
    h = jnp.take(params["embed"], tokens[:, None],
                 axis=0).astype(cfg.act_dtype)

    def apply(p, kind, h, st, layer):
        h, st = _apply_block_step_paged(p, kind, h, positions, cfg, st,
                                        block_tables, block_size,
                                        layer=layer)
        return h, st, None

    h, new_state, _ = _paged_stack(params, cfg, state, h, apply)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = _head(params, cfg, h)[:, 0]
    return logits, new_state
