"""DeepSeek-V2's block: multi-head latent attention (MLA) with YaRN rotary,
a leading dense SwiGLU layer, then MoE layers of routed experts (softmax
scores, greedy top-k) plus always-on shared experts. The weight tree, the
plain float32 forward and the work counts of this one architecture;
`common.arch` finds this file for a spec whose `arch` is "deepseek_v2".

Weights: the layout `models/lm.py` takes for `block_pattern ==
("mla_moe",)` with `n_dense_layers` leading dense layers: those under
"prefix", the MoE layers stacked under blocks/p0, norms as (1 + scale).

Forward, written from arXiv:2405.04434 and `modeling_deepseek.py`,
importing nothing of the program:

    x = embed[tokens]
    per layer:  h = rmsnorm(x) * (1 + g1)
                q = h Wq -> per head [q_nope (N) | q_pe (R)]
                [c | k_pe] = h Wkv_a;  c = rmsnorm(c) * (1 + g_kv)
                [k_nope | v] = c Wkv_b, per head
                q_pe, k_pe = yarn_rope(q_pe), yarn_rope(k_pe)  (k_pe shared)
                x = x + softmax([q_nope|q_pe] [k_nope|k_pe]^T * scale,
                                causal) v Wo
                    scale = (N + R)^-1/2 * yarn_mscale(factor, m_all)^2
                h = rmsnorm(x) * (1 + g2)
                dense layers:  x = x + swiglu(h)
                MoE layers:    p = softmax(h Wr) in float32; top-k of p,
                               gates p (norm_topk_prob false, scaling 1);
                               x = x + sum_topk gate_e swiglu_e(h)
                               + swiglu_shared(h)
    logits = (rmsnorm(x) * (1 + gf)) Whead

Departures from the checkpoint, each a fixed reparametrisation of the same
weights: gains stored as (1 + g); rotary pairs split-half (dims i, i + R/2)
where the checkpoint interleaves them.

Attention is taken in blocks of query rows against the keys up to each
block's end. Each expert's rows are gathered into tiles of TILE rows, each
tile belonging to one expert, and each tile goes through its own expert's
weights: the experts never see a token not routed to them, so a long
check costs the routed work and not 64 experts' worth.

Counts: matrix-product FLOPs (2 per multiply-add) and bytes, from the
shapes alone. A dispatch of n tokens reads the experts they are expected
to touch under uniform top-k routing, E (1 - ((E - k) / E)^n), never all
E; the cache counts the (L + R) values a token needs per layer, not the
padded pool row.
"""
from __future__ import annotations

import gc
import math

import jax
import jax.numpy as jnp

from ops import ACT_BYTES, HI, Q_BLOCK, f32, mm, qdq

TILE = 256          # rows of one expert tile

# ----------------------------------------------------------------------------
# weights
# ----------------------------------------------------------------------------


def _dims(spec: dict):
    D, H = spec["d_model"], spec["n_heads"]
    L, R = spec["kv_lora_rank"], spec["qk_rope_head_dim"]
    N, V = spec["qk_nope_head_dim"], spec["v_head_dim"]
    return D, H, L, N, R, V


def _n_dense(spec: dict) -> int:
    return spec.get("n_dense_layers", 0)


def _attn_shapes(spec: dict, lead: tuple) -> dict:
    D, H, L, N, R, V = _dims(spec)
    return {"wq": lead + (D, H * (N + R)), "wkv_a": lead + (D, L + R),
            "kv_norm": lead + (L,), "wkv_b": lead + (L, H * (N + V)),
            "wo": lead + (H * V, D)}


def _mlp_shapes(D: int, F: int, lead: tuple) -> dict:
    return {"w_gate": lead + (D, F), "w_up": lead + (D, F),
            "w_down": lead + (F, D)}


def shapes(spec: dict) -> dict:
    D, V = spec["d_model"], spec["vocab_size"]
    E, Fe = spec["n_experts"], spec["moe_d_ff"]
    n_moe = spec["n_layers"] - _n_dense(spec)
    lead = (n_moe,)
    dense = {"norm1": (D,), "norm2": (D,), "attn": _attn_shapes(spec, ()),
             "mlp": _mlp_shapes(D, spec["d_ff"], ())}
    moe = {"router": lead + (D, E), "w_gate": lead + (E, D, Fe),
           "w_up": lead + (E, D, Fe), "w_down": lead + (E, Fe, D)}
    if spec.get("n_shared_experts", 0):
        moe["shared"] = _mlp_shapes(D, spec["n_shared_experts"] * Fe, lead)
    return {
        "embed": (V, D),
        "prefix": [dense for _ in range(_n_dense(spec))],
        "blocks": {"p0": {"norm1": lead + (D,), "norm2": lead + (D,),
                          "attn": _attn_shapes(spec, lead), "moe": moe}},
        "final_norm": (D,),
        "head": (D, V),
    }


def scale(path: str, shape: tuple) -> float:
    if path == "embed":
        return 0.02
    if "norm" in path:
        return 0.1                 # (1 + scale): gains of 1 +- 0.1
    return shape[-2] ** -0.5       # fan-in of a matrix

# ----------------------------------------------------------------------------
# the plain forward
# ----------------------------------------------------------------------------


def _rms(x, g, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + g)


def _mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def _yarn(spec: dict):
    """DeepseekV2YarnRotaryEmbedding's inverse frequencies and cos/sin
    scale, for the R rotary dims."""
    R, base = spec["qk_rope_head_dim"], spec["rope_theta"]
    extra = [base ** (-i / R) for i in range(0, R, 2)]
    f = spec.get("yarn_factor", 0.0)
    if not f:
        return jnp.asarray(extra, jnp.float32), 1.0

    def corr(rot):
        return (R * math.log(spec["yarn_original_max_pos"]
                             / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(spec["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr(spec["yarn_beta_slow"])), R - 1)
    if low == high:
        high += 0.001
    inv = []
    for i, e in enumerate(extra):
        keep = 1.0 - min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append(e / f * (1.0 - keep) + e * keep)
    m = _mscale(f, spec["yarn_mscale"]) / _mscale(
        f, spec["yarn_mscale_all_dim"])
    return jnp.asarray(inv, jnp.float32), m


def _scale(spec: dict) -> float:
    s = (spec["qk_nope_head_dim"] + spec["qk_rope_head_dim"]) ** -0.5
    m = _mscale(spec.get("yarn_factor", 0.0),
                spec.get("yarn_mscale_all_dim", 0.0))
    return s * m * m


def _rope(x, pos, inv_freq, m):
    """x (S, ..., R) rotated split-half."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    c, s = jnp.cos(ang) * m, jnp.sin(ang) * m
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attention(q, k, v, scale, quant):
    """q, k (S, H, dq), v (S, H, dv) -> (S, H * dv), causal, blocked by
    query rows; each block reads the keys up to its own end."""
    S = q.shape[0]
    outs = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(lo + Q_BLOCK, S)
        kb, vb = qdq(k[:hi], quant), qdq(v[:hi], quant)
        s = jnp.einsum("qhd,khd->hqk", qdq(q[lo:hi], quant), kb,
                       precision=HI) * scale
        mask = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", qdq(p, quant), vb, precision=HI)
        outs.append(o.reshape(hi - lo, -1))
    return jnp.concatenate(outs, axis=0)


def _mla(x, a, spec, pos, quant):
    S = x.shape[0]
    D, H, L, N, R, V = _dims(spec)
    inv_freq, m = _yarn(spec)
    q = mm(x, f32(a["wq"]), quant).reshape(S, H, N + R)
    kva = mm(x, f32(a["wkv_a"]), quant)
    c = _rms(kva[:, :L], f32(a["kv_norm"]), spec["norm_eps"])
    k_pe = _rope(kva[:, L:], pos, inv_freq, m)
    q_pe = _rope(q[..., N:], pos, inv_freq, m)
    kv = mm(c, f32(a["wkv_b"]), quant).reshape(S, H, N + V)
    k = jnp.concatenate(
        [kv[..., :N], jnp.broadcast_to(k_pe[:, None], (S, H, R))], axis=-1)
    o = _attention(jnp.concatenate([q[..., :N], q_pe], axis=-1), k,
                   kv[..., N:], _scale(spec), quant)
    return mm(o, f32(a["wo"]), quant)


def _swiglu(x, w, quant):
    h = jax.nn.silu(mm(x, f32(w["w_gate"]), quant)) \
        * mm(x, f32(w["w_up"]), quant)
    return mm(h, f32(w["w_down"]), quant)


def _experts(x, m, spec, quant):
    """Routed experts of one layer over x (T, D), each expert's tokens
    gathered into whole tiles of its own, plus the shared experts."""
    T, D = x.shape
    E, k = spec["n_experts"], spec["experts_per_token"]
    p = jax.nn.softmax(mm(x, f32(m["router"]), quant), axis=-1)
    gates, idx = jax.lax.top_k(p, k)                         # (T, k)
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    e_of = flat[order]                                      # sorted experts
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
    padded = -(-sizes // TILE) * TILE
    first = jnp.cumsum(padded) - padded                     # tile-aligned
    rank = jnp.arange(T * k) - (jnp.cumsum(sizes) - sizes)[e_of]
    dest = first[e_of] + rank                               # row in tiles
    n_tiles = -(-(T * k + E * (TILE - 1)) // TILE)
    buf = jnp.zeros((n_tiles * TILE, D), jnp.float32).at[dest].set(
        x[order // k])
    tile_e = jnp.minimum(jnp.searchsorted(jnp.cumsum(padded),
                                          jnp.arange(n_tiles) * TILE,
                                          side="right"), E - 1)

    def tile(args):
        xt, e = args
        w = {n: m[n][e] for n in ("w_gate", "w_up", "w_down")}
        return _swiglu(xt, w, quant)

    ys = jax.lax.map(tile, (buf.reshape(n_tiles, TILE, D), tile_e))
    ys = ys.reshape(-1, D)[dest] * gates.reshape(-1)[order][:, None]
    y = jnp.zeros((T, D), jnp.float32).at[order // k].add(ys)
    if "shared" in m:
        y = y + _swiglu(x, m["shared"], quant)
    return y


def _layer(x, lw, spec, pos, quant):
    eps = spec["norm_eps"]
    x = x + _mla(_rms(x, f32(lw["norm1"]), eps), lw["attn"], spec, pos,
                 quant)
    h = _rms(x, f32(lw["norm2"]), eps)
    if "moe" in lw:
        return x + _experts(h, lw["moe"], spec, quant)
    return x + _swiglu(h, lw["mlp"], quant)


def hidden(w, spec, tokens, quant=None, remat=False):
    """Final normed hidden states (S, D) of one sequence."""
    pos = jnp.arange(tokens.shape[0])
    x = w["embed"][tokens].astype(jnp.float32)
    for lw in w["prefix"]:
        x = _layer(x, lw, spec, pos, quant)

    def body(x, lw):
        return _layer(x, lw, spec, pos, quant), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, w["blocks"]["p0"])
    return _rms(x, f32(w["final_norm"]), spec["norm_eps"])


def logits(w, spec, tokens, quant=None, remat=False):
    """(S, V) float32 logits of one sequence of token ids.

    At a long cell's width the logits are the largest array a check holds
    (16,384 x 102,400 in float32 is 6.25 GiB beside 7.47 GiB of bfloat16
    weights), so two things keep it to one such array on a 16 GiB chip:
    - a full collection when the forward is traced, before the program
      that allocates the logits runs: a serving engine dropped before the
      check can still hold its paged pool in reference cycles (its bound
      methods, and a traced run's wrappers, refer back to it);
    - a barrier after the head's product: fused with the first reduction
      of the log-softmax that follows, the product's output is one buffer
      and the normalised log-probabilities another; unfused, the
      normalisation is done in place (a compile for a described v5e at
      16,384 positions: 19.97 GiB without the barrier, 13.98 with it).
    Neither changes a value."""
    gc.collect()
    h = hidden(w, spec, tokens, quant, remat)
    return jax.lax.optimization_barrier(mm(h, f32(w["head"]), quant))

# ----------------------------------------------------------------------------
# work counts
# ----------------------------------------------------------------------------


def _attn_params(spec: dict) -> int:
    """Matrix parameters of one MLA layer (Wkv_b counts once per token:
    expanded, it makes the token's keys and values; absorbed, its two
    halves multiply the query and the output)."""
    D, H, L, N, R, V = _dims(spec)
    return D * H * (N + R) + D * (L + R) + L * H * (N + V) + H * V * D


def _expert_params(spec: dict) -> int:
    return 3 * spec["d_model"] * spec["moe_d_ff"]


def _shared_params(spec: dict) -> int:
    return spec.get("n_shared_experts", 0) * _expert_params(spec)


def _n_moe(spec: dict) -> int:
    return spec["n_layers"] - _n_dense(spec)


def _dense_params(spec: dict) -> int:
    """Matrix parameters every token reads: attention, the dense layers'
    MLPs, the routers, the shared experts (the head apart)."""
    D, E = spec["d_model"], spec["n_experts"]
    return (spec["n_layers"] * _attn_params(spec)
            + _n_dense(spec) * 3 * D * spec["d_ff"]
            + _n_moe(spec) * (D * E + _shared_params(spec)))


def head_params(spec: dict) -> int:
    return spec["d_model"] * spec["vocab_size"]


def param_count(spec: dict) -> int:
    """Every parameter: embedding, head, layers with their norms (the
    latent norm included), final norm."""
    D, E, L = spec["d_model"], spec["n_experts"], spec["kv_lora_rank"]
    norms = spec["n_layers"] * (2 * D + L)
    return (2 * spec["vocab_size"] * D + _dense_params(spec)
            + _n_moe(spec) * E * _expert_params(spec) + norms + D)


def matmul_params(spec: dict) -> int:
    """Parameters in a matrix product per token: the dense part, the k
    routed experts of each MoE layer, and the head."""
    return (_dense_params(spec) + _n_moe(spec) * spec["experts_per_token"]
            * _expert_params(spec) + head_params(spec))


def _row_values(spec: dict) -> int:
    return spec["kv_lora_rank"] + spec["qk_rope_head_dim"]


def kv_bytes_per_token(spec: dict) -> int:
    """The latent row a token adds to each layer's cache: (L + R) values
    (576), not the padded pool row."""
    return spec["n_layers"] * _row_values(spec) * ACT_BYTES


def experts_touched(spec: dict, n: int) -> float:
    """Distinct experts n tokens are expected to reach in one layer under
    uniform top-k routing: E (1 - ((E - k) / E)^n)."""
    E, k = spec["n_experts"], spec["experts_per_token"]
    return E * (1.0 - ((E - k) / E) ** n)


def _weight_bytes(spec: dict, n: int) -> float:
    """Weights a dispatch of n tokens must read: the dense part, the head
    and the experts those tokens are expected to touch."""
    return ACT_BYTES * (_dense_params(spec) + head_params(spec)
                        + _n_moe(spec) * experts_touched(spec, n)
                        * _expert_params(spec))


def _pair_flops(spec: dict) -> int:
    """One query against one expanded key and value, one layer."""
    D, H, L, N, R, V = _dims(spec)
    return 2 * H * (N + R + V)


def _absorbed_pair_flops(spec: dict) -> int:
    """One query against one latent row, absorbed, one layer."""
    D, H, L, N, R, V = _dims(spec)
    return 2 * H * (L + R + L)


def _expand_flops(spec: dict) -> int:
    """Expanding one cached latent into per-head keys and values."""
    D, H, L, N, R, V = _dims(spec)
    return 2 * L * H * (N + V)


def attn_flops(spec: dict, ctx: int) -> int:
    """One decode query over `ctx` cached latent rows, all layers
    (absorbed, the least work for one query)."""
    return spec["n_layers"] * _absorbed_pair_flops(spec) * ctx


def prefill_flops(spec: dict, start: int, n: int) -> int:
    """Forward FLOPs of prefilling positions start .. start+n-1 of one
    prompt: every token through the layers and its k experts, causal
    attention within the n new tokens, the cached prefix in whichever
    form costs less for n queries (expanded once per dispatch, or
    absorbed), and the head at the last position only."""
    body = 2 * (matmul_params(spec) - head_params(spec)) * n
    new_pairs = n * (n + 1) // 2
    prefix = min(start * _expand_flops(spec)
                 + n * start * _pair_flops(spec),
                 n * start * _absorbed_pair_flops(spec))
    attn = spec["n_layers"] * (new_pairs * _pair_flops(spec) + prefix)
    return body + attn + 2 * head_params(spec)


def prefill_bytes(spec: dict, start: int, n: int) -> float:
    """Bytes prefilling positions start .. start+n-1 must move: the
    weights its n tokens need, the start cached latent rows read and the
    n new ones written."""
    return _weight_bytes(spec, n) + kv_bytes_per_token(spec) * (start + n)


def decode_flops(spec: dict, positions) -> int:
    """Forward FLOPs of one decode step: one token per live lane at its
    position, attending (absorbed) to position + 1 latent rows."""
    n = len(positions)
    keys = sum(p + 1 for p in positions)
    return 2 * matmul_params(spec) * n + attn_flops(spec, 1) * keys


def decode_bytes(spec: dict, positions) -> float:
    """Bytes one decode step must move: the weights its lanes need (the
    experts they are expected to touch), the live lanes' cached latent
    rows, and the new rows."""
    kv = kv_bytes_per_token(spec)
    return _weight_bytes(spec, len(positions)) + kv * sum(positions) \
        + kv * len(positions)


def train_flops_per_token(spec: dict, seq_len: int) -> int:
    """Forward and backward FLOPs per token of one pass: 6 per matrix
    parameter a token reads, plus causal attention (expanded) three times
    over at the mean context. Recomputation does not count."""
    mean_keys = (seq_len + 1) / 2
    return 6 * matmul_params(spec) + 3 * spec["n_layers"] \
        * _pair_flops(spec) * mean_keys
