"""Top-k routed Mixture-of-Experts: static capacity dispatch, and dropless
grouped dispatch for configs with `dropless` set (every served MoE).

Classic dispatch/combine formulation (Mesh-TF / GShard style) chosen because
it is fully static-shaped (compiles under pjit for any mesh) and the dispatch
one-hots shard cleanly: experts over the 'model' axis (EP), tokens over
'data'. The dispatch tensors are built per *sequence chunk* (scan) so their
transient footprint is O(chunk * E * C), not O(S * E * C) — required for the
128-expert llama4 cells at 32k.

Aux losses: load-balancing loss (Switch) + router z-loss, returned to the
caller for logging / the training objective.

Dropless (`moe_dropless`): every token reaches all k of its experts,
whatever else is in the batch, so a sequence's output never depends on
the rows it is batched with. The (token, expert) pairs are sorted by
expert and each expert's rows go through one grouped matrix product
(`lax.ragged_dot`), never a dense product over all experts. Shared
experts (`n_shared_experts`, DeepSeek) are one always-on SwiGLU of
n_shared * expert width, added unweighted, on both paths.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.distributed import context as dctx
from repro.models.layers import init_dense, init_mlp, mlp


def init_moe(key, d_model, d_ff, n_experts, dtype, kind: str = "swiglu",
             n_shared: int = 0):
    kr, k1, k2, k3 = jax.random.split(key, 4)
    E = n_experts
    p = {"router": init_dense(kr, d_model, E, jnp.float32),
         "w_up": (jax.random.normal(k2, (E, d_model, d_ff))
                  * d_model**-0.5).astype(dtype),
         "w_down": (jax.random.normal(k3, (E, d_ff, d_model))
                    * d_ff**-0.5).astype(dtype)}
    if kind in ("swiglu", "geglu"):
        p["w_gate"] = (jax.random.normal(k1, (E, d_model, d_ff))
                       * d_model**-0.5).astype(dtype)
    if n_shared:
        p["shared"] = init_mlp(jax.random.fold_in(key, 4), d_model,
                               n_shared * d_ff, dtype, kind)
    return p


def _route_topk(probs, k, capacity):
    """probs: (N, E). Returns (slots (k, N) int32 in [0, E*C] where E*C is
    the overflow slot, gates (k, N) f32, per-expert routed fraction (E,)).

    Scatter-based routing: instead of (N, E, C) one-hot dispatch tensors
    (whose einsums cost O(N * E * C * D) = O(N^2 * cf * k * D) — dominated
    grok/llama4 train compute), tokens get flat slot ids expert*C + pos and
    are moved with scatter/gather (pure data movement, zero matmul FLOPs).
    """
    N, E = probs.shape
    g = probs
    idxs, gate_list = [], []
    for _ in range(k):
        idx = jnp.argmax(g, axis=-1)                      # (N,)
        gate_list.append(jnp.take_along_axis(g, idx[:, None], -1)[:, 0])
        oh = jax.nn.one_hot(idx, E, dtype=jnp.int32)
        idxs.append(idx)
        g = g * (1 - oh)                                  # mask chosen expert
    # CAUSAL slot assignment: token-major interleaving of the k rounds so a
    # token's slots depend only on tokens <= it (a shared per-round fill
    # counter lets FUTURE tokens' round-1 choices displace PAST tokens'
    # round-2 slots — caught by tests/test_model_invariants.py).
    idx_tok_major = jnp.stack(idxs, axis=1).reshape(N * k)     # (N*k,)
    oh_all = jax.nn.one_hot(idx_tok_major, E, dtype=jnp.int32)
    pos_all = jnp.cumsum(oh_all, axis=0) - 1                   # (N*k, E)
    pos = jnp.take_along_axis(pos_all, idx_tok_major[:, None],
                              -1)[:, 0]                        # (N*k,)
    ok_all = pos < capacity
    slot_all = jnp.where(ok_all, idx_tok_major * capacity + pos,
                         E * capacity).astype(jnp.int32)
    slots = slot_all.reshape(N, k).T                           # (k, N)
    ok = ok_all.reshape(N, k).T
    gates = jnp.stack(gate_list) * ok.astype(probs.dtype)
    routed = (oh_all * ok_all[:, None]).sum(0).astype(jnp.float32)
    return slots, gates, routed / N


def moe_block(params, x, cfg, *, kind: str = "swiglu"):
    """x: (B, S, D) -> (B, S, D), aux dict. Chunked over S.

    GROUPED dispatch (GShard): capacity slots are assigned per batch element
    (group), so the dispatch/combine einsums carry the group dim and every
    contraction is LOCAL to the data shard that owns the group — without
    grouping, the `nec,nd->ecd` contraction runs over the data-sharded token
    dim and GSPMD all-reduces (E, C, D) expert inputs across the data axis
    (observed: 18.7 TB/step on grok-1 train — see EXPERIMENTS.md §Perf).
    """
    B, S, D = x.shape
    E = cfg.n_experts
    k = cfg.experts_per_token
    gather_specs = dctx.get_moe_gather_specs()
    if gather_specs is not None:
        # hoist the FSDP gather of expert weights out of the chunk loop
        params = dict(params)
        for key in ("w_gate", "w_up", "w_down"):
            if key in params and key in gather_specs:
                params[key] = jax.lax.with_sharding_constraint(
                    params[key], gather_specs[key])
    chunk = min(cfg.moe_chunk, S)
    n_chunks = -(-S // chunk)
    pad = n_chunks * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    xc = x.reshape(B, n_chunks, chunk, D).transpose(1, 0, 2, 3)
    capacity = max(1, int(chunk * k * cfg.capacity_factor / E))

    grouped_route = jax.vmap(_route_topk, in_axes=(0, None, None))

    def one_chunk(xi):
        # xi: (B, chunk, D); group dim = B (sharded over data).
        # One-hot dispatch einsums (GShard): scatter/gather routing was
        # tried and REJECTED — XLA SPMD partitions the scatters into dense
        # rewrites (compute x7.7, all-gather 31.9 TB on grok; §Perf it. 6).
        n = xi.shape[1]
        logits = (xi.astype(jnp.float32)
                  @ params["router"].astype(jnp.float32))    # (B, n, E)
        probs = jax.nn.softmax(logits, axis=-1)
        slots, gates, routed = grouped_route(probs, k, capacity)
        aux = E * jnp.sum(routed.mean(0) * probs.mean((0, 1)))
        zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        # build (B, n, E*C) one-hot dispatch from flat slot ids
        # slots/gates: (B, k, n) after the vmap over groups
        slot_oh = jax.nn.one_hot(slots, E * capacity,
                                 dtype=xi.dtype)             # (B, k, n, EC)
        dispatch = slot_oh.sum(1)                            # (B, n, EC)
        combine = (slot_oh
                   * gates[..., None].astype(xi.dtype)).sum(1)
        xe = jnp.einsum("gns,gnd->gsd", dispatch, xi)
        xe = xe.reshape(B, E, capacity, D)
        xe_spec = dctx.get_moe_xe_spec()
        if xe_spec is not None:
            # weight-stationary EP: reshard routed tokens (MBs) to the
            # experts instead of FSDP-gathering expert weights (GBs)
            xe = jax.lax.with_sharding_constraint(xe, xe_spec)
        if "w_gate" in params:
            h = jax.nn.silu(
                jnp.einsum("gecd,edf->gecf", xe, params["w_gate"]))
            h = h * jnp.einsum("gecd,edf->gecf", xe, params["w_up"])
        else:
            h = jax.nn.gelu(
                jnp.einsum("gecd,edf->gecf", xe, params["w_up"]))
        ye = jnp.einsum("gecf,efd->gecd", h, params["w_down"])
        y = jnp.einsum("gns,gsd->gnd", combine,
                       ye.reshape(B, E * capacity, D))
        return y, aux, zloss

    ys, auxs, zs = lax.map(one_chunk, xc)
    y = ys.transpose(1, 0, 2, 3).reshape(B, n_chunks * chunk, D)[:, :S]
    if "shared" in params:
        y = y + mlp(params["shared"], x[:, :S], kind)
    return y, {"moe_aux": auxs.mean(), "moe_zloss": zs.mean()}


# the routed experts' weights, (E, ...) per layer
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def split_stacked_experts(params):
    """A layer stack's MoE params, (n_stack, ...) per leaf, as (the rest,
    which a layer loop slices per layer, and the routed experts, held
    whole). Each layer's dispatch reads the experts at its index
    (moe_dropless's `layer`, with the two merged back into its params):
    a grouped product cannot fuse a dynamic slice of its weights, so a
    sliced layer's experts would be copied every dispatch."""
    experts = {n: a for n, a in params.items() if n in EXPERT_LEAVES}
    return {n: a for n, a in params.items() if n not in experts}, experts


def moe_dropless(params, x, cfg, *, kind: str = "swiglu", valid=None,
                 layer=None):
    """Dropless top-k MoE over the tokens of x (..., D).

    Router: softmax over the experts in float32, greedy top-k, the gates
    the chosen experts' probabilities (not renormalised, unscaled, as
    moe_block routes). Dispatch: the T * k (token, expert) pairs
    sorted by expert, each expert's rows through one grouped product per
    weight (lax.ragged_dot), the results weighted by their gates and
    summed back per token; plus the shared experts. `valid` (x.shape[:-1]
    bool, default all) marks the tokens that count: padding and idle
    lanes are computed but not counted.

    With `layer` (a traced index), the expert weights are a whole layer
    stack, (n_stack, E, ...), taken as n_stack * E groups of which only
    this layer's E have rows: the grouped products read this layer's
    experts in place, where a slice of the stack would copy them (the
    grouped product cannot fuse a dynamic slice of its weights).

    Returns (y (..., D) in x.dtype, aux losses, routed (E,) int32: the
    valid tokens' routes per expert)."""
    lead, D = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    E, k = cfg.n_experts, cfg.experts_per_token
    with jax.named_scope("moe_route"):
        logits = xt.astype(jnp.float32) @ params["router"].astype(
            jnp.float32)                                     # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = lax.top_k(probs, k)                     # (T, k)
    with jax.named_scope("moe_dispatch"):
        flat = idx.reshape(T * k)
        order = jnp.argsort(flat, stable=True)               # by expert
        sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1)
        w = {n: params[n] for n in EXPERT_LEAVES if n in params}
        groups = sizes
        if layer is not None:
            n_stack = w["w_up"].shape[0]
            w = {n: a.reshape(n_stack * E, *a.shape[2:])
                 for n, a in w.items()}
            groups = lax.dynamic_update_slice(
                jnp.zeros((n_stack * E,), jnp.int32), sizes, (layer * E,))
        xs = xt[order // k]                                  # (T*k, D)
        if "w_gate" in w:                # gated, as moe_block computes it
            h = (jax.nn.silu(lax.ragged_dot(xs, w["w_gate"], groups))
                 * lax.ragged_dot(xs, w["w_up"], groups))
        else:
            h = jax.nn.gelu(lax.ragged_dot(xs, w["w_up"], groups))
        ys = lax.ragged_dot(h, w["w_down"], groups,
                            preferred_element_type=jnp.float32)
        ys = ys[jnp.argsort(order)].reshape(T, k, D)         # token-major
        y = jnp.einsum("tkd,tk->td", ys, gates)
    if "shared" in params:
        y = y + mlp(params["shared"], xt, kind).astype(jnp.float32)
    ok = (jnp.ones((T,), jnp.int32) if valid is None
          else valid.reshape(T).astype(jnp.int32))
    routed = jnp.zeros((E,), jnp.int32).at[flat].add(jnp.repeat(ok, k))
    frac = sizes.astype(jnp.float32) / (T * k)
    aux = {"moe_aux": E * jnp.sum(frac * probs.mean(0)),
           "moe_zloss": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)}
    return y.astype(x.dtype).reshape(*lead, D), aux, routed
