"""Serving subsystem: paged-attention kernel vs oracle, block-allocator
invariants under churn, engine outputs vs the legacy generate() path,
prefix-cache on/off token identity, and bucketed batched prefill."""
import os
import random
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref
from repro.kernels.paged_attention import paged_attention
from repro.launch.serve import generate
from repro.models import attention, lm
from repro.serving.block_manager import BlockAllocator
from repro.serving.engine import (Request, ServingEngine,
                                  shared_prefix_requests, summarize,
                                  synthetic_requests)

pytestmark = pytest.mark.serving

KEY = jax.random.PRNGKey(0)


def _rand(i, shape, dtype=jnp.float32, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(KEY, i), shape)
            * scale).astype(dtype)


# ----------------------------------------------------------------------------
# Pallas paged-attention kernel vs the jnp oracle
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,KV,hd,bs,M", [
    (2, 4, 4, 32, 8, 3),     # MHA
    (3, 4, 2, 64, 16, 4),    # GQA 2:1
    (1, 8, 1, 128, 8, 2),    # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_kernel_sweep(B, H, KV, hd, bs, M, dtype):
    N = B * M + 1
    q = _rand(1, (B, H, hd), dtype)
    kp = _rand(2, (N, bs, KV, hd), dtype)
    vp = _rand(3, (N, bs, KV, hd), dtype)
    # disjoint tables; ragged context lengths incl. a partial last block
    bt = (1 + jnp.arange(B * M, dtype=jnp.int32)).reshape(B, M)
    cl = jnp.asarray([(i * 7 + 3) % (M * bs) + 1 for i in range(B)],
                     jnp.int32)
    out = paged_attention(q, kp, vp, bt, cl, interpret=True)
    expect = ref.paged_attention_ref(q, kp, vp, bt, cl)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32),
                               atol=tol, rtol=tol)


def test_paged_attention_kernel_empty_slot():
    """ctx_len == 0 lanes (idle decode slots) must return zeros, not NaN."""
    q = _rand(1, (2, 4, 32))
    kp = _rand(2, (5, 8, 2, 32))
    vp = _rand(3, (5, 8, 2, 32))
    bt = jnp.array([[1, 2], [0, 0]], jnp.int32)
    cl = jnp.array([9, 0], jnp.int32)
    out = np.asarray(paged_attention(q, kp, vp, bt, cl, interpret=True))
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[1], 0.0)
    expect = np.asarray(ref.paged_attention_ref(q, kp, vp, bt, cl))
    np.testing.assert_allclose(out, expect, atol=2e-5, rtol=2e-5)


def test_paged_model_path_matches_dense_decode():
    """paged_decode_attention_block == decode_attention_block on the same
    history (the paged layout must be a pure re-indexing)."""
    cfg = get_config("smollm-135m").reduced()
    B, pos, bs, M = 2, 10, 4, 4
    S_max = M * bs
    params = attention.init_attention(jax.random.fold_in(KEY, 9),
                                      cfg.d_model, cfg.n_heads,
                                      cfg.n_kv_heads, cfg.head_dim,
                                      jnp.float32)
    x = _rand(4, (B, 1, cfg.d_model), scale=0.3)
    hist_k = _rand(5, (B, S_max, cfg.n_kv_heads, cfg.head_dim))
    hist_v = _rand(6, (B, S_max, cfg.n_kv_heads, cfg.head_dim))
    mask = (jnp.arange(S_max) < pos)[None, :, None, None]
    dense = {"k": hist_k * mask, "v": hist_v * mask}
    out_d, _ = attention.decode_attention_block(params, x, dense,
                                                jnp.int32(pos), cfg)
    # same history scattered into a row pool through a shuffled block
    # table: block perm[b, j] holds tokens j*bs.. of slot b, one row each,
    # K then V side by side
    perm = np.array([[3, 1, 4, 2], [7, 5, 8, 6]], np.int32)
    N = 9
    width = cfg.n_kv_heads * cfg.head_dim
    pool = jnp.zeros((N * bs, 2 * width))
    for b in range(B):
        for j in range(M):
            hist = jnp.concatenate(
                [dense["k"][b, j * bs:(j + 1) * bs].reshape(bs, width),
                 dense["v"][b, j * bs:(j + 1) * bs].reshape(bs, width)], -1)
            pool = pool.at[perm[b, j] * bs:(perm[b, j] + 1) * bs].set(hist)
    out_p, new_cache = attention.paged_decode_attention_block(
        params, x, {"kv": pool}, jnp.full((B,), pos, jnp.int32),
        jnp.asarray(perm), cfg, block_size=bs)
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out_p),
                               atol=1e-5, rtol=1e-5)


def test_prefill_and_decode_land_kv_in_token_rows():
    """The row form: the K and V that prefill and decode write for the
    token at position p land in pool row table[p // bs] * bs + p % bs of
    every layer, K in the first KV * hd columns and V in the last, equal
    to the dense forward's K/V; the runner's greedy tokens equal
    generate()'s."""
    from repro.serving.runner import ModelRunner, PrefillRow
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    bs, P = 4, 6
    prompt = np.asarray(jax.random.randint(jax.random.fold_in(KEY, 40),
                                           (P,), 0, cfg.vocab_size),
                        np.int32)
    table = np.array([3, 5, 0, 0], np.int32)
    runner = ModelRunner(params, cfg, num_slots=2, block_size=bs,
                         num_blocks=8, max_blocks_per_seq=4)
    runner.write_table(0, table)
    first, _, _ = runner.prefill([PrefillRow(tokens=prompt, cached_len=0,
                                             slot=0, table_row=table)])
    nxt, _, _ = runner.decode(np.array([first[0], 0], np.int32),
                              np.array([P, 0], np.int32))
    np.testing.assert_array_equal(
        [first[0], nxt[0]], np.asarray(generate(params, cfg, prompt[None],
                                                2))[0])

    seq = np.append(prompt, first[0])
    _, dense = lm.prefill(params, cfg, {"tokens": jnp.asarray(seq)[None]})
    width = cfg.n_kv_heads * cfg.head_dim
    pool = np.asarray(runner.state["blocks"]["p0"]["kv"])  # (L, 8*bs, 2w)
    assert pool.shape == (cfg.n_super, 8 * bs, 2 * width)
    k = np.asarray(dense["blocks"]["p0"]["k"])[:, 0].reshape(
        cfg.n_super, P + 1, width)
    v = np.asarray(dense["blocks"]["p0"]["v"])[:, 0].reshape(
        cfg.n_super, P + 1, width)
    for p in range(P + 1):                   # positions 0-5 prefill, 6 decode
        row = table[p // bs] * bs + p % bs
        np.testing.assert_allclose(pool[:, row, :width], k[:, p],
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(pool[:, row, width:], v[:, p],
                                   atol=1e-5, rtol=1e-5)
    # nothing else was written outside the null block's rows
    written = {table[p // bs] * bs + p % bs for p in range(P + 1)}
    others = [r for r in range(bs, 8 * bs) if r not in written]
    assert not pool[:, others].any()


# ----------------------------------------------------------------------------
# Block allocator invariants under random admit/evict churn
# ----------------------------------------------------------------------------

def test_block_allocator_churn():
    rng = random.Random(0)
    alloc = BlockAllocator(64)
    live = {}  # rid -> blocks
    rid = 0
    for _ in range(2000):
        if live and rng.random() < 0.45:
            victim = rng.choice(sorted(live))
            alloc.free(live.pop(victim))
        else:
            n = rng.randint(0, 9)
            got = alloc.alloc(n)
            if got is not None:
                live[rid] = got
                rid += 1
        # invariants: disjoint ownership, no null block, conservation
        owned = [b for bs in live.values() for b in bs]
        assert len(owned) == len(set(owned))
        assert 0 not in owned
        assert alloc.num_free + len(owned) == 63
    # exhaustion returns None without a partial grant
    free_before = alloc.num_free
    assert alloc.alloc(free_before + 1) is None
    assert alloc.num_free == free_before


def test_block_allocator_errors():
    alloc = BlockAllocator(8)
    blocks = alloc.alloc(3)
    alloc.free(blocks)
    with pytest.raises(ValueError):
        alloc.free(blocks)          # double free
    with pytest.raises(ValueError):
        alloc.free([0])             # reserved null block


# ----------------------------------------------------------------------------
# prefill == token-by-token priming
# ----------------------------------------------------------------------------

def test_prefill_matches_stepwise_priming():
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, P = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                              cfg.vocab_size)
    logits_pf, _ = lm.prefill(params, cfg, {"tokens": toks})
    state = lm.init_decode_state(cfg, B, max_len=P + 1)
    logits_step = None
    for pos in range(P):
        logits_step, state = lm.decode_step(params, cfg, state,
                                            toks[:, pos], jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(logits_pf[:, -1]),
                               np.asarray(logits_step),
                               atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------------------
# engine greedy outputs == generate() (bit-identical token ids)
# ----------------------------------------------------------------------------

def test_engine_matches_generate_exactly():
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    B, P, gen = 4, 8, 12
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0,
                                 cfg.vocab_size)
    expect = np.asarray(generate(params, cfg, prompts, gen))
    engine = ServingEngine(params, cfg, num_slots=B, block_size=4,
                           max_seq_len=P + gen + 1)
    done = engine.run([Request(rid=i, prompt=np.asarray(prompts[i]),
                               max_new_tokens=gen) for i in range(B)])
    assert len(done) == B
    for c in done:
        np.testing.assert_array_equal(c.tokens, expect[c.rid])


# ----------------------------------------------------------------------------
# runner programs read the weights as arguments, never as constants
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("program", ["decode", "verify", "prefill"])
def test_runner_programs_take_weights_as_arguments(program):
    """A closed-over weight array would be lowered into every compiled
    variant as a `stablehlo.constant` (one copy of the model per
    executable). Each program that reads the weights must instead list
    every weight leaf among its arguments and hold no weight-shaped
    constant."""
    import re

    from repro.serving.runner import ModelRunner

    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    slots, mb = 2, 4
    runner = ModelRunner(params, cfg, num_slots=slots, block_size=4,
                         num_blocks=1 + slots * mb, max_blocks_per_seq=mb,
                         speculate=2)
    z = jnp.zeros(slots, jnp.int32)
    tables = jnp.zeros((slots, mb), jnp.int32)
    sampling_args = runner._sampling_device()
    if program == "decode":
        lowered = runner._decode_fn.lower(
            runner.params, runner.state, z, z, tables, *sampling_args,
            False, True)
    elif program == "verify":
        lowered = runner._verify_fn.lower(
            runner.params, runner.state, jnp.zeros((slots, 3), jnp.int32),
            z, z, tables, *sampling_args, False, True)
    else:
        lowered = runner._prefill_fn.lower(
            runner.params, runner.state, jnp.zeros((slots, 16), jnp.int32),
            z, z, tables, z, False)
    weight_args = jax.tree.leaves(lowered.args_info[0][0])
    assert len(weight_args) == len(jax.tree.leaves(params))
    assert not any(a.donated for a in weight_args)
    weight_types = {"x".join(map(str, w.shape)) + "x"
                    + str(jnp.dtype(w.dtype)).replace("float", "f")
                    for w in jax.tree.leaves(params)}
    consts = re.findall(r"stablehlo\.constant dense<[^>]*> : tensor<([^>]*)>",
                        lowered.as_text())
    assert consts                        # the pattern does match constants
    assert not weight_types & set(consts)


@pytest.mark.parametrize("arch", ["smollm-135m", "recurrentgemma-2b"])
def test_engine_continuous_batching_churn(arch):
    """More requests than slots, ragged lengths: every request completes,
    every output matches its own single-request generate(), and all blocks
    are returned to the pool."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    n, P = 7, 8
    gens = [5, 12, 3, 9, 12, 7, 4]
    prompts = jax.random.randint(jax.random.PRNGKey(1), (n, P), 0,
                                 cfg.vocab_size)
    engine = ServingEngine(params, cfg, num_slots=3, block_size=4,
                           max_seq_len=P + max(gens) + 1)
    free0 = engine.allocator.num_free
    done = engine.run([Request(rid=i, prompt=np.asarray(prompts[i]),
                               max_new_tokens=gens[i]) for i in range(n)])
    assert len(done) == n
    assert engine.allocator.num_free == free0
    for c in done:
        expect = np.asarray(generate(params, cfg, prompts[c.rid][None],
                                     gens[c.rid]))[0]
        np.testing.assert_array_equal(c.tokens, expect)


def test_engine_eos_and_telemetry():
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                 cfg.vocab_size)
    full = np.asarray(generate(params, cfg, prompts, 8))[0]
    eos = int(full[3])  # stops at eos's FIRST occurrence (may be < index 3)
    stop = int(np.argmax(full == eos)) + 1
    engine = ServingEngine(params, cfg, num_slots=2, block_size=4,
                           max_seq_len=32)
    done = engine.run([Request(rid=0, prompt=np.asarray(prompts[0]),
                               max_new_tokens=8, eos_id=eos)])
    assert len(done[0].tokens) == stop
    np.testing.assert_array_equal(done[0].tokens, full[:stop])
    from repro.serving.engine import summarize
    stats = summarize(done, engine.wall_time, engine)
    assert stats["generated_tokens"] == stop
    assert stats["tokens_per_s"] > 0
    assert 0 < stats["slot_occupancy"] <= 1
    assert stats["kv_cache_mb"] > 0
    # TTFT covers admission->first token, and timestamps are ordered
    c = done[0]
    assert c.arrival <= c.t_admit <= c.t_first_token <= c.t_done
    # empty run: telemetry degrades gracefully
    empty = summarize(engine.run([]), engine.wall_time, engine)
    assert empty["requests"] == 0 and empty["tokens_per_s"] == 0.0


def test_synthetic_requests_open_loop():
    reqs = synthetic_requests(16, vocab_size=100, prompt_len=8,
                              max_new=(2, 5), rate=100.0, seed=3)
    arr = [r.arrival for r in reqs]
    assert arr == sorted(arr) and arr[-1] > 0
    assert all(2 <= r.max_new_tokens <= 5 for r in reqs)
    assert all(r.prompt.shape == (8,) and r.prompt.dtype == np.int32
               for r in reqs)


def test_workload_generators_mixed_and_shared_prefix():
    reqs = synthetic_requests(32, vocab_size=100, prompt_len=(4, 24),
                              max_new=(2, 5), seed=1)
    lens = {len(r.prompt) for r in reqs}
    assert all(4 <= n <= 24 for n in lens) and len(lens) > 4
    reqs = shared_prefix_requests(12, vocab_size=100, prefix_len=16,
                                  suffix_len=(2, 6), max_new=(2, 4),
                                  n_prefixes=2, seed=2)
    p0 = reqs[0].prompt[:16]
    p1 = reqs[1].prompt[:16]
    assert not np.array_equal(p0, p1)          # two distinct system prompts
    for i, r in enumerate(reqs):
        np.testing.assert_array_equal(r.prompt[:16], p0 if i % 2 == 0
                                      else p1)
        assert 18 <= len(r.prompt) <= 22


# ----------------------------------------------------------------------------
# length-masked batched prefill (models/lm.py)
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["smollm-135m", "recurrentgemma-2b",
                                  "rwkv6-3b"])
def test_prefill_length_masked(arch):
    """Right-padded mixed-length prefill with `lengths` must reproduce
    each row's unpadded logits and (for recurrent mixers) final states."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    lens = [5, 11, 8]
    S = max(lens)
    rows = [jax.random.randint(jax.random.fold_in(KEY, i), (n,), 0,
                               cfg.vocab_size) for i, n in enumerate(lens)]
    toks = jnp.stack([jnp.pad(r, (0, S - len(r))) for r in rows])
    logits, cache = lm.prefill(params, cfg, {
        "tokens": toks, "lengths": jnp.asarray(lens, jnp.int32)})

    def recurrent_leaves(tree):
        out = []
        for kind, st in zip(cfg.prefix_pattern, tree["prefix"]):
            if kind in ("rwkv", "rec"):
                out.extend(jax.tree.leaves(st))
        for pi, kind in enumerate(cfg.block_pattern):
            if kind in ("rwkv", "rec"):
                out.extend(jax.tree.leaves(tree["blocks"][f"p{pi}"]))
        return out

    batched_states = recurrent_leaves(cache)
    for b, row in enumerate(rows):
        ref_logits, ref_cache = lm.prefill(params, cfg,
                                           {"tokens": row[None]})
        np.testing.assert_allclose(
            np.asarray(logits[b, lens[b] - 1]),
            np.asarray(ref_logits[0, -1]), atol=1e-4, rtol=1e-4)
        for got, want in zip(batched_states, recurrent_leaves(ref_cache)):
            # leaves are (B, ...) or stacked (n_super, B, ...)
            got_b = got[b] if got.shape[0] == len(lens) else got[:, b]
            want_b = want[0] if want.shape[0] == 1 else want[:, 0]
            np.testing.assert_allclose(np.asarray(got_b),
                                       np.asarray(want_b),
                                       atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("arch", ["smollm-135m", "recurrentgemma-2b",
                                  "deepseek-v2-lite"])
def test_prefill_paged_matches_per_sequence_load_prefill(arch):
    """The fused batched path (lm.prefill_paged) must leave the paged
    state identical to the per-sequence oracle (lm.prefill + kv_cache.
    load_prefill) — same KV (or MLA latent rows) in every block it owns,
    same recurrent slot state, same last-token logits. A MoE layer's
    expert counters count what a dispatch computed, which the oracle
    does not, so they are not compared."""
    from repro.serving import kv_cache
    cfg = get_config(arch).reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    bs, num_slots, M = 4, 2, 4
    lens = [7, 10]
    rows = [jax.random.randint(jax.random.fold_in(KEY, 20 + i), (n,), 0,
                               cfg.vocab_size) for i, n in enumerate(lens)]
    tables = np.full((2, M), 0, np.int32)
    tables[0, :2] = [1, 2]
    tables[1, :3] = [3, 4, 5]

    oracle = kv_cache.init_paged_state(cfg, num_slots, 6, bs)
    ref_last = []
    for i, row in enumerate(rows):
        logits, cache = lm.prefill(params, cfg, {"tokens": row[None]})
        oracle = kv_cache.load_prefill(cfg, oracle, cache, jnp.int32(i),
                                       jnp.asarray(tables[i]), bs)
        ref_last.append(np.asarray(logits[0, lens[i] - 1]))

    fused = kv_cache.init_paged_state(cfg, num_slots, 6, bs)
    Ls = max(lens)
    toks = jnp.stack([jnp.pad(r, (0, Ls - len(r))) for r in rows])
    last, fused = lm.prefill_paged(
        params, cfg, fused, toks, jnp.asarray(lens, jnp.int32),
        jnp.zeros(2, jnp.int32), jnp.asarray(tables),
        jnp.arange(2, dtype=jnp.int32), bs)

    np.testing.assert_allclose(np.asarray(last), np.stack(ref_last),
                               atol=1e-4, rtol=1e-4)
    # compare every owned block / slot; block 0 is the pad sink (skip its
    # rows)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(fused),
                                 jax.tree.leaves(oracle)):
        got, want = np.asarray(got), np.asarray(want)
        if path[-1] == jax.tree_util.DictKey("routed"):
            continue
        if path[-1] == jax.tree_util.DictKey("kv"):   # (.., 6 * bs, C)
            np.testing.assert_allclose(got[..., bs:, :], want[..., bs:, :],
                                       atol=1e-4, rtol=1e-4)
        else:                         # recurrent slot state
            np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


# ----------------------------------------------------------------------------
# prefix caching: token identity on/off, copy-on-write, churn
# ----------------------------------------------------------------------------

def _engine_outputs(params, cfg, reqs, **kw):
    eng = ServingEngine(params, cfg, **kw)
    done = eng.run(list(reqs))
    assert len(done) == len(reqs)
    return {c.rid: c.tokens for c in done}, eng


def test_prefix_cache_on_off_identical_under_churn():
    """Greedy outputs must be token-identical with the prefix cache on
    vs off and vs generate(), with more requests than slots (admit/evict
    churn) on a shared-prefix workload that hits every sharing path."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = shared_prefix_requests(9, vocab_size=cfg.vocab_size,
                                  prefix_len=20, suffix_len=(1, 9),
                                  max_new=(2, 7), seed=4)
    kw = dict(num_slots=3, block_size=8, max_seq_len=48,
              prefill_max_batch=2)
    on, eng_on = _engine_outputs(params, cfg, reqs, prefix_cache=True, **kw)
    off, eng_off = _engine_outputs(params, cfg, reqs, prefix_cache=False,
                                   **kw)
    assert eng_on.scheduler.cached_prompt_tokens > 0
    assert eng_off.scheduler.cached_prompt_tokens == 0
    for r in reqs:
        exp = np.asarray(generate(params, cfg, np.asarray(r.prompt)[None],
                                  r.max_new_tokens))[0]
        np.testing.assert_array_equal(on[r.rid], exp)
        np.testing.assert_array_equal(off[r.rid], exp)
    # all blocks returned (shared ones may idle in the cached-free pool)
    assert eng_on.allocator.num_free == eng_on.allocator.num_blocks - 1


def test_prefix_cache_copy_on_write_paths():
    """Eager COW (prompt diverges mid-block) and lazy COW (whole prompt
    cached; generation writes the shared block) both fire and stay
    token-identical to generate()."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(7)
    base = rng.integers(0, cfg.vocab_size, 27).astype(np.int32)
    reqs = [Request(rid=0, prompt=base, max_new_tokens=4),
            # same first 22 tokens, diverges inside block 2 -> eager COW
            Request(rid=1, prompt=np.concatenate(
                [base[:22], rng.integers(0, cfg.vocab_size, 5)
                 .astype(np.int32)]), max_new_tokens=5),
            # strict prefix ending mid-block -> fully cached -> lazy COW
            Request(rid=2, prompt=base[:20].copy(), max_new_tokens=6)]
    out, eng = _engine_outputs(params, cfg, reqs, num_slots=1,
                               block_size=8, max_seq_len=64,
                               prefix_cache=True)
    assert eng.runner.block_copies >= 2          # one eager + one lazy
    for r in reqs:
        exp = np.asarray(generate(params, cfg, np.asarray(r.prompt)[None],
                                  r.max_new_tokens))[0]
        np.testing.assert_array_equal(out[r.rid], exp)


def test_prefix_cache_hit_under_tight_pool_backpressures():
    """Admission must charge for matched blocks it revives from the
    cached-free pool: with a pool sized so a cache-hit admission would
    otherwise over-commit the reserved block budget, the request has to
    wait (backpressure), not crash a later infallible claim. Regression
    for the incremental-allocation admission gate."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    pa = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    pc = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=16, num_blocks=7, prefix_cache=True)
    # A completes and parks its 2 prompt blocks in the cached-free pool
    eng.run([Request(rid=0, prompt=pa, max_new_tokens=8)])
    # C (distinct) binds blocks + budget; B (cache hit on A) must wait
    # until C's blocks come back even though num_free looks sufficient
    reqs = [Request(rid=1, prompt=pc, max_new_tokens=8),
            Request(rid=2, prompt=pa.copy(), max_new_tokens=8)]
    done = eng.run(list(reqs))
    assert len(done) == 2
    for c in done:
        exp = np.asarray(generate(params, cfg,
                                  np.asarray(reqs[c.rid - 1].prompt)[None],
                                  8))[0]
        np.testing.assert_array_equal(c.tokens, exp)
    # everything back in the allocatable supply (free or cached-free)
    assert eng.allocator.num_free == eng.allocator.num_blocks - 1


def test_prefix_cache_rejected_for_recurrent_archs():
    cfg = get_config("recurrentgemma-2b").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError):
        ServingEngine(params, cfg, prefix_cache=True)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=32)
    assert not eng.prefix_cache                  # auto-gated off


# ----------------------------------------------------------------------------
# bucketed batched prefill
# ----------------------------------------------------------------------------

def test_bucketed_prefill_mixed_lengths_matches_generate():
    """Mixed-length traffic: every output matches generate(), and the
    number of distinct prefill jit shapes is bounded by the bucket grid,
    not by the number of distinct prompt lengths."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = synthetic_requests(12, vocab_size=cfg.vocab_size,
                              prompt_len=(3, 40), max_new=(2, 6), seed=6)
    out, eng = _engine_outputs(params, cfg, reqs, num_slots=4,
                               block_size=8, max_seq_len=64,
                               prefill_max_batch=4)
    n_lens = len({len(r.prompt) for r in reqs})
    bound = len(eng.runner.prefill_buckets) * len(eng.runner.width_buckets)
    assert len(eng.runner.prefill_shapes) <= bound
    assert len(eng.runner.prefill_shapes) < n_lens
    assert eng.runner.prefill_dispatches < len(reqs)   # batched admission
    for r in reqs:
        exp = np.asarray(generate(params, cfg, np.asarray(r.prompt)[None],
                                  r.max_new_tokens))[0]
        np.testing.assert_array_equal(out[r.rid], exp)


def test_summarize_reports_prefill_and_prefix_stats():
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = shared_prefix_requests(4, vocab_size=cfg.vocab_size,
                                  prefix_len=16, suffix_len=4,
                                  max_new=(2, 3), seed=8)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=8,
                        max_seq_len=32)
    stats = summarize(eng.run(reqs), eng.wall_time, eng)
    pf = stats["prefill"]
    assert pf["prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    assert pf["computed_tokens"] + pf["cached_tokens"] \
        == pf["prompt_tokens"]
    assert pf["cached_tokens"] > 0
    assert pf["shapes"] <= pf["buckets"]
    assert stats["prefix_cache"]["enabled"]
    assert stats["prefix_cache"]["hit_requests"] > 0


# ----------------------------------------------------------------------------
# serving_bench is importable and runs end to end (CI smoke)
# ----------------------------------------------------------------------------

def test_serving_bench_smoke(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    try:
        import serving_bench
    finally:
        sys.path.pop(0)
    rec = serving_bench.run_bench([
        "--requests", "3", "--prompt-len", "6", "12", "--max-new", "2", "3",
        "--slots", "2", "--block-size", "4", "--workload", "mixed",
        "--out", str(tmp_path)])
    assert rec["speedup"] > 0
    assert rec["engine"]["requests"] == 3
    assert (tmp_path / "bench_smollm-135m_mixed.json").exists()
