"""Quantized KV block pools + the host-RAM spill tier.

Covers the layers the quantized/tiered cache spans:
  * attention.quantize_kv/dequantize_kv — per-(slot, kv-head) scale
    round-trip error bound, exact zero handling, and the verbatim
    (q, scale) copy being a lossless round-trip;
  * kv_cache — pool dtype selection (fp16 keeps the activation dtype,
    int8/fp8 the narrow element types), dtype-aware paged_bytes/block_bytes,
    row-form scale side-tables in init_paged_state, copy_block carrying
    scales, and gather_blocks/scatter_blocks round-tripping every pool
    leaf exactly (the host-tier payload path);
  * Pallas kernels — paged_attention and paged_prefill_attention with
    int8 pools + scale side-tables against their full-precision
    references (interpret mode);
  * BlockAllocator host tier — demote on eviction pressure, revive on
    the next prefix hit with payloads restored bit-exact and refcounts
    re-parked cached-free, the LRU capacity bound, and a hypothesis
    churn sweep asserting content-correct matches throughout;
  * engine — fp16 pools bit-identical to the default path, int8 greedy
    within the pinned per-token divergence budget, the tiered engine
    bit-identical to device-only while reviving spilled chains, the
    router probe counting spilled tokens without moving payloads, and
    host-tier promotion never compiling outside the bucket grid.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.kernels.ref import paged_attention_ref
from repro.models import lm
from repro.models.attention import (dequantize_kv, pool_qmax, quantize_kv,
                                    streamed_paged_attention)
from repro.serving import kv_cache
from repro.serving.block_manager import BlockAllocator
from repro.serving.bucketing import pick_bucket
from repro.serving.engine import (ServingEngine, shared_prefix_requests,
                                  summarize, synthetic_requests)
from repro.serving.replica import Replica

pytestmark = pytest.mark.serving

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                   # property tests degrade gracefully
    HAVE_HYPOTHESIS = False

    def given(*a, **k):               # keep decorators importable
        return pytest.mark.skip(reason="hypothesis not installed")

    def settings(*a, **k):
        return lambda f: f

    class st:                         # noqa: N801 — stand-in namespace
        @staticmethod
        def lists(*a, **k):
            return None

        @staticmethod
        def integers(*a, **k):
            return None

CFG = get_config("smollm-135m").reduced()


@functools.lru_cache(maxsize=1)
def _params():
    return lm.init_params(jax.random.PRNGKey(0), CFG)


# ---------------------------------------------------------------------------
# quantizer
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 8, 3, 16)) * 3.0
    q, scale = quantize_kv(x, jnp.int8)
    assert q.dtype == jnp.int8 and scale.shape == x.shape[:-1]
    y = dequantize_kv(q, scale)
    # symmetric rounding: error per element <= half a quantization step
    bound = np.asarray(scale)[..., None] * (0.5 + 1e-3) + 1e-6
    assert np.all(np.abs(np.asarray(y) - np.asarray(x)) <= bound)
    # an all-zero row quantizes (and dequantizes) to exact zeros
    z = jnp.zeros((2, 4, 1, 8))
    qz, sz = quantize_kv(z, jnp.int8)
    assert not np.any(np.asarray(qz)) and not np.any(np.asarray(sz))
    np.testing.assert_array_equal(np.asarray(dequantize_kv(qz, sz)), 0.0)
    assert pool_qmax(jnp.dtype(jnp.int8)) == 127.0


def test_pool_dtype_selection_and_fp8_gating():
    assert kv_cache.pool_dtype(CFG, "fp16") == CFG.act_dtype
    assert kv_cache.pool_dtype(CFG, "int8") == jnp.dtype(jnp.int8)
    assert not kv_cache.quantized("fp16") and kv_cache.quantized("int8")
    with pytest.raises(ValueError):
        kv_cache.pool_dtype(CFG, "int4")
    assert kv_cache.pool_dtype(CFG, "fp8") == jnp.dtype(jnp.float8_e4m3fn)


def test_paged_bytes_dtype_aware():
    nb, bs = 8, 16
    b16 = kv_cache.paged_bytes(CFG, nb, bs, "fp16")
    b8 = kv_cache.paged_bytes(CFG, nb, bs, "int8")
    assert 0 < b8 < b16               # int8 payload + f32 scale side-table
    # block_bytes is exactly the one-block slice of the pool, and the
    # pool total is linear in block count
    assert kv_cache.block_bytes(CFG, bs, "int8") == (
        kv_cache.paged_bytes(CFG, 1, bs, "int8"))
    assert b8 == nb * kv_cache.block_bytes(CFG, bs, "int8")


def test_init_state_scales_and_copy_block_carries_them():
    nb, bs = 6, 4
    state = kv_cache.init_paged_state(CFG, 1, nb, bs, kv_dtype="int8")
    layers = [st for st in state["prefix"] if isinstance(st, dict)
              and "kv" in st]
    stacked = [v for v in state["blocks"].values()
               if isinstance(v, dict) and "kv" in v]
    assert all("kv_scale" in st for st in layers + stacked)
    width = 2 * CFG.n_kv_heads * CFG.head_dim
    for v in stacked:
        assert v["kv"].shape == (CFG.n_super, nb * bs, width)
        assert v["kv"].dtype == jnp.int8
        assert v["kv_scale"].shape == (CFG.n_super, nb * bs,
                                       2 * CFG.n_kv_heads)
    # write recognizable payload + scale into block 2's rows of one
    # stacked pool, then COW-copy to block 4: both must carry over exactly
    name = next(iter(state["blocks"]))
    leaf = state["blocks"][name]
    b2, b4 = slice(2 * bs, 3 * bs), slice(4 * bs, 5 * bs)
    kv = leaf["kv"].at[:, b2].set(7)
    ks = leaf["kv_scale"].at[:, b2].set(0.5)
    state["blocks"][name] = dict(leaf, kv=kv, kv_scale=ks)
    out = kv_cache.copy_block(CFG, state, jnp.int32(2), jnp.int32(4), bs)
    got = out["blocks"][name]
    np.testing.assert_array_equal(np.asarray(got["kv"][:, b4]),
                                  np.asarray(got["kv"][:, b2]))
    np.testing.assert_array_equal(np.asarray(got["kv_scale"][:, b4]),
                                  np.asarray(got["kv_scale"][:, b2]))
    assert np.all(np.asarray(got["kv_scale"][:, b4]) == 0.5)
    assert np.all(np.asarray(got["kv"][:, b4]) == 7)


def test_gather_scatter_blocks_exact_roundtrip():
    """The host-tier payload path: gather -> (host) -> scatter restores
    every pool leaf, including quantized payloads and scale tables."""
    nb, bs = 8, 4
    key = jax.random.PRNGKey(3)
    state = kv_cache.init_paged_state(CFG, 1, nb, bs, kv_dtype="int8")
    state = jax.tree.map(
        lambda x: (jax.random.randint(key, x.shape, -100, 100)
                   .astype(x.dtype) if x.dtype == jnp.int8 else
                   jax.random.uniform(key, x.shape, x.dtype)
                   if jnp.issubdtype(x.dtype, jnp.floating) else x),
        state)
    ids = jnp.asarray([2, 5, 1], jnp.int32)
    payload = kv_cache.gather_blocks(CFG, state, ids, bs)
    blank = kv_cache.init_paged_state(CFG, 1, nb, bs, kv_dtype="int8")
    restored = kv_cache.scatter_blocks(CFG, blank, ids, payload, bs)
    name = next(iter(state["blocks"]))
    for field in ("kv", "kv_scale"):
        for b in (2, 5, 1):
            rows = slice(b * bs, (b + 1) * bs)
            np.testing.assert_array_equal(
                np.asarray(restored["blocks"][name][field][:, rows]),
                np.asarray(state["blocks"][name][field][:, rows]))


# ---------------------------------------------------------------------------
# kernels: quantized pools vs full-precision references
# ---------------------------------------------------------------------------

def test_paged_attention_kernel_quantized_matches_ref():
    rng = np.random.default_rng(0)
    B, H, KV, hd, bs, nb, M = 2, 4, 2, 16, 8, 12, 3
    key = jax.random.PRNGKey(7)
    q = jax.random.normal(key, (B, H, hd))
    kp = jax.random.normal(jax.random.fold_in(key, 1), (nb, bs, KV, hd))
    vp = jax.random.normal(jax.random.fold_in(key, 2), (nb, bs, KV, hd))
    bt = jnp.asarray(rng.choice(nb - 1, size=(B, M), replace=False) + 1,
                     jnp.int32)
    cl = jnp.asarray([bs * M, bs * 2 - 3], jnp.int32)
    qk, sk = quantize_kv(kp, jnp.int8)
    qv, sv = quantize_kv(vp, jnp.int8)
    ref = paged_attention_ref(q, qk, qv, bt, cl, k_scale=sk, v_scale=sv)
    got = paged_attention(q, qk, qv, bt, cl, k_scale=sk, v_scale=sv,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # the ref with scales equals dense dequant-then-attend exactly
    dense = paged_attention_ref(q, dequantize_kv(qk, sk),
                                dequantize_kv(qv, sv), bt, cl)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(dense),
                               atol=1e-6, rtol=1e-6)


def test_paged_prefill_kernel_quantized_matches_oracle():
    KEY = jax.random.PRNGKey(0)

    def _rand(i, shape):
        return jax.random.normal(jax.random.fold_in(KEY, i),
                                 shape).astype(jnp.float32)

    N, Ls, H, KV, hd, bs, M, P = 3, 16, 4, 2, 16, 4, 8, 20
    starts, lengths = (0, 7, 20), (10, 23, 0)
    q = _rand(0, (N, Ls, H, hd))
    k_suf, v_suf = _rand(1, (N, Ls, KV, hd)), _rand(2, (N, Ls, KV, hd))
    k_pool, v_pool = _rand(3, (P, bs, KV, hd)), _rand(4, (P, bs, KV, hd))
    rng = np.random.default_rng(0)
    bt = rng.integers(1, P, (N, M)).astype(np.int32)
    st_ = np.minimum(np.asarray(starts, np.int32), M * bs)
    ln = np.asarray(lengths, np.int32)
    pos = st_[:, None] + np.arange(Ls)[None, :].astype(np.int32)
    # the oracle's row-form pool; the kernel reads its (P, bs, KV, hd)
    # halves through reshape views
    qkv, skv = quantize_kv(jnp.concatenate([k_pool, v_pool], axis=-2),
                           jnp.int8)
    cache = {"kv": qkv.reshape(P * bs, 2 * KV * hd),
             "kv_scale": skv.reshape(P * bs, 2 * KV)}
    qk, qv = qkv[..., :KV, :], qkv[..., KV:, :]
    sk, sv = skv[..., :KV], skv[..., KV:]
    oracle = streamed_paged_attention(
        q, k_suf, v_suf, cache, jnp.asarray(bt), jnp.asarray(pos),
        jnp.asarray(st_), jnp.asarray(ln), scale=hd**-0.5,
        attn_chunk=8, block_size=bs, window=0)
    got = paged_prefill_attention(
        q, k_suf, v_suf, qk, qv, jnp.asarray(bt), jnp.asarray(st_),
        jnp.asarray(ln), k_scale=sk, v_scale=sv, window=0, bq=8,
        interpret=True)
    for n in range(N):
        s = int(np.clip(ln[n] - st_[n], 0, Ls))
        if s:
            np.testing.assert_allclose(np.asarray(got)[n, :s],
                                       np.asarray(oracle)[n, :s],
                                       atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# allocator host tier
# ---------------------------------------------------------------------------

def _host_alloc(num_blocks, bs, cap, payloads):
    def fetch(b):
        return payloads[b].copy()

    def store(ids, pls):
        for b, p in zip(ids, pls):
            payloads[b] = np.array(p)

    return BlockAllocator(num_blocks, block_size=bs,
                          host_cache_blocks=cap, fetch_block=fetch,
                          store_blocks=store)


def test_host_tier_demote_revive_roundtrip():
    bs = 2
    payloads = {}
    alloc = _host_alloc(6, bs, 8, payloads)
    prompt = np.array([1, 2, 3, 4], np.int32)
    blocks = alloc.alloc(2)
    for j, b in enumerate(blocks):
        payloads[b] = prompt[j * bs:(j + 1) * bs] * 10  # "device KV"
    originals = {j: payloads[b].copy() for j, b in enumerate(blocks)}
    alloc.register_prefix(prompt, blocks)
    alloc.free(blocks)                      # -> cached-free
    # pressure: taking every block demotes the chain to the host tier
    taken = alloc.alloc(5)
    assert taken is not None
    assert alloc.host_demotions == 2 and alloc.num_spilled == 2
    assert alloc.match_prefix(prompt, promote=False).spilled_tokens == 4
    for b in taken:                          # scribble over the device KV
        payloads[b] = np.full(bs, -1, np.int32)
    alloc.free(taken)
    # the next prefix hit revives the chain: payloads restored bit-exact,
    # blocks re-registered cached-free under their original keys
    m = alloc.match_prefix(prompt)
    assert m.tokens(bs) == 4 and alloc.host_revivals == 2
    assert alloc.num_spilled == 0
    for j, b in enumerate(m.full_blocks):
        np.testing.assert_array_equal(payloads[b], originals[j])
        assert alloc.refcount(b) == 0        # parked cached-free
    assert alloc.num_cached == 2
    alloc.share(m)                           # admission takes references
    assert all(alloc.refcount(b) == 1 for b in m.full_blocks)
    alloc.unshare(m)
    # conservation with the tier in play (num_free counts cached-free
    # blocks — they are allocatable on demand)
    assert alloc.num_free == 5 and alloc.num_cached == 2


def test_host_tier_lru_capacity_and_reset():
    bs = 2
    payloads = {}
    alloc = _host_alloc(6, bs, 1, payloads)  # capacity: one spilled block
    prompt = np.array([1, 2, 3, 4], np.int32)
    blocks = alloc.alloc(2)
    for j, b in enumerate(blocks):
        payloads[b] = prompt[j * bs:(j + 1) * bs]
    alloc.register_prefix(prompt, blocks)
    alloc.free(blocks)
    taken = alloc.alloc(5)
    assert alloc.host_demotions == 2 and alloc.num_spilled == 1  # LRU bound
    alloc.free(taken)
    alloc.reset_prefix_cache()
    assert alloc.num_spilled == 0            # reset clears the tier too


def test_host_tier_noop_without_callbacks():
    alloc = BlockAllocator(6, block_size=2, host_cache_blocks=8)
    prompt = np.array([1, 2, 3, 4], np.int32)
    blocks = alloc.alloc(2)
    alloc.register_prefix(prompt, blocks)
    alloc.free(blocks)
    taken = alloc.alloc(5)
    assert taken is not None and alloc.num_spilled == 0
    assert alloc.host_demotions == 0


@settings(max_examples=30, deadline=None)
@given(st.lists(st.integers(0, 2**31 - 1), min_size=1, max_size=60))
def test_host_tier_churn_content_correct(seeds):
    """Random admit/free churn over prompts with shared prefixes and a
    pool small enough to keep demoting: every match (device-resident or
    revived from the host tier) must return blocks whose payload equals
    the prompt's corresponding chunk, and block conservation holds."""
    bs = 2
    n_blocks = 8
    prompts = [np.array(p, np.int32) for p in (
        [1, 2, 3, 4, 5, 6], [1, 2, 3, 4, 9, 9], [5, 5, 5, 5],
        [7, 8], [1, 2, 3, 4, 5, 6, 7, 8])]
    payloads = {}
    alloc = _host_alloc(n_blocks, bs, 6, payloads)
    live = []
    for s in seeds:
        if s % 2 == 0:                       # admit
            prompt = prompts[s // 2 % len(prompts)]
            nfull = len(prompt) // bs
            m = alloc.match_prefix(prompt)   # may revive from the tier
            for j, b in enumerate(m.full_blocks):
                np.testing.assert_array_equal(
                    payloads[b], prompt[j * bs:(j + 1) * bs])
            alloc.share(m)
            fresh = alloc.alloc(nfull - len(m.full_blocks))
            if fresh is None:
                alloc.unshare(m)
                continue
            if m.partial_block is not None:  # not needed: all-full chain
                alloc.decref(m.partial_block)
            blocks = list(m.full_blocks) + list(fresh)
            for j, b in enumerate(blocks):
                if alloc.is_writable(b):
                    payloads[b] = np.array(prompt[j * bs:(j + 1) * bs])
            alloc.register_prefix(prompt[:nfull * bs], blocks)
            live.append(blocks)
        elif live:                           # finish a sequence
            alloc.free(live.pop(s % len(live)))
        held = set(b for h in live for b in h)
        # conservation: num_free (incl. cached-free) + referenced
        assert alloc.num_free + len(held) == n_blocks - 1
    for h in live:
        alloc.free(h)


# ---------------------------------------------------------------------------
# engine: identity gates, divergence budget, tier revival, bucket bound
# ---------------------------------------------------------------------------

def _run_engine(reqs, max_seq, slots=4, **kw):
    eng = ServingEngine(_params(), CFG, num_slots=slots, block_size=16,
                        max_seq_len=max_seq, **kw)
    done = eng.run(list(reqs))
    eng.last_stats = summarize(done, max(eng.wall_time, 1e-9), eng)
    return {c.rid: list(map(int, c.tokens)) for c in done}, eng


def _pinned_reqs():
    return synthetic_requests(8, vocab_size=CFG.vocab_size,
                              prompt_len=(16, 48), max_new=(8, 16), seed=0)


def test_engine_fp16_bit_identity_and_int8_budget():
    base, _ = _run_engine(_pinned_reqs(), 80)
    fp16, _ = _run_engine(_pinned_reqs(), 80, kv_dtype="fp16")
    assert base == fp16, "fp16 pools changed greedy output"
    i8, eng = _run_engine(_pinned_reqs(), 80, kv_dtype="int8")
    tot = sum(len(v) for v in base.values())
    mism = sum(x != y for r in base for x, y in zip(base[r], i8[r]))
    # the pinned per-token divergence budget (measured 0 on this fixed
    # workload; 10% margin catches a broken quantizer, not jitter)
    assert mism / tot <= 0.10, f"int8 divergence {mism}/{tot}"
    assert eng.kv_dtype == "int8"
    assert eng.cache_bytes == kv_cache.paged_bytes(
        CFG, eng.allocator.num_blocks, eng.block_size, "int8")


def test_engine_rejects_unknown_kv_dtype():
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(_params(), CFG, num_slots=2, block_size=16,
                      max_seq_len=64, kv_dtype="int4")


def _tiered_reqs():
    # 4 rotating system prompts vs a slots-only pool: every admission
    # evicts the other prefix chains, so revival is the only way a
    # later request of the same tenant finds its prefix cached
    return shared_prefix_requests(16, vocab_size=CFG.vocab_size,
                                  prefix_len=48, suffix_len=(8, 16),
                                  max_new=(4, 8), n_prefixes=4, seed=0)


def test_tiered_engine_identity_revival_and_gain():
    kw = dict(slots=2, prefix_cache=True, num_blocks=13)
    dev, dev_eng = _run_engine(_tiered_reqs(), 96, **kw)
    tier, eng = _run_engine(_tiered_reqs(), 96, host_cache_blocks=32, **kw)
    assert dev == tier, "host spill tier changed greedy output"
    assert eng.allocator.host_revivals >= 1
    assert eng.allocator.host_demotions >= eng.allocator.host_revivals
    s_dev = dev_eng.last_stats
    s_tier = eng.last_stats
    assert (s_tier["prefill"]["cached_tokens"]
            > s_dev["prefill"]["cached_tokens"])
    kv = s_tier["kv"]
    assert kv["dtype"] == "fp16" and kv["host_cache_blocks"] == 32
    assert kv["host_pool_bytes"] == 32 * eng.runner.block_bytes
    assert kv["host_revivals"] == eng.allocator.host_revivals
    # scheduler stats surface the spilled tier for the router
    assert eng.stats().spilled_blocks == eng.allocator.num_spilled


def test_tiered_int8_roundtrip_identity():
    kw = dict(slots=2, prefix_cache=True, num_blocks=13, kv_dtype="int8")
    a, _ = _run_engine(_tiered_reqs(), 96, **kw)
    b, eng = _run_engine(_tiered_reqs(), 96, host_cache_blocks=32, **kw)
    assert a == b, "int8 demote/revive is not an exact round-trip"
    assert eng.allocator.host_revivals >= 1


def test_promotion_stays_on_bucket_grid():
    kw = dict(slots=2, prefix_cache=True, num_blocks=13,
              host_cache_blocks=32)
    _, eng = _run_engine(_tiered_reqs(), 96, **kw)
    shapes = eng.runner.promote_shapes
    buckets = eng.runner.promote_buckets
    assert shapes, "tiered run never promoted"
    assert shapes <= set(buckets)
    assert all(w == pick_bucket(w, buckets) for w in shapes)


def test_replica_probe_counts_spilled_tokens_readonly():
    rep = Replica(_params(), CFG, num_slots=2, block_size=16,
                  max_seq_len=96, prefix_cache=True, num_blocks=13,
                  host_cache_blocks=32)
    rep.engine.run(_tiered_reqs())
    rev0 = rep.engine.allocator.host_revivals
    probe = rep.probe_prefix(_tiered_reqs()[0].prompt)
    assert probe >= 48                       # sees the spilled prefix
    assert rep.engine.allocator.host_revivals == rev0  # probe is read-only
    assert rep.snapshot().spilled_blocks == rep.engine.allocator.num_spilled
