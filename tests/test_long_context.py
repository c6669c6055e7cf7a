"""Long-context serving: chunked prefill admission + the streamed /
Pallas paged-prefill attention pair.

Covers the three layers the long-context path spans:
  * kernels/paged_prefill.py vs attention.streamed_paged_attention —
    the Pallas kernel against its pure-JAX lax.scan oracle (interpret
    mode), over ragged starts/lengths, GQA, and sliding windows;
  * chunked admission bit-identity — a prompt longer than every
    prefill bucket, admitted chunk-by-chunk, must emit exactly the
    tokens of (a) the unchunked engine and (b) the token-by-token
    generate() path, across chunk sizes, architectures (including the
    recurrent resume path), prefix-cache on/off, and with tracing on;
  * guards + telemetry — oversized suffixes raise an actionable error
    when chunking is disabled, per-chunk dispatch records land in the
    trace, and peak score-tile bytes stay flat as prompts grow.

A hypothesis property sweep rides along where the package is
installed; the deterministic sweeps above run everywhere.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.launch.serve import generate
from repro.models import lm
from repro.models.attention import streamed_paged_attention
from repro.serving.engine import ServingEngine, long_document_requests
from repro.serving.observability import (DISPATCH_TID, NULL_OBS,
                                         Observability)
from repro.serving.scheduler import Request

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
        def integers(*a, **k):
            return None

        @staticmethod
        def sampled_from(*a, **k):
            return None

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------

def _rand(i, shape, scale=1.0):
    return (jax.random.normal(jax.random.fold_in(KEY, i), shape)
            * scale).astype(jnp.float32)


def _kernel_case(window, *, seed=0, N=3, Ls=16, H=4, KV=2, hd=16, bs=4,
                 M=8, P=20, starts=(0, 7, 20), lengths=(10, 23, 0),
                 attn_chunk=8):
    """One ragged batch through both implementations; compares only the
    rows inside each sequence's real suffix (padding rows carry
    finite garbage in both paths by design)."""
    q = _rand(seed, (N, Ls, H, hd))
    k_suf = _rand(seed + 1, (N, Ls, KV, hd))
    v_suf = _rand(seed + 2, (N, Ls, KV, hd))
    k_pool = _rand(seed + 3, (P, bs, KV, hd))
    v_pool = _rand(seed + 4, (P, bs, KV, hd))
    rng = np.random.default_rng(seed)
    bt = rng.integers(1, P, (N, M)).astype(np.int32)
    st_ = np.minimum(np.asarray(starts, np.int32), M * bs)
    ln = np.asarray(lengths, np.int32)
    pos = st_[:, None] + np.arange(Ls)[None, :].astype(np.int32)

    # the oracle reads the row-form pool (K then V in each token's row);
    # the kernel reads the same pool's halves through reshape views
    rows = jnp.concatenate([k_pool, v_pool], axis=-2).reshape(P * bs, -1)
    k_pool = rows[:, :KV * hd].reshape(P, bs, KV, hd)
    v_pool = rows[:, KV * hd:].reshape(P, bs, KV, hd)
    oracle = streamed_paged_attention(
        q, k_suf, v_suf, {"kv": rows}, jnp.asarray(bt), jnp.asarray(pos),
        jnp.asarray(st_), jnp.asarray(ln), scale=hd**-0.5,
        attn_chunk=attn_chunk, block_size=bs, window=window)
    got = paged_prefill_attention(
        q, k_suf, v_suf, k_pool, v_pool, jnp.asarray(bt),
        jnp.asarray(st_), jnp.asarray(ln), window=window, bq=8,
        interpret=True)
    for n in range(N):
        s = int(np.clip(ln[n] - st_[n], 0, Ls))
        if s == 0:
            continue
        np.testing.assert_allclose(np.asarray(got)[n, :s],
                                   np.asarray(oracle)[n, :s],
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("window", [0, 6])
def test_paged_prefill_kernel_matches_streamed_oracle(window):
    _kernel_case(window)


def test_paged_prefill_kernel_ragged_sweep():
    # varying raggedness: fresh prompts (start 0), resumed chunks
    # (start mid-pool), fully-padded rows, MHA and GQA head layouts
    _kernel_case(0, seed=11, starts=(3, 0, 15), lengths=(19, 16, 31))
    _kernel_case(4, seed=12, H=4, KV=4, starts=(8, 1, 0),
                 lengths=(24, 1, 8))
    _kernel_case(0, seed=13, Ls=8, bs=8, M=4, starts=(16, 2, 0),
                 lengths=(24, 10, 0), attn_chunk=32)


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 10_000), window=st.sampled_from([0, 3, 6]),
           bs=st.sampled_from([4, 8]), kv=st.sampled_from([2, 4]))
    def test_paged_prefill_kernel_property(seed, window, bs, kv):
        rng = np.random.default_rng(seed)
        M = int(rng.integers(2, 8))
        starts = tuple(int(x) for x in rng.integers(0, M * bs + 1, 3))
        lengths = tuple(min(int(s) + int(g), M * bs + 16)
                        for s, g in zip(starts, rng.integers(0, 17, 3)))
        _kernel_case(window, seed=seed, KV=kv, bs=bs, M=M,
                     starts=starts, lengths=lengths)


# ---------------------------------------------------------------------------
# chunked admission identity
# ---------------------------------------------------------------------------

def _arch_setup(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _run_chunked(params, cfg, prompt, max_new, *, chunk, buckets,
                 prefix_cache=None, obs=None, num_slots=2, block_size=8):
    eng = ServingEngine(params, cfg, num_slots=num_slots,
                        block_size=block_size,
                        max_seq_len=len(prompt) + max_new + 1,
                        prefill_buckets=buckets, prefill_chunk=chunk,
                        prefix_cache=prefix_cache,
                        obs=obs if obs is not None else NULL_OBS)
    done = eng.run([Request(rid=0, prompt=np.asarray(prompt, np.int32),
                            max_new_tokens=max_new)])
    return eng, done[0].tokens


@pytest.mark.parametrize("arch", ["smollm-135m", "rwkv6-3b",
                                  "recurrentgemma-2b"])
@pytest.mark.parametrize("chunk", [32, 48])
def test_chunked_prefill_matches_generate(arch, chunk):
    cfg, params = _arch_setup(arch)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, cfg.vocab_size, 150).astype(np.int32)
    ref = np.asarray(generate(params, cfg, prompt[None], 6))[0]
    _, got = _run_chunked(params, cfg, prompt, 6, chunk=chunk,
                          buckets=[16, 32])
    np.testing.assert_array_equal(got, ref)


def test_chunked_matches_unchunked_engine_and_prefix_cache():
    cfg, params = _arch_setup("smollm-135m")
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, 200).astype(np.int32)
    # unchunked: buckets wide enough to take the prompt in one dispatch
    _, ref = _run_chunked(params, cfg, prompt, 8, chunk=None,
                          buckets=[64, 256])
    for cache in (False, True):
        eng, got = _run_chunked(params, cfg, prompt, 8, chunk=64,
                                buckets=[16, 32, 64], prefix_cache=cache)
        np.testing.assert_array_equal(got, ref)
        assert eng.runner.prefill_chunk == 64
    # with the cache warm, a repeat of the same prompt is fully cached
    # (suffix 1) and must admit WITHOUT chunking
    done = eng.run([Request(rid=1, prompt=prompt, max_new_tokens=8)])
    np.testing.assert_array_equal(done[0].tokens, ref)
    assert eng.scheduler.prefix_hit_requests >= 1


def test_chunked_interleaves_with_running_decode():
    """A short request admitted first must keep decoding while a long
    prompt chunks in; both outputs stay bit-identical to generate()."""
    cfg, params = _arch_setup("smollm-135m")
    rng = np.random.default_rng(9)
    long_p = rng.integers(0, cfg.vocab_size, 180).astype(np.int32)
    short_p = rng.integers(0, cfg.vocab_size, 20).astype(np.int32)
    eng = ServingEngine(params, cfg, num_slots=4, block_size=8,
                        max_seq_len=256, prefill_buckets=[16, 32, 64],
                        prefill_chunk=64)
    done = eng.run([Request(rid=0, prompt=short_p, max_new_tokens=24),
                    Request(rid=1, prompt=long_p, max_new_tokens=6)])
    by_rid = {c.rid: c for c in done}
    for rid, p in ((0, short_p), (1, long_p)):
        exp = np.asarray(generate(params, cfg, p[None],
                                  by_rid[rid].tokens.shape[0]))[0]
        np.testing.assert_array_equal(by_rid[rid].tokens, exp)
    # the long admission spanned several engine steps; the short lane
    # kept emitting during them (TTFT of rid 0 precedes rid 1's)
    assert by_rid[0].t_first_token < by_rid[1].t_first_token


def test_chunked_prefill_with_speculation():
    """Chunked lanes sit out verify dispatches until admitted; greedy
    output under speculation stays identical to generate()."""
    cfg, params = _arch_setup("smollm-135m")
    rng = np.random.default_rng(5)
    pattern = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
    prompt = np.tile(pattern, 25)     # 150 tokens, n-gram friendly
    eng = ServingEngine(params, cfg, num_slots=2, block_size=8,
                        max_seq_len=256, prefill_buckets=[16, 32],
                        prefill_chunk=32, speculate=3)
    done = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=10)])
    exp = np.asarray(generate(params, cfg, prompt[None], 10))[0]
    np.testing.assert_array_equal(done[0].tokens, exp)


# ---------------------------------------------------------------------------
# guards + telemetry
# ---------------------------------------------------------------------------

def test_oversized_prompt_without_chunking_raises_actionable():
    cfg, params = _arch_setup("smollm-135m")
    eng = ServingEngine(params, cfg, num_slots=2, block_size=8,
                        max_seq_len=256, prefill_buckets=[16, 32],
                        prefill_chunk=0)
    prompt = np.arange(100, dtype=np.int32) % cfg.vocab_size
    with pytest.raises(ValueError, match="prefill-chunk"):
        eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=4))
    # runner-level guard carries the same guidance
    with pytest.raises(ValueError, match="prefill-chunk"):
        eng.runner.suffix_bucket(100)


def test_chunk_steps_traced_and_identity_with_tracing():
    cfg, params = _arch_setup("smollm-135m")
    rng = np.random.default_rng(13)
    prompt = rng.integers(0, cfg.vocab_size, 150).astype(np.int32)
    _, ref = _run_chunked(params, cfg, prompt, 6, chunk=32,
                          buckets=[16, 32])
    obs = Observability()
    eng = ServingEngine(params, cfg, num_slots=2, block_size=8,
                        max_seq_len=256, prefill_buckets=[16, 32],
                        prefill_chunk=32, obs=obs)
    done = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=6)])
    np.testing.assert_array_equal(done[0].tokens, ref)
    steps = [s for s in obs.spans if s["tid"] == DISPATCH_TID
             and s["name"] == "prefill"]
    chunked = [s for s in steps if "chunk" in s["args"]]
    assert len(chunked) >= 2, "multi-chunk admission left no chunk records"
    total = chunked[0]["args"]["chunks_total"]
    assert [s["args"]["chunk"] for s in chunked] == list(range(total))
    assert all(s["args"]["chunks_total"] == total for s in chunked)
    assert all("computed_tokens" in s["args"]
               and "first_dispatch" in s["args"] for s in chunked)
    # resumed chunks are a distinct jit variant: chunk 1's first
    # occurrence is flagged as a first dispatch (compile attribution)
    assert chunked[1]["args"]["first_dispatch"] is True


def test_peak_score_bytes_flat_past_chunk_budget():
    """The memory claim, on the runner's analytic accounting: the peak
    score-tile bytes of the largest prefill dispatch stop growing once
    prompts exceed the chunk budget (sub-linear in prompt length)."""
    cfg, params = _arch_setup("smollm-135m")
    rng = np.random.default_rng(21)
    peaks = {}
    for L in (96, 192, 384):
        prompt = rng.integers(0, cfg.vocab_size, L).astype(np.int32)
        eng, _ = _run_chunked(params, cfg, prompt, 4, chunk=32,
                              buckets=[16, 32])
        peaks[L] = eng.runner.prefill_peak_score_bytes
    assert peaks[96] == peaks[192] == peaks[384], peaks
    assert peaks[384] > 0


def test_long_document_workload_generator():
    reqs = long_document_requests(3, vocab_size=256, prompt_len=(64, 128),
                                  max_new=(4, 8), seed=0)
    assert len(reqs) == 3
    assert all(64 <= len(r.prompt) <= 128 for r in reqs)
    assert all(4 <= r.max_new_tokens <= 8 for r in reqs)
    # deterministic in the seed
    again = long_document_requests(3, vocab_size=256, prompt_len=(64, 128),
                                   max_new=(4, 8), seed=0)
    for a, b in zip(reqs, again):
        np.testing.assert_array_equal(a.prompt, b.prompt)


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(seed=st.integers(0, 1000), chunk=st.sampled_from([32, 48, 64]),
           plen=st.integers(80, 220))
    def test_chunked_identity_property(seed, chunk, plen):
        cfg, params = _arch_setup("smollm-135m")
        rng = np.random.default_rng(seed)
        prompt = rng.integers(0, cfg.vocab_size, plen).astype(np.int32)
        ref = np.asarray(generate(params, cfg, prompt[None], 4))[0]
        _, got = _run_chunked(params, cfg, prompt, 4, chunk=chunk,
                              buckets=[16, 32, 64])
        np.testing.assert_array_equal(got, ref)
