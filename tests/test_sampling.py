"""Per-request sampling API: SamplingParams resolution, logit warping,
position-keyed batch-composition invariance (unit + engine e2e),
distribution-preserving speculative sampling (tiny-vocab frequency
test), unified stop handling incl. mid-speculative-chain truncation,
streaming, logprobs, and the deprecation shim."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import generate
from repro.models import lm
from repro.serving import sampling
from repro.serving.block_manager import BlockAllocator
from repro.serving.bucketing import chain_buckets, pick_bucket, pow2_buckets
from repro.serving.engine import (Request, ServingEngine, summarize,
                                  synthetic_requests)
from repro.serving.sampling import SamplingParams, resolve
from repro.serving.scheduler import Scheduler

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

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------------------------
# SamplingParams: validation, stop normalization, legacy-field resolution
# ----------------------------------------------------------------------------

def test_sampling_params_validation_and_stop_normalization():
    sp = SamplingParams(temperature=0.7, top_k=5, top_p=0.9, stop=[3, (4, 5)])
    assert sp.stop == ((3,), (4, 5))
    assert SamplingParams(stop=7).stop == ((7,),)
    assert SamplingParams().greedy and not sp.greedy
    assert sp.with_seed(9).seed == 9 and sp.seed == 0     # frozen
    for bad in (dict(temperature=-0.1), dict(top_k=-1), dict(top_p=0.0),
                dict(top_p=1.5), dict(max_new_tokens=0),
                dict(stop=[()])):
        with pytest.raises(ValueError):
            SamplingParams(**bad)


def test_resolve_merges_legacy_fields():
    default = SamplingParams(temperature=0.5, seed=4)
    # request sampling wins over the engine default
    sp = resolve(SamplingParams(temperature=0.9), default)
    assert sp.temperature == 0.9
    # no request sampling: the engine default applies
    assert resolve(None, default).temperature == 0.5
    # legacy max_new_tokens overrides the config's cap
    assert resolve(None, default, max_new_tokens=3).max_new_tokens == 3
    # legacy eos_id becomes one more single-token stop (deduplicated)
    sp = resolve(SamplingParams(stop=[2]), None, eos_id=9)
    assert sp.stop == ((2,), (9,))
    assert resolve(SamplingParams(stop=[9]), None, eos_id=9).stop == ((9,),)


def test_seed32_folds_any_int():
    assert sampling.seed32(0) == 0 and sampling.seed32(7) == 7
    assert sampling.seed32(2**40 + 3) == sampling.seed32(3)
    assert sampling.seed32(-1) == sampling.seed32(0xFFFFFFFF)


# ----------------------------------------------------------------------------
# warp_logits: temperature / top-k / top-p
# ----------------------------------------------------------------------------

def test_warp_logits_topk_topp():
    x = jnp.asarray([[1.0, 2.0, 3.0, 4.0, 0.0]])
    one = jnp.ones(1)
    w = sampling.warp_logits(x, one, jnp.asarray([2]), one)
    np.testing.assert_array_equal(np.isfinite(np.asarray(w[0])),
                                  [False, False, True, True, False])
    # probs are ~[.03, .09, .23, .64, .01]: a 0.6 nucleus is {3} alone,
    # 0.7 needs {3, 2}
    w = sampling.warp_logits(x, one, jnp.asarray([0]), jnp.asarray([0.6]))
    assert np.isfinite(np.asarray(w[0])).sum() == 1
    w = sampling.warp_logits(x, one, jnp.asarray([0]), jnp.asarray([0.7]))
    np.testing.assert_array_equal(np.isfinite(np.asarray(w[0])),
                                  [False, False, True, True, False])
    # top_p=1 and top_k=0 are exact no-ops; temperature rescales
    w = sampling.warp_logits(x, one, jnp.asarray([0]), one)
    np.testing.assert_allclose(np.asarray(w), np.asarray(x))
    w = sampling.warp_logits(x, 2.0 * one, jnp.asarray([0]), one)
    np.testing.assert_allclose(np.asarray(w), np.asarray(x) / 2.0)
    # per-row configs are independent (config-as-data batching)
    xb = jnp.stack([x[0], x[0]])
    w = sampling.warp_logits(xb, jnp.ones(2), jnp.asarray([2, 0]),
                             jnp.asarray([1.0, 0.6]))
    assert np.isfinite(np.asarray(w[0])).sum() == 2
    assert np.isfinite(np.asarray(w[1])).sum() == 1


def test_sample_tokens_batch_invariant_and_greedy():
    logits = jax.random.normal(jax.random.fold_in(KEY, 1), (3, 16))
    temps = jnp.asarray([0.0, 0.8, 1.2])
    topks = jnp.asarray([0, 4, 0])
    topps = jnp.asarray([1.0, 1.0, 0.9])
    seeds = jnp.asarray([0, 11, 11])
    pos = jnp.asarray([5, 5, 9])
    tok, lp = sampling.sample_tokens(logits, pos, temps, topks, topps,
                                     seeds)
    assert int(tok[0]) == int(jnp.argmax(logits[0]))
    # each sampled lane reproduces bit-identically when run ALONE —
    # the draw depends only on (seed, position), not on batch mates
    for b in (1, 2):
        solo, _ = sampling.sample_tokens(
            logits[b:b + 1], pos[b:b + 1], temps[b:b + 1], topks[b:b + 1],
            topps[b:b + 1], seeds[b:b + 1])
        assert int(solo[0]) == int(tok[b])
    # same seed, different position -> a fresh draw stream
    tok2, _ = sampling.sample_tokens(logits, pos + 1, temps, topks, topps,
                                     seeds)
    assert np.asarray(lp).max() <= 0.0
    assert tok.dtype == jnp.int32 and tok2.shape == tok.shape


# ----------------------------------------------------------------------------
# verify_tokens: greedy accept rule + distribution preservation
# ----------------------------------------------------------------------------

def test_verify_tokens_greedy_matches_argmax_accept():
    V, T = 8, 4
    logits = jax.random.normal(jax.random.fold_in(KEY, 2), (2, T, V))
    am = np.asarray(jnp.argmax(logits, -1))
    # lane 0: drafts agree with argmax at chain idx 1,2 then diverge;
    # lane 1: first draft already disagrees
    chain = np.zeros((2, T), np.int32)
    chain[0] = [3, am[0, 0], am[0, 1], (am[0, 2] + 1) % V]
    chain[1] = [2, (am[1, 0] + 1) % V, 0, 0]
    counts = jnp.asarray([4, 2], jnp.int32)
    emit, acc, lp = sampling.verify_tokens(
        logits, jnp.asarray(chain), counts, jnp.asarray([7, 9]),
        jnp.zeros(2), jnp.zeros(2, jnp.int32), jnp.ones(2),
        jnp.zeros(2, jnp.int32))
    assert list(np.asarray(acc)) == [2, 0]
    np.testing.assert_array_equal(np.asarray(emit), am)   # greedy emits
    assert np.asarray(lp).max() <= 0.0


def _spec_marginal(row_logits, draft_tok, temps, topk, topp, n=16384):
    """Empirical marginal of the token verify_tokens emits at chain
    index 0, over n per-request seeds (the tiny-vocab frequency test)."""
    V = row_logits.shape[-1]
    logits = jnp.broadcast_to(row_logits[None, None], (n, 2, V))
    chain = jnp.broadcast_to(jnp.asarray([[1, draft_tok]]),
                             (n, 2)).astype(jnp.int32)
    emit, acc, _ = jax.jit(sampling.verify_tokens)(
        logits, chain, jnp.full((n,), 2, jnp.int32),
        jnp.full((n,), 13, jnp.int32), jnp.full((n,), temps),
        jnp.full((n,), topk, jnp.int32), jnp.full((n,), topp),
        jnp.arange(n, dtype=jnp.int32))
    freq = np.bincount(np.asarray(emit[:, 0]), minlength=V) / n
    return freq, np.asarray(acc)


def test_speculative_sampling_preserves_marginal_tiny_vocab():
    """Leviathan accept/reject with a deterministic draft must leave the
    next-token marginal exactly the target distribution: accept d w.p.
    q(d), else resample from q with d masked — marginal q. Checked by
    frequency over 16k independent per-request seeds, draft inside and
    OUTSIDE the nucleus, warped and unwarped."""
    V = 8
    row = jax.random.normal(jax.random.fold_in(KEY, 3), (V,))
    temp = 0.9
    q = np.asarray(jax.nn.softmax(row / temp))
    # draft = a mid-probability token, no warping
    d = int(np.argsort(q)[V // 2])
    freq, acc = _spec_marginal(row, d, temp, 0, 1.0)
    assert 0.5 * np.abs(freq - q).sum() < 0.03
    assert abs(acc.astype(bool).mean() - q[d]) < 0.02   # accept w.p. q(d)
    # draft OUTSIDE the top-k: q_k(d) = 0, every draft rejected, and the
    # marginal is the WARPED target
    wq = np.asarray(jax.nn.softmax(sampling.warp_logits(
        row[None], jnp.asarray([temp]), jnp.asarray([3]),
        jnp.asarray([1.0]))[0]))
    d_out = int(np.argsort(q)[0])
    assert wq[d_out] == 0.0
    freq, acc = _spec_marginal(row, d_out, temp, 3, 1.0)
    assert acc.sum() == 0
    assert 0.5 * np.abs(freq - wq).sum() < 0.03


# ----------------------------------------------------------------------------
# engine e2e: mixed-config batches, batch-composition invariance
# ----------------------------------------------------------------------------

def _expect(params, cfg, req):
    return np.asarray(generate(params, cfg, np.asarray(req.prompt)[None],
                               req.max_new_tokens))[0]


def _mixed_requests(cfg, repetitive=False):
    rng = np.random.default_rng(5)
    if repetitive:
        pat = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
        prompts = [np.tile(pat, 4)[:16] for _ in range(4)]
    else:
        prompts = [rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
                   for _ in range(4)]
    return [
        Request(rid=0, prompt=prompts[0], max_new_tokens=8),   # greedy
        Request(rid=1, prompt=prompts[1], sampling=SamplingParams(
            temperature=0.8, top_k=32, seed=11, max_new_tokens=9)),
        Request(rid=2, prompt=prompts[2], sampling=SamplingParams(
            temperature=1.2, top_p=0.9, seed=7, max_new_tokens=6)),
        # explicit temperature-0 SamplingParams: must stay bit-identical
        # to generate() through every path, including speculation
        Request(rid=3, prompt=prompts[3], sampling=SamplingParams(
            temperature=0.0, max_new_tokens=7)),
    ]


def test_engine_mixed_batch_and_composition_invariance():
    """One batch serving greedy + sampled + nucleus lanes at once:
    greedy lanes stay bit-identical to generate(), and each sampled
    lane's output is bit-identical when rerun alone or in a different
    mix (same per-request seed) — the position-keyed PRNG contract."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _mixed_requests(cfg)
    eng = ServingEngine(params, cfg, num_slots=4, block_size=4,
                        max_seq_len=32)
    done = eng.run(list(reqs))
    out = {c.rid: c.tokens for c in done}
    assert len(done) == 4
    for rid in (0, 3):
        np.testing.assert_array_equal(out[rid],
                                      _expect(params, cfg, reqs[rid]))
    stats = summarize(done, eng.wall_time, eng)
    assert stats["sampling"]["sampled_requests"] == 2
    assert stats["sampling"]["greedy_requests"] == 2
    # rerun each sampled request alone, then in a different mix
    for rid in (1, 2):
        solo = eng.run([dataclasses.replace(reqs[rid], arrival=0.0)])
        np.testing.assert_array_equal(solo[0].tokens, out[rid])
    pair = eng.run([dataclasses.replace(reqs[2], arrival=0.0),
                    dataclasses.replace(reqs[0], arrival=0.0)])
    np.testing.assert_array_equal(
        {c.rid: c.tokens for c in pair}[2], out[2])


def test_engine_spec_sampled_mixed_batch_invariance():
    """Speculation on, mixed greedy/sampled/temp-0 batch: greedy and
    explicit temperature-0 lanes stay bit-identical to generate()
    through the verify path, sampled lanes are batch-composition
    invariant, and pools fully restore."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = _mixed_requests(cfg, repetitive=True)
    eng = ServingEngine(params, cfg, num_slots=4, block_size=4,
                        max_seq_len=32, speculate=4)
    free0 = eng.allocator.num_free
    done = eng.run(list(reqs))
    out = {c.rid: c.tokens for c in done}
    proposed = eng.scheduler.proposed_tokens   # stats reset per run
    assert proposed > 0
    assert eng.allocator.num_free == free0
    for rid in (0, 3):   # greedy + explicit temp-0 SamplingParams
        np.testing.assert_array_equal(out[rid],
                                      _expect(params, cfg, reqs[rid]))
    for rid in (1, 2):
        solo = eng.run([dataclasses.replace(reqs[rid], arrival=0.0)])
        np.testing.assert_array_equal(solo[0].tokens, out[rid])


# ----------------------------------------------------------------------------
# unified stop handling (eos == stop seq; mid-speculative-chain cut)
# ----------------------------------------------------------------------------

class _OracleProposer:
    """Proposes the request's true greedy continuation verbatim, so
    every draft is accepted — drives stops deep into accepted chains."""

    def __init__(self, scripts):
        self.scripts = scripts        # [(prompt list, continuation list)]

    def propose(self, history, k):
        hist = list(history)
        for prompt, out in self.scripts:
            full = prompt + out
            if (len(prompt) <= len(hist) <= len(full)
                    and hist == full[:len(hist)]):
                return full[len(hist):len(hist) + k]
        return []


def _stop_cut_index(full, stop):
    """Earliest end index in `full` where `stop` completes."""
    L = len(stop)
    for end in range(L, len(full) + 1):
        if list(full[end - L:end]) == list(stop):
            return end
    return None


def test_stop_sequence_plain_decode_and_multi_token():
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    full = np.asarray(generate(params, cfg, prompt, 10))[0]
    stop = (int(full[2]), int(full[3]))          # multi-token stop
    cut = _stop_cut_index(full, stop)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=32)
    done = eng.run([Request(rid=0, prompt=np.asarray(prompt[0]),
                            sampling=SamplingParams(
                                max_new_tokens=10, stop=(stop,)))])
    assert done[0].finish_reason == "stop"
    np.testing.assert_array_equal(done[0].tokens, full[:cut])
    # no stop hit -> length finish
    done = eng.run([Request(rid=1, prompt=np.asarray(prompt[0]),
                            max_new_tokens=4)])
    assert done[0].finish_reason == "length"


def test_stop_sequence_mid_speculative_chain_frees_blocks():
    """A stop completing inside an ACCEPTED draft chain must truncate
    the output exactly at the stop, and the chain's claimed-but-unused
    blocks must all return to the pool (accepted prefix truncates,
    rejected/cut tail frees its claims)."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0,
                                cfg.vocab_size)
    full = np.asarray(generate(params, cfg, prompt, 12))[0]
    stop = (int(full[4]), int(full[5]))
    cut = _stop_cut_index(full, stop)
    assert cut is not None and cut >= 2
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=32, speculate=6)
    script = [([int(t) for t in prompt[0]], [int(t) for t in full])]
    eng.scheduler._proposers = [_OracleProposer(script)] * 2
    free0 = eng.allocator.num_free
    done = eng.run([Request(rid=0, prompt=np.asarray(prompt[0]),
                            sampling=SamplingParams(
                                max_new_tokens=12, stop=(stop,)))])
    assert done[0].finish_reason == "stop"
    np.testing.assert_array_equal(done[0].tokens, full[:cut])
    assert eng.allocator.num_free == free0       # chain claims all freed
    # the oracle drafted past the stop: some drafts were cut, so
    # accepted < proposed even though every draft agreed
    assert eng.scheduler.accepted_tokens < eng.scheduler.proposed_tokens


def test_eos_and_stop_are_one_code_path():
    """Legacy eos_id resolves into the unified stop list and behaves
    exactly like a one-token stop sequence."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    full = np.asarray(generate(params, cfg, prompt, 8))[0]
    eos = int(full[3])
    cut = _stop_cut_index(full, (eos,))
    eng = ServingEngine(params, cfg, num_slots=1, block_size=4,
                        max_seq_len=32)
    legacy = eng.run([Request(rid=0, prompt=np.asarray(prompt[0]),
                              max_new_tokens=8, eos_id=eos)])
    new = eng.run([Request(rid=1, prompt=np.asarray(prompt[0]),
                           sampling=SamplingParams(max_new_tokens=8,
                                                   stop=(eos,)))])
    np.testing.assert_array_equal(legacy[0].tokens, full[:cut])
    np.testing.assert_array_equal(new[0].tokens, legacy[0].tokens)
    assert legacy[0].finish_reason == new[0].finish_reason == "stop"


# ----------------------------------------------------------------------------
# streaming + logprobs + deprecation shim
# ----------------------------------------------------------------------------

def test_stream_matches_run_and_orders_events():
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = synthetic_requests(5, vocab_size=cfg.vocab_size, prompt_len=8,
                              max_new=(3, 8), seed=9)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=32)
    chunks, finals = {r.rid: [] for r in reqs}, {}
    for ev in eng.stream(list(reqs)):
        if ev.done:
            assert ev.rid not in finals          # done fires once, last
            finals[ev.rid] = ev.completion
        else:
            assert ev.rid not in finals          # no tokens after done
            chunks[ev.rid].extend(ev.tokens)
    assert set(finals) == {r.rid for r in reqs}
    expect = {c.rid: c.tokens for c in eng.run(list(reqs))}
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(chunks[r.rid], np.int32),
                                      expect[r.rid])
        np.testing.assert_array_equal(finals[r.rid].tokens, expect[r.rid])
    assert eng.scheduler.on_event is None        # callback restored


def test_chosen_token_logprobs():
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0,
                                cfg.vocab_size)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=32, speculate=3)
    reqs = [Request(rid=0, prompt=np.asarray(prompt[0]),
                    sampling=SamplingParams(max_new_tokens=6,
                                            logprobs=True)),
            Request(rid=1, prompt=np.asarray(prompt[0]),
                    sampling=SamplingParams(max_new_tokens=6,
                                            temperature=0.9, seed=3,
                                            logprobs=True)),
            Request(rid=2, prompt=np.asarray(prompt[0]),
                    max_new_tokens=6)]
    done = {c.rid: c for c in eng.run(reqs)}
    for rid in (0, 1):
        lp = done[rid].logprobs
        assert lp is not None and lp.shape == (len(done[rid].tokens),)
        assert np.isfinite(lp).all() and (lp <= 0).all()
        # logprobs=True is the back-compat spelling of k=1
        assert done[rid].top_ids.shape == (len(done[rid].tokens), 1)
    assert done[2].logprobs is None              # not requested
    assert done[2].top_ids is None and done[2].top_logprobs is None


def test_top_alternatives_unit():
    logits = jax.random.normal(jax.random.fold_in(KEY, 4), (2, 3, 16))
    ids, lps = sampling.top_alternatives(logits, 5)
    assert ids.shape == (2, 3, 5) and lps.shape == (2, 3, 5)
    ref = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    np.testing.assert_allclose(
        np.asarray(lps), np.take_along_axis(ref, np.asarray(ids), -1),
        rtol=1e-6)
    assert (np.diff(np.asarray(lps), axis=-1) <= 1e-7).all()  # descending
    np.testing.assert_array_equal(np.asarray(ids[..., 0]),
                                  np.argmax(np.asarray(logits), -1))


def test_topk_alternative_logprobs_decode_and_verify_paths():
    """SamplingParams.logprobs=k (satellite): Completion carries the k
    alternative (ids, logprobs) per emitted position, through the plain
    decode path AND the speculative verify path (oracle drafts of the
    greedy continuation, so chains verify and are accepted by
    construction), for greedy and sampled lanes — and the greedy
    realization is unchanged by asking for them."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    pat = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
    prompt = np.tile(pat, 4)[:16]
    greedy = _expect(params, cfg, Request(rid=0, prompt=prompt,
                                          max_new_tokens=8))
    oracle = _OracleProposer([([int(t) for t in prompt],
                               [int(t) for t in greedy])])
    for speculate in (0, 3):
        eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                            max_seq_len=32, speculate=speculate)
        if speculate:
            eng.scheduler._proposers = [oracle] * eng.num_slots
        reqs = [Request(rid=0, prompt=prompt.copy(),
                        sampling=SamplingParams(max_new_tokens=8,
                                                logprobs=3)),
                Request(rid=1, prompt=prompt.copy(),
                        sampling=SamplingParams(max_new_tokens=8,
                                                temperature=0.9, seed=11,
                                                top_k=4, logprobs=2))]
        done = {c.rid: c for c in eng.run(reqs)}
        if speculate:
            assert eng.scheduler.accepted_tokens > 0   # verify path ran
        g = done[0]
        assert g.top_ids.shape == (8, 3)
        assert g.top_logprobs.shape == (8, 3)
        # greedy chosen token IS the top-1 alternative, logprob matches,
        # alternatives sorted descending
        np.testing.assert_array_equal(g.tokens, g.top_ids[:, 0])
        np.testing.assert_allclose(g.logprobs, g.top_logprobs[:, 0],
                                   rtol=1e-5)
        assert (np.diff(g.top_logprobs, axis=1) <= 1e-6).all()
        np.testing.assert_array_equal(g.tokens, greedy)  # output unchanged
        s = done[1]
        assert s.top_ids.shape == (8, 2)
        # a sampled token need not be the argmax, but its RAW-dist
        # logprob can never exceed the top alternative's
        assert (s.logprobs <= s.top_logprobs[:, 0] + 1e-6).all()
    # streaming carries the same alternatives the completion records
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=32, speculate=3)
    eng.scheduler._proposers = [oracle] * eng.num_slots
    got_ids, final = [], None
    for ev in eng.stream([Request(rid=0, prompt=prompt.copy(),
                                  sampling=SamplingParams(
                                      max_new_tokens=8, logprobs=3))]):
        if ev.done:
            final = ev.completion
        else:
            assert len(ev.top_ids) == len(ev.tokens)
            got_ids.extend(ev.top_ids)
    np.testing.assert_array_equal(np.asarray(got_ids, np.int32),
                                  final.top_ids)


def test_logprobs_validation_and_cap():
    with pytest.raises(ValueError):
        SamplingParams(logprobs=-1)
    assert SamplingParams(logprobs=True).logprobs == 1
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(params, cfg, num_slots=1, block_size=4,
                        max_seq_len=32, max_logprobs=4)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                           sampling=SamplingParams(max_new_tokens=2,
                                                   logprobs=5)))
    done = eng.run([Request(rid=1, prompt=np.arange(4, dtype=np.int32),
                            sampling=SamplingParams(max_new_tokens=2,
                                                    logprobs=4))])
    assert done[0].top_ids.shape == (2, 4)


def test_engine_deprecation_shim_and_default_sampling():
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(8, dtype=np.int32) % cfg.vocab_size
    with pytest.warns(DeprecationWarning):
        eng = ServingEngine(params, cfg, num_slots=1, block_size=4,
                            max_seq_len=32, temperature=0.7, seed=3)
    assert eng.default_sampling.temperature == 0.7
    assert eng.default_sampling.seed == 3
    done = eng.run([Request(rid=0, prompt=prompt, max_new_tokens=5)])
    assert len(done[0].tokens) == 5              # shim still serves
    # an engine-default SamplingParams applies to sampling-less requests
    # and matches the per-request spelling bit-for-bit
    eng2 = ServingEngine(params, cfg, num_slots=1, block_size=4,
                         max_seq_len=32,
                         sampling=SamplingParams(temperature=0.7, seed=3))
    done2 = eng2.run([Request(rid=0, prompt=prompt.copy(),
                              max_new_tokens=5)])
    np.testing.assert_array_equal(done2[0].tokens, done[0].tokens)
    done3 = eng2.run([Request(rid=1, prompt=prompt.copy(),
                              sampling=SamplingParams(temperature=0.7,
                                                      seed=3,
                                                      max_new_tokens=5))])
    np.testing.assert_array_equal(done3[0].tokens, done[0].tokens)
    # identical prompts under a sampled engine DEFAULT draw distinct
    # streams (per-request seed = default.seed + rid): best-of-n over a
    # shared prompt must not collapse to n copies — but each stream is
    # still reproducible (rerun alone matches, seeds stay per-request)
    pair = eng2.run([Request(rid=0, prompt=prompt.copy(),
                             max_new_tokens=5),
                     Request(rid=1, prompt=prompt.copy(),
                             max_new_tokens=5)])
    t = {c.rid: c.tokens for c in pair}
    assert not np.array_equal(t[0], t[1])
    solo = eng2.run([Request(rid=1, prompt=prompt.copy(),
                             max_new_tokens=5)])
    np.testing.assert_array_equal(solo[0].tokens, t[1])


# ----------------------------------------------------------------------------
# property: rejected SAMPLED drafts restore allocator pools exactly
# ----------------------------------------------------------------------------

class _FakeRunner:
    """Host-only runner stand-in (block accounting needs no device)."""

    prefill_max_batch = 4
    max_logprobs = 8

    def __init__(self, speculate=8):
        self.prefill_buckets = pow2_buckets(64, start=8)
        self.verify_buckets = chain_buckets(speculate)

    def suffix_bucket(self, n):
        return pick_bucket(n, self.prefill_buckets)

    def chain_bucket(self, n):
        return pick_bucket(n, self.verify_buckets)

    def prefill(self, rows):
        return (np.full(len(rows), 1, np.int32),
                np.zeros(len(rows), np.float32), None)

    def verify(self, tokens, positions, counts):
        return (np.full(tokens.shape, -1, np.int32),
                np.zeros(tokens.shape[0], np.int32),
                np.zeros(tokens.shape, np.float32), None)

    def commit(self, idx):
        pass

    def copy_block(self, src, dst):
        pass

    def write_table(self, slot, row):
        pass

    def clear_table(self, slot):
        pass

    def set_sampling(self, slot, sp):
        pass

    def clear_sampling(self, slot):
        pass


def _alloc_snapshot(alloc):
    return (alloc.num_free, alloc.num_cached, dict(alloc._ref))


@settings(max_examples=60, deadline=None)
@given(plen=st.integers(1, 18), max_new=st.integers(4, 40),
       consumed=st.integers(0, 8), k=st.integers(1, 8),
       bs=st.integers(2, 5), seed=st.integers(0, 2**34))
def test_rejected_sampled_draft_restores_pools(plen, max_new, consumed,
                                               k, bs, seed):
    """Property (satellite): a SAMPLED lane whose entire draft chain is
    rejected through the real prepare_verify/consume_verify path must
    leave the allocator (refcounts, free list, pools) and the global
    reserved budget exactly as a single-token advance would have —
    every block the chain claimed beyond the advance comes back."""
    if plen + max_new > 64:
        max_new = 64 - plen
        if max_new < 4:
            return
    consumed = min(consumed, max_new - 3)
    alloc = BlockAllocator(72, block_size=bs)
    sched = Scheduler(alloc, _FakeRunner(), num_slots=2, block_size=bs,
                      max_blocks_per_seq=-(-64 // bs), max_seq_len=64,
                      prefix_cache=False, now_fn=lambda: 0.0, speculate=8)
    sched.submit(Request(rid=0, prompt=np.arange(plen, dtype=np.int32),
                         sampling=SamplingParams(temperature=0.9,
                                                 seed=seed,
                                                 max_new_tokens=max_new)))
    sched.admit()
    s = sched._slots[0]
    assert s is not None and not s.sp.greedy
    for _ in range(consumed):             # walk to a reachable position
        sched._claim_blocks(0, s.pos)
        s.pos += 1
    sched._claim_blocks(0, s.pos)
    k_eff = min(k, max_new - len(s.out) - consumed - 1)
    if k_eff <= 0:
        return
    sched._proposers = [type("P", (), {
        "propose": staticmethod(lambda h, kk: [3] * min(kk, k_eff))})()] * 2
    pre = (_alloc_snapshot(alloc), s.budget + 0, s.n_blocks,
           sched._reserved_budget)
    batch = sched.prepare_verify()
    assert batch is not None
    tokens, positions, counts, active = batch
    out = np.full(tokens.shape, -1, np.int32)        # full rejection
    sched.consume_verify(active, out, np.zeros(tokens.shape[0], np.int32))
    assert sched._slots[0] is s                      # still live
    # the single advanced (bonus) token may legitimately keep one
    # claimed block; everything past it must be back in the pool
    keep = max((s.pos - 1) // bs + 1, s.prompt_blocks)
    assert s.n_blocks == keep
    grew = s.n_blocks - pre[2]
    assert _alloc_snapshot(alloc)[0] == pre[0][0] - grew
    assert s.budget == pre[1] - grew
    assert sched._reserved_budget == pre[3] - grew
