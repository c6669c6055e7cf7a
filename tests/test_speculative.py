"""Speculative decoding: multi-token verify vs sequential decode,
n-gram proposer, accept/rollback invariants (blocks + recurrent state),
engine greedy bit-identity under speculation, and the verify-shape
compile bound."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch.serve import generate
from repro.models import lm
from repro.serving.block_manager import NULL_BLOCK, BlockAllocator
from repro.serving.bucketing import (chain_buckets, next_pow2, pick_bucket,
                                     pow2_buckets, width_buckets)
from repro.serving.draft import NGramProposer, make_proposer
from repro.serving.engine import (Request, ServingEngine,
                                  repetitive_requests,
                                  shared_prefix_requests, summarize)
from repro.serving.scheduler import Scheduler
from repro.serving import kv_cache

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
        def lists(*a, **k):
            return None

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------------------------
# bucketing helpers (shared grid definitions)
# ----------------------------------------------------------------------------

def test_bucketing_helpers():
    assert [next_pow2(n) for n in (0, 1, 2, 3, 8, 9)] == [1, 1, 2, 4, 8, 16]
    assert pow2_buckets(64, start=16) == [16, 32, 64]
    assert pow2_buckets(60, start=16) == [16, 32, 64]
    assert pow2_buckets(5) == [1, 2, 4, 8]
    assert width_buckets(4) == [1, 2, 4]
    assert width_buckets(6) == [1, 2, 4, 6]
    assert pick_bucket(3, [2, 4, 8]) == 4
    assert pick_bucket(9, [2, 4, 8]) == 8   # clamped to the last bucket
    assert chain_buckets(4) == [2, 4, 5]    # tops out at speculate+1
    assert chain_buckets(1) == [2]
    assert chain_buckets(0) == []


# ----------------------------------------------------------------------------
# n-gram (prompt-lookup) proposer
# ----------------------------------------------------------------------------

def test_ngram_proposer_basic():
    p = NGramProposer(max_ngram=3)
    # history ends in the 2-gram (1, 2) seen earlier -> propose what
    # followed its most recent earlier occurrence
    assert p.propose([1, 2, 3, 4, 1, 2], 2) == [3, 4]
    assert p.propose([1, 2, 3, 4, 1, 2], 4) == [3, 4, 1, 2]
    # no recurring suffix -> nothing proposed
    assert p.propose([1, 2, 3, 4, 5], 4) == []
    assert p.propose([7], 4) == []
    assert p.propose([1, 2, 1, 2], 0) == []


def test_ngram_proposer_prefers_longest_and_most_recent():
    p = NGramProposer(max_ngram=3)
    # suffix (5, 1, 2): full 3-gram match at index 0 beats the shorter
    # 2-gram (1, 2) match later in the stream
    hist = [5, 1, 2, 9, 1, 2, 8, 5, 1, 2]
    assert p.propose(hist, 1) == [9]
    # only 1-gram matches: most recent earlier occurrence of 3 wins
    assert p.propose([3, 7, 3, 8, 4, 3], 1) == [8]


def test_make_proposer():
    assert isinstance(make_proposer("ngram", ngram=4), NGramProposer)
    with pytest.raises(ValueError):
        make_proposer("draft-model")


# ----------------------------------------------------------------------------
# lm.decode_verify_paged == sequential decode_step_paged; commit rollback
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["smollm-135m", "recurrentgemma-2b",
                                  "rwkv6-3b"])
def test_decode_verify_matches_sequential_decode(arch):
    """Per-position verify logits must equal feeding the chain through
    decode_step_paged one token at a time, and committing a partial
    accept must continue decoding bit-identically to a replay of only
    the accepted prefix (recurrent state rollback)."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    bs, M, num_slots = 4, 6, 2
    lens = [7, 10]
    rows = [jax.random.randint(jax.random.fold_in(KEY, 30 + i), (n,), 0,
                               cfg.vocab_size) for i, n in enumerate(lens)]
    tables = np.zeros((2, M), np.int32)
    tables[0, :3] = [1, 2, 3]
    tables[1, :4] = [4, 5, 6, 7]
    state = kv_cache.init_paged_state(cfg, num_slots, 9, bs)
    Ls = max(lens)
    toks = jnp.stack([jnp.pad(r, (0, Ls - len(r))) for r in rows])
    _, state = lm.prefill_paged(params, cfg, state, toks,
                                jnp.asarray(lens, jnp.int32),
                                jnp.zeros(2, jnp.int32),
                                jnp.asarray(tables),
                                jnp.arange(2, dtype=jnp.int32), bs)

    rng = np.random.default_rng(0)
    chains = rng.integers(0, cfg.vocab_size, (2, 4)).astype(np.int32)
    counts = np.array([4, 3], np.int32)
    ref_logits = {0: [], 1: []}
    seq_state = state
    for t in range(4):
        lg, seq_state = lm.decode_step_paged(
            params, cfg, seq_state, jnp.asarray(chains[:, t]),
            jnp.asarray([lens[0] + t, lens[1] + t], jnp.int32),
            jnp.asarray(tables), bs)
        ref_logits[0].append(np.asarray(lg[0]))
        if t < 3:
            ref_logits[1].append(np.asarray(lg[1]))

    logits, vstate, snaps = lm.decode_verify_paged(
        params, cfg, state, jnp.asarray(chains),
        jnp.asarray(lens, jnp.int32), jnp.asarray(counts),
        jnp.asarray(tables), bs)
    for b in range(2):
        for t in range(int(counts[b])):
            np.testing.assert_allclose(np.asarray(logits[b, t]),
                                       ref_logits[b][t],
                                       atol=2e-4, rtol=2e-4)

    # commit lane 0 at 3 consumed tokens, lane 1 at 1; continuing must
    # match a replay that consumed exactly those prefixes
    cstate = lm.commit_decode_state(cfg, vstate, snaps,
                                    jnp.asarray([3, 1], jnp.int32))
    rs = state
    for t in range(3):
        _, rs = lm.decode_step_paged(
            params, cfg, rs, jnp.asarray([chains[0, t], chains[1, 0]]),
            jnp.asarray([lens[0] + t, lens[1]], jnp.int32),
            jnp.asarray(tables), bs)
    rs1 = state
    _, rs1 = lm.decode_step_paged(
        params, cfg, rs1, jnp.asarray([chains[0, 0], chains[1, 0]]),
        jnp.asarray([lens[0], lens[1]], jnp.int32), jnp.asarray(tables),
        bs)
    nxt = jnp.asarray([11, 12], jnp.int32)
    pos = jnp.asarray([lens[0] + 3, lens[1] + 1], jnp.int32)
    lg_commit, _ = lm.decode_step_paged(params, cfg, cstate, nxt, pos,
                                        jnp.asarray(tables), bs)
    lg_ref0, _ = lm.decode_step_paged(params, cfg, rs, nxt, pos,
                                      jnp.asarray(tables), bs)
    lg_ref1, _ = lm.decode_step_paged(params, cfg, rs1, nxt, pos,
                                      jnp.asarray(tables), bs)
    np.testing.assert_allclose(np.asarray(lg_commit[0]),
                               np.asarray(lg_ref0[0]),
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(lg_commit[1]),
                               np.asarray(lg_ref1[1]),
                               atol=2e-4, rtol=2e-4)


# ----------------------------------------------------------------------------
# engine: greedy bit-identity under speculation (accept AND reject paths)
# ----------------------------------------------------------------------------

def _expect(params, cfg, req):
    return np.asarray(generate(params, cfg, np.asarray(req.prompt)[None],
                               req.max_new_tokens))[0]


@pytest.mark.parametrize("arch", ["smollm-135m", "recurrentgemma-2b",
                                  "rwkv6-3b"])
def test_engine_speculative_identical_to_generate(arch):
    """n-gram speculation on a repetitive workload: every output must be
    token-identical to generate(), blocks fully returned, and drafts
    proposed and verified (the prompts repeat, so lookups hit). Whether
    a random-weight model's argmax happens to agree with a lookup draft
    is up to the weights, so acceptance is driven by construction: the
    same requests rerun with oracle drafts of their greedy continuations
    must accept and stay identical."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = repetitive_requests(6, vocab_size=cfg.vocab_size, period=5,
                               prompt_len=24, max_new=(10, 20), seed=3)
    expect = {r.rid: _expect(params, cfg, r) for r in reqs}
    eng = ServingEngine(params, cfg, num_slots=3, block_size=4,
                        max_seq_len=64, speculate=4)
    free0 = eng.allocator.num_free
    done = eng.run(list(reqs))
    assert len(done) == len(reqs)
    assert eng.allocator.num_free == free0
    for c in done:
        np.testing.assert_array_equal(c.tokens, expect[c.rid])
    stats = summarize(done, eng.wall_time, eng)
    sp = stats["speculation"]
    assert sp["proposed_tokens"] > 0
    assert sp["verify_dispatches"] > 0
    assert sp["tokens_per_dispatch"] > 0

    scripts = [([int(t) for t in r.prompt],
                [int(t) for t in expect[r.rid]]) for r in reqs]
    eng.scheduler._proposers = [
        _ScriptedProposer(scripts, cfg.vocab_size, adversarial=False)
    ] * eng.num_slots
    done = eng.run(list(reqs))
    assert len(done) == len(reqs)
    assert eng.allocator.num_free == free0
    for c in done:
        np.testing.assert_array_equal(c.tokens, expect[c.rid])
    sp = summarize(done, eng.wall_time, eng)["speculation"]
    assert sp["proposed_tokens"] > 0 and sp["accepted_tokens"] > 0
    assert 0 < sp["acceptance_rate"] <= 1


class _ScriptedProposer:
    """Test proposer that knows each request's true greedy continuation
    and proposes it verbatim (oracle: every draft accepted) or off by
    one (adversarial: every draft rejected)."""

    def __init__(self, scripts, vocab_size, adversarial):
        self.scripts = scripts        # [(prompt list, expected out list)]
        self.vocab_size = vocab_size
        self.adversarial = adversarial

    def propose(self, history, k):
        hist = list(history)
        for prompt, out in self.scripts:
            full = prompt + out
            if (len(prompt) < len(hist) <= len(full)
                    and hist == full[:len(hist)]):
                nxt = full[len(hist):len(hist) + k]
                if self.adversarial:
                    return [(t + 1) % self.vocab_size for t in nxt]
                return nxt
        return []


@pytest.mark.parametrize("arch", ["smollm-135m", "recurrentgemma-2b"])
@pytest.mark.parametrize("adversarial", [False, True])
def test_engine_forced_accept_and_reject_paths(arch, adversarial):
    """Oracle drafts (all accepted) and adversarial drafts (all
    rejected — every verify dispatch rolls back) must BOTH leave output
    bit-identical to generate(): full-rollback covers the recurrent
    state-restore satellite end to end."""
    cfg = get_config(arch).reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (4, 9), 0,
                                 cfg.vocab_size)
    gens = [12, 7, 10, 5]
    reqs = [Request(rid=i, prompt=np.asarray(prompts[i]),
                    max_new_tokens=gens[i]) for i in range(4)]
    scripts = [([int(t) for t in r.prompt],
                [int(t) for t in _expect(params, cfg, r)]) for r in reqs]
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=32, speculate=4)
    prop = _ScriptedProposer(scripts, cfg.vocab_size, adversarial)
    eng.scheduler._proposers = [prop] * eng.num_slots
    free0 = eng.allocator.num_free
    done = eng.run(list(reqs))
    assert len(done) == 4
    assert eng.allocator.num_free == free0
    for c in done:
        np.testing.assert_array_equal(c.tokens, scripts[c.rid][1])
    sched = eng.scheduler
    assert sched.proposed_tokens > 0
    if adversarial:
        assert sched.accepted_tokens == 0          # pure rollback
    else:
        assert sched.accepted_tokens == sched.proposed_tokens


def test_engine_speculative_with_prefix_cache():
    """Speculation composes with prefix caching (shared-prefix
    workload): identity holds, cache hits happen, pools restore."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    reqs = shared_prefix_requests(8, vocab_size=cfg.vocab_size,
                                  prefix_len=20, suffix_len=(1, 9),
                                  max_new=(6, 12), seed=4)
    eng = ServingEngine(params, cfg, num_slots=3, block_size=8,
                        max_seq_len=48, prefix_cache=True, speculate=4)
    done = eng.run(list(reqs))
    assert len(done) == len(reqs)
    assert eng.scheduler.cached_prompt_tokens > 0
    for c in done:
        np.testing.assert_array_equal(c.tokens,
                                      _expect(params, cfg, reqs[c.rid]))
    assert eng.allocator.num_free == eng.allocator.num_blocks - 1


def test_engine_speculative_eos_mid_chain():
    """An eos inside an accepted draft run must cut the output at the
    first eos, exactly like unspeculated decoding."""
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.vocab_size)
    full = np.asarray(generate(params, cfg, prompt, 10))[0]
    eos = int(full[4])
    stop = int(np.argmax(full == eos)) + 1
    req = Request(rid=0, prompt=np.asarray(prompt[0]), max_new_tokens=10,
                  eos_id=eos)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=32, speculate=4)
    script = [([int(t) for t in prompt[0]], [int(t) for t in full])]
    eng.scheduler._proposers = [_ScriptedProposer(script, cfg.vocab_size,
                                                  False)] * 2
    done = eng.run([req])
    assert len(done[0].tokens) == stop
    np.testing.assert_array_equal(done[0].tokens, full[:stop])


def test_engine_verify_shapes_bounded_and_flags():
    cfg = get_config("smollm-135m").reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    # speculation + temperature used to hard-error (greedy-only); it is
    # now a legal combination (distribution-preserving accept/reject),
    # and the engine-wide temperature knob survives as a deprecated shim
    with pytest.warns(DeprecationWarning):
        ServingEngine(params, cfg, num_slots=2, block_size=4,
                      max_seq_len=32, speculate=4, temperature=0.7)
    reqs = repetitive_requests(8, vocab_size=cfg.vocab_size, period=4,
                               prompt_len=(12, 30), max_new=(4, 18),
                               seed=5)
    eng = ServingEngine(params, cfg, num_slots=4, block_size=4,
                        max_seq_len=64, speculate=5)
    done = eng.run(list(reqs))
    assert len(done) == len(reqs)
    # every verify dispatch shape sits on the bucket grid, so compiles
    # are bounded by the grid — not by the per-step draft lengths; the
    # grid tops out at exactly speculate+1 (a full draft never pads)
    assert eng.runner.verify_buckets == chain_buckets(5) == [2, 4, 6]
    assert eng.runner.verify_shapes <= set(eng.runner.verify_buckets)
    for c in done:
        np.testing.assert_array_equal(c.tokens,
                                      _expect(params, cfg, reqs[c.rid]))


# ----------------------------------------------------------------------------
# accept/rollback block accounting (scheduler + allocator, no device)
# ----------------------------------------------------------------------------

class _FakeRunner:
    """Host-only ModelRunner stand-in: the scheduler's block accounting
    never needs the device."""

    prefill_max_batch = 4
    max_logprobs = 8
    prefill_chunk = 0         # chunked admission off; prompts fit the grid

    def __init__(self, speculate=8):
        self.prefill_buckets = pow2_buckets(64, start=8)
        self.verify_buckets = chain_buckets(speculate)   # same grid as
        # the real runner — test/prod bucket drift is what bucketing.py
        # exists to prevent

    def suffix_bucket(self, n):
        return pick_bucket(n, self.prefill_buckets)

    def chain_bucket(self, n):
        return pick_bucket(n, self.verify_buckets)

    def prefill(self, rows):
        return (np.full(len(rows), 1, np.int32),
                np.zeros(len(rows), np.float32), None)

    def verify(self, tokens, positions, counts):
        # rejects everything: the emitted correction disagrees with
        # every draft and zero drafts are accepted
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


def _make_sched(num_blocks=72, bs=4, num_slots=2, speculate=8):
    alloc = BlockAllocator(num_blocks, block_size=bs)
    runner = _FakeRunner(speculate=speculate)
    sched = Scheduler(alloc, runner, num_slots=num_slots, block_size=bs,
                      max_blocks_per_seq=-(-64 // bs), max_seq_len=64,
                      prefix_cache=False, now_fn=lambda: 0.0,
                      speculate=speculate)
    return alloc, sched


@settings(max_examples=60, deadline=None)
@given(plen=st.integers(1, 20), max_new=st.integers(2, 40),
       consumed=st.integers(0, 10), k=st.integers(1, 8),
       bs=st.integers(2, 5))
def test_rejected_draft_frees_exactly_reserved_blocks(plen, max_new,
                                                      consumed, k, bs):
    """Property (satellite): claiming the blocks a k-token draft chain
    would write and then rolling the chain back entirely must return
    the allocator (refcounts, free list, LRU pool) and the slot's
    budget to their exact pre-draft state, from any reachable decode
    position."""
    if plen + max_new > 64:
        max_new = 64 - plen
        if max_new < 2:
            return
    consumed = min(consumed, max_new - 1)
    alloc, sched = _make_sched(bs=bs)
    sched.submit(Request(rid=0, prompt=np.arange(plen, dtype=np.int32),
                         max_new_tokens=max_new))
    sched.admit()
    s = sched._slots[0]
    assert s is not None
    # walk the lane to an arbitrary reachable position (plain decode)
    for _ in range(consumed):
        sched._claim_blocks(0, s.pos)
        s.pos += 1
    sched._claim_blocks(0, s.pos)       # pending-token coverage
    pre = (_alloc_snapshot(alloc), s.budget, s.n_blocks,
           s.table_row.copy().tolist(), sched._reserved_budget)
    k_eff = min(k, max_new - consumed - 1)
    if k_eff <= 0:
        return
    claimed = sched._claim_blocks(0, s.pos + k_eff)   # draft reservation
    freed = sched._trim_blocks(0, s.pos)              # full rejection
    assert freed == claimed
    post = (_alloc_snapshot(alloc), s.budget, s.n_blocks,
            s.table_row.copy().tolist(), sched._reserved_budget)
    assert post == pre


def test_full_rejection_through_the_real_verify_path():
    """consume_verify with a verify output that rejects every draft
    frees exactly the chain's claimed blocks and advances exactly one
    token (the bonus token), via the public scheduler API."""
    alloc, sched = _make_sched(bs=2)
    sched.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32),
                         max_new_tokens=20))
    sched.admit()
    s = sched._slots[0]
    sched._claim_blocks(0, s.pos)
    pre_free, pre_cached, pre_ref = _alloc_snapshot(alloc)
    pre_blocks, pos0, out0 = s.n_blocks, s.pos, len(s.out)
    # inject a draft long enough to cross block boundaries
    sched._proposers = [type("P", (), {
        "propose": staticmethod(lambda hist, k: [3] * min(k, 6))})()] * 2
    batch = sched.prepare_verify()
    assert batch is not None
    tokens, positions, counts, active = batch
    assert s.n_blocks > pre_blocks                    # chain claimed blocks
    out_tok = np.full(tokens.shape, -1, np.int32)     # model disagrees
    accept = np.zeros(tokens.shape[0], np.int32)
    sched.consume_verify(active, out_tok, accept)
    assert s.pos == pos0 + 1 and len(s.out) == out0 + 1
    # the one committed write may have crossed into the chain's first
    # claimed block; everything past it went back
    assert s.n_blocks == max((s.pos - 1) // 2 + 1, s.prompt_blocks)
    assert (_alloc_snapshot(alloc)[0]
            == pre_free - (s.n_blocks - pre_blocks))
    assert sched.accepted_tokens == 0


# ----------------------------------------------------------------------------
# serving_bench speculative smoke (the CI gate path)
# ----------------------------------------------------------------------------

@pytest.mark.slow
def test_serving_bench_speculative_smoke(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "benchmarks"))
    try:
        import serving_bench
    finally:
        sys.path.pop(0)
    rec = serving_bench.run_bench([
        "--workload", "repetitive", "--smoke", "--seed", "0",
        "--out", str(tmp_path)])
    gate = rec["speculation_gate"]
    assert gate["greedy_identical"] and gate["verify_shapes_bounded"]
    assert rec["engine_speculative"]["speculation"]["acceptance_rate"] > 0
    assert (tmp_path / "bench_smollm-135m_repetitive.json").exists()
