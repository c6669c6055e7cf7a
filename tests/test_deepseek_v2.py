"""DeepSeek-V2 (MLA + shared and routed experts) against the plain float32
reference in `deepseek_v2_reference.py`, on the reduced config with seeded
random weights: the sequence forward, paged prefill and decode through the
serving engine's path, absorbed against expanded MLA, dropless routing,
YaRN at the published values, the expert counters and the refusals.

Tolerances: the program computes in float32 on the CPU here, and the
reference in float32 at "highest" precision, the same arithmetic in
another order (grouped expert products, chunked and streamed softmax,
absorbed weights). Logits of this size differ by about 1e-6 from that
alone; 2e-4 leaves two orders of magnitude and still fails on a wrong
mask, a lost shared expert, a dropped route or a wrong rotary (each
moves logits by 1e-2 or more).
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepseek_v2_reference as ref
from repro.configs import get_config
from repro.models import attention, layers, lm, moe
from repro.serving import kv_cache
from repro.serving.engine import Request, ServingEngine
from repro.serving.observability import Observability
from repro.serving.sampling import SamplingParams

pytestmark = pytest.mark.serving

FULL = get_config("deepseek-v2-lite")
CFG = FULL.reduced()
TOL = 2e-4          # see the module docstring


def _params(cfg=CFG, seed=0):
    """Seeded weights with norm gains drawn too (init leaves them 0)."""
    p = lm.init_params(jax.random.PRNGKey(seed), cfg)
    leaves, tree = jax.tree_util.tree_flatten_with_path(p)
    out = []
    for i, (path, x) in enumerate(leaves):
        if "norm" in jax.tree_util.keystr(path):
            x = 0.1 * jax.random.normal(jax.random.PRNGKey(1000 + i),
                                        x.shape, x.dtype)
        out.append(x)
    return jax.tree.unflatten(tree, out)


def _ref_logprobs(params, cfg, seq):
    return np.asarray(jax.nn.log_softmax(
        ref.logits(params, cfg, jnp.asarray(seq, jnp.int32)), -1))


def test_forward_matches_reference():
    params = _params()
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 37), 0,
                              CFG.vocab_size)
    got, _ = lm.forward(params, CFG, {"tokens": toks})
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(got[b]), np.asarray(ref.logits(params, CFG, toks[b])),
            atol=TOL, rtol=TOL)


def test_paged_prefill_then_decode_matches_reference():
    """A prompt of 40 tokens prefilled in chunks of 16 (two resumed
    chunks) into a latent pool whose rows are padded (40 values in 128
    columns), then six decode steps: logits at every position equal the
    reference's full forward."""
    params = _params(seed=1)
    bs, nb, M = 4, 40, 16
    assert kv_cache._row_width(CFG) == 128 > attention.mla_row_width(CFG)
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (46,), 0,
                                        CFG.vocab_size), np.int32)
    expect = np.asarray(ref.logits(params, CFG, jnp.asarray(seq)))
    state = kv_cache.init_paged_state(CFG, 2, nb, bs)
    table = np.zeros((1, M), np.int32)
    table[0] = np.arange(3, 3 + M)        # lane 0's blocks; lane 1 idle
    prefill = jax.jit(lm.prefill_paged, static_argnums=(1, 8, 9))
    for start in (0, 16, 32):
        n = min(16, 40 - start)
        toks = np.zeros((1, 16), np.int32)
        toks[0, :n] = seq[start:start + n]
        last, state = prefill(params, CFG, state, jnp.asarray(toks),
                              jnp.asarray([start + n], jnp.int32),
                              jnp.asarray([start], jnp.int32),
                              jnp.asarray(table), jnp.asarray([0]), bs,
                              start > 0)
        np.testing.assert_allclose(np.asarray(last[0]),
                                   expect[start + n - 1], atol=TOL, rtol=TOL)
    tables = np.zeros((2, M), np.int32)
    tables[0] = table[0]
    step = jax.jit(lm.decode_step_paged, static_argnums=(1, 6))
    for pos in range(40, 46):
        lg, state = step(params, CFG, state, jnp.asarray([seq[pos], 0]),
                         jnp.asarray([pos, 0], jnp.int32),
                         jnp.asarray(tables), bs)
        np.testing.assert_allclose(np.asarray(lg[0]), expect[pos],
                                   atol=TOL, rtol=TOL)


def test_engine_serves_reference_logprobs():
    """Through ServingEngine (scheduler, block manager with the prefix
    cache on, runner, sampler): greedy tokens are the reference's argmax
    and their logprobs the reference's, for prompts that span several
    prefill chunks and share a prefix."""
    params = _params(seed=2)
    rng = np.random.default_rng(3)
    shared = rng.integers(0, CFG.vocab_size, 24)
    prompts = [np.concatenate([shared, rng.integers(0, CFG.vocab_size, n)])
               for n in (13, 5, 30)] + [rng.integers(0, CFG.vocab_size, 9)]
    eng = ServingEngine(params, CFG, num_slots=2, block_size=4,
                        max_seq_len=72, prefill_chunk=16)
    assert eng.prefix_cache
    done = eng.run([Request(rid=i, prompt=p.astype(np.int32),
                            sampling=SamplingParams(max_new_tokens=7,
                                                    logprobs=1))
                    for i, p in enumerate(prompts)])
    assert len(done) == len(prompts)
    assert sum(c.cached_tokens for c in done) > 0
    for c in done:
        seq = np.concatenate([prompts[c.rid], c.tokens])
        lp = _ref_logprobs(params, CFG, seq)
        at = np.arange(len(prompts[c.rid]) - 1, len(seq) - 1)
        np.testing.assert_array_equal(c.tokens, lp[at].argmax(-1))
        np.testing.assert_allclose(c.logprobs, lp[at, c.tokens],
                                   atol=TOL, rtol=TOL)


def test_engine_serves_a_capacity_routed_moe_dropless():
    """ServingEngine routes every MoE dropless, whatever capacity the
    config gives (grok-1's block): its greedy tokens and logprobs are
    lm.forward's under the same config with `dropless` set, and the paged
    paths refuse a stacked MoE layer routed with a capacity."""
    cfg = get_config("grok-1-314b").reduced()
    assert not cfg.dropless and cfg.capacity_factor > 0
    params = lm.init_params(jax.random.PRNGKey(4), cfg)
    eng = ServingEngine(params, cfg, num_slots=2, block_size=4,
                        max_seq_len=48, prefill_chunk=16)
    assert eng.cfg.dropless and eng.runner.cfg.dropless
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (21, 6, 11)]
    done = eng.run([Request(rid=i, prompt=p,
                            sampling=SamplingParams(max_new_tokens=5,
                                                    logprobs=1))
                    for i, p in enumerate(prompts)])
    served = dataclasses.replace(cfg, dropless=True)
    for c in done:
        seq = np.concatenate([prompts[c.rid], c.tokens])
        logits, _ = lm.forward(params, served,
                               {"tokens": jnp.asarray(seq)[None]})
        lp = np.asarray(jax.nn.log_softmax(logits[0], -1))
        at = np.arange(len(prompts[c.rid]) - 1, len(seq) - 1)
        np.testing.assert_array_equal(c.tokens, lp[at].argmax(-1))
        np.testing.assert_allclose(c.logprobs, lp[at, c.tokens],
                                   atol=TOL, rtol=TOL)
    state = kv_cache.init_paged_state(cfg, 1, 4, 4)
    with pytest.raises(ValueError, match="dropless"):
        lm.prefill_paged(params, cfg, state, jnp.zeros((1, 4), jnp.int32),
                         jnp.full((1,), 4, jnp.int32),
                         jnp.zeros((1,), jnp.int32),
                         jnp.asarray([[1, 0]], jnp.int32), jnp.arange(1), 4)


def test_speculative_verify_matches_plain_decode():
    """Verify runs MLA through the paged prefill path: greedy output with
    n-gram drafting equals plain decode's."""
    params = _params(seed=3)
    rng = np.random.default_rng(4)
    motif = rng.integers(0, CFG.vocab_size, 6)
    reqs = [np.tile(motif, 4).astype(np.int32),
            rng.integers(0, CFG.vocab_size, 11).astype(np.int32)]
    outs = []
    for speculate in (0, 3):
        eng = ServingEngine(params, CFG, num_slots=2, block_size=4,
                            max_seq_len=64, speculate=speculate)
        done = eng.run([Request(rid=i, prompt=p, max_new_tokens=10)
                        for i, p in enumerate(reqs)])
        outs.append({c.rid: c.tokens for c in done})
        if speculate:
            assert eng.runner.verify_dispatches > 0
    for rid in outs[0]:
        np.testing.assert_array_equal(outs[0][rid], outs[1][rid])


def test_absorbed_decode_equals_expanded():
    """Absorbed MLA (W_kb folded into the query, W_vb after attention)
    equals the expanded form on the same latent rows, in float32, to
    rounding (1e-5: the two differ only in summation order)."""
    cfg = CFG
    params = lm.init_params(jax.random.PRNGKey(4), cfg)["prefix"][0]["attn"]
    B, S = 2, 19
    x = jax.random.normal(jax.random.PRNGKey(8), (B, S, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    q_nope, q_pe, latent = attention.mla_project(params, x, pos, cfg)
    k, v = attention.mla_expand(params, latent, cfg)
    q = jnp.concatenate([q_nope, q_pe], -1)
    with jax.default_matmul_precision("highest"):
        expanded = attention.chunked_causal_attention(
            q, k, v, chunk=8, scale=attention.mla_scale(cfg))
        rows = jnp.pad(latent, ((0, 0), (0, 0), (0, 24)))   # padded row
        valid = jnp.tril(jnp.ones((S, S), bool))[None].repeat(B, 0)
        absorbed = attention.mla_absorbed(params, q_nope, q_pe, rows,
                                          valid, cfg)
    np.testing.assert_allclose(np.asarray(absorbed),
                               np.asarray(expanded.reshape(B, S, -1)),
                               atol=1e-5, rtol=1e-5)


def _crowding(params, cfg):
    """Weights whose router scores every expert alike, so top-k sends
    every token of every row to the same k experts (ties go to the lowest
    index): the most a batch can crowd one expert."""
    blocks = dict(params["blocks"])
    p0 = dict(blocks["p0"])
    p0["moe"] = {**p0["moe"], "router": jnp.zeros_like(p0["moe"]["router"])}
    blocks["p0"] = p0
    return {**params, "blocks": blocks}


def test_dropless_output_does_not_depend_on_the_batch():
    """A sequence's logits are the same alone and batched with rows whose
    every token goes to the same experts as its own. The capacity
    dispatch on the same batch does drop routes (the contrast shows the
    crowding is real)."""
    params = _crowding(_params(seed=5), CFG)
    rng = np.random.default_rng(6)
    probe = rng.integers(0, CFG.vocab_size, 24).astype(np.int32)
    others = rng.integers(0, CFG.vocab_size, (3, 24)).astype(np.int32)
    bs, M = 4, 8

    def serve(rows):
        n = len(rows)
        state = kv_cache.init_paged_state(CFG, n, 1 + n * M, bs)
        tables = 1 + np.arange(n * M, dtype=np.int32).reshape(n, M)
        last, _ = lm.prefill_paged(
            params, CFG, state, jnp.asarray(np.stack(rows)),
            jnp.full((n,), 24, jnp.int32), jnp.zeros((n,), jnp.int32),
            jnp.asarray(tables), jnp.arange(n), bs)
        return np.asarray(last[0])

    alone, batched = serve([probe]), serve([probe, *others])
    # the same arithmetic at batch widths 1 and 4: rounding at most
    np.testing.assert_allclose(alone, batched, atol=1e-5, rtol=1e-5)

    h = jax.random.normal(jax.random.PRNGKey(9), (4, 24, CFG.d_model))
    w = jax.tree.map(lambda a: a[0], params["blocks"]["p0"]["moe"])
    capped = dataclasses.replace(CFG, capacity_factor=1.0, moe_chunk=24)
    y_cap_alone, _ = moe.moe_block(w, h[:1], capped)
    y_cap, _ = moe.moe_block(w, h, capped)
    y_drop, _, _ = moe.moe_dropless(w, h, CFG)
    y_drop_alone, _, _ = moe.moe_dropless(w, h[:1], CFG)
    np.testing.assert_allclose(np.asarray(y_drop[0]),
                               np.asarray(y_drop_alone[0]), atol=1e-5)
    assert float(jnp.abs(y_cap[0] - y_drop[0]).max()) > 1e-2
    # capacity is per row (group): alone, the same row is still capped
    np.testing.assert_allclose(np.asarray(y_cap_alone[0]),
                               np.asarray(y_cap[0]), atol=1e-5)


def test_dropless_matches_dense_expert_sum():
    """The grouped dispatch equals every expert applied to every token,
    weighted by its gate, plus the shared experts."""
    params = _params(seed=6)
    w = jax.tree.map(lambda a: a[1], params["blocks"]["p0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(10), (3, 11, CFG.d_model))
    with jax.default_matmul_precision("highest"):
        y, _, routed = moe.moe_dropless(w, x, CFG)
        expect = jax.vmap(lambda xi: ref._moe(xi, w, CFG))(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                               atol=1e-5, rtol=1e-5)
    assert int(routed.sum()) == 3 * 11 * CFG.experts_per_token


def test_yarn_at_published_values():
    """DeepSeek-V2-Lite's YaRN: correction range 10..23 over the 32
    frequency pairs of the 64 rotary dims, cos/sin scale 1.0, softmax
    scale 192^-1/2 * (1 + 0.1 * 0.707 * ln 40)^2 = 0.114721, and the
    blended frequencies of `modeling_deepseek.py`."""
    assert layers.yarn_correction_range(64, 10000.0, 4096, 32.0, 1.0) \
        == (10, 23)
    assert layers.yarn_rope_scale(FULL) == 1.0
    scale = 192 ** -0.5 * (1 + 0.1 * 0.707 * math.log(40)) ** 2
    assert attention.mla_scale(FULL) == pytest.approx(scale, rel=1e-12)
    assert attention.mla_scale(FULL) == pytest.approx(0.114721, abs=5e-7)
    assert ref.softmax_scale(FULL) == pytest.approx(scale, rel=1e-12)
    inv, m = ref.yarn_inv_freq(FULL)
    assert m == 1.0
    got = np.asarray(layers.yarn_freqs(64, FULL), np.float64)
    np.testing.assert_allclose(got, inv, rtol=1e-6)   # float32 rounding
    # below the range the original frequency, above it divided by 40
    np.testing.assert_allclose(got[:10], 10000.0 ** (-np.arange(0, 20, 2)
                                                      / 64), rtol=1e-6)
    np.testing.assert_allclose(got[23:], 10000.0 ** (-np.arange(46, 64, 2)
                                                     / 64) / 40, rtol=1e-6)


def test_param_count_is_published():
    assert FULL.param_count() == 15_706_484_224
    assert FULL.prefix_pattern == ("mla",) and FULL.n_super == 26
    assert kv_cache._row_width(FULL) == 640
    assert attention.mla_row_width(FULL) == 576
    # the pool counts its rows as stored: 640 bf16 values a token a layer
    assert kv_cache.block_bytes(FULL, 16) == 27 * 16 * 640 * 2


def _served_tokens(eng):
    r = eng.runner
    return (r.prefill_computed_tokens + eng.busy_lane_steps
            + r.verify_chain_tokens)


def test_expert_counters_sum_to_routes_and_are_read_only_when_asked():
    """Per layer, the device counters hold k routes for every token the
    model computed for a request (padding and idle lanes excluded); the
    registry sees them only when the engine is asked for them."""
    cfg = FULL.reduced(n_experts=8, experts_per_token=6)
    params = _params(cfg, seed=7)
    obs = Observability()
    eng = ServingEngine(params, cfg, num_slots=3, block_size=4,
                        max_seq_len=48, prefill_chunk=8, obs=obs)
    rng = np.random.default_rng(8)
    reqs = [Request(rid=i, prompt=rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=m)
            for i, (n, m) in enumerate([(5, 6), (19, 3), (12, 9), (3, 4)])]
    eng.run(reqs)
    assert eng.runner.prefill_dispatches >= 3 and eng.steps > 5
    assert not obs.registry.gauges_named("moe_routed_tokens")
    counts = eng.read_expert_counts()
    assert counts.shape == (cfg.n_layers - cfg.n_dense_layers, 8)
    np.testing.assert_array_equal(counts.sum(1),
                                  6 * _served_tokens(eng))
    gauges = obs.registry.gauges_named("moe_routed_tokens")
    assert len(gauges) == counts.size
    assert sum(gauges.values()) == counts.sum()
    eng.begin_run()
    assert eng.read_expert_counts().sum() == 0


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_engine_refuses_quantized_latent_pool(kv_dtype):
    with pytest.raises(ValueError, match="latent \\(MLA\\) pool"):
        ServingEngine(_params(), CFG, num_slots=2, block_size=4,
                      max_seq_len=32, kv_dtype=kv_dtype)
