"""`arch/deepseek_v2.py`, the benchmark's own DeepSeek-V2 block: its forward
against the repository's tier-1 reference (`tests/deepseek_v2_reference.py`),
its weight tree against the program's, its counts, and the prefill
roofline reader it feeds."""
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
import flops
import reference
import weights

SMALL = {"name": "dsv2-small", "arch": "deepseek_v2", "family": "moe",
         "n_layers": 3, "n_dense_layers": 1, "d_model": 64, "n_heads": 4,
         "n_kv_heads": 4, "d_ff": 96, "vocab_size": 256,
         "block_pattern": ["mla_moe"], "mlp_kind": "swiglu",
         "n_experts": 8, "experts_per_token": 3, "dropless": True,
         "moe_d_ff": 32, "n_shared_experts": 2, "kv_lora_rank": 32,
         "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
         "rope_theta": 10000.0, "yarn_factor": 40.0,
         "yarn_original_max_pos": 4096, "yarn_beta_fast": 32.0,
         "yarn_beta_slow": 1.0, "yarn_mscale": 0.707,
         "yarn_mscale_all_dim": 0.707, "norm_eps": 1e-6,
         "tie_embeddings": False, "attn_chunk": 16, "dtype": "float32",
         "param_dtype": "float32"}
# deepseek-v2-lite-7l, the cell's configuration: the published widths, the
# first 7 of 27 layers
PUBLISHED = common.config("deepseek-v2-lite-7l")["model"]


def _tier1_reference():
    path = common.ROOT / "tests" / "deepseek_v2_reference.py"
    spec = importlib.util.spec_from_file_location("dsv2_tier1_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg(spec):
    import drivers.serve as serve
    return serve.model_config(spec)


def test_logits_equal_the_tier1_reference():
    """Both are float32 at highest precision from the same equations; they
    differ in summation order (blocked attention, expert tiles) only, so
    1e-4 on logits of order 1 is rounding with room."""
    w = weights.make(SMALL, 2**33 + 5)
    toks = jnp.asarray(np.random.default_rng(1).integers(0, 256, 45),
                       jnp.int32)
    ours = reference.logits(w, SMALL, toks)
    theirs = _tier1_reference().logits(w, _cfg(SMALL), toks)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=1e-4, rtol=1e-4)


def test_weight_tree_loads_into_the_program():
    """The seeded tree has the program's structure and shapes, at the
    published widths (as shapes) and small (run through lm.forward, whose
    logits match this module's to float32 rounding). Every leaf is stored
    in the spec's param_dtype (the program's own init keeps its routers in
    float32; it reads them in float32 either way)."""
    from repro.models import lm
    for spec in (SMALL, PUBLISHED):
        cfg = _cfg(spec)
        ours = jax.eval_shape(lambda s=spec: weights.make(s, 3))
        prog = jax.eval_shape(
            lambda c=cfg: lm.init_params(jax.random.PRNGKey(0), c))
        assert jax.tree.structure(ours) == jax.tree.structure(prog)
        for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(prog)):
            assert a.shape == b.shape
            assert a.dtype == jnp.dtype(spec["param_dtype"])
    w = weights.make(SMALL, 11)
    toks = jnp.arange(3, 40, dtype=jnp.int32)
    got, _ = lm.forward(w, _cfg(SMALL), {"tokens": toks[None]})
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(reference.logits(w, SMALL, toks)),
                               atol=2e-4, rtol=2e-4)


def test_published_counts():
    arch = common.arch(PUBLISHED)
    assert PUBLISHED["arch"] == "deepseek_v2"
    assert flops.param_count(PUBLISHED) == 4_009_526_784
    assert flops.param_count(PUBLISHED) == _cfg(PUBLISHED).param_count()
    # the 576 values of a latent row, per layer, not the 640-wide pool row
    assert flops.kv_bytes_per_token(PUBLISHED) == 7 * 576 * 2
    assert arch.experts_touched(PUBLISHED, 0) == 0


@pytest.mark.parametrize("n", [1, 2, 7, 32, 192, 2048, 16384])
def test_expert_bytes_stay_within_the_held_experts(n):
    """A dispatch of n tokens reads at most all 64 experts, and one token
    reads exactly its 6."""
    arch = common.arch(PUBLISHED)
    touched = arch.experts_touched(PUBLISHED, n)
    assert touched <= 64
    if n == 1:
        assert touched == pytest.approx(6.0, rel=1e-12)
    every = arch._weight_bytes(PUBLISHED, 10**6)
    assert arch._weight_bytes(PUBLISHED, n) <= every
    assert arch.prefill_bytes(PUBLISHED, 0, n) <= every \
        + n * flops.kv_bytes_per_token(PUBLISHED)


def test_prefill_roofline_reads_none_without_a_trace():
    read = common.load_reader("prefill_roofline")
    peak = common.peaks("TPU v5 lite")
    rec = {"spec": PUBLISHED, "peak": peak,
           "layers": {"prefills": [(1.0, [(0, 2048)])], "decodes": [],
                      "pool": []}}
    assert read(rec) is None
    assert read(dict(rec, trace={"program_s": {"prefill": 0.5}})) is None
    traced = dict(rec, trace={"program_s": {"prefill": 0.5}},
                  trace_span=[0.5, 2.0])
    got = read(traced)
    arch = common.arch(PUBLISHED)
    least = max(arch.prefill_flops(PUBLISHED, 0, 2048) / peak["bf16_flops"],
                arch.prefill_bytes(PUBLISHED, 0, 2048)
                / peak["hbm_bytes_per_s"])
    assert got == pytest.approx(100 * least / 0.5)
    # an architecture that counts no prefill bytes reads FLOPs alone
    dense = dict(common.config("smollm2-1.7b")["model"])
    got = read(dict(traced, spec=dense))
    assert got == pytest.approx(
        100 * flops.prefill_flops(dense, 0, 2048) / peak["bf16_flops"] / 0.5)


def test_logits_free_a_dropped_engine_first():
    """A served window's engine, dropped before the check (a traced run's
    wrappers around it too), holds its pool in reference cycles; the
    reference's forward collects them before it allocates its logits."""
    import weakref

    import cells
    import drivers.serve as serve
    b = serve.build(cells.serve_ctx(seconds=0.5), 7)
    serve.Recorder(b["engine"], lambda: 0.0)
    runner = weakref.ref(b.pop("engine").runner)
    reference.logits(weights.make(SMALL, 3), SMALL,
                     jnp.arange(8, dtype=jnp.int32))
    assert runner() is None

def test_check_holds_one_logits_array_at_the_cells_width():
    """The check's readings, compiled for a described v5e (two layers, 1024
    positions, so that it compiles in under a minute): besides the
    weights, one (S, V) float32 array and little else. Were the head's
    product fused with the log-softmax's first reduction, the normalised
    log-probabilities would take a second such array, and at the cell's
    16,384 positions the check would not fit (19.97 GiB against 15.75)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = dict(PUBLISHED, n_layers=2)
    S = 1024
    w = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=chip),
        weights.shapes(spec), is_leaf=lambda x: isinstance(x, tuple))
    items = tuple(sorted((k, v) for k, v in spec.items()
                         if isinstance(v, (int, float, bool, str))))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        mem = reference._readings.lower(
            w, jax.ShapeDtypeStruct((S,), jnp.int32, sharding=chip), items,
            False).compile().memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    logits_bytes = S * spec["vocab_size"] * 4
    assert logits_bytes <= mem.temp_size_in_bytes < 1.5 * logits_bytes
