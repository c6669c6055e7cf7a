"""Compiles the main path for a described TPU v5e, without the chip.

The TPU compiler is installed with JAX and compiles for a chip that is
described and not attached. Interpret mode cannot show what it refuses
(block shapes off the (8, 128) tiling, too much VMEM, a program that
does not fit the device), so these tests compile, at smollm-135m's
published widths:

  * the paged decode and paged prefill Pallas kernels (bf16 pools,
    block 16, 3 KV heads of 64), plain and int8-quantized, and the
    flash-attention kernel;
  * the paged decode step exactly as `serving/runner.py` jits it
    (`lm.decode_step_paged` plus greedy sampling, weights passed as
    shapes), with the depth cut to 2 layers so it compiles quickly.

Nothing runs, so these say nothing about results or times. The topology
is described inside a module fixture, never at import: only one process
may load the TPU library at a time.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.models import lm
from repro.serving.runner import ModelRunner

SMOLLM = get_config("smollm-135m")
KV, HD, BS = SMOLLM.n_kv_heads, SMOLLM.head_dim, 16
H = SMOLLM.n_heads


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs in /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                   # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one, so keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _is_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, no_persistent_cache,
                                              kv_dtype):
    B, N, M = 8, 512, 64
    pool_dt = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    pool = _sds(one_chip, (N, BS, KV, HD), pool_dt)
    scales = {}
    if kv_dtype == "int8":
        scales = dict(k_scale=_sds(one_chip, (N, BS, KV), jnp.float32),
                      v_scale=_sds(one_chip, (N, BS, KV), jnp.float32))
    compiled = jax.jit(
        lambda *a, **k: paged_attention(*a, interpret=False, **k)).lower(
        _sds(one_chip, (B, H, HD), jnp.bfloat16), pool, pool,
        _sds(one_chip, (B, M), jnp.int32), _sds(one_chip, (B,), jnp.int32),
        **scales).compile()
    assert _is_kernel(compiled)


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_prefill_kernel_compiles_for_v5e(one_chip,
                                               no_persistent_cache,
                                               kv_dtype):
    N, Ls, P, M = 4, 256, 512, 64
    pool_dt = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    pool = _sds(one_chip, (P, BS, KV, HD), pool_dt)
    suf = _sds(one_chip, (N, Ls, KV, HD), jnp.bfloat16)
    scales = {}
    if kv_dtype == "int8":
        scales = dict(k_scale=_sds(one_chip, (P, BS, KV), jnp.float32),
                      v_scale=_sds(one_chip, (P, BS, KV), jnp.float32))
    rows = _sds(one_chip, (N,), jnp.int32)
    compiled = jax.jit(
        lambda *a, **k: paged_prefill_attention(*a, interpret=False,
                                                **k)).lower(
        _sds(one_chip, (N, Ls, H, HD), jnp.bfloat16), suf, suf, pool, pool,
        _sds(one_chip, (N, M), jnp.int32), rows, rows, **scales).compile()
    assert _is_kernel(compiled)


def test_flash_attention_kernel_compiles_for_v5e(one_chip,
                                                 no_persistent_cache):
    S = 1024
    q = _sds(one_chip, (2, H, S, HD), jnp.bfloat16)
    kv = _sds(one_chip, (2, KV, S, HD), jnp.bfloat16)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)).lower(
        q, kv, kv).compile()
    assert _is_kernel(compiled)


def test_runner_decode_step_compiles_for_v5e(one_chip, no_persistent_cache):
    """The runner's greedy decode program at published widths (depth cut
    to 2 layers), weights and pools passed as shapes on the chip."""
    cfg = dataclasses.replace(SMOLLM, n_layers=2)
    param_shapes = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    slots, mb = 8, 64
    runner = ModelRunner(param_shapes, cfg, num_slots=slots,
                         block_size=BS, num_blocks=1 + slots * mb,
                         max_blocks_per_seq=mb)

    def on_chip(x):
        return _sds(one_chip, x.shape, x.dtype)

    lanes = _sds(one_chip, (slots,), jnp.int32)
    temps = _sds(one_chip, (slots,), jnp.float32)
    compiled = runner._decode_fn.lower(
        jax.tree.map(on_chip, param_shapes),
        jax.tree.map(on_chip, runner.state), lanes, lanes,
        _sds(one_chip, (slots, mb), jnp.int32), temps, lanes, temps, lanes,
        False, False).compile()
    mem = compiled.memory_analysis()
    weight_bytes = sum(x.size * x.dtype.itemsize
                       for x in jax.tree.leaves(param_shapes))
    # the weights arrive as arguments: none of them is compiled in
    assert mem.argument_size_in_bytes >= weight_bytes
    assert mem.generated_code_size_in_bytes < weight_bytes
