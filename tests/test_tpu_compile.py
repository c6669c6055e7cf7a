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
    shapes), with the depth cut to 2 layers so it compiles quickly;
  * every runner program that takes the paged KV pool (decode greedy and
    sampled, one prefill bucket, verify, copy-on-write and host-tier
    upload) at 64 lanes and 4097 blocks of 16, depth cut to 4 layers:
    none may copy, slice or rebuild the pool, and each must take and
    return it in the same row-major format;
  * the same for DeepSeek-V2-Lite's latent (MLA) pool at its published
    widths, depth cut to one dense and two MoE layers: rows of 576
    values stored padded to 640 lanes.

Nothing runs, so these say nothing about results or times. The topology
is described inside a module fixture, never at import: only one process
may load the TPU library at a time.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.paged_prefill import paged_prefill_attention
from repro.models import lm
from repro.serving import kv_cache
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


# ----------------------------------------------------------------------------
# The paged KV pool stays in place: no runner program moves it
# ----------------------------------------------------------------------------

LANES, MB, NB = 64, 64, 4097        # 64 lanes of 1024 tokens, 4097 blocks
MOVES = ("copy", "copy-start", "dynamic-slice", "dynamic-update-slice")


def _is_pool(shape):
    """A pool array, a layer of one, or the old (.., blocks, bs, KV, hd)
    form: the shape holds the pool's row count or its block count."""
    return NB * BS in shape or NB in shape


def _pool_moves(hlo):
    """Instructions of the non-fused computations (entry, loop bodies)
    that copy, slice or update-slice an array of the pool's size."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo))
    moves, comp = [], None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        ins = re.match(r"\s+(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                       r"([\w\-]+)\(", line)
        if comp in fused or not ins or ins.group(3) not in MOVES:
            continue
        dims = tuple(int(d) for d in ins.group(2).split(",") if d)
        if _is_pool(dims):
            moves.append(f"{comp}: {ins.group(1)} {ins.group(3)}{list(dims)}")
    return moves


def _pool_formats(formats, state):
    """The formats of the pool leaves of a state-shaped format tree."""
    leaves = jax.tree.leaves(state)
    fmts = jax.tree.leaves(formats, is_leaf=lambda x: x is None)
    assert len(fmts) == len(leaves)
    return [f for f, x in zip(fmts, leaves) if _is_pool(x.shape)]


@pytest.fixture(scope="module")
def pool_programs(one_chip, no_persistent_cache):
    """Every runner program that takes the pool, at smollm-135m widths
    and 4 layers, 64 lanes."""
    return _programs(dataclasses.replace(SMOLLM, n_layers=4), one_chip,
                     LANES)


@pytest.fixture(scope="module")
def mla_pool_programs(one_chip, no_persistent_cache):
    """The same for DeepSeek-V2-Lite at published widths (bfloat16
    weights), one dense and two MoE layers, 32 lanes."""
    cfg = dataclasses.replace(get_config("deepseek-v2-lite"), n_layers=3)
    return _programs(cfg, one_chip, 32)


def _programs(cfg, one_chip, n_lanes):
    """Lowers every runner program that takes the pool. The runner is
    built over a 2-block pool (no pool is allocated here); its programs
    are lowered for the full 4097-block state as shapes on the chip."""
    param_shapes = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg))
    runner = ModelRunner(param_shapes, cfg, num_slots=n_lanes,
                         block_size=BS,
                         num_blocks=2, max_blocks_per_seq=MB, speculate=3)

    def on_chip(x):
        return _sds(one_chip, x.shape, x.dtype)

    P = jax.tree.map(on_chip, param_shapes)
    S = jax.tree.map(on_chip, jax.eval_shape(
        lambda: kv_cache.init_paged_state(cfg, n_lanes, NB, BS)))
    lanes = _sds(one_chip, (n_lanes,), jnp.int32)
    temps = _sds(one_chip, (n_lanes,), jnp.float32)
    tables = _sds(one_chip, (n_lanes, MB), jnp.int32)
    samp = (temps, lanes, temps, lanes)
    rows = _sds(one_chip, (4,), jnp.int32)
    ids = _sds(one_chip, (8,), jnp.int32)
    one = _sds(one_chip, (), jnp.int32)
    payload = jax.tree.map(on_chip, jax.eval_shape(runner._gather_fn, S,
                                                   ids))
    # program -> (lowering thunk, index of the state in args, in outputs)
    return S, {
        "decode": (lambda: runner._decode_fn.lower(
            P, S, lanes, lanes, tables, *samp, False, False), 1, 3),
        "decode_sampled": (lambda: runner._decode_fn.lower(
            P, S, lanes, lanes, tables, *samp, True, True), 1, 3),
        "prefill": (lambda: runner._prefill_fn.lower(
            P, S, _sds(one_chip, (4, 256), jnp.int32), rows, rows,
            _sds(one_chip, (4, MB), jnp.int32), rows, False), 1, 1),
        "verify": (lambda: runner._verify_fn.lower(
            P, S, _sds(one_chip, (n_lanes, 4), jnp.int32), lanes, lanes,
            tables, *samp, False, False), 1, 4),
        "copy_block": (lambda: runner._copy_fn.lower(S, one, one), 0, None),
        "upload": (lambda: runner._upload_fn.lower(S, ids, payload), 0,
                   None),
    }


@pytest.mark.parametrize("program", ["decode", "decode_sampled", "prefill",
                                     "verify", "copy_block", "upload"])
def test_runner_programs_keep_the_pool_in_place(pool_programs, program):
    """No non-fused computation of the program copies, slices or rebuilds
    an array of the pool's size (the layer loop carries the pool and
    scatters its new rows in place), and the program takes and returns
    the pool in one row-major format, so no call relayouts it."""
    _check_pool_in_place(pool_programs, program)


@pytest.mark.parametrize("program", ["decode", "prefill", "verify",
                                     "copy_block"])
def test_mla_runner_programs_keep_the_latent_pool_in_place(
        mla_pool_programs, program):
    """A 576-value latent row is stored padded to 640 lanes, so the
    latent pool keeps the row-major format, and no program copies,
    slices or relayouts it."""
    _check_pool_in_place(mla_pool_programs, program)


def _check_pool_in_place(pool_programs, program):
    state, programs = pool_programs
    lower, arg, out = programs[program]
    compiled = lower().compile()
    assert _pool_moves(compiled.as_text()) == []
    args, _ = compiled.input_formats
    outs = compiled.output_formats
    fmt_in = _pool_formats(args[arg], state)
    fmt_out = _pool_formats(outs if out is None else outs[out], state)
    assert fmt_in and fmt_in == fmt_out
    for f in fmt_in:
        assert f.layout.major_to_minor == tuple(range(
            len(f.layout.major_to_minor)))
