"""The architecture a configuration names: `arch/<name>.py`, found by file.
A spec without `arch` is the dense block's, and an entry point of
`weights`, `reference` or `flops` uses the module that the spec names."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import common
import flops
import reference
import weights

COUNTS = ("param_count", "matmul_params", "kv_bytes_per_token")


def _counts(spec):
    peak = common.peaks("TPU v5 lite")
    lanes = [0, 17, 1023, 4000]
    out = [getattr(flops, f)(spec) for f in COUNTS]
    return out + [flops.prefill_flops(spec, 100, 2048),
                  flops.decode_flops(spec, lanes),
                  flops.decode_bytes(spec, lanes),
                  flops.decode_least_s(spec, lanes, peak),
                  flops.train_flops_per_token(spec, 2048)]


@pytest.mark.parametrize("tie", [True, False])
def test_dense_is_the_default(tie):
    spec = dict(cells.TINY, tie_embeddings=tie)
    named = dict(spec, arch="dense")
    a, b = weights.make(spec, 2**33 + 1), weights.make(named, 2**33 + 1)
    leaves_a = jax.tree_util.tree_leaves_with_path(a)
    leaves_b = jax.tree_util.tree_leaves_with_path(b)
    assert [p for p, _ in leaves_a] == [p for p, _ in leaves_b]
    for (_, x), (_, y) in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    toks = jnp.asarray(np.random.default_rng(4).integers(0, 256, 40),
                       jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(reference.logits(a, spec, toks)),
        np.asarray(reference.logits(a, named, toks)))
    ra = reference.readings(a, spec, np.asarray(toks), control=True)
    rb = reference.readings(a, named, np.asarray(toks), control=True)
    for k in ra:
        np.testing.assert_array_equal(ra[k], rb[k])
    assert _counts(spec) == _counts(named)


CONFIGS = sorted(p.stem for p in (common.HERE / "configs").glob("*.json"))


@pytest.mark.parametrize("config", CONFIGS)
def test_dense_is_the_default_for_every_config(config):
    spec = common.config(config)["model"]
    assert "arch" not in spec
    assert common.arch(spec) is common.arch(dict(spec, arch="dense"))
    assert _counts(spec) == _counts(dict(spec, arch="dense"))


def test_smollm2_counts():
    """SmolLM2-1.7B's published total, and its multi-head KV row."""
    spec = common.config("smollm2-1.7b")["model"]
    assert flops.param_count(spec) == 1_711_376_384
    assert flops.kv_bytes_per_token(spec) == 24 * 2 * 32 * 64 * 2


def test_weights_are_stored_in_the_param_dtype():
    """bfloat16 weights are the float32 draws rounded, leaf by leaf; the
    reference reads them in float32."""
    spec = dict(cells.TINY, tie_embeddings=False)
    half = dict(spec, param_dtype="bfloat16")
    full, rounded = weights.make(spec, 2**35 + 7), weights.make(half, 2**35 + 7)
    for x, y in zip(jax.tree.leaves(full), jax.tree.leaves(rounded)):
        assert y.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(x.astype(jnp.bfloat16)),
                                      np.asarray(y))
    toks = jnp.arange(30, dtype=jnp.int32)
    up = jax.tree.map(lambda x: x.astype(jnp.float32), rounded)
    np.testing.assert_array_equal(
        np.asarray(reference.logits(rounded, half, toks)),
        np.asarray(reference.logits(up, spec, toks)))


def test_unknown_arch_exits_naming_its_file():
    spec = dict(cells.TINY, arch="no-such-block")
    for call in (lambda: weights.make(spec, 1),
                 lambda: reference.logits({}, spec, jnp.zeros(4, jnp.int32)),
                 lambda: flops.decode_least_s(spec, [3], {})):
        with pytest.raises(SystemExit, match="arch/no-such-block.py"):
            call()


PROBE = '''

CALLS = []


def _probe(fn):
    def wrapped(*a, **k):
        CALLS.append(fn.__name__)
        return fn(*a, **k)
    return wrapped


shapes, scale, logits = _probe(shapes), _probe(scale), _probe(logits)
decode_flops, decode_bytes = _probe(decode_flops), _probe(decode_bytes)
'''


def test_the_named_module_is_the_one_used(tmp_path, monkeypatch):
    """A copy of dense.py under another name, in a directory the lookup is
    pointed at, gives the same numbers and is the module every entry point
    calls."""
    src = (common.ARCH_DIR / "dense.py").read_text()
    (tmp_path / "dense.py").write_text(src)
    (tmp_path / "probe.py").write_text(src + PROBE)
    monkeypatch.setattr(common, "ARCH_DIR", tmp_path)
    spec = dict(cells.TINY, tie_embeddings=False)
    probe_spec = dict(spec, arch="probe")
    calls = common.arch(probe_spec).CALLS

    w = weights.make(probe_spec, 9)
    assert {"shapes", "scale"} <= set(calls)
    for x, y in zip(jax.tree.leaves(w), jax.tree.leaves(weights.make(spec,
                                                                     9))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    calls.clear()
    toks = jnp.arange(24, dtype=jnp.int32)
    got = reference.logits(w, probe_spec, toks)
    assert calls == ["logits"]
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(reference.logits(w, spec, toks)))

    calls.clear()
    peak = common.peaks("TPU v5 lite")
    least = flops.decode_least_s(probe_spec, [5, 60], peak)
    assert calls == ["decode_flops", "decode_bytes"]
    assert least == flops.decode_least_s(spec, [5, 60], peak)


def test_model_config_drops_arch():
    import drivers.serve as serve
    spec = dict(cells.TINY, arch="dense")
    cfg = serve.model_config(spec)
    assert cfg == serve.model_config(cells.TINY)
    assert not hasattr(cfg, "arch")
