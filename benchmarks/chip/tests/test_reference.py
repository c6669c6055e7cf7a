"""The plain reference agrees with the program's float32 forward pass on the
benchmark's seeded weights, for a grouped-query tied model (smollm's family)
and a multi-head untied one (stablelm's)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cells
import reference
import weights

SMALL = {
    "gqa_tied": dict(cells.TINY),
    "mha_untied": dict(cells.TINY, n_heads=4, n_kv_heads=4, head_dim=20,
                       tie_embeddings=False, n_layers=3),
}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_matches_program_forward(name):
    from repro.models import lm
    import drivers.serve as serve
    spec = SMALL[name]
    w = weights.make(spec, 2**32 + 11)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, 40),
                       jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = lm.forward(w, serve.model_config(spec), {"tokens": toks[None]})
    ref = reference.logits(w, spec, toks)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_weights_are_a_function_of_the_seed():
    spec = SMALL["gqa_tied"]
    a, b = weights.make(spec, 5), weights.make(spec, 5)
    c = weights.make(spec, 6)
    for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                       jax.tree.leaves(c)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert not np.array_equal(np.asarray(x), np.asarray(z))


def test_control_reads_above_the_reference():
    """The fp8 control departs from the float32 reference by far more than
    float32 rounding: its gap and log-probability error are not nought."""
    spec = SMALL["gqa_tied"]
    w = weights.make(spec, 3)
    toks = np.random.default_rng(1).integers(0, 256, 48).astype(np.int32)
    got = reference.readings(w, spec, toks, control=True)
    assert got["gap"].shape == (48,)
    assert np.abs(got["control_lp"] - got["ref_lp"]).max() > 1e-3
    assert (got["control_gap"] >= 0).all()
