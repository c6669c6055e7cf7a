"""Seeded weights, made by the benchmark and not by the program, so the
reference and the program read the same numbers and the reference takes
nothing the program made.

The tree and each leaf's init scale are the architecture's
(`arch/<name>.py`, found by `common.arch`). Every leaf is a seeded normal
times that scale, the i-th leaf in flattening order drawn from
fold_in(key, i). It is built in one jitted call, on the device, drawn in
float32 and stored in the spec's `param_dtype` (default float32, the
program's master copy; it casts to its compute dtype at each call).
"""
from __future__ import annotations

import numpy as np

import common


def key_for(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    import jax
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def shapes(spec: dict) -> dict:
    """The weight tree of `spec`'s architecture, leaf shapes as tuples."""
    return common.arch(spec).shapes(spec)


def make(spec: dict, seed: int, sharding=None):
    """All weights of `spec` from `seed`, on the default device or, given a
    sharding, placed by it (a replicated one for a data mesh)."""
    import jax
    import jax.numpy as jnp

    arch = common.arch(spec)
    tree = arch.shapes(spec)
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    scales = [arch.scale(p.replace("'", "").strip("[]"), s)
              for p, s in zip(paths, leaves)]

    dtype = jnp.dtype(spec.get("param_dtype", "float32"))

    def build(key):
        out = []
        for i, (shape, sc) in enumerate(zip(leaves, scales)):
            out.append((jax.random.normal(jax.random.fold_in(key, i), shape,
                                          jnp.float32) * sc).astype(dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build, out_shardings=sharding)(key_for(seed))
