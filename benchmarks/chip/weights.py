"""Seeded weights, made by the benchmark and not by the program, so the
reference and the program read the same numbers and the reference takes
nothing the program made.

The layout is the one `models/lm.py` takes for a dense model whose every
layer is attention + SwiGLU (`block_pattern == ("attn",)`): layers stacked
on a leading axis under blocks/p0, norms as (1 + scale). It is built in one
jitted call, on the device, in float32 (the program's master copy; it casts
to its compute dtype at each call).
"""
from __future__ import annotations

import numpy as np


def key_for(seed: int):
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    import jax
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def shapes(spec: dict) -> dict:
    L, D, F, V = (spec["n_layers"], spec["d_model"], spec["d_ff"],
                  spec["vocab_size"])
    H, KV, hd = spec["n_heads"], spec["n_kv_heads"], spec["head_dim"]
    tree = {
        "embed": (V, D),
        "prefix": [],
        "blocks": {"p0": {
            "norm1": (L, D), "norm2": (L, D),
            "attn": {"wq": (L, D, H * hd), "wk": (L, D, KV * hd),
                     "wv": (L, D, KV * hd), "wo": (L, H * hd, D)},
            "mlp": {"w_gate": (L, D, F), "w_up": (L, D, F),
                    "w_down": (L, F, D)},
        }},
        "final_norm": (D,),
    }
    if not spec.get("tie_embeddings", False):
        tree["head"] = (D, V)
    return tree


def _scale(path: str, shape: tuple) -> float:
    if path == "embed":
        return 0.02
    if "norm" in path:
        return 0.1                 # (1 + scale): gains of 1 +- 0.1
    return shape[-2] ** -0.5       # fan-in of a matrix


def make(spec: dict, seed: int, sharding=None):
    """All weights of `spec` from `seed`, on the default device or, given a
    sharding, placed by it (a replicated one for a data mesh)."""
    import jax
    import jax.numpy as jnp

    tree = shapes(spec)
    leaves, treedef = jax.tree.flatten(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 tree, is_leaf=lambda x: isinstance(x, tuple))[0]]
    scales = [_scale(p.replace("'", "").strip("[]"), s)
              for p, s in zip(paths, leaves)]

    def build(key):
        out = []
        for i, (shape, sc) in enumerate(zip(leaves, scales)):
            out.append(jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32) * sc)
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build, out_shardings=sharding)(key_for(seed))
