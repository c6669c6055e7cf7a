"""The plain reference: a float32 forward pass written from the model's
equations, importing nothing of the program. The layer equations are the
architecture's (`arch/<name>.py`, found by `common.arch`, built on the
float32 matrix product and blocked attention of `ops.py`); this file holds
the readings a serving check compares. The architecture reads the weights
in float32 (`ops.f32`), whatever type they are stored in.

`quant="fp8"` is the control: the same pass with both operands of every
matrix product rounded to float8 (e4m3, one max-abs scale per tensor), the
precision below the program's bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import common


def logits(w, spec, tokens, quant=None, remat=False):
    """(S, V) float32 logits of one sequence of token ids."""
    return common.arch(spec).logits(w, spec, tokens, quant, remat)


@functools.partial(jax.jit, static_argnames=("spec_items", "control"))
def _readings(w, tokens, spec_items, control):
    spec = dict(spec_items)
    ref = jax.nn.log_softmax(logits(w, spec, tokens), axis=-1)
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    best = ref.max(axis=-1)
    served = jnp.take_along_axis(ref, nxt[:, None], axis=1)[:, 0]
    out = {"ref_lp": served, "gap": best - served}
    if control:
        ctl = jax.nn.log_softmax(logits(w, spec, tokens, "fp8"), axis=-1)
        first = jnp.argmax(ctl, axis=-1)
        out["control_gap"] = best - jnp.take_along_axis(
            ref, first[:, None], axis=1)[:, 0]
        out["control_lp"] = jnp.take_along_axis(ctl, nxt[:, None],
                                                axis=1)[:, 0]
    return out


def readings(w, spec: dict, tokens, control: bool = False) -> dict:
    """Per position t of one padded sequence, about the token at t + 1:
    `ref_lp`, its log-probability under the reference, and `gap`, how far
    that lies below the reference's best. With `control`, also
    `control_gap`, the gap of the token the fp8 pass puts first, and
    `control_lp`, the fp8 pass's log-probability of the token at t + 1.
    Compiled once per padded length."""
    import numpy as np
    items = tuple(sorted((k, v) for k, v in spec.items()
                         if isinstance(v, (int, float, bool, str))))
    out = _readings(w, jnp.asarray(tokens, jnp.int32), items, control)
    return {k: np.asarray(v, np.float64) for k, v in out.items()}
