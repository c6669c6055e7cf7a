"""Minibatch-prox as a first-class deep-learning optimizer (the framework's
integration of the paper's technique).

One MBProx *outer step* consumes a held global minibatch of `n_micro`
microbatches and approximately solves (paper eq. 12)

    min_w  loss_minibatch(w) + (gamma/2) ||w - anchor||^2 ,

then advances the anchor. Two execution variants map it onto a TPU mesh:

  * `local` (MP-DANE form, App. D / Algorithm 2): every data shard solves the
    prox subproblem on ITS OWN shard of the minibatch with `inner_passes`
    epochs of momentum-SGD (zero data-axis collectives), then the solutions
    are averaged (eq. 34; ONE all-reduce of params). An optional DANE gradient
    correction <pmean(g) - g_local, w> costs one more all-reduce at the
    anchor. Implemented with `shard_map` manual over the data/pod axes and
    GSPMD-auto over 'model' (TP stays automatic inside).
    => data/pod-axis collectives per outer step: 1 (2 with correction),
       versus `n_micro` for the baseline. This is the paper's
       communication↔memory tradeoff realized at the training-step level.

  * `sync` (Theorem 7's generic inexact solver): inner steps are synchronous
    minibatch-SGD steps on the held minibatch (grad all-reduce per inner
    step, standard GSPMD). Used for the FSDP-sharded >10B archs where the
    divergent local copies of variant `local` cannot be represented (each
    data shard owns a param *slice*, not a replica). Still paper-faithful:
    it is exactly "inexact minibatch-prox with a distributed first-order
    solver"; the statistical large-batch benefit is retained while the
    communication saving is not — recorded honestly in EXPERIMENTS.md.

State kept per parameter: the anchor (1 vector) + inner momentum — versus
Adam's 2 moments; the held minibatch is token ids (cheap). This is the LM
analogue of the paper's "memory = b samples" column.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.optim.optimizers import Optimizer, sgd


@dataclasses.dataclass(frozen=True)
class MBProxConfig:
    gamma: float = 0.1            # prox strength (theory.py scaling)
    inner_lr: float = 0.02
    inner_momentum: float = 0.9
    inner_passes: int = 1         # epochs over the held minibatch
    dane_correction: bool = True  # gradient-correction all-reduce at anchor
    variant: str = "local"        # 'local' | 'sync'


def _tree_add(a, b, alpha=1.0):
    return jax.tree.map(lambda x, y: x + alpha * y.astype(x.dtype), a, b)


def _tree_sub(a, b):
    return jax.tree.map(lambda x, y: x - y.astype(x.dtype), a, b)


def make_mbprox_step(loss_fn: Callable, mp_cfg: MBProxConfig, mesh,
                     dp_axes: tuple):
    """Returns mbprox_train_step(params, inner_state, batch, lr)
    -> (params, inner_state, metrics).

    loss_fn(params, microbatch) -> (loss, metrics); microbatch is a pytree
    whose leaves have a leading microbatch-batch dim.
    `batch` leaves: (n_micro, B_micro, ...).
    """
    inner_opt = sgd(momentum=mp_cfg.inner_momentum)

    def local_subproblem(params, inner_state, local_batch, lr):
        """Runs on ONE data shard (inside shard_map): local prox solve."""
        anchor = params

        if mp_cfg.dane_correction:
            def anchor_loss(p):
                # anchor gradient from the FIRST microbatch of the local
                # held minibatch (a stochastic DANE correction — one
                # microbatch, not an average over all n_micro)
                l, _ = loss_fn(p, jax.tree.map(lambda x: x[0], local_batch))
                return l
            g_loc = jax.grad(anchor_loss)(params)
            g_glob = jax.tree.map(
                lambda g: lax.pmean(g, dp_axes), g_loc)      # all-reduce #1
            correction = _tree_sub(g_glob, g_loc)
        else:
            correction = None

        def inner_step(carry, micro):
            p, s = carry
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(p, micro)
            # prox pull + DANE correction
            g = _tree_add(g, _tree_sub(p, anchor), mp_cfg.gamma)
            if correction is not None:
                g = _tree_add(g, correction)
            p, s = inner_opt.update(g, s, p, lr)
            return (p, s), l

        def one_pass(carry, _):
            return lax.scan(inner_step, carry, local_batch)

        (params, inner_state), losses = lax.scan(
            one_pass, (params, inner_state), None,
            length=mp_cfg.inner_passes)

        # average the local solutions (eq. 34)           # all-reduce #2
        params = jax.tree.map(lambda p: lax.pmean(p, dp_axes), params)
        inner_state = jax.tree.map(lambda s: lax.pmean(s, dp_axes),
                                   inner_state)
        return params, inner_state, lax.pmean(losses.mean(), dp_axes)

    def sync_subproblem(params, inner_state, batch, lr):
        """Synchronous inexact prox (plain GSPMD; per-step all-reduce)."""
        anchor = params

        def inner_step(carry, micro):
            p, s = carry
            (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(p, micro)
            g = _tree_add(g, _tree_sub(p, anchor), mp_cfg.gamma)
            p, s = inner_opt.update(g, s, p, lr)
            return (p, s), l

        def one_pass(carry, _):
            return lax.scan(inner_step, carry, batch)

        (params, inner_state), losses = lax.scan(
            one_pass, (params, inner_state), None,
            length=mp_cfg.inner_passes)
        return params, inner_state, losses.mean()

    if mp_cfg.variant == "sync":
        def step(params, inner_state, batch, lr):
            p, s, l = sync_subproblem(params, inner_state, batch, lr)
            return p, s, {"loss": l}
        return step

    # --- 'local' variant: shard_map manual over dp axes, auto over model ---
    def step(params, inner_state, batch, lr):
        batch_spec = jax.tree.map(lambda _: P(None, dp_axes), batch)

        fn = jax.shard_map(
            local_subproblem,
            mesh=mesh,
            in_specs=(jax.tree.map(lambda _: P(), params),
                      jax.tree.map(lambda _: P(), inner_state),
                      batch_spec, P()),
            out_specs=(jax.tree.map(lambda _: P(), params),
                       jax.tree.map(lambda _: P(), inner_state), P()),
            check_vma=False,
            axis_names=set(dp_axes))
        p, s, l = fn(params, inner_state, batch, lr)
        return p, s, {"loss": l}

    return step
