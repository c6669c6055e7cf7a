"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --optimizer mbprox --ckpt-dir /tmp/run1 [--resume]

Trains the architecture at its published widths; `--reduced` (like
`launch/serve.py`'s) swaps in the small same-family variant for CPU runs.
Runs on whatever devices exist (host mesh); the same step builders power
the 512-chip dry-run. Checkpoint/restart via runtime.fault_tolerance.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpoint as ckpt_lib
from repro.configs import get_config
from repro.data.synthetic import TokenStream
from repro.launch import steps as steps_lib
from repro.launch.mesh import data_axes, make_host_mesh
from repro.models import lm
from repro.optim.optimizers import Schedule, adamw
from repro.runtime.compile_cache import enable_compile_cache


def make_batch(cfg, stream, key, batch_size, n_micro):
    toks, targets = stream.batch(key, batch_size)
    Bm = batch_size // n_micro
    return {"tokens": toks.reshape(n_micro, Bm, -1),
            "targets": targets.reshape(n_micro, Bm, -1)}


def train(arch: str, steps: int, *, optimizer: str = "mbprox",
          batch_size: int = 8, n_micro: int = 2, seq_len: int = 64,
          lr: float = 3e-3, ckpt_dir: str | None = None,
          resume: bool = False, reduced: bool = False, log_every: int = 10,
          seed: int = 0):
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    mesh = make_host_mesh()
    stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=seq_len,
                         seed=seed)

    params = lm.init_params(jax.random.PRNGKey(seed), cfg)
    if optimizer == "mbprox":
        step_fn, inner_opt, mp_cfg = steps_lib.make_mbprox_train_step(
            cfg, mesh)
        opt_state = inner_opt.init(params)
    else:
        step_fn, opt = steps_lib.make_baseline_train_step(cfg, mesh)
        opt_state = opt.init(params)
    step_fn = jax.jit(step_fn)
    sched = Schedule(peak=lr, warmup=max(5, steps // 20), total=steps)

    start = 0
    if ckpt_dir and resume:
        restored, s = ckpt_lib.restore(ckpt_dir, {"params": params,
                                                  "opt": opt_state})
        if restored is not None:
            params, opt_state = restored["params"], restored["opt"]
            start = s + 1
            print(f"resumed from step {s}")

    losses = []
    t0 = time.time()
    with jax.set_mesh(mesh):
        for step in range(start, steps):
            key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), step)
            batch = make_batch(cfg, stream, key, batch_size, n_micro)
            params, opt_state, metrics = step_fn(
                params, opt_state, batch, jnp.float32(sched(step)))
            losses.append(float(metrics["loss"]))
            if step % log_every == 0 or step == steps - 1:
                dt = time.time() - t0
                print(f"step {step:5d} loss {losses[-1]:.4f} "
                      f"({dt:.1f}s)", flush=True)
            if ckpt_dir and (step + 1) % 50 == 0:
                ckpt_lib.save(ckpt_dir, step, {"params": params,
                                               "opt": opt_state})
    if ckpt_dir:
        ckpt_lib.save(ckpt_dir, steps - 1, {"params": params,
                                            "opt": opt_state})
    return params, losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--optimizer", default="mbprox",
                    choices=["mbprox", "baseline"])
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--reduced", action="store_true",
                    help="train the small same-family variant (CPU runs) "
                         "instead of the published widths")
    args = ap.parse_args()
    enable_compile_cache()
    _, losses = train(args.arch, args.steps, optimizer=args.optimizer,
                      batch_size=args.batch_size, seq_len=args.seq_len,
                      lr=args.lr, ckpt_dir=args.ckpt_dir,
                      resume=args.resume, reduced=args.reduced)
    print(f"final loss: {losses[-1]:.4f} "
          f"(start {losses[0]:.4f}, min {min(losses):.4f})")


if __name__ == "__main__":
    main()
