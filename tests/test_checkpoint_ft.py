"""Checkpoint, restart, fault-tolerance, elasticity tests."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.runtime.fault_tolerance import (FailureInjector, RestartableLoop,
                                           eta_budget,
                                           straggler_safe_inner_steps)
from repro.core import theory


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"params": {"w": jax.random.normal(k, (32, 16)),
                       "b": jnp.zeros(16, jnp.bfloat16)},
            "step": jnp.int32(7),
            "m": [jax.random.normal(jax.random.fold_in(k, 1), (8,))]}


def test_save_restore_roundtrip(tmp_path):
    tree = _tree()
    ckpt.save(str(tmp_path), 3, tree)
    restored, step = ckpt.restore(str(tmp_path), tree)
    assert step == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_restore_latest_and_gc(tmp_path):
    for s in range(6):
        ckpt.save(str(tmp_path), s, _tree(s), keep=2)
    files = [f for f in os.listdir(tmp_path) if f.endswith(".ckpt")]
    assert len(files) == 2
    _, step = ckpt.restore(str(tmp_path), _tree())
    assert step == 5
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_integrity_check(tmp_path):
    ckpt.save(str(tmp_path), 1, _tree())
    import json
    mf = json.load(open(tmp_path / "manifest.json"))
    path = tmp_path / mf["file"]
    blob = path.read_bytes()
    path.write_bytes(blob[:-4] + b"\x00\x00\x00\x00")
    with pytest.raises(IOError):
        ckpt.restore(str(tmp_path), _tree())


def test_async_checkpointer(tmp_path):
    acp = ckpt.AsyncCheckpointer(str(tmp_path))
    tree = _tree()
    acp.save(11, tree)
    acp.wait()
    restored, step = ckpt.restore(str(tmp_path), tree)
    assert step == 11
    np.testing.assert_array_equal(np.asarray(restored["params"]["w"]),
                                  np.asarray(tree["params"]["w"]))


def test_restartable_loop_survives_failures(tmp_path):
    """A loop with injected failures, restarted until done, produces the
    SAME final state as an uninterrupted run (exactly-once steps)."""
    def step_fn(state, step):
        return {"x": state["x"] + jnp.float32(step + 1)}

    init = {"x": jnp.float32(0.0)}
    clean = RestartableLoop(str(tmp_path / "clean"), step_fn,
                            ckpt_every=3).run(init, 17)

    inj = FailureInjector(prob=0.25, seed=42)
    loop = RestartableLoop(str(tmp_path / "faulty"), step_fn, ckpt_every=3,
                           injector=inj)
    attempts = 0
    state = None
    while attempts < 100:
        attempts += 1
        try:
            state = loop.run(init, 17)
            break
        except RuntimeError:
            continue
    assert state is not None, "never completed"
    assert attempts > 1, "no failure was injected — raise prob"
    # NOTE: steps between the last checkpoint and a crash are re-executed;
    # the step function is deterministic in (state, step) so the result is
    # identical.
    np.testing.assert_allclose(float(state["x"]), float(clean["x"]))


def test_straggler_budgets():
    spec = theory.ProblemSpec(L=1.0, beta=1.0, B=1.0, lam=0.1)
    etas = [eta_budget(spec, 64, 32, t) for t in (1, 2, 4)]
    assert etas[0] > etas[1] > etas[2] > 0
    assert straggler_safe_inner_steps(100, 0.35) == 35
    assert straggler_safe_inner_steps(100, 0.0) == 1


def test_elastic_rebalance():
    from repro.runtime.elastic import rebalance_plan
    b, T = rebalance_plan(n_old=16, n_new=8, b=128, T_remaining=10)
    assert b == 128 and T == 20     # half the machines => double the steps
    b, T = rebalance_plan(n_old=8, n_new=16, b=128, T_remaining=20)
    assert T == 10


def test_elastic_rebalance_conserves_budget():
    """T rounds UP when b*n_new does not divide the remaining budget —
    flooring would silently drop up to n_new-1 steps' worth of samples
    (4->3 machines with an odd product used to plan 6*2*3=36 < 40)."""
    from repro.runtime.elastic import rebalance_plan
    b, T = rebalance_plan(n_old=4, n_new=3, b=2, T_remaining=5)
    assert T == 7                   # ceil(40 / 6), not floor = 6
    for n_old, n_new, bb, tr in [(4, 3, 2, 5), (16, 7, 3, 11),
                                 (5, 2, 1, 1), (2, 9, 4, 13)]:
        b, T = rebalance_plan(n_old=n_old, n_new=n_new, b=bb,
                              T_remaining=tr)
        assert b * n_new * T >= bb * n_old * tr   # never fewer samples
        # and never overshoots by a full extra outer step
        assert b * n_new * (T - 1) < bb * n_old * tr


def test_train_driver_resume(tmp_path):
    """train.py --resume continues from the checkpoint (integration)."""
    from repro.launch.train import train
    d = str(tmp_path / "run")
    _, losses1 = train("smollm-135m", 4, optimizer="baseline",
                       batch_size=4, n_micro=2, seq_len=16, ckpt_dir=d,
                       reduced=True, log_every=100)
    _, losses2 = train("smollm-135m", 6, optimizer="baseline",
                       batch_size=4, n_micro=2, seq_len=16, ckpt_dir=d,
                       resume=True, reduced=True, log_every=100)
    assert len(losses2) == 2        # resumed at step 4, ran 4..5
