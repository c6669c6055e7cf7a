"""The training driver end to end on four CPU devices at a tiny size: a
sound run matches the plain MBProx reference, and each fault of the
training step comes out not correct."""
import pytest

import cells
import run


def test_sound_training_run_is_correct():
    out = run.run_cell(cells.train_ctx())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"train_tok_s", "setup_s"}


def _state_unchanged(monkeypatch):
    from repro.launch import steps
    inner = steps.make_mbprox_train_step

    def make(*a, **k):
        step, opt, mp = inner(*a, **k)

        def frozen(params, state, batch, lr):
            _, _, met = step(params, state, batch, lr)
            return params, state, met
        return frozen, opt, mp

    monkeypatch.setattr(steps, "make_mbprox_train_step", make)


def _half_batch(monkeypatch):
    from repro.launch import steps
    import jax
    inner = steps.make_mbprox_train_step

    def make(*a, **k):
        step, opt, mp = inner(*a, **k)

        def half(params, state, batch, lr):
            rows = jax.tree.leaves(batch)[0].shape[1]
            keep = jax.tree.map(lambda x: x[:, :rows // 2], batch)
            return step(params, state, keep, lr)
        return half, opt, mp

    monkeypatch.setattr(steps, "make_mbprox_train_step", make)


def _no_exchange(monkeypatch):
    from repro.optim import mbprox
    monkeypatch.setattr(mbprox.lax, "pmean", lambda x, axes: x)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_exchange"])
def test_broken_training_step_is_not_correct(fault, monkeypatch):
    {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
     "no_exchange": _no_exchange}[fault](monkeypatch)
    out = run.run_cell(cells.train_ctx(seed=9))
    assert not out["correct"], out["checks"]


def test_control_and_faults_read_above_the_program(capsys):
    """The readings control.py takes on the chip, at a tiny size: the fp8
    control and the planted faults, each in the program's place, read far
    above the sound program's numbers."""
    import json

    import control
    ctx = cells.train_ctx(seed=4)
    control.train_readings(ctx, [4], faults=True)
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    prog = row["program"]
    for name in ("control", "half_batch", "no_exchange"):
        assert max(row[name].values()) > 10 * max(prog.values()), row
