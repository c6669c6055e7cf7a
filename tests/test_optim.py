"""Optimizers, MBProx deep-learning step, gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import compression as comp
from repro.optim.optimizers import (Schedule, adamw, clip_by_global_norm,
                                    sgd)


def _quad_problem(seed=0, d=16):
    k = jax.random.PRNGKey(seed)
    A = jax.random.normal(k, (d, d)) / d**0.5
    H = A @ A.T + 0.1 * jnp.eye(d)
    b = jax.random.normal(jax.random.fold_in(k, 1), (d,))

    def loss(params):
        w = params["w"]
        return 0.5 * w @ H @ w - b @ w

    w_star = jnp.linalg.solve(H, b)
    return loss, w_star


@pytest.mark.parametrize("make_opt", [
    lambda: sgd(momentum=0.0), lambda: sgd(momentum=0.9),
    lambda: sgd(momentum=0.9, nesterov=True), lambda: adamw()])
def test_optimizers_minimize_quadratic(make_opt):
    loss, w_star = _quad_problem()
    opt = make_opt()
    params = {"w": jnp.zeros(16)}
    state = opt.init(params)
    lr = 0.1
    grad_fn = jax.jit(jax.grad(loss))
    for _ in range(1500):
        g = grad_fn(params)
        params, state = opt.update(g, state, params, lr)
    np.testing.assert_allclose(np.asarray(params["w"]), np.asarray(w_star),
                               atol=0.05)


def test_sgd_bf16_params_stay_bf16():
    opt = sgd(momentum=0.9)
    params = {"w": jnp.ones(8, jnp.bfloat16)}
    state = opt.init(params)
    g = {"w": jnp.ones(8, jnp.float32)}
    params, state = opt.update(g, state, params, jnp.float32(0.1))
    assert params["w"].dtype == jnp.bfloat16


def test_clip_by_global_norm():
    g = {"a": jnp.full((4,), 10.0), "b": jnp.full((4,), 10.0)}
    clipped, gn = clip_by_global_norm(g, 1.0)
    total = jnp.sqrt(sum(jnp.sum(x**2) for x in jax.tree.leaves(clipped)))
    np.testing.assert_allclose(float(total), 1.0, rtol=1e-5)
    np.testing.assert_allclose(float(gn), (800.0) ** 0.5, rtol=1e-5)


def test_schedule():
    s = Schedule(peak=1.0, warmup=10, total=100, floor=0.1)
    assert float(s(0)) == 0.0
    np.testing.assert_allclose(float(s(10)), 1.0, rtol=1e-5)
    assert float(s(100)) == pytest.approx(0.1, rel=1e-3)
    assert float(s(55)) < float(s(20))


# ----------------------------------------------------------------------------
# MBProx deep-learning step
# ----------------------------------------------------------------------------

def test_mbprox_step_solves_prox_subproblem():
    """With many inner passes and gamma, the local variant approaches the
    prox point of the quadratic loss (single machine => pmean is identity)."""
    from repro.optim.mbprox import MBProxConfig, make_mbprox_step
    from repro.launch.mesh import make_host_mesh
    loss_fn_inner, w_star = _quad_problem()

    def loss_fn(params, micro):
        return loss_fn_inner(params) * micro["scale"][0], {}

    mesh = make_host_mesh()
    gamma = 0.5
    mp = MBProxConfig(gamma=gamma, inner_momentum=0.0, inner_passes=50,
                      dane_correction=False, variant="local")
    step = make_mbprox_step(loss_fn, mp, mesh, ("data",))
    params = {"w": jnp.zeros(16)}
    batch = {"scale": jnp.ones((4, 1))}
    with jax.set_mesh(mesh):
        new_p, _, m = jax.jit(step)(params, (), batch, jnp.float32(0.05))
    # prox point: argmin loss + gamma/2 ||w||^2 = (H + gamma I)^{-1} b
    loss, _ = _quad_problem()
    k = jax.random.PRNGKey(0)
    A = jax.random.normal(k, (16, 16)) / 4.0
    H = A @ A.T + 0.1 * jnp.eye(16)
    b = jax.random.normal(jax.random.fold_in(k, 1), (16,))
    expect = jnp.linalg.solve(H + gamma * jnp.eye(16), b)
    np.testing.assert_allclose(np.asarray(new_p["w"]), np.asarray(expect),
                               atol=0.05)


def test_mbprox_sync_equals_local_on_one_shard():
    """On a 1-device mesh the 'local' and 'sync' variants are the same
    algorithm (no averaging) — outputs must match."""
    from repro.optim.mbprox import MBProxConfig, make_mbprox_step
    from repro.launch.mesh import make_host_mesh
    loss_quad, _ = _quad_problem()

    def loss_fn(params, micro):
        return loss_quad(params) + 0.0 * micro["x"].sum(), {}

    mesh = make_host_mesh()
    batch = {"x": jnp.zeros((2, 4))}
    params = {"w": jnp.ones(16)}
    outs = {}
    for variant in ("local", "sync"):
        mp = MBProxConfig(gamma=0.2, inner_momentum=0.9, inner_passes=2,
                          dane_correction=False, variant=variant)
        step = make_mbprox_step(loss_fn, mp, mesh, ("data",))
        with jax.set_mesh(mesh):
            p, s, _ = jax.jit(step)(params,
                                    jax.tree.map(jnp.zeros_like, params),
                                    batch, jnp.float32(0.03))
        outs[variant] = p["w"]
    np.testing.assert_allclose(np.asarray(outs["local"]),
                               np.asarray(outs["sync"]), atol=1e-5)


# ----------------------------------------------------------------------------
# compression
# ----------------------------------------------------------------------------

def test_int8_roundtrip_error_feedback():
    k = jax.random.PRNGKey(0)
    tree = {"w": jax.random.normal(k, (1000,)),
            "b": jax.random.normal(jax.random.fold_in(k, 1), (37,))}
    ef = comp.init_ef(tree)
    compressed, ef = comp.quantize_int8(tree, ef)
    deq = comp.dequantize_int8(compressed)
    # block-scaled int8: ~1% relative error per entry
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(deq)):
        err = float(jnp.abs(a - b).max())
        scale = float(jnp.abs(a).max())
        assert err <= scale / 127.0 * 1.01
    # error feedback: residual equals the quantization error
    for r, a, b in zip(jax.tree.leaves(ef.residual), jax.tree.leaves(tree),
                       jax.tree.leaves(deq)):
        np.testing.assert_allclose(np.asarray(r), np.asarray(a - b),
                                   atol=1e-6)
    # wire size ~4x smaller than f32
    wire = comp.compressed_bytes_int8(tree)
    raw = sum(x.size * 4 for x in jax.tree.leaves(tree))
    assert wire < raw / 3.5


def test_error_feedback_unbiased_over_rounds():
    """With EF, the SUM of transmitted (dequantized) values converges to
    the sum of true values — compression error does not accumulate."""
    k = jax.random.PRNGKey(3)
    true = jax.random.normal(k, (512,)) * 0.1
    ef = comp.init_ef({"g": true})
    sent = jnp.zeros_like(true)
    for _ in range(30):
        compressed, ef = comp.quantize_int8({"g": true}, ef)
        sent = sent + comp.dequantize_int8(compressed)["g"]
    np.testing.assert_allclose(np.asarray(sent / 30), np.asarray(true),
                               atol=2e-3)


def test_topk_roundtrip():
    k = jax.random.PRNGKey(1)
    tree = {"w": jax.random.normal(k, (2048,))}
    ef = comp.init_ef(tree)
    compressed, ef = comp.topk_sparsify(tree, ef, frac=0.1)
    dense = comp.topk_densify(compressed)
    nz = int((dense["w"] != 0).sum())
    assert nz == 204  # 10% of 2048
    # kept entries are the largest-magnitude ones
    thresh = float(jnp.sort(jnp.abs(tree["w"]))[-204])
    kept = jnp.abs(dense["w"][dense["w"] != 0])
    assert float(kept.min()) >= thresh - 1e-6
