"""The training driver: jits the program's MBProx step
(`launch/steps.make_mbprox_train_step`, the `local` variant with the
defaults of `default_mbprox_config`) on a (chips, 1) data mesh, drives its
first three steps from the seed during set-up, checks them against the
plain reference, and hands the same step and state to the window.

Window rules: batches are made on the device before the window, each with
rows of their own. The loop never waits on the step it just dispatched: it
waits only on the loss of the step two before, so at most two steps are in
flight, and it ends with `block_until_ready`. `train_tok_s` counts the
tokens of every outer step dispatched in the window, over the time until
the last of them completed.
"""
from __future__ import annotations

import collections
import sys
import time

import numpy as np

import mbprox_reference
import trace as trace_lib
import weights as weights_lib

PROGRAMS = {"train": "jit_train_step"}
CHECKED_STEPS = 3


def make_batches(spec: dict, mix: dict, seed: int, n: int, sharding):
    """n global batches {"tokens", "targets"} of (n_micro, rows, S) int32,
    all rows distinct, made on the devices in one jitted call. Targets copy
    the program's synthetic stream: half of the positions follow
    x -> (31 x + 7) mod V, the rest are the next token."""
    import jax
    import jax.numpy as jnp

    V, S = spec["vocab_size"], mix["seq_len"]
    rows = mix["micro_batch_per_chip"] * mix["chips"]
    shape = (n, mix["n_micro"], rows, S + 1)

    def build(key):
        toks = jax.random.randint(key, shape, 0, V, jnp.int32)
        det = (toks[..., :-1] * 31 + 7) % V
        mix_ = jax.random.bernoulli(jax.random.fold_in(key, 1), 0.5,
                                    det.shape)
        return toks[..., :-1], jnp.where(mix_, det, toks[..., 1:])

    toks, tgts = jax.jit(build, out_shardings=(sharding, sharding))(
        jax.random.fold_in(weights_lib.key_for(seed), 7))
    return toks, tgts


def leaf_norms(tree) -> dict:
    """{name: norm} for every leaf; a leaf stacked over layers (under
    `blocks`) gives one norm per layer."""
    import jax
    import jax.numpy as jnp

    def one(path, x):
        x = x.astype(jnp.float32)
        if "blocks" in jax.tree_util.keystr(path):
            return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        return jnp.sqrt(jnp.sum(x * x))

    norms = jax.device_get(jax.jit(
        lambda t: jax.tree_util.tree_map_with_path(one, t))(tree))
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(norms)[0]:
        name = jax.tree_util.keystr(path)
        v = np.asarray(v, np.float64)
        if v.ndim:
            out.update({f"{name}[{i}]": float(x) for i, x in enumerate(v)})
        else:
            out[name] = float(v)
    return out


def _gaps(prog: dict, ref: dict, keep) -> float:
    """Worst leaf of |prog norm - ref norm| over the larger of the leaf's
    and the median leaf's reference norm."""
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def build(ctx: dict) -> dict:
    """The mesh and the jitted program step, built once per process."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import steps as steps_lib

    import drivers.serve as serve

    spec = ctx["config"]["model"]
    devices = ctx["devices"]
    mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                         devices=devices)
    step_fn, inner_opt, mp = steps_lib.make_mbprox_train_step(
        serve.model_config(spec), mesh)
    return {"mesh": mesh, "step": jax.jit(step_fn), "opt": inner_opt,
            "mp": mp, "spec": spec, "devices": devices,
            "mix": dict(ctx["traffic"], chips=len(devices)),
            "rep": NamedSharding(mesh, P()),
            "data": NamedSharding(mesh, P(None, None, "data")),
            "batch": NamedSharding(mesh, P(None, "data"))}


def checked(ctx: dict, b: dict, seed: int, n_batches: int) -> dict:
    """Weights and batches from `seed`, then the first steps through the
    window's own call and feed, with what they are checked on: each step's
    loss, the momentum's norms after step 1, the change's after step 3."""
    import jax
    import jax.numpy as jnp

    mix, spec, step = b["mix"], b["spec"], b["step"]
    params0 = weights_lib.make(spec, seed, sharding=b["rep"])
    state = jax.jit(b["opt"].init, out_shardings=b["rep"])(params0)
    toks, tgts = make_batches(spec, mix, seed, n_batches, b["data"])
    pick = jax.jit(lambda t, y, i: {"tokens": t[i], "targets": y[i]},
                   out_shardings=b["batch"])
    batches = [pick(toks, tgts, i) for i in range(n_batches)]
    lr = jnp.float32(mix["lr"])
    losses, p = [], params0
    with jax.set_mesh(b["mesh"]):
        for i in range(CHECKED_STEPS):
            p, state, met = step(p, state, batches[i], lr)
            losses.append(float(met["loss"]))
            if i == 0:
                state1 = leaf_norms(state)
        change3 = leaf_norms(jax.tree.map(jnp.subtract, p, params0))
    print(f"[setup] {CHECKED_STEPS} checked steps, losses {losses}; "
          f"{ctx['log'].compiles} compiles so far", file=sys.stderr,
          flush=True)
    return {"params0": params0, "toks": toks, "tgts": tgts,
            "batches": batches, "lr": lr, "p": p, "state": state,
            "losses": losses, "state1": state1, "change3": change3}


def run(ctx: dict) -> dict:
    import jax

    seed, seconds = ctx["seed"], ctx["seconds"]
    b = build(ctx)
    mix, devices, step, mesh = b["mix"], b["devices"], b["step"], b["mesh"]
    n_batches = CHECKED_STEPS + int(4 * seconds) + 2
    c = checked(ctx, b, seed, n_batches)
    p, state, batches, lr = c.pop("p"), c.pop("state"), c.pop("batches"), \
        c["lr"]

    # the window
    log = ctx["log"]
    compiles0 = log.compiles
    t_start = time.perf_counter()
    clock = lambda: time.perf_counter() - t_start   # noqa: E731
    inflight = collections.deque()
    n, k = 0, CHECKED_STEPS
    trace_dir, span = None, None
    t_trace = (seconds * 0.3, seconds * 0.3 + ctx.get("trace_seconds", 3.0))
    with jax.set_mesh(mesh):
        while clock() < seconds:
            now = clock()
            if ctx["trace"] and trace_dir is None and now >= t_trace[0]:
                trace_dir, span = trace_lib.start(ctx["scratch"]), [now, None]
            if span and span[1] is None and now >= t_trace[1]:
                jax.block_until_ready(p)
                span[1] = clock()        # before the profiler's write-out
                jax.profiler.stop_trace()
            with jax.profiler.TraceAnnotation("bench.train.step"):
                p, state, met = step(p, state, batches[k], lr)
            inflight.append(met["loss"])
            n += 1
            k = CHECKED_STEPS + (k + 1 - CHECKED_STEPS) % (
                n_batches - CHECKED_STEPS)
            if len(inflight) > 2:
                with jax.profiler.TraceAnnotation("bench.train.wait"):
                    inflight.popleft().block_until_ready()
        jax.block_until_ready((p, state))
    elapsed = clock()
    if span and span[1] is None:
        span[1] = clock()
        jax.profiler.stop_trace()
    in_window = log.compiles - compiles0
    print(f"[window] {n} steps in {elapsed:.3f} s, {in_window} compiles "
          f"inside the window", file=sys.stderr, flush=True)
    rec = {"window_s": elapsed, "attempted": n, "failed": 0,
           "setup_s": t_start - ctx["t0"], "compiles_in_window": in_window,
           "train_tokens": n * mix["n_micro"] * mix["micro_batch_per_chip"]
           * len(devices) * mix["seq_len"],
           "seq_len": mix["seq_len"], "chips": len(devices),
           "passes_per_token": (mix["n_micro"] + 1) / mix["n_micro"]}
    if trace_dir is not None:
        summary = trace_lib.summarize(trace_dir, PROGRAMS,
                                      span_prefix="bench.",
                                      devices=len(devices))
        summary["window_s"] = span[1] - span[0]
        rec["trace"] = summary
        trace_lib.remove(trace_dir)
    rec["memory"] = ctx["memory"]()
    del p, state, met, inflight, batches

    # the reference, once the window's state is freed
    ref = reference_readings(c["params0"], c["toks"], c["tgts"], b["spec"],
                             mix, b["mp"], devices)
    rec["checks"] = compare(ctx["cell"]["check"], c["losses"], c["state1"],
                            c["change3"], ref)
    return rec


def shards_of(toks, tgts, i: int, mix: dict) -> list:
    """Batch i split as the mesh splits it: shard k holds rows
    [k*b, (k+1)*b) of every microbatch."""
    b = mix["micro_batch_per_chip"]
    t, y = np.asarray(toks[i]), np.asarray(tgts[i])
    return [(t[:, k * b:(k + 1) * b], y[:, k * b:(k + 1) * b])
            for k in range(mix["chips"])]


def reference_readings(params0, toks, tgts, spec, mix, mp, devices,
                       **fault) -> dict:
    """Losses, momentum norms after step 1, change norms after step 3, and
    the first exchanged gradient's norms, from the plain reference."""
    import jax
    import jax.numpy as jnp

    hp = {"lr": float(mix["lr"]), "gamma": float(mp.gamma),
          "mu": float(mp.inner_momentum)}
    host0 = jax.device_get(params0)        # off the program's mesh
    a = jax.device_put(host0, devices[0])
    m = jax.tree.map(jnp.zeros_like, a)
    out = {"losses": []}
    for i in range(CHECKED_STEPS):
        a, m, loss, g = mbprox_reference.outer_step(
            a, m, shards_of(toks, tgts, i, mix), spec, hp, devices,
            **fault)
        out["losses"].append(loss)
        if i == 0:
            out["state1"] = leaf_norms(m)
            out["grad1"] = leaf_norms(g)
    p0 = jax.device_put(host0, devices[0])
    out["change3"] = leaf_norms(jax.tree.map(jnp.subtract, a, p0))
    return out


def compare(chk: dict, losses, state1, change3, ref: dict,
            values: bool = False) -> dict:
    """The compared numbers, each with its limit. Leaves whose first
    reference gradient is under a thousandth of the median leaf's are
    left out of the norms (they move by round-off alone)."""
    g = ref["grad1"]
    med = float(np.median(list(g.values())))
    keep = [k for k in g if g[k] >= 1e-3 * med]
    got = {
        "loss_gap": max(abs(a - b) / abs(b)
                        for a, b in zip(losses, ref["losses"])),
        "state1_gap": _gaps(state1, ref["state1"], keep),
        "change3_gap": _gaps(change3, ref["change3"], keep),
    }
    print(f"[check] {len(keep)} of {len(g)} leaves compared; losses "
          f"{losses} against {ref['losses']}", file=sys.stderr, flush=True)
    if values:
        return got
    return {name: {"value": got[name], "limit": lim}
            for name, lim in chk["limits"].items()}
