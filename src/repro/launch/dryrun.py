import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any jax import (device count and backend
# lock on first init): the dry-run is a CPU tool and never takes a chip,
# whatever the machine has. Everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh) cell.

For each cell:
  * build the production mesh (16x16 single-pod or 2x16x16 multi-pod),
  * construct ShapeDtypeStruct inputs via launch.inputs.input_specs,
  * jit the step (MBProx train / baseline train / prefill / decode),
  * .lower().compile() — failures here are bugs in the sharding config,
  * record memory_analysis(), cost_analysis() and parsed collective stats
    into experiments/dryrun/<cell>.json (incremental; reruns skip done cells).

Usage:
  python -m repro.launch.dryrun                       # all cells, both meshes
  python -m repro.launch.dryrun --arch smollm-135m --shape train_4k \
        --mesh single --variant mbprox
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, get_config, list_archs
from repro.distributed import sharding as shd
from repro.launch import (cost_model, hlo_analysis, inputs as inputs_lib,
                          steps as steps_lib)
from repro.launch.mesh import make_production_mesh

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def _skip_reason(cfg, shape) -> str | None:
    if shape.name == "long_500k" and not cfg.subquadratic:
        return ("pure full-attention arch: 512k dense decode is out of "
                "design scope (DESIGN.md §4)")
    return None


def model_flops(cfg, shape, inner_passes: int = 1) -> float:
    """Useful FLOPs per step: 6*N_active*tokens (train), 2*N_active*tokens
    (inference); decode = one token per sequence."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len * inner_passes
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one new token


def _mirror_state_struct(opt_state_shapes, params):
    """Optimizer-state leaves mirror the param leaf sharding 1:1 where the
    subtree structure matches params (m/v/momentum); scalars replicate."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def walk(node):
        treedef_p = jax.tree.structure(params)
        if jax.tree.structure(node) == treedef_p:
            return jax.tree.map(
                lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                  sharding=p.sharding),
                node, params)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        # scalar counters etc.
        mesh = jax.tree.leaves(params)[0].sharding.mesh
        return jax.ShapeDtypeStruct(node.shape, node.dtype,
                                    sharding=NamedSharding(mesh, P()))

    return walk(opt_state_shapes)


def _register_inloop_specs(cfg, mesh):
    """Compute sliced-layer specs (stacked axis stripped) and register them
    for in-loop pinning (distributed/context.py)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import context as dctx

    from repro.models import lm
    shapes = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    specs = shd.param_specs(shapes, cfg)
    sliced_specs = {}
    for key, sub in specs["blocks"].items():
        sub_shapes = shapes["blocks"][key]
        sliced = jax.tree.map(
            lambda sp, s: shd.sanitize_spec(P(*tuple(sp)[1:]), s.shape[1:],
                                            mesh),
            sub, sub_shapes, is_leaf=lambda x: isinstance(x, P))
        sliced_specs[key] = sliced
    dctx.set_inloop_specs(sliced_specs)


def build_cell(cfg, shape, mesh, variant: str):
    """Returns (fn, args) ready for jit(fn).lower(*args)."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed import context as dctx
    dp = tuple(a for a in mesh.axis_names if a in ("pod", "data"))
    mbprox_local = (shape.kind == "train" and variant != "baseline"
                    and not shd.needs_fsdp(cfg))
    if shape.kind == "train":
        # train: per-layer FSDP gathers are loop-index-dependent (scan
        # slices) so LICM cannot hoist them; pinning would instead force
        # per-einsum activation psums over 'data' (measured 8.8 TB/step on
        # grok — EXPERIMENTS.md §Perf iteration 2)
        dctx.set_inloop_specs(None)
    else:
        # serve: weights stay sharded in-loop (2D TP), avoiding hoisted
        # whole-stack gathers at decode
        _register_inloop_specs(cfg, mesh)
    if mbprox_local:
        # inside shard_map the data axis is manual — constraints may only
        # reference auto axes; batch is local by construction
        dctx.set_activation_spec(None)
    else:
        # pin batch-over-data on layer activations so FSDP feature
        # shardings cannot steal the data axis (§Perf iteration 3)
        dctx.set_activation_spec(P(dp, None, None))
    ep = cfg.n_experts and cfg.n_experts % 16 == 0
    if (variant == "opt" and ep and shd.needs_fsdp(cfg)
            and shape.kind == "train"):
        # weight-stationary expert parallelism: route tokens to the expert
        # shards (xe resharded E@model, D@data — MBs) instead of FSDP-
        # gathering expert weights (GBs per layer visit); §Perf it. 9
        dctx.set_moe_gather_specs(None)
        dctx.set_moe_xe_spec(P(None, "model", None, "data"))
    elif cfg.n_experts and shd.needs_fsdp(cfg) and shape.kind == "train":
        dctx.set_moe_xe_spec(None)
        dctx.set_moe_gather_specs({
            "w_gate": P("model", None, None) if ep else P(None, None,
                                                          "model"),
            "w_up": P("model", None, None) if ep else P(None, None,
                                                        "model"),
            "w_down": P("model", None, None) if ep else P(None, "model",
                                                          None),
        })
    else:
        dctx.set_moe_gather_specs(None)
        dctx.set_moe_xe_spec(None)
    params, _ = inputs_lib.params_struct(cfg, mesh)
    if shape.kind == "train":
        batch = inputs_lib.input_specs(cfg, shape, mesh)
        lr = jax.ShapeDtypeStruct((), jnp.float32)
        if variant == "baseline":
            step, opt = steps_lib.make_baseline_train_step(cfg, mesh)
            opt_state = _mirror_state_struct(jax.eval_shape(opt.init, params),
                                             params)
            return step, (params, opt_state, batch, lr)
        n_micro = jax.tree.leaves(batch)[0].shape[0]
        micro_b = jax.tree.leaves(batch)[0].shape[1]
        step, inner_opt, mp_cfg = steps_lib.make_mbprox_train_step(
            cfg, mesh, micro_batch=micro_b)
        inner_state = _mirror_state_struct(
            jax.eval_shape(inner_opt.init, params), params)
        return step, (params, inner_state, batch, lr)
    if shape.kind == "prefill":
        batch = inputs_lib.input_specs(cfg, shape, mesh)
        step = steps_lib.make_prefill_step(cfg)
        return step, (params, batch)
    # decode
    state, tokens, pos = inputs_lib.input_specs(cfg, shape, mesh)
    step = steps_lib.make_decode_step(cfg)
    return step, (params, state, tokens, pos)


def run_cell(arch: str, shape_name: str, mesh_kind: str, variant: str,
             out_dir: str, force: bool = False) -> dict:
    cell_id = f"{arch}__{shape_name}__{mesh_kind}__{variant}"
    out_path = os.path.join(out_dir, cell_id + ".json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    import dataclasses as _dc
    cfg = get_config(arch)
    if variant == "opt":
        # beyond-paper perf variant: bisection-causal attention (halves the
        # S^2 attention FLOPs), dots-saveable remat (no re-forward), flash
        # kernels assumed for the memory model (§Perf)
        cfg = _dc.replace(cfg, attn_impl="bisect", remat_policy="dots")
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "variant": variant, "status": "unknown"}
    reason = _skip_reason(cfg, shape)
    if reason:
        rec.update(status="skipped", reason=reason)
        _write(out_path, rec)
        return rec

    try:
        t0 = time.time()
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        n_chips = mesh.devices.size
        fn, args = build_cell(cfg, shape, mesh, variant)
        # donate mutable state (params/opt for train, KV cache for decode) —
        # production aliasing; otherwise memory doubles
        donate = {"train": (0, 1), "decode": (1,),
                  "prefill": ()}[shape.kind]
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        analysis = hlo_analysis.analyze_hlo(hlo)
        coll = analysis["collectives"]
        flops_per_chip = analysis["dot_flops"]
        hbm = cost_model.hbm_bytes(cfg, shape, n_chips, variant=variant,
                                   flash=(variant == "opt"))
        mf = model_flops(cfg, shape)
        roof = hlo_analysis.roofline(flops_per_chip, hbm["total"], coll,
                                     n_chips, mf,
                                     ew_flops=analysis["elementwise_flops"])
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            n_chips=n_chips,
            memory=_mem_dict(mem, hlo),
            xla_cost={k: cost.get(k) for k in
                      ("flops", "bytes accessed", "optimal_seconds")
                      if k in cost},
            hbm_model=hbm,
            collectives=coll,
            elementwise_flops=analysis["elementwise_flops"],
            roofline=roof.as_dict(),
        )
        print(f"[ok] {cell_id}: compile={t_compile:.0f}s "
              f"argbytes/dev={rec['memory'].get('argument_size_gb', '?')}GB "
              f"bottleneck={roof.bottleneck} mfu_bound={roof.mfu_bound:.3f}",
              flush=True)
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        print(f"[ERR] {cell_id}: {type(e).__name__}: {e}", flush=True)
    _write(out_path, rec)
    return rec


_UPCAST_RE = None


def _cpu_upcast_bytes(hlo: str) -> int:
    """Bytes of large f32 tensors produced by bf16->f32 `convert` ops.

    The CPU backend upcasts bf16 dot operands to f32 (TPU computes bf16
    natively), and hoists loop-invariant converts of whole weight stacks /
    KV caches out of while loops — inflating measured temp. We report those
    separately so the fits-16GB verdict reflects the TPU target.
    """
    import re
    # Pairing heuristic: every large f32[dims] tensor whose bf16[dims] twin
    # also exists in the module is (with overwhelming likelihood for this
    # codebase — all activations/weights are declared bf16) a CPU-backend
    # upcast: hoisted weight converts, loop-carried remat stacks, KV-cache
    # copies. Each unique shape is counted once (the resident copy).
    f32_shapes, bf16_shapes = set(), set()
    for m in re.finditer(r"(f32|bf16)\[([\d,]+)\]", hlo):
        (f32_shapes if m.group(1) == "f32" else bf16_shapes).add(m.group(2))
    total = 0
    for dims in f32_shapes & bf16_shapes:
        n = 4
        for d in dims.split(","):
            n *= int(d)
        if n >= 2**27:  # only large (>=128MB) copies matter
            total += n
    return total


def _mem_dict(mem, hlo: str = "") -> dict:
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes"):
        v = getattr(mem, attr, None)
        if v is not None:
            out[attr] = int(v)
    gb = 1024**3
    if "argument_size_in_bytes" in out:
        out["argument_size_gb"] = round(out["argument_size_in_bytes"] / gb, 2)
    if "temp_size_in_bytes" in out:
        out["temp_size_gb"] = round(out["temp_size_in_bytes"] / gb, 2)
    total = (out.get("argument_size_in_bytes", 0)
             + out.get("output_size_in_bytes", 0)
             + out.get("temp_size_in_bytes", 0)
             - out.get("alias_size_in_bytes", 0))
    out["total_gb"] = round(total / gb, 2)
    upcast = _cpu_upcast_bytes(hlo) if hlo else 0
    out["cpu_upcast_artifact_gb"] = round(upcast / gb, 2)
    adj = total - upcast
    out["tpu_adjusted_total_gb"] = round(adj / gb, 2)
    out["fits_16gb"] = bool(adj <= 16 * 1024**3)
    return out


def _write(path, rec):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default=None, choices=["single", "multi"])
    ap.add_argument("--variant", default="mbprox",
                    choices=["mbprox", "baseline", "opt"])
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]

    results = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                if SHAPES[shape].kind == "train":
                    variant = args.variant
                else:
                    variant = "opt" if args.variant == "opt" else "serve"
                results.append(run_cell(arch, shape, mesh_kind, variant,
                                        args.out, force=args.force))
    ok = sum(r["status"] == "ok" for r in results)
    sk = sum(r["status"] == "skipped" for r in results)
    err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run: {ok} ok, {sk} skipped, {err} errors "
          f"/ {len(results)} cells")
    return 1 if err else 0


if __name__ == "__main__":
    raise SystemExit(main())
