"""One run of one benchmark cell on the chip.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell is the `workloads` entry of
`BENCHMARK.json` with that name; its configuration, traffic and cell files
are found by name under `benchmarks/chip/`, and its driver by the cell
file's `driver`. The run exits nonzero, and prints no result, when JAX finds
no TPU or fewer chips than the cell asks for.

The last stdout line is one JSON object: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with `--trace 1` its per-layer
metrics), `device`, with `--trace 1` `breakdown`, and last `checks`, each
compared number with its limit (also the last lines of stderr).
"""
from __future__ import annotations

import time

T0 = time.perf_counter()          # set-up is timed from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)

import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def devices_or_exit(chips: int):
    """The chips this cell runs on; exits when JAX finds no TPU or too few."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"run.py: JAX found no TPU (platform {devs[0].platform!r});"
                 f" the benchmark has no CPU fallback")
    if len(devs) < chips:
        sys.exit(f"run.py: the cell needs {chips} chips, JAX found "
                 f"{len(devs)}")
    return devs[:chips]


def context(args, devices, log) -> dict:
    """What a driver needs: the cell, its configuration and traffic by name,
    the run's arguments, and where it may write."""
    bench = common.benchmark()
    wl = common.workload(args.workload, bench)
    cell = wl["cell"]
    scratch = tempfile.mkdtemp(prefix="chipbench-")
    return {
        "bench": bench, "workload": wl, "cell": cell,
        "config": common.config(wl["config"]),
        "traffic": common.traffic(wl["traffic"]),
        "seed": args.seed, "seconds": args.seconds, "trace": bool(args.trace),
        "trace_seconds": cell.get("trace_seconds", 3.0),
        "devices": devices, "log": log, "scratch": scratch,
        "t0": T0,
        "memory": lambda: common.device_record(devices),
    }


def driver(name: str):
    import importlib
    return importlib.import_module(f"drivers.{name}")


def run_cell(ctx: dict) -> dict:
    """Drives the cell once and returns the result line's object."""
    import shutil

    cell = ctx["cell"]
    drv = driver(cell["driver"])
    try:
        rec = drv.run(ctx)
    finally:
        shutil.rmtree(ctx["scratch"], ignore_errors=True)
    return result(ctx, rec)


def result(ctx: dict, rec: dict) -> dict:
    spec = ctx["config"]["model"]
    rec["spec"] = spec
    dev = rec["memory"]
    rec["peak"] = (common.peaks(dev["kind"]) if dev["platform"] == "tpu"
                   else None)
    metrics = {}
    for m in common.metrics_for(ctx["bench"], ctx["workload"]["name"],
                                ctx["trace"]):
        v = common.load_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    checks = rec["checks"]
    out = {"correct": common.checks_ok(checks)
           and rec.get("compiles_in_window", 0) == 0,
           "attempted": rec["attempted"], "failed": rec["failed"],
           "metrics": metrics, "device": dict(dev)}
    tr = rec.get("trace")
    if tr is not None:
        out["device"]["busy_s"] = tr["busy_s"]
        out["device"]["window_s"] = tr["window_s"]
        out["breakdown"] = {"device_ops": tr["device_ops"],
                            "idle_gaps": tr["idle_gaps"]}
    out["checks"] = checks
    return out


def main(argv=None):
    args = parse(argv)
    bench = common.benchmark()
    wl = common.workload(args.workload, bench)
    devices = devices_or_exit(int(wl["chips"]))
    sys.path.insert(0, os.path.join(str(common.ROOT), "src"))
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # every program, however quick to compile, goes to the cache, so that
    # only a cell's first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log = common.CompileLog()
    d = devices[0]
    print(f"[device] {d.platform} {d.device_kind} x {len(devices)}; "
          f"compile cache {cache}", file=sys.stderr, flush=True)
    ctx = context(args, devices, log)
    common.emit(run_cell(ctx))


if __name__ == "__main__":
    main()
