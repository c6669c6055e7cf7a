"""Readings for a cell's limits, on the chip: for each seed, the numbers a
sound run of the program gives, and the same numbers for the control, the
fp8 reference put in the program's place.

    python3 benchmarks/chip/control.py --workload smollm-chat \
        --seconds 10 --seeds 101 102 103 ...
    python3 benchmarks/chip/control.py --workload smollm-mbprox-dp4 \
        --seeds 101 102 103 [--faults]

One process. Serving: the engine is built and warmed up once; for each seed
the weights are made anew from it and handed to the same compiled programs,
a window of `--seconds` at the cell's own load is served, and the sample
that a run of `run.py` would compare is compared, with the control beside
it. Training: for each seed the checked steps of a run are taken (no
window) and compared with the reference; the control, and with `--faults`
the reference with half of each microbatch left out and with the exchange
between chips left out, are each compared as if they were the program.
Prints one JSON line per seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import common  # noqa: E402
import run as run_lib  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    bench = common.benchmark()
    wl = common.workload(args.workload, bench)
    devices = run_lib.devices_or_exit(int(wl["chips"]))
    sys.path.insert(0, os.path.join(str(common.ROOT), "src"))
    import jax
    from repro.runtime.compile_cache import enable_compile_cache

    import drivers.serve as serve
    import weights as weights_lib
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx = run_lib.context(run_lib.parse([
        "--workload", args.workload, "--seed", str(args.seeds[0]),
        "--seconds", str(args.seconds)]), devices, common.CompileLog())
    if ctx["cell"]["driver"] == "train":
        return train_readings(ctx, args.seeds, args.faults)
    b = serve.build(ctx, args.seeds[0])
    engine, params = b.pop("engine"), b.pop("params")
    for seed in args.seeds:
        if seed != args.seeds[0]:
            # free the last seed's weights before making the next ones
            engine.runner.params = params = None
            params = weights_lib.make(b["spec"], seed)
        engine.runner.params = params
        engine.reset_prefix_cache()
        reqs = serve.cell_requests(ctx, seed, args.seconds)
        st = serve.window(engine, reqs, ctx["traffic"], args.seconds, False,
                          ctx, b["pool_blocks"])
        got = serve.check(params, b["spec"], ctx["cell"], reqs,
                          st["completions"], seed, control=True)
        print(json.dumps({"seed": seed, "failed": st["rec"]["failed"],
                          **got["readings"]}), flush=True)


def train_readings(ctx: dict, seeds, faults: bool) -> None:
    import drivers.train as train
    b = train.build(ctx)
    chk = ctx["cell"]["check"]
    variants = {"control": {"quant": "fp8"}}
    if faults:
        variants.update(half_batch={"half_batch": True},
                        no_exchange={"no_exchange": True})
    for seed in seeds:
        c = train.checked(ctx, b, seed, train.CHECKED_STEPS)
        del c["p"], c["state"], c["batches"]
        args = (c["params0"], c["toks"], c["tgts"], b["spec"], b["mix"],
                b["mp"], b["devices"])
        ref = train.reference_readings(*args)
        row = {"seed": seed, "program": train.compare(
            chk, c["losses"], c["state1"], c["change3"], ref, values=True)}
        for name, fault in variants.items():
            v = train.reference_readings(*args, **fault)
            row[name] = train.compare(chk, v["losses"], v["state1"],
                                      v["change3"], ref, values=True)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
