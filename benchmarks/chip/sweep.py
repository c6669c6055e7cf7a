"""Finds a serving cell's knee once, on the chip: the cell's engine is set
up once, then driven for a window at each offered rate in turn.

    python3 benchmarks/chip/sweep.py --workload smollm-chat --seed 5 \
        --seconds 10 --rates 10 20 30 40 50 60

For each rate it prints the requests sent and completed, the tokens per
second emitted in the window, and the medians and 95th percentiles of time
to first token and time per output token, and the output tokens per second
offered (those of the requests due in the window). The knee is the highest
rate whose tokens per second still follow the offered load and whose time
to first token has not begun to grow with the backlog. The sweep stops at
the first rate served at under 80% of its offered load.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import common  # noqa: E402
import run as run_lib  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = common.benchmark()
    wl = common.workload(args.workload, bench)
    devices = run_lib.devices_or_exit(int(wl["chips"]))
    sys.path.insert(0, os.path.join(str(common.ROOT), "src"))
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serving import ServingEngine

    import drivers.serve as serve
    import traffic as traffic_lib
    import weights as weights_lib
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    ctx = run_lib.context(run_lib.parse([
        "--workload", args.workload, "--seed", str(args.seed),
        "--seconds", str(args.seconds)]), devices, common.CompileLog())
    spec = ctx["config"]["model"]
    params = weights_lib.make(spec, args.seed)
    engine = ServingEngine(params, serve.model_config(spec),
                           **ctx["cell"]["engine"])
    serve.warm_up(engine, ctx["traffic"])
    pool = engine.allocator.num_blocks - 1
    for rate in args.rates:
        mix = dict(ctx["traffic"], rate=rate)
        reqs = traffic_lib.requests(mix, args.seed, traffic_lib.count_for(
            mix, args.seconds), spec["vocab_size"])
        st = serve.window(engine, reqs, mix, args.seconds, False, ctx, pool)
        rec = st["rec"]
        offered = sum(r["max_new"] for r in reqs
                      if r["arrival"] < args.seconds) / args.seconds
        row = {"rate": rate, "sent": rec["attempted"],
               "failed": rec["failed"], "offered_tok_s": offered,
               "output_tok_s": rec["tokens_in_window"] / args.seconds}
        for key, vals in (("ttft_ms", rec["ttft_s"]),
                          ("tpot_ms", rec["tpot_s"])):
            row[key + "_p50"] = float(np.percentile(vals, 50)) * 1e3
            row[key + "_p95"] = float(np.percentile(vals, 95)) * 1e3
        row["lane_occupancy"] = rec["lane_steps"] / max(
            rec["steps"] * rec["num_slots"], 1)
        print(json.dumps(row), flush=True)
        if row["output_tok_s"] < 0.8 * offered:
            break


if __name__ == "__main__":
    main()
