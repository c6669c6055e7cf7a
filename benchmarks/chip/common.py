"""What every part of the chip benchmark shares: where its files are, how a
name in `BENCHMARK.json` or a configuration finds its file, the compile
counter, the device record and the result line.

Nothing here imports the program under test (`src/repro`); the drivers do.
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]            # the checkout: BENCHMARK.json, src/
ARCH_DIR = HERE / "arch"
_ARCHS: dict = {}


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def workload(name: str, bench: dict | None = None) -> dict:
    """The `workloads` entry called `name`, with its cell file merged in."""
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return {**w, "cell": load_json(HERE / "cells" / f"{name}.json")}
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    return load_json(HERE / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(HERE / "traffic" / f"{name}.json")


def peaks(device_kind: str) -> dict:
    table = load_json(HERE / "peaks.json")["chips"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"peaks.json; add its published figures")
    return table[device_kind]


def metrics_for(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of `cell` reports: its end-to-end metrics
    with trace off, its per-layer metrics with trace on."""
    out = []
    for m in bench["per_layer" if trace else "end_to_end"]:
        if cell in m.get("workloads", [cell]):
            out.append(m)
    return out


def load_reader(metric: str):
    """`metrics/<metric>.py`'s `read(rec)`: the value, or None when the run
    holds nothing for this metric to read."""
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def arch(spec: dict):
    """The architecture module that a configuration's `model` block names
    with `"arch"` (default "dense"): `arch/<name>.py`. It gives the weight
    tree `shapes(spec)` with each leaf's init std `scale(path, shape)`, the
    plain float32 forward `logits(w, spec, tokens, quant, remat)`, and the
    work counts `param_count`, `matmul_params`, `kv_bytes_per_token`,
    `prefill_flops`, `decode_flops`, `decode_bytes` and
    `train_flops_per_token` (see `arch/dense.py`)."""
    name = spec.get("arch", "dense")
    path = ARCH_DIR / f"{name}.py"
    if path not in _ARCHS:
        if not path.is_file():
            raise SystemExit(f"no architecture {name!r}: {path} not found")
        mod_spec = importlib.util.spec_from_file_location(
            f"chipbench_arch_{name}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _ARCHS[path] = mod
    return _ARCHS[path]


def device_record(devices, trace_summary=None) -> dict:
    d = devices[0]
    rec = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices)}
    peaks_seen = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks_seen.append(int(stats["peak_bytes_in_use"]))
    rec["memory_peak_bytes"] = max(peaks_seen) if peaks_seen else 0
    if trace_summary is not None:
        rec["busy_s"] = trace_summary["busy_s"]
        rec["window_s"] = trace_summary["window_s"]
    return rec


class CompileLog:
    """Counts backend compilations and their seconds through JAX's
    monitoring events, so a compile inside the window shows."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration


def checks_ok(checks: dict) -> bool:
    """Every compared number is finite and within its limit."""
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def emit(result: dict) -> None:
    """The checks as the last lines of stderr, then the result as the last
    line of stdout, with `checks` as its last key."""
    checks = result.pop("checks")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
