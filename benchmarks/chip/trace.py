"""Reduction of a `jax.profiler` trace to the numbers the per-layer metrics
read: device busy and idle time, device time per jitted program, all-reduce
time, the device operations that took most time, and the longest idle gaps,
each labelled by the harness's host span that was open in its middle.

The trace is read with `jax.profiler.ProfileData` alone. A device is a plane
named `/device:TPU:<n>`; its line `XLA Modules` holds one event per program
execution, named after the jitted function (`jit__decode(...)`), and its line
`XLA Ops` one event per operation. Host spans are events on the `/host:CPU`
plane. Every time here is in seconds.
"""
from __future__ import annotations

import glob
import gzip
import os
import shutil
import tempfile

MODULES, OPS = "XLA Modules", "XLA Ops"


def start(scratch: str) -> str:
    """Starts a trace into a new directory under `scratch`. Python's own
    function calls are not traced (they would slow the host several-fold);
    `TraceAnnotation` spans are."""
    import jax
    d = tempfile.mkdtemp(prefix="trace-", dir=scratch)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    return d


def remove(d: str) -> None:
    shutil.rmtree(d, ignore_errors=True)


def xplane_file(d: str) -> str:
    found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {d}")
    return max(found, key=os.path.getmtime)


def op_name(name: str) -> str:
    """An operation's HLO text shortened to its name and result shape, as
    in `%fusion.245 = bf16[4096,16,3,64]`."""
    head, _, rest = name.partition(" = ")
    return f"{head} = {rest.split(' ', 1)[0]}" if rest else head


def _events(line):
    return [(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
            for e in line.events]


def read(path: str) -> dict:
    """{"devices": {plane: {"modules": [...], "ops": [...]}}, "host": [...]}
    with events as (name, start_s, duration_s), sorted by start."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = xplane_file(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == MODULES:
                    dev["modules"] += _events(line)
                elif line.name == OPS:
                    dev["ops"] += _events(line)
            out["devices"][plane.name] = {k: sorted(v, key=lambda e: e[1])
                                          for k, v in dev.items()}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                out["host"] += _events(line)
    out["host"].sort(key=lambda e: e[1])
    return out


def _union(intervals):
    """Merged (start, end) intervals of [(start, dur)] sorted by start."""
    merged = []
    for s, d in intervals:
        e = s + d
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(host, a: float, b: float, prefix: str) -> str:
    """The innermost harness span open at the gap's middle."""
    mid = (a + b) / 2
    best = None
    for name, s, d in host:
        if s > mid:
            break
        if name.startswith(prefix) and s <= mid <= s + d:
            if best is None or d < best[1]:
                best = (name, d)
    return best[0] if best else "outside the harness's spans"


def summarize_device(dev: dict, programs: dict, host: list,
                     span_prefix: str) -> dict:
    mods = dev["modules"]
    merged = _union([(s, d) for _, s, d in mods])
    busy = sum(e - s for s, e in merged)
    prog_s, prog_n, runs = {}, {}, {}
    for key, pre in programs.items():
        hits = [(s, d) for n, s, d in mods if n.startswith(pre)]
        prog_s[key] = sum(d for _, d in hits)
        prog_n[key] = len(hits)
        runs[key] = hits
    gaps = [(merged[i][1], merged[i + 1][0])
            for i in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    ops = {}
    allreduce = 0.0
    for n, s, d in dev["ops"]:
        if " while(" in n or " conditional(" in n:
            continue          # a loop's or branch's own ops are listed
        short = op_name(n)
        ops[short] = ops.get(short, 0.0) + d
        if "all-reduce" in short:
            allreduce += d
    top = sorted(ops.items(), key=lambda kv: kv[1], reverse=True)[:10]
    span = (merged[-1][1] - merged[0][0]) if merged else 0.0
    return {
        "busy_s": busy, "span_s": span,
        "program_s": prog_s, "program_n": prog_n,
        "allreduce_s": allreduce,
        "device_ops": [[n, v] for n, v in top],
        "idle_gaps": [[_label(host, a, b, span_prefix), b - a]
                      for a, b in gaps[:10]],
        "between": _between(runs, merged),
    }


def _between(runs: dict, merged: list) -> dict:
    """For each program, the device-idle time between consecutive runs of
    it: the time from one run's end to the next one's start, less what
    other programs kept the device busy in between."""
    out = {}
    for key, hits in runs.items():
        idle = []
        for (s0, d0), (s1, _) in zip(hits, hits[1:]):
            a, b = s0 + d0, s1
            busy = sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged)
            idle.append(max(0.0, (b - a) - busy))
        out[key] = idle
    return out


def summarize(path: str, programs: dict, span_prefix: str = "bench.",
              devices: int | None = None) -> dict:
    """The trace reduced for the metrics. Busy time is averaged over the
    devices traced (or the first `devices`); program times, all-reduce
    time, gaps and top operations are those of the first device."""
    tr = read(path)
    names = sorted(tr["devices"], key=lambda n: int(n.rsplit(":", 1)[1]))
    if devices:
        names = names[:devices]
    if not names:
        raise ValueError("the trace holds no TPU device plane")
    per = [summarize_device(tr["devices"][n], programs, tr["host"],
                            span_prefix) for n in names]
    first = per[0]
    first["busy_s"] = sum(p["busy_s"] for p in per) / len(per)
    first["devices"] = len(per)
    return first
