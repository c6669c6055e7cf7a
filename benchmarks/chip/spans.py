"""Reduction of a traced serving window to what the program's own spans
say: where the host's time went while the device waited.

The serving stack opens `serve.*` spans in the profiler's own trace
(`repro.serving.observability.SPANS`): the engine step, each scheduler
phase, each runner dispatch with its `stage` (host arrays and uploads) and
`fetch` (blocking read-back) children, and every garbage collection. They
share the device trace's clock. The trace is read with `trace.read`; the
device is the first TPU plane, as in `trace.summarize`. For the traced
window this gives:

  spans         per name: count, total and self time (less the nested
                `serve.*` spans);
  idle          the device's idle gaps between programs: their count and
                total, summed by the innermost `serve.*` span open at each
                gap's middle, and `unspanned_s`, the gaps whose middle lies
                in none;
  idle_split    the same idle time split exactly: each instant of a gap goes
                to the innermost `serve.*` span open then (`unspanned` if
                none);
  stage_s       per runner call (decode, prefill, verify), the durations of
                its `stage` spans;
  fetch_tail_s  for each `serve.runner.decode.fetch`, its end less the end
                of its decode program on the device;
  sched_s       for each `serve.step`, the self time of the scheduler spans
                inside it, less the runner spans nested in them;
  decode_intervals  the number of intervals between consecutive decode
                programs, over which `idle_split` gives a mean per decode.

A trace with no TPU plane (the CPU) gives {}. Every time is in seconds.
`readings` gives the medians of that reduction in ms.

    python benchmarks/chip/spans.py <trace dir or .xplane.pb>

prints the reduction of a trace taken on the chip (for instance by
`repro.launch.serve --profile-dir DIR`) as one JSON object, with its
readings under `readings`.
"""
from __future__ import annotations

import statistics

import trace as trace_lib

PREFIX = "serve."
UNSPANNED = "unspanned"


def _nest(evs: list) -> list:
    """The parent index (or None) of each (name, start, end) event, sorted
    by start with outer spans first: the innermost earlier event that
    holds it whole."""
    parent, stack = [], []
    for i, ev in enumerate(evs):
        while stack and not _holds(evs[stack[-1]], ev):
            stack.pop()
        parent.append(stack[-1] if stack else None)
        stack.append(i)
    return parent


def _holds(outer, ev) -> bool:
    return outer[1] <= ev[1] and ev[2] <= outer[2]


def _innermost(evs: list, t: float):
    """The name of the shortest event open at `t`, or None."""
    best = None
    for name, s, e in evs:
        if s > t:
            break
        if t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else None


def _gaps(dev: dict) -> list:
    """The device's idle gaps between programs, as `trace.py` finds
    them."""
    merged = trace_lib._union([(s, d) for _, s, d in dev["modules"]])
    return [(merged[i][1], merged[i + 1][0])
            for i in range(len(merged) - 1)]


def _split(evs: list, a: float, b: float, out: dict) -> None:
    """Adds the gap [a, b] to `out`, instant by instant, under the
    innermost span open then."""
    inside = [ev for ev in evs if ev[1] < b and ev[2] > a]
    cuts = sorted({a, b} | {t for _, s, e in inside for t in (s, e)
                            if a < t < b})
    for x, y in zip(cuts, cuts[1:]):
        name = _innermost(inside, (x + y) / 2) or UNSPANNED
        out[name] = out.get(name, 0.0) + (y - x)


def reduce(host: list, dev: dict, programs: dict) -> dict:
    """The reduction of one device's events and the host's `serve.*`
    spans; `host` is (name, start, duration) as `trace.read` gives it."""
    evs = sorted(((n, s, s + d) for n, s, d in host
                  if n.startswith(PREFIX)), key=lambda ev: (ev[1], -ev[2]))
    parent = _nest(evs)
    spans = {}
    for name, s, e in evs:
        st = spans.setdefault(name,
                              {"n": 0, "total_s": 0.0, "self_s": 0.0})
        st["n"] += 1
        st["total_s"] += e - s
        st["self_s"] += e - s
    for i, p in enumerate(parent):
        if p is not None:
            spans[evs[p][0]]["self_s"] -= evs[i][2] - evs[i][1]

    gaps = _gaps(dev)
    by_mid, unspanned, split = {}, 0.0, {}
    for a, b in gaps:
        name = _innermost(evs, (a + b) / 2)
        if name is None:
            unspanned += b - a
        else:
            by_mid[name] = by_mid.get(name, 0.0) + (b - a)
        _split(evs, a, b, split)

    stage = {}
    for call in ("decode", "prefill", "verify"):
        key = f"{PREFIX}runner.{call}.stage"
        stage[call] = [e - s for n, s, e in evs if n == key]

    decode = sorted((s, s + d) for n, s, d in dev["modules"]
                    if n.startswith(programs["decode"]))
    tails = []
    for i, (name, s, e) in enumerate(evs):
        if name != f"{PREFIX}runner.decode.fetch":
            continue
        p = parent[i]
        call_start = evs[p][1] if p is not None else s
        ends = [de for ds, de in decode if ds >= call_start and de <= e]
        if ends:
            tails.append(e - ends[-1])

    def outermost(layer):
        """The spans of `layer` not nested in another span of it."""
        pre = f"{PREFIX}{layer}."
        return [ev for ev, p in zip(evs, parent) if ev[0].startswith(pre)
                and (p is None or not evs[p][0].startswith(pre))]

    runner, sched = outermost("runner"), outermost("scheduler")
    sched_s = []
    for step in (ev for ev in evs if ev[0] == f"{PREFIX}step"):
        t = 0.0
        for ev in (ev for ev in sched if _holds(step, ev)):
            t += ev[2] - ev[1] - sum(r[2] - r[1] for r in runner
                                     if _holds(ev, r))
        sched_s.append(t)

    return {
        "spans": spans,
        "idle": {"n": len(gaps), "total_s": sum(b - a for a, b in gaps),
                 "by_span": by_mid, "unspanned_s": unspanned},
        "idle_split": split,
        "stage_s": stage,
        "fetch_tail_s": tails,
        "sched_s": sched_s,
        "decode_intervals": max(0, len(decode) - 1),
    }


def summarize(path: str, programs: dict, window=None) -> dict:
    """The reduction of the trace under `path`, or {} when it holds no TPU
    plane. `window` is the traced part of the run as (start, end) on the
    run's own clock; the trace holds that part alone, so only its length
    is kept, as `window_s`."""
    tr = trace_lib.read(path)
    if not tr["devices"]:
        return {}
    first = min(tr["devices"], key=lambda n: int(n.rsplit(":", 1)[1]))
    out = reduce(tr["host"], tr["devices"][first], programs)
    if window is not None:
        out["window_s"] = window[1] - window[0]
    return out


def readings(red: dict) -> dict:
    """The reduction's medians: `host_stage_ms.decode` (the decode call's
    uploads), `host_fetch_ms.decode` (its read-back after the program
    ended) and `host_sched_ms.step` (the scheduler's own time per step), in
    ms, and `idle_unspanned.serve`, the share of idle time in gaps whose
    middle lies in no `serve.*` span (%). What the trace holds nothing for
    is left out."""
    out = {}
    for key, xs in (("host_stage_ms.decode",
                     red.get("stage_s", {}).get("decode")),
                    ("host_fetch_ms.decode", red.get("fetch_tail_s")),
                    ("host_sched_ms.step", red.get("sched_s"))):
        if xs:
            out[key] = statistics.median(xs) * 1e3
    idle = red.get("idle", {})
    if red.get("spans") and idle.get("total_s"):
        out["idle_unspanned.serve"] = (100.0 * idle["unspanned_s"]
                                       / idle["total_s"])
    return out


def main(argv=None) -> None:
    import argparse
    import json

    from drivers.serve import PROGRAMS

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a trace directory or .xplane.pb(.gz)")
    red = summarize(ap.parse_args(argv).trace, PROGRAMS)
    if red:
        red["readings"] = readings(red)
    print(json.dumps(red))


if __name__ == "__main__":
    main()
