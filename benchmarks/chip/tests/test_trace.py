"""The trace reduction: busy and idle time, per-program time, the idle time
between runs of a program, all-reduce time and the labelled gaps, checked
on hand-made events and on a trace recorded on a TPU v5e."""
import os

import pytest

import trace as trace_lib

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROGRAMS = {"decode": "jit__decode", "prefill": "jit__prefill"}


def _dev():
    # three decodes with a prefill between the last two; times in seconds
    mods = [("jit__decode(1)", 0.0, 2.0), ("jit__decode(1)", 3.0, 2.0),
            ("jit__prefill(2)", 6.0, 1.0), ("jit__decode(1)", 8.0, 2.0)]
    ops = [("fusion.1", 0.0, 1.5), ("all-reduce.3", 1.5, 0.25),
           ("fusion.1", 3.0, 1.5), ("convolution.2", 6.0, 1.0)]
    return {"modules": mods, "ops": ops}


def test_busy_programs_and_gaps_by_hand():
    host = [("bench.scheduler.admit", 5.0, 1.2),
            ("bench.runner.decode", 7.5, 3.0),
            ("bench.step", 0.0, 11.0)]
    s = trace_lib.summarize_device(_dev(), PROGRAMS, host, "bench.")
    assert s["busy_s"] == pytest.approx(7.0)
    assert s["span_s"] == pytest.approx(10.0)
    assert s["program_s"] == {"decode": 6.0, "prefill": 1.0}
    assert s["program_n"] == {"decode": 3, "prefill": 1}
    assert s["allreduce_s"] == pytest.approx(0.25)
    # idle between decodes: 1.0, then 3.0 less the prefill's 1.0
    assert s["between"]["decode"] == pytest.approx([1.0, 2.0])
    gaps = dict((round(d, 6), name) for name, d in s["idle_gaps"])
    assert gaps[1.0] in ("bench.scheduler.admit", "bench.runner.decode",
                         "bench.step")
    assert sorted(round(d, 6) for _, d in s["idle_gaps"]) == [1.0, 1.0, 1.0]
    assert gaps[1.0]
    assert s["device_ops"][0] == ["fusion.1", 3.0]


def test_gap_label_is_the_innermost_open_span():
    host = [("bench.step", 0.0, 10.0), ("bench.scheduler.consume", 2.1, 0.8),
            ("other", 2.0, 1.0)]
    assert trace_lib._label(host, 2.0, 3.0, "bench.") == \
        "bench.scheduler.consume"
    assert trace_lib._label(host, 10.5, 11.0, "bench.") == \
        "outside the harness's spans"


def test_recorded_v5e_trace():
    """A trace recorded on a TPU v5e by the serving driver's calls: six
    decode steps of smollm-135m at 64 lanes, with three prefills of four
    new prompts, under the harness's `bench.step` span."""
    s = trace_lib.summarize(os.path.join(DATA, "decode_prefill.xplane.pb.gz"),
                            {"decode": "jit__decode",
                             "prefill": "jit__prefill",
                             "first": "jit__first"})
    assert s["devices"] == 1
    assert s["program_n"] == {"decode": 6, "prefill": 3, "first": 3}
    assert 0.25 < s["program_s"]["decode"] < 0.35       # ~51 ms each
    assert 0 < s["busy_s"] <= s["span_s"]
    assert s["busy_s"] >= sum(s["program_s"].values()) - 1e-9
    assert len(s["between"]["decode"]) == 5
    assert all(0 <= g < 0.05 for g in s["between"]["decode"])
    assert s["allreduce_s"] == 0.0
    names = [n for n, _ in s["device_ops"]]
    assert all(" = " in n and len(n) < 120 for n in names)
    assert not any("while" in n for n in names)
    assert all(lbl == "bench.step" or lbl.startswith("outside")
               for lbl, _ in s["idle_gaps"])
