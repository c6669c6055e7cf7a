"""The reduction of the program's own `serve.*` spans: self time, idle gaps
by the innermost span open in them, the decode read-back's tail and the
scheduler's time per step, checked on hand-made events, on a trace recorded
on a TPU v5e before the program had spans, and on a CPU trace."""
import os

import pytest

import spans

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
PROGRAMS = {"decode": "jit__decode", "prefill": "jit__prefill",
            "first": "jit__first"}


def _host():
    """Two engine steps as (name, start, duration), in seconds: the first
    admits a prompt and has a collection inside `consume`; between them
    the host is in no span."""
    ev = [
        ("serve.step", 0.0, 9.5),
        ("serve.scheduler.admit", 0.0, 3.0),
        ("serve.runner.prefill", 1.0, 1.5),
        ("serve.runner.prefill.stage", 1.0, 0.2),
        ("serve.runner.prefill.fetch", 2.0, 0.5),
        ("serve.scheduler.prepare_decode", 3.0, 0.5),
        ("serve.runner.decode", 3.5, 3.5),
        ("serve.runner.decode.stage", 3.5, 0.5),
        ("serve.runner.decode.fetch", 4.5, 2.5),
        ("serve.scheduler.consume", 7.0, 2.0),
        ("serve.host.gc", 8.0, 0.5),
        ("serve.step", 10.5, 9.5),
        ("serve.scheduler.prepare_decode", 10.5, 1.0),
        ("serve.runner.decode", 11.5, 4.5),
        ("serve.runner.decode.stage", 11.5, 0.5),
        ("serve.runner.decode.fetch", 12.5, 3.5),
        ("serve.scheduler.consume", 16.0, 2.5),
        # the harness's own spans are not the program's
        ("bench.runner.decode", 3.4, 3.7),
        ("bench.scheduler.consume", 9.0, 2.0),
    ]
    return sorted(ev, key=lambda e: e[1])


def _dev():
    return {"modules": [("jit__prefill(1)", 1.5, 0.7),
                        ("jit__decode(1)", 4.2, 2.6),
                        ("jit__decode(1)", 13.0, 2.5)],
            "ops": []}


@pytest.fixture
def red():
    return spans.reduce(_host(), _dev(), PROGRAMS)


def test_self_time_less_nested_spans(red):
    sp = red["spans"]
    assert set(sp) == {n for n, _, _ in _host() if n.startswith("serve.")}
    assert sp["serve.step"]["n"] == 2
    assert sp["serve.step"]["total_s"] == pytest.approx(19.0)
    assert sp["serve.step"]["self_s"] == pytest.approx(0.5 + 1.5)
    assert sp["serve.scheduler.admit"]["self_s"] == pytest.approx(1.5)
    assert sp["serve.runner.prefill"]["self_s"] == pytest.approx(0.8)
    assert sp["serve.runner.decode"]["self_s"] == pytest.approx(0.5 + 0.5)
    assert sp["serve.scheduler.consume"]["self_s"] == pytest.approx(
        1.5 + 2.5)
    assert sp["serve.host.gc"]["self_s"] == pytest.approx(0.5)


def test_idle_gaps_by_innermost_span(red):
    idle = red["idle"]
    # gaps [2.2, 4.2] (middle in prepare_decode) and [6.8, 13.0] (middle
    # 9.9, between the steps)
    assert idle["n"] == 2
    assert idle["total_s"] == pytest.approx(8.2)
    assert idle["by_span"] == {
        "serve.scheduler.prepare_decode": pytest.approx(2.0)}
    assert idle["unspanned_s"] == pytest.approx(6.2)
    want = {"serve.runner.prefill.fetch": 0.3, "serve.scheduler.admit": 0.5,
            "serve.scheduler.prepare_decode": 1.5,
            "serve.runner.decode.stage": 1.0, "serve.runner.decode": 0.7,
            "serve.runner.decode.fetch": 0.7, "serve.scheduler.consume": 1.5,
            "serve.host.gc": 0.5, "serve.step": 0.5, "unspanned": 1.0}
    assert red["idle_split"] == pytest.approx(want)
    assert sum(red["idle_split"].values()) == pytest.approx(idle["total_s"])


def test_fetch_tail_stage_and_scheduler_per_step(red):
    assert red["fetch_tail_s"] == pytest.approx([0.2, 0.5])
    assert red["stage_s"]["decode"] == pytest.approx([0.5, 0.5])
    assert red["stage_s"]["prefill"] == pytest.approx([0.2])
    assert red["stage_s"]["verify"] == []
    # step 1: admit less its prefill, prepare_decode, consume (the
    # collection inside it counts); step 2: prepare_decode and consume
    assert red["sched_s"] == pytest.approx([1.5 + 0.5 + 2.0, 1.0 + 2.5])
    assert red["decode_intervals"] == 1


def test_readings(red):
    assert spans.readings(red) == pytest.approx({
        "host_stage_ms.decode": 500.0, "host_fetch_ms.decode": 350.0,
        "host_sched_ms.step": 3750.0,
        "idle_unspanned.serve": 100.0 * 6.2 / 8.2})
    assert spans.readings({}) == {}


def test_recorded_v5e_trace_has_no_program_spans():
    """The trace recorded before the program had spans: every idle gap is
    unspanned, and the readings stay silent rather than report it."""
    s = spans.summarize(os.path.join(DATA, "decode_prefill.xplane.pb.gz"),
                        PROGRAMS, (1.0, 1.5))
    assert s["spans"] == {}
    assert s["idle"]["n"] > 0
    assert s["idle"]["unspanned_s"] == pytest.approx(s["idle"]["total_s"])
    assert s["idle_split"] == pytest.approx(
        {"unspanned": s["idle"]["total_s"]})
    assert s["fetch_tail_s"] == [] and s["sched_s"] == []
    assert s["decode_intervals"] == 5
    assert s["window_s"] == pytest.approx(0.5)
    assert spans.readings(s) == {}


def test_trace_without_a_tpu_plane(tmp_path, capsys):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4)).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("serve.step"):
            f(jnp.ones(4)).block_until_ready()
    assert spans.summarize(str(tmp_path), PROGRAMS, (0.0, 1.0)) == {}
    spans.main([str(tmp_path)])
    assert capsys.readouterr().out.strip() == "{}"
