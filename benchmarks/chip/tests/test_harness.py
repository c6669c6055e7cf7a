"""The harness end to end at a size a test holds, on the CPU, with the
chip check skipped: sound runs come out correct, and runs with the timed
path broken underneath come out not correct."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import cells
import common
import run

ROOT = common.ROOT


def test_exits_nonzero_off_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "smollm-chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


def test_every_cell_and_metric_finds_its_files():
    bench = common.benchmark()
    for w in bench["workloads"]:
        wl = common.workload(w["name"], bench)
        assert wl["cell"]["driver"] in ("serve", "train")
        common.config(w["config"])
        common.traffic(w["traffic"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(common.load_reader(m["name"]))
    for c in bench["configs"]:
        conf = common.load_json(ROOT / c["file"])
        assert conf["source"] == c["source"]
        assert sorted(conf["reduced"]) == sorted(c["reduced"])


@pytest.mark.parametrize("loop", ["open", "closed_chunked"])
def test_sound_run_is_correct(loop):
    if loop == "open":
        ctx = cells.serve_ctx()
    else:
        ctx = cells.serve_ctx(mix=cells.CLOSED, engine=dict(
            cells.ENGINE, prefill_chunk=32))
    out = run.run_cell(ctx)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 5
    assert set(out["metrics"]) == {"output_tok_s", "ttft_p95_ms",
                                   "tpot_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    json.dumps(out)


def test_long_closed_loop_prefills_in_chunks(monkeypatch):
    """smollm2-code's shape: multi-head, weights in bfloat16, a closed loop
    whose prompts run past `prefill_chunk`, on a pool that cannot hold every
    lane at its longest. Warm-up covers every chunked-prefill variant, so
    nothing compiles after it, in the priming steps or the window, and what
    was served passes the reference check at tight limits."""
    import drivers.serve as serve
    from repro.serving.runner import ModelRunner
    ctx = cells.serve_ctx(mix=cells.LONG_CLOSED, engine=cells.LONG_ENGINE,
                          model=cells.MHA, seed=2**33 + 3)
    seen = {"resumed": 0}
    inner_prefill, inner_warm, inner_check = (ModelRunner.prefill,
                                              serve.warm_up, serve.check)

    def prefill(self, rows, resume=False, **kw):
        seen["resumed"] += bool(resume)
        return inner_prefill(self, rows, resume=resume, **kw)

    def warm_up(*a):
        n = inner_warm(*a)
        seen["warm"] = ctx["log"].compiles
        return n

    def check(*a, **k):
        seen["served"] = ctx["log"].compiles
        return inner_check(*a, **k)

    monkeypatch.setattr(ModelRunner, "prefill", prefill)
    monkeypatch.setattr(serve, "warm_up", warm_up)
    monkeypatch.setattr(serve, "check", check)
    out = run.run_cell(ctx)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 5
    assert seen["resumed"] > 0
    assert seen["served"] == seen["warm"]


def test_traced_counters_without_a_device_trace():
    """lane_occupancy and kv_pool_use come from the program's counters, so
    they read on the CPU too; the trace's metrics stay silent there."""
    import drivers.serve as serve
    ctx = cells.serve_ctx()
    ctx["trace"] = True
    orig = serve.trace_lib.summarize
    serve.trace_lib.summarize = lambda *a, **k: {}
    try:
        rec = serve.run(ctx)
    finally:
        serve.trace_lib.summarize = orig
    rec.pop("trace", None)
    rec.pop("trace_span", None)
    rec["counts"] = serve.layer_counts(rec, ctx["config"]["model"])
    out = run.result(ctx, rec)
    assert 0 < out["metrics"]["lane_occupancy"]["value"] <= 100
    assert 0 < out["metrics"]["kv_pool_use"]["value"] <= 100


def _altered_token(monkeypatch):
    from repro.serving.runner import ModelRunner
    inner = ModelRunner.decode
    calls = [0]

    def decode(self, tokens, positions):
        tok, lp, alt = inner(self, tokens, positions)
        calls[0] += 1
        if calls[0] % 3 == 0:
            tok = tok.copy()
            tok[:] = (tok + 1) % self.cfg.vocab_size
        return tok, lp, alt

    monkeypatch.setattr(ModelRunner, "decode", decode)


def _state_unchanged(monkeypatch):
    from repro.models import lm
    inner = lm.decode_step_paged

    def decode_step_paged(params, cfg, state, *a):
        logits, _ = inner(params, cfg, state, *a)
        return logits, state

    monkeypatch.setattr(lm, "decode_step_paged", decode_step_paged)


def _prefill_state_unchanged(monkeypatch):
    from repro.models import lm
    inner = lm.prefill_paged

    def prefill_paged(params, cfg, state, *a, **k):
        last, _ = inner(params, cfg, state, *a, **k)
        return last, state

    monkeypatch.setattr(lm, "prefill_paged", prefill_paged)


SHAPES = {
    "chat": lambda seed: cells.serve_ctx(seed=seed),
    "code": lambda seed: cells.serve_ctx(
        mix=cells.LONG_CLOSED, engine=cells.LONG_ENGINE,
        model=cells.MHA, seed=seed),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("fault", ["altered_token", "decode_state_unchanged",
                                   "prefill_state_unchanged"])
def test_broken_timed_path_is_not_correct(fault, shape, monkeypatch):
    {"altered_token": _altered_token,
     "decode_state_unchanged": _state_unchanged,
     "prefill_state_unchanged": _prefill_state_unchanged}[fault](monkeypatch)
    out = run.run_cell(SHAPES[shape](11))
    assert not out["correct"]
    assert max(c["value"] for c in out["checks"].values()) > 1e-2


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_control_is_not_correct(shape):
    """The control, the fp8 reference put in the program's place, fails the
    limits that a sound run of the same tiny cell passes."""
    import drivers.serve as serve
    ctx = SHAPES[shape](5)
    b = serve.build(ctx, 5)
    reqs = serve.cell_requests(ctx, 5, ctx["seconds"])
    st = serve.window(b["engine"], reqs, ctx["traffic"], ctx["seconds"],
                      False, ctx, b["pool_blocks"])
    got = serve.check(b["params"], b["spec"], ctx["cell"], reqs,
                      st["completions"], 5, control=True)
    assert common.checks_ok(got["checks"])
    r, lim = got["readings"], ctx["cell"]["check"]["limits"]
    assert r["control_gap_max"] > lim["gap_max"] \
        or r["control_lp_err_max"] > lim["lp_err_max"]
