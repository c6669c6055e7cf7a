"""The serving driver: builds `ServingEngine` for a cell, warms up the
shapes its traffic hits, drives `submit` and `step` for the window, and
checks what the window served against the plain reference.

Window rules:
  - Open loop: each request is submitted when it is due and timed from
    then. Closed loop: `clients` requests are in flight from the window's
    start; a client sends its next request when its last one completes.
  - Only requests that arrive inside the window are sent. Those still
    running when it closes are served to the end (at most `DRAIN_S`
    more), so every tail covers every request that arrived in the window.
    One that has not completed by then counts as failed, and its time to
    first token is counted as at least the time it waited.
  - `output_tok_s` counts the tokens emitted inside the window, over the
    window.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import time

import numpy as np

import flops
import reference
import trace as trace_lib
import traffic as traffic_lib
import weights as weights_lib

DRAIN_S = 60.0
# host spans the harness puts around its calls into each layer (trace runs)
SPANS = {"scheduler": ("admit", "prefill_step", "prepare_decode", "consume"),
         "runner": ("prefill", "decode")}
PROGRAMS = {"decode": "jit__decode", "prefill": "jit__prefill",
            "first": "jit__first"}


def model_config(spec: dict):
    """The program's `ModelConfig` for a configuration's `model` block; the
    block's `arch` names the harness's own architecture module and is not
    the program's."""
    from repro.configs.base import ModelConfig
    kw = dict(spec)
    kw.pop("arch", None)
    kw["block_pattern"] = tuple(kw.get("block_pattern", ("attn",)))
    return ModelConfig(**kw)


def _sampling(r: dict):
    from repro.serving import SamplingParams
    return SamplingParams(temperature=r["temperature"], top_p=r["top_p"],
                          seed=r["seed"], max_new_tokens=r["max_new"],
                          logprobs=r["logprobs"])


def _request(r: dict, arrival: float):
    from repro.serving import Request
    return Request(rid=r["rid"], prompt=r["prompt"], arrival=arrival,
                   sampling=_sampling(r))


# ----------------------------------------------------------------------------
# warm-up: exactly the programs this cell's traffic dispatches
# ----------------------------------------------------------------------------

def prefill_variants(runner, mix: dict) -> set:
    """(width, bucket, resume) of every prefill dispatch the mix can cause:
    prompts up to the largest bucket go in groups of up to
    `prefill_max_batch`; longer ones go alone, chunk by chunk."""
    lo, hi = mix["prompt"]["min"], mix["prompt"]["max"]
    top = runner.prefill_buckets[-1]
    out = set()
    direct = {runner.suffix_bucket(n) for n in range(lo, min(hi, top) + 1)}
    for b in direct:
        for w in runner.width_buckets:
            out.add((w, b, False))
    if hi > top:
        chunk = runner.prefill_chunk
        out.add((1, runner.suffix_bucket(chunk), False))
        for n in range(max(lo, top + 1), hi + 1):
            done = chunk
            while n - done > 0:
                out.add((1, runner.suffix_bucket(min(chunk, n - done)), True))
                done += chunk
    return out


def warm_up(engine, mix: dict) -> int:
    """Dispatch every prefill and decode variant the mix will use, writing
    only to the null block. Returns the number of dispatches."""
    from repro.serving.block_manager import NULL_BLOCK
    from repro.serving.runner import PrefillRow

    runner = engine.runner
    every = mix.get("greedy_every", 1)
    do_sample = [False] if every == 1 else [False, True]
    sp_greedy = _sampling({"temperature": 0.0, "top_p": 1.0, "seed": 0,
                           "max_new": 1, "logprobs": mix.get("logprobs", 0)})
    sp_sampled = dataclasses.replace(
        sp_greedy, temperature=mix.get("sampling", {}).get("temperature", 1.0),
        top_p=mix.get("sampling", {}).get("top_p", 1.0))
    null_row = np.full(runner.max_blocks_per_seq, NULL_BLOCK, np.int32)
    n = 0
    for w, b, resume in sorted(prefill_variants(runner, mix)):
        for s in do_sample:
            sp = sp_sampled if s else sp_greedy
            start = b if resume else 0
            rows = [PrefillRow(tokens=np.zeros(start + b, np.int32),
                               cached_len=start, slot=runner.num_slots,
                               table_row=null_row, sampling=sp)
                    for _ in range(w)]
            runner.prefill(rows, resume=resume)
            n += 1
    tokens = np.zeros(runner.num_slots, np.int32)
    for s in do_sample:
        runner.set_sampling(0, sp_sampled if s else sp_greedy)
        runner.decode(tokens, tokens)
        n += 1
    runner.clear_sampling(0)
    if engine.prefix_cache:
        # a prompt whose start matches a cached block copies that block
        # (copy-on-write); with random prompts only a first token or a few
        # match, but over hundreds of requests some always do
        runner.copy_block(NULL_BLOCK, NULL_BLOCK)
        n += 1
    return n


# ----------------------------------------------------------------------------
# instrumentation for traced runs: spans around the calls into each layer,
# and what each call computed
# ----------------------------------------------------------------------------

class Recorder:
    """Wraps the engine's layer calls (trace runs only) with profiler spans
    named `bench.<layer>.<call>`, and records per call what the FLOP and
    byte counts need: prefill rows (start, suffix) and decode lanes'
    positions, each with its host time on the window clock."""

    def __init__(self, engine, clock):
        import jax
        self.prefills = []        # (t, [(start, n), ...])
        self.decodes = []         # (t, [pos, ...])
        self.pool = []            # (t, used share of the pool)
        self._clock = clock
        sched, runner = engine.scheduler, engine.runner
        for layer, obj in (("scheduler", sched), ("runner", runner)):
            for name in SPANS[layer]:
                setattr(obj, name, self._span(f"bench.{layer}.{name}",
                                              getattr(obj, name), jax))
        inner_prefill, inner_prep = runner.prefill, sched.prepare_decode

        def prefill(rows, **kw):
            self.prefills.append((self._clock(), [(r.start, r.suffix_len)
                                                  for r in rows]))
            return inner_prefill(rows, **kw)

        def prepare_decode():
            out = inner_prep()
            if out is not None:
                _, positions, active = out
                self.decodes.append((self._clock(),
                                     [int(positions[i]) for i in active]))
            return out

        runner.prefill, sched.prepare_decode = prefill, prepare_decode
        self._engine = engine

    @staticmethod
    def _span(name, fn, jax):
        def wrapped(*a, **kw):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        return wrapped

    def sample_pool(self, pool_blocks: int) -> None:
        st = self._engine.stats()
        self.pool.append((self._clock(), 1.0 - st.free_blocks / pool_blocks))


# ----------------------------------------------------------------------------
# the run
# ----------------------------------------------------------------------------

def build(ctx: dict, seed: int) -> dict:
    """The weights from `seed`, the engine, warmed up on this cell's
    traffic's shapes."""
    import jax
    from repro.serving import ServingEngine

    spec = ctx["config"]["model"]
    params = weights_lib.make(spec, seed)
    jax.block_until_ready(params)
    engine = ServingEngine(params, model_config(spec), **ctx["cell"]["engine"])
    n_warm = warm_up(engine, ctx["traffic"])
    print(f"[setup] {n_warm} warm-up dispatches, {ctx['log'].compiles} "
          f"compiles so far", file=sys.stderr, flush=True)
    return {"engine": engine, "params": params, "spec": spec,
            "pool_blocks": engine.allocator.num_blocks - 1}


def cell_requests(ctx: dict, seed: int, seconds: float) -> list:
    mix = ctx["traffic"]
    return traffic_lib.requests(mix, seed, traffic_lib.count_for(
        mix, seconds), ctx["config"]["model"]["vocab_size"])


def run(ctx: dict) -> dict:
    seed, seconds = ctx["seed"], ctx["seconds"]
    b = build(ctx, seed)
    reqs = cell_requests(ctx, seed, seconds)
    st = window(b["engine"], reqs, ctx["traffic"], seconds, ctx["trace"],
                ctx, b["pool_blocks"])
    rec = st["rec"]
    rec["counts"] = layer_counts(rec, b["spec"])
    rec["memory"] = ctx["memory"]()
    del b["engine"], st["sent"]
    rec["checks"] = check(b["params"], b["spec"], ctx["cell"], reqs,
                          st["completions"], seed)
    return rec


def window(engine, reqs, mix, seconds, traced, ctx, pool_blocks) -> dict:
    """Drives one window (and its drain) and returns the raw record."""
    import jax

    log = ctx["log"]
    origin = [time.perf_counter()]
    clock = lambda: time.perf_counter() - origin[0]   # noqa: E731
    emitted = [0]                  # tokens emitted inside the window
    done = {}

    def on_event(ev):
        if ev.done:
            done[ev.rid] = ev.completion
        elif 0.0 <= clock() <= seconds:
            emitted[0] += len(ev.tokens)

    engine.scheduler.on_event = on_event
    engine.begin_run(origin[0])
    sent = {}                      # rid -> arrival on the window clock
    closed = mix["loop"] == "closed"
    if closed:
        queue = collections.deque(reqs)
        prime = int(mix.get("prime_steps", 0))
        for _ in range(int(mix["clients"])):
            r = queue.popleft()
            engine.submit(_request(r, 0.0))
            if not prime:
                sent[r["rid"]] = 0.0
        # a closed loop's clients start together; priming runs them for a
        # fixed number of steps before the window, so that the window sees
        # them in their steady state and not all prefilling at once
        seen = 0
        for _ in range(prime):
            for _ in list(done)[seen:]:
                engine.submit(_request(queue.popleft(), clock()))
            seen = len(done)
            engine.step()
        done.clear()
    else:
        queue = collections.deque((r["arrival"], r) for r in reqs)
    t_start = time.perf_counter()
    origin[0] = t_start
    engine.align_clock(t_start)
    steps0, lanes0 = engine.steps, engine.busy_lane_steps
    rec_layers = Recorder(engine, clock) if traced else None
    compiles0 = log.compiles
    seen_done = 0
    trace_dir = None
    t_trace = (seconds * 0.3, seconds * 0.3 + ctx.get("trace_seconds", 3.0))
    trace_span = None
    while True:
        now = clock()
        if now >= seconds:
            break
        if traced and trace_dir is None and now >= t_trace[0]:
            trace_dir = trace_lib.start(ctx["scratch"])
            trace_span = [clock(), None]
        if traced and trace_span and trace_span[1] is None \
                and now >= t_trace[1]:
            trace_span[1] = clock()      # before the profiler's write-out
            jax.profiler.stop_trace()
        if closed:
            finished = list(done)[seen_done:]
            seen_done = len(done)
            for _ in finished:
                if queue:
                    r = queue.popleft()
                    engine.submit(_request(r, now))
                    sent[r["rid"]] = now
                else:
                    raise SystemExit("closed loop ran out of requests: "
                                     "raise the mix's per_client")
        else:
            while queue and queue[0][0] <= now:
                t_due, r = queue.popleft()
                engine.submit(_request(r, t_due))
                sent[r["rid"]] = t_due
        if engine.has_work:
            if rec_layers is not None:
                rec_layers.sample_pool(pool_blocks)
            engine.step()
        else:
            nxt = queue[0][0] if (queue and not closed) else seconds
            time.sleep(max(0.0, min(nxt, seconds) - now) if nxt > now
                       else 0.0)
    t_close = clock()
    if traced and trace_span and trace_span[1] is None:
        trace_span[1] = clock()
        jax.profiler.stop_trace()
    in_window_compiles = log.compiles - compiles0
    steps = engine.steps - steps0
    lane_steps = engine.busy_lane_steps - lanes0
    while engine.has_work and any(rid not in done for rid in sent) and \
            clock() < t_close + DRAIN_S:
        engine.step()
    t_end = clock()
    engine.scheduler.on_event = None
    print(f"[window] {in_window_compiles} compiles inside the window; "
          f"{len(sent)} requests sent, {len(done)} completed, drain "
          f"{t_end - t_close:.3f} s", file=sys.stderr, flush=True)

    ttft, tpot, failed = [], [], 0
    for rid, t_due in sent.items():
        c = done.get(rid)
        if c is None:
            failed += 1
            ttft.append(t_end - t_due)
            continue
        ttft.append(c.t_first_token - t_due)
        if len(c.tokens) > 1:
            tpot.append((c.t_done - c.t_first_token) / (len(c.tokens) - 1))
    rec = {
        "window_s": seconds, "attempted": len(sent), "failed": failed,
        "tokens_in_window": emitted[0], "ttft_s": ttft, "tpot_s": tpot,
        "compiles_in_window": in_window_compiles,
        "steps": steps, "lane_steps": lane_steps,
        "num_slots": engine.num_slots,
        "setup_s": t_start - ctx["t0"],
    }
    if traced and trace_dir is not None:
        summary = trace_lib.summarize(trace_dir, PROGRAMS,
                                      span_prefix="bench.")
        summary["window_s"] = trace_span[1] - trace_span[0]
        rec["trace"] = summary
        rec["trace_span"] = trace_span
        trace_lib.remove(trace_dir)
    if rec_layers is not None:
        rec["layers"] = {"prefills": rec_layers.prefills,
                         "decodes": rec_layers.decodes,
                         "pool": rec_layers.pool}
    return {"rec": rec, "completions": done, "sent": sent}


# ----------------------------------------------------------------------------
# correct: what the window served, against the reference
# ----------------------------------------------------------------------------

def sample_for_check(reqs, completions: dict, seed: int, n_greedy: int,
                     n_sampled: int) -> list:
    """A seeded sample of the finished requests: the longest greedy one
    (prompt + served), then others drawn from the seed."""
    rng = np.random.default_rng([seed, 1])
    by_rid = {r["rid"]: r for r in reqs}
    fin = [rid for rid in sorted(completions)
           if len(completions[rid].tokens)]
    greedy = [rid for rid in fin if by_rid[rid]["temperature"] == 0.0]
    sampled = [rid for rid in fin if by_rid[rid]["temperature"] > 0.0]
    size = lambda rid: (len(by_rid[rid]["prompt"])          # noqa: E731
                        + len(completions[rid].tokens))
    pick = []
    if greedy:
        longest = max(greedy, key=size)
        rest = [r for r in greedy if r != longest]
        pick = [longest] + list(rng.permutation(rest)[:n_greedy - 1])
    pick += list(rng.permutation(sampled)[:n_sampled]) if sampled else []
    return [int(r) for r in pick]


def readings(params, spec, reqs, completions, rids, width: int,
             control: bool = False) -> dict:
    """The compared numbers over the sampled requests:
      gap_max    the widest gap by which a greedy served token's
                 log-probability lies below the reference's best;
      lp_err_max the widest difference between a served token's
                 log-probability as served and under the reference.
    With `control`, the same two numbers for the fp8 reference put in the
    program's place, at the same positions."""
    by_rid = {r["rid"]: r for r in reqs}
    out = {"gap_max": 0.0, "lp_err_max": 0.0, "tokens": 0}
    if control:
        out.update(control_gap_max=0.0, control_lp_err_max=0.0)
    for rid in rids:
        r, c = by_rid[rid], completions[rid]
        P, n = len(r["prompt"]), len(c.tokens)
        seq = np.zeros(width, np.int32)
        seq[:P] = r["prompt"]
        seq[P:P + n] = c.tokens
        got = reference.readings(params, spec, seq, control)
        at = slice(P - 1, P - 1 + n)
        out["tokens"] += n
        lp = np.asarray(c.logprobs, np.float64)
        out["lp_err_max"] = max(out["lp_err_max"],
                                float(np.abs(lp - got["ref_lp"][at]).max()))
        greedy = r["temperature"] == 0.0
        if greedy:
            out["gap_max"] = max(out["gap_max"], float(got["gap"][at].max()))
        if control:
            out["control_lp_err_max"] = max(
                out["control_lp_err_max"],
                float(np.abs(got["control_lp"][at]
                             - got["ref_lp"][at]).max()))
            if greedy:
                out["control_gap_max"] = max(
                    out["control_gap_max"],
                    float(got["control_gap"][at].max()))
    return out


def check(params, spec, cell, reqs, completions, seed,
          control: bool = False) -> dict:
    """Each compared number with its limit; with `control`, the control's
    readings too, under `control`."""
    chk = cell["check"]
    rids = sample_for_check(reqs, completions, seed, chk["greedy"],
                            chk["sampled"])
    width = cell["engine"]["max_seq_len"]
    got = readings(params, spec, reqs, completions, rids, width, control)
    print(f"[check] {len(rids)} requests, {got['tokens']} served tokens "
          f"compared with the reference", file=sys.stderr, flush=True)
    checks = {name: {"value": got[name], "limit": lim}
              for name, lim in chk["limits"].items()}
    if control:
        return {"checks": checks, "readings": got}
    return checks


# ----------------------------------------------------------------------------
# what the per-layer readers need besides the record: FLOPs and bytes
# ----------------------------------------------------------------------------

def layer_counts(rec: dict, spec: dict) -> dict:
    """FLOPs of the window's prefill and decode work, and the decode
    dispatches' least time on the chip, from the recorded calls."""
    out = {}
    lay = rec.get("layers")
    if not lay:
        return out
    T = rec["window_s"]
    f_total = 0.0
    for t, rows in lay["prefills"]:
        if t <= T:
            f_total += sum(flops.prefill_flops(spec, s, n) for s, n in rows)
    for t, lanes in lay["decodes"]:
        if t <= T:
            f_total += flops.decode_flops(spec, lanes)
    out["window_flops"] = f_total
    span = rec.get("trace_span")
    if span:
        a, b = span
        out["traced_decodes"] = [lanes for t, lanes in lay["decodes"]
                                 if a <= t <= b]
        out["traced_prefill_tokens"] = sum(
            n for t, rows in lay["prefills"] if a <= t <= b
            for _, n in rows)
    pool = [u for t, u in lay["pool"] if t <= T]
    if pool:
        out["kv_pool_use"] = float(np.mean(pool))
    return out
