"""Serving CLI: continuous-batching engine (default) or the legacy
fixed-batch path, with an open-loop synthetic traffic generator and
throughput/latency telemetry.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --requests 32 --slots 8 --prompt-len 24 64 --max-new 8 32 --rate 50

The model is served at its published widths; `--reduced` (like
`launch/train.py`'s) swaps in the small same-family variant, which is
what the CPU examples below use.

    # shared-prefix traffic (system prompt + per-request suffix) with
    # prefix caching and explicit prefill length buckets:
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --workload shared-prefix --prefix-len 48 --prefix-cache \
        --prefill-buckets 16 32 64

    # n-gram speculative decoding (greedy lanes stay bit-identical to
    # generate()) on a repetitive-text workload:
    PYTHONPATH=src python -m repro.launch.serve --reduced \
        --workload repetitive --speculate 4 --max-new 16 32

    # per-request sampling (position-keyed: batch-composition
    # independent) with nucleus/top-k warping and a stop sequence —
    # composes with speculation (distribution-preserving accept/reject):
    PYTHONPATH=src python -m repro.launch.serve --reduced --temperature 0.8 \
        --top-k 50 --top-p 0.95 --stop 7 11 --speculate 4

    # multi-replica cluster: a router fronting N full engine stacks
    # (per-replica device pools + prefix caches) with least-loaded or
    # prefix-affinity placement; outputs are bit-identical to a
    # single-replica run (batch-composition independence, one level up):
    PYTHONPATH=src python -m repro.launch.serve --reduced --replicas 2 \
        --router prefix --workload multi-tenant --tenants 4

    # elastic autoscaling on bursty traffic: start one replica, scale
    # out (jit-warm standby stacks) under sustained queue pressure and
    # drain back when the burst passes; outputs stay bit-identical to
    # a fixed-size run. Mix priority classes to exercise preemption:
    PYTHONPATH=src python -m repro.launch.serve --reduced --workload bursty \
        --autoscale --min-replicas 1 --max-replicas 3 --priorities 0 1

    # observability: export a Perfetto-loadable trace (request lifecycle
    # spans per slot + the dispatch timeline) and a metrics dump
    # (counters/gauges/histograms + occupancy time series); outputs stay
    # bit-identical with the recorder on:
    PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \
        --trace-out trace.json --metrics-out metrics.json

    # the stack's own host spans (serve.step, serve.scheduler.*,
    # serve.runner.* with their stage/fetch parts, serve.host.gc) on
    # the device's clock: a jax.profiler trace of the served run
    PYTHONPATH=src python -m repro.launch.serve --reduced --requests 8 \
        --profile-dir /tmp/serve-profile

    # SLO layer: declare a TTFT objective (streaming latency sketches +
    # burn-rate windows), shed hopeless requests against a per-request
    # deadline, scale on burn rate instead of queue depth, and arm the
    # anomaly flight recorder on diurnal (sinusoidal-rate) traffic:
    PYTHONPATH=src python -m repro.launch.serve --reduced --workload diurnal \
        --slo-ttft-ms 100 --slo-shed --deadline-ms 500 \
        --autoscale --slo-autoscale --flight-recorder flight.json

    # legacy single-batch path (token-by-token cache priming; kept as the
    # benchmark baseline and for the audio/vision frontends):
    PYTHONPATH=src python -m repro.launch.serve --reduced --mode naive \
        --batch 4

`generate()` below is the seed serving path, unchanged: it primes the KV
cache one token at a time and decodes a fixed batch in lockstep. The
engine replaces it for sustained traffic — see repro.serving.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.models import lm
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving.autoscaler import Autoscaler, AutoscalePolicy
from repro.serving.engine import (Request, ServingEngine, bursty_requests,
                                  diurnal_requests,
                                  long_document_requests,
                                  multi_tenant_requests,
                                  repetitive_requests,
                                  shared_prefix_requests, summarize,
                                  synthetic_requests)
from repro.serving.observability import (NULL_OBS, FlightRecorder,
                                         Observability, export_metrics,
                                         export_trace,
                                         validate_metrics_dump,
                                         validate_trace_events)
from repro.serving.replica import Replica
from repro.serving.router import Router, summarize_cluster
from repro.serving.sampling import SamplingParams
from repro.serving.slo import SLOPolicy, SLOSignal, SLOTracker


# module-level so repeated generate() calls with the same shapes reuse the
# compiled step (cfg is a frozen dataclass => a valid static argument)
_decode_step_jit = jax.jit(lm.decode_step, static_argnums=(1,))


def generate(params, cfg, prompts, gen_len: int, *, temperature: float = 0.0,
             seed: int = 0):
    """prompts: (B, P) int32 (or (B, P, n_cb) audio). Greedy/temperature
    decode with a KV cache primed token-by-token from the prompt."""
    B = prompts.shape[0]
    P = prompts.shape[1]
    max_len = P + gen_len + 1
    state = lm.init_decode_state(cfg, B, max_len=max_len)

    def step(s, t, p):
        return _decode_step_jit(params, cfg, s, t, p)

    # prime the cache on the prompt
    logits = None
    for pos in range(P):
        logits, state = step(state, prompts[:, pos], jnp.int32(pos))

    out = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    key = jax.random.PRNGKey(seed)
    for i in range(gen_len):
        out.append(tok)
        logits, state = step(state, tok, jnp.int32(P + i))
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, logits / temperature, -1)
            tok = tok.astype(jnp.int32)
        else:
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
    return jnp.stack(out, axis=1)


def _prompt_len_spec(values):
    """One int = fixed length; two ints = uniform (lo, hi) mixed."""
    if len(values) == 1:
        return values[0]
    if len(values) == 2:
        return (values[0], values[1])
    raise SystemExit("--prompt-len takes one or two ints")


def _sampling_from_args(args):
    """Per-workload SamplingParams from the CLI flags; None (greedy,
    no stops, no logprobs) when every flag sits at its default."""
    stop = (tuple(args.stop),) if args.stop else ()
    if (args.temperature <= 0 and args.top_k == 0 and args.top_p >= 1.0
            and not stop and args.logprobs == 0):
        return None
    return SamplingParams(temperature=args.temperature, top_k=args.top_k,
                          top_p=args.top_p, seed=args.seed, stop=stop,
                          logprobs=args.logprobs)


def _make_workload(args, cfg):
    rate = float("inf") if args.rate <= 0 else args.rate
    plen = _prompt_len_spec(args.prompt_len)
    sampling = _sampling_from_args(args)
    if args.deadline_ms is not None:
        # stamp a per-request soft TTFT deadline: decoding is unchanged;
        # under --slo-shed the scheduler sheds requests that cannot make
        # it and admits tighter deadlines first within a priority class
        base = sampling if sampling is not None else SamplingParams()
        sampling = dataclasses.replace(base, deadline_ms=args.deadline_ms)
    if args.workload == "shared-prefix":
        return shared_prefix_requests(
            args.requests, vocab_size=cfg.vocab_size,
            prefix_len=args.prefix_len, suffix_len=plen,
            max_new=tuple(args.max_new), n_prefixes=args.n_prefixes,
            rate=rate, sampling=sampling, seed=args.seed)
    if args.workload == "multi-tenant":
        return multi_tenant_requests(
            args.requests, vocab_size=cfg.vocab_size,
            n_tenants=args.tenants, prefix_len=args.prefix_len,
            suffix_len=plen, max_new=tuple(args.max_new), rate=rate,
            tenant_priorities=args.tenant_priorities,
            sampling=sampling, seed=args.seed)
    if args.workload == "bursty":
        return bursty_requests(
            args.requests, vocab_size=cfg.vocab_size,
            base_rate=args.base_rate, burst_rate=args.burst_rate,
            burst_every=args.burst_every, burst_len=args.burst_len,
            prompt_len=plen, max_new=tuple(args.max_new),
            priorities=tuple(args.priorities), sampling=sampling,
            seed=args.seed)
    if args.workload == "diurnal":
        return diurnal_requests(
            args.requests, vocab_size=cfg.vocab_size,
            rate_min=args.rate_min, rate_max=args.rate_max,
            period=args.diurnal_period, prompt_len=plen,
            max_new=tuple(args.max_new),
            priorities=tuple(args.priorities), sampling=sampling,
            seed=args.seed)
    if args.workload == "repetitive":
        return repetitive_requests(
            args.requests, vocab_size=cfg.vocab_size, period=args.period,
            prompt_len=plen, max_new=tuple(args.max_new), rate=rate,
            sampling=sampling, seed=args.seed)
    if args.workload == "long-document":
        return long_document_requests(
            args.requests, vocab_size=cfg.vocab_size, prompt_len=plen,
            max_new=tuple(args.max_new), rate=rate, sampling=sampling,
            seed=args.seed)
    return synthetic_requests(
        args.requests, vocab_size=cfg.vocab_size, prompt_len=plen,
        max_new=tuple(args.max_new), rate=rate, sampling=sampling,
        seed=args.seed)


def _engine_kwargs(args, max_seq_len):
    return dict(num_slots=args.slots, block_size=args.block_size,
                max_seq_len=max_seq_len, prefix_cache=args.prefix_cache,
                prefill_buckets=args.prefill_buckets,
                prefill_max_batch=args.prefill_batch,
                prefill_chunk=args.prefill_chunk,
                speculate=args.speculate,
                ngram=args.ngram, kv_dtype=args.kv_dtype,
                host_cache_blocks=args.host_cache_blocks,
                priority_aging=args.priority_aging,
                # widen the compiled top-k side output when the CLI asks
                # for more alternatives than the engine default carries
                max_logprobs=max(args.logprobs, 8))


def _slo_from_args(args):
    """(SLOPolicy, SLOTracker) when any SLO flag asks for the layer,
    else (None, None) — the default path carries zero SLO state."""
    slo_on = (args.slo_ttft_ms is not None
              or args.slo_latency_ms is not None
              or args.slo_shed or args.slo_autoscale)
    if not slo_on:
        return None, None
    policy = SLOPolicy(
        ttft_objective_ms=(args.slo_ttft_ms if args.slo_ttft_ms is not None
                           else 200.0),
        latency_objective_ms=args.slo_latency_ms,
        error_budget=args.slo_budget)
    return policy, SLOTracker(policy)


def _run_engine(args, cfg, params):
    reqs = _make_workload(args, cfg)
    max_prompt = max(len(r.prompt) for r in reqs)
    kwargs = _engine_kwargs(args, max_prompt + max(args.max_new) + 1)
    slo_policy, slo_tracker = _slo_from_args(args)
    # the recorder is on only when an export was asked for — the default
    # NULL_OBS path records nothing and adds no work (and outputs are
    # bit-identical either way). --flight-recorder implies the recorder:
    # the ring is fed by the same instruments.
    recorder = (FlightRecorder(dump_path=args.flight_recorder)
                if args.flight_recorder else None)
    tracing = bool(args.trace_out or args.metrics_out
                   or args.flight_recorder)
    obs = Observability(recorder=recorder) if tracing else NULL_OBS
    if slo_tracker is not None:
        kwargs["slo_tracker"] = slo_tracker
        kwargs["slo_shed"] = args.slo_shed
        if obs.enabled:
            obs.slo = slo_tracker    # root view: metrics_dump sketches
    if args.autoscale:
        # elastic cluster: the router starts with min_replicas enabled
        # stacks; the rest are built up front and parked in the
        # autoscaler's standby pool, to be activated (jit-warm) when
        # sustained queue pressure demands it and drained back when the
        # burst passes. Outputs stay bit-identical to any fixed size.
        n_max = max(args.max_replicas, args.min_replicas)
        replicas = [Replica(params, cfg, replica_id=i, obs=obs, **kwargs)
                    for i in range(n_max)]
        router = Router(replicas[:args.min_replicas], policy=args.router,
                        obs=obs)
        policy = AutoscalePolicy(
            min_replicas=args.min_replicas, max_replicas=n_max,
            queue_high=args.queue_high, queue_low=args.queue_low,
            cooldown_s=args.scale_cooldown)
        controller = (SLOSignal(slo_tracker, policy, obs=obs)
                      if args.slo_autoscale else None)
        Autoscaler(router, policy=policy,
                   standby=replicas[args.min_replicas:], obs=obs,
                   controller=controller)
        done = router.run(reqs)
        stats = summarize_cluster(done, router.wall_time, router)
    elif args.replicas > 1:
        replicas = [Replica(params, cfg, replica_id=i, obs=obs, **kwargs)
                    for i in range(args.replicas)]
        router = Router(replicas, policy=args.router, obs=obs)
        done = router.run(reqs)
        stats = summarize_cluster(done, router.wall_time, router)
    else:
        engine = ServingEngine(params, cfg, obs=obs, **kwargs)
        done = engine.run(reqs)
        stats = summarize(done, engine.wall_time, engine)
    if slo_tracker is not None and "slo" not in stats:
        # cluster paths: summarize_cluster has no engine handle, so the
        # shared tracker's snapshot is attached here
        stats["slo"] = slo_tracker.snapshot()
    if args.flight_recorder:
        doc = recorder.dump()
        errs = validate_trace_events(doc)
        if errs:
            raise SystemExit(f"invalid flight-recorder dump: {errs[:3]}")
        fr = doc["otherData"]["flight_recorder"]
        print(f"flight recorder: {fr['events']} events "
              f"({fr['dropped']} dropped, {len(fr['anomalies'])} "
              f"anomalies) to {args.flight_recorder}")
    if args.trace_out:
        doc = export_trace(obs, args.trace_out)
        errs = validate_trace_events(doc)
        if errs:
            raise SystemExit(f"invalid trace_event export: {errs[:3]}")
        print(f"wrote {len(doc['traceEvents'])} trace events "
              f"to {args.trace_out} (open in https://ui.perfetto.dev)")
    if args.metrics_out:
        doc = export_metrics(obs, args.metrics_out)
        errs = validate_metrics_dump(doc)
        if errs:
            raise SystemExit(f"invalid metrics dump: {errs[:3]}")
        print(f"wrote metrics ({len(doc['counters'])} counters, "
              f"{len(doc['series'])} series samples) to {args.metrics_out}")
    print(json.dumps(stats, indent=1))
    if done:
        sample = min(done, key=lambda c: c.rid)
        print(f"sample (req {sample.rid}): {sample.tokens[:16]}")


def _run_naive(args, cfg, params):
    plen = args.prompt_len[0]     # naive path is fixed-shape by design
    if cfg.frontend == "audio":
        prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                     (args.batch, plen,
                                      cfg.n_codebooks), 0, cfg.vocab_size)
    else:
        prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                     (args.batch, plen), 0,
                                     cfg.vocab_size)
    t0 = time.time()
    tokens = generate(params, cfg, prompts, max(args.max_new),
                      temperature=args.temperature)
    dt = time.time() - t0
    n_tok = tokens.shape[0] * tokens.shape[1]
    print(f"generated {tokens.shape} in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s batched)")
    print(np.asarray(tokens[0][:16]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the small same-family variant (CPU runs) "
                         "instead of the published widths")
    ap.add_argument("--mode", default="engine", choices=["engine", "naive"])
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4,
                    help="fixed batch for --mode naive")
    ap.add_argument("--prompt-len", type=int, nargs="+", default=[64],
                    help="fixed length, or LO HI for mixed-length traffic "
                         "(suffix length under --workload shared-prefix)")
    ap.add_argument("--max-new", type=int, nargs=2, default=(8, 32),
                    metavar=("LO", "HI"))
    ap.add_argument("--workload", default="synthetic",
                    choices=["synthetic", "shared-prefix", "multi-tenant",
                             "repetitive", "long-document", "bursty",
                             "diurnal"])
    ap.add_argument("--prefix-len", type=int, default=48,
                    help="shared system-prompt length (shared-prefix / "
                         "multi-tenant)")
    ap.add_argument("--n-prefixes", type=int, default=1,
                    help="distinct system prompts (shared-prefix)")
    ap.add_argument("--tenants", type=int, default=4,
                    help="distinct tenants, each with its own shared "
                         "prefix, interleaved arrivals (multi-tenant)")
    ap.add_argument("--tenant-priorities", type=int, nargs="+", default=None,
                    help="per-tenant scheduler priority classes (one int "
                         "per tenant; higher preempts lower — an SLO mix "
                         "for --workload multi-tenant)")
    ap.add_argument("--base-rate", type=float, default=4.0,
                    help="off-burst arrival rate req/s (bursty)")
    ap.add_argument("--burst-rate", type=float, default=64.0,
                    help="in-burst arrival rate req/s (bursty)")
    ap.add_argument("--burst-every", type=float, default=2.0,
                    help="burst cycle period in seconds (bursty)")
    ap.add_argument("--burst-len", type=float, default=0.25,
                    help="burst duration per cycle in seconds (bursty)")
    ap.add_argument("--priorities", type=int, nargs="+", default=[0],
                    help="priority classes drawn uniformly per request "
                         "(bursty / diurnal)")
    ap.add_argument("--rate-min", type=float, default=1.0,
                    help="trough arrival rate req/s (diurnal)")
    ap.add_argument("--rate-max", type=float, default=32.0,
                    help="peak arrival rate req/s (diurnal)")
    ap.add_argument("--diurnal-period", type=float, default=8.0,
                    help="seconds per sinusoidal rate cycle (diurnal)")
    ap.add_argument("--priority-aging", type=float, default=2.0,
                    help="seconds of queue wait worth one priority class "
                         "at admission (starvation bound; <=0 disables)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="model replicas behind the cluster router "
                         "(each a full engine stack; 1 = no router)")
    ap.add_argument("--autoscale", action="store_true",
                    help="elastic cluster: start --min-replicas, scale "
                         "out to --max-replicas under sustained queue "
                         "pressure and drain back when idle (overrides "
                         "--replicas)")
    ap.add_argument("--min-replicas", type=int, default=1,
                    help="enabled replicas at run start (--autoscale)")
    ap.add_argument("--max-replicas", type=int, default=3,
                    help="replica ceiling; the surplus stacks are built "
                         "up front as the jit-warm standby pool "
                         "(--autoscale)")
    ap.add_argument("--queue-high", type=float, default=2.0,
                    help="per-replica queue depth that accumulates "
                         "toward a scale-out (--autoscale)")
    ap.add_argument("--queue-low", type=float, default=1.0,
                    help="per-replica load at or below which idleness "
                         "accumulates toward a scale-in (--autoscale)")
    ap.add_argument("--scale-cooldown", type=float, default=0.25,
                    help="minimum seconds between scaling decisions "
                         "(--autoscale)")
    ap.add_argument("--router", default="least-loaded",
                    choices=["rr", "least-loaded", "prefix"],
                    help="replica placement policy: round-robin, "
                         "least-loaded (slot+queue occupancy), or "
                         "prefix-affinity (BlockAllocator match_prefix "
                         "probe)")
    ap.add_argument("--period", type=int, default=6,
                    help="repeated-pattern length (repetitive)")
    ap.add_argument("--speculate", type=int, default=0,
                    help="max draft tokens per verify dispatch "
                         "(speculative decoding; 0 = off, greedy-only)")
    ap.add_argument("--ngram", type=int, default=3,
                    help="longest n-gram the proposer matches")
    ap.add_argument("--kv-dtype", default="fp16",
                    choices=["fp16", "int8", "fp8"],
                    help="paged KV pool storage dtype: fp16 keeps the "
                         "model activation dtype (bit-identical), "
                         "int8/fp8 quantize blocks on landing with "
                         "per-slot-per-head scale tables")
    ap.add_argument("--host-cache-blocks", type=int, default=0,
                    help="host-RAM spill tier capacity in KV blocks: "
                         "evicted cached prefix blocks demote to pinned "
                         "host memory and revive on a later prefix hit "
                         "(0 = off)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="share cached prompt-prefix blocks (default: auto "
                         "— on for pure-attention archs)")
    ap.add_argument("--prefill-buckets", type=int, nargs="+", default=None,
                    help="suffix-length buckets for batched prefill "
                         "(default: powers of two up to max_seq_len)")
    ap.add_argument("--prefill-batch", type=int, default=4,
                    help="max prompts admitted per prefill dispatch")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-admission budget: prompts longer than "
                         "the largest prefill bucket are admitted in "
                         "chunks of this many tokens, one per engine "
                         "step (default 2048; 0 disables — oversized "
                         "prompts are then rejected at submit)")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate req/s (<=0: all at t=0)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy; "
                         "each request gets its own PRNG stream)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = off)")
    ap.add_argument("--stop", type=int, nargs="+", default=None,
                    help="stop token sequence: generation ends when the "
                         "output ends with these ids")
    ap.add_argument("--logprobs", type=int, default=0,
                    help="record the chosen token's logprob plus the "
                         "top-k alternatives per position (0 = off)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="TTFT objective in ms: turns on the SLO layer "
                         "(streaming latency sketches + burn-rate "
                         "windows; see repro.serving.slo)")
    ap.add_argument("--slo-latency-ms", type=float, default=None,
                    help="end-to-end latency objective in ms (optional "
                         "second SLO besides TTFT)")
    ap.add_argument("--slo-budget", type=float, default=0.1,
                    help="error budget: tolerated fraction of requests "
                         "over objective (burn rate 1.0 = spending "
                         "exactly this budget)")
    ap.add_argument("--slo-shed", action="store_true",
                    help="SLO-aware admission: order by deadline slack "
                         "within a priority class and shed requests "
                         "whose --deadline-ms cannot be met (OFF by "
                         "default — without it admission order and "
                         "outputs are untouched)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request soft TTFT deadline in ms after "
                         "arrival (stamped on every request; acted on "
                         "only under --slo-shed)")
    ap.add_argument("--slo-autoscale", action="store_true",
                    help="drive --autoscale decisions from the TTFT "
                         "burn rate (SLOSignal) instead of queue depth")
    ap.add_argument("--flight-recorder", default=None, metavar="PATH",
                    help="always-on bounded ring of recent trace events; "
                         "dumps a Perfetto trace to PATH on anomalies "
                         "(TTFT breach, preemption storm, eviction "
                         "thrash) and at end of run. Enables the "
                         "observability recorder.")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace_event JSON of "
                         "the run (request lifecycle spans per slot, "
                         "dispatch timeline; open in ui.perfetto.dev). "
                         "Enables the observability recorder.")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write the metrics-registry dump JSON (counters/"
                         "gauges/histograms + SchedulerStats time series). "
                         "Enables the observability recorder.")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="run the served traffic under jax.profiler.trace("
                         "DIR): the device's programs and the stack's "
                         "serve.* host spans on one clock (open the "
                         ".xplane.pb in TensorBoard's profile plugin or "
                         "XProf)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.slo_autoscale and not args.autoscale:
        raise SystemExit("--slo-autoscale requires --autoscale")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    enable_compile_cache()
    mesh = make_host_mesh()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    profile = (jax.profiler.trace(args.profile_dir) if args.profile_dir
               else contextlib.nullcontext())
    with jax.set_mesh(mesh), profile:
        if args.mode == "engine":
            _run_engine(args, cfg, params)
        else:
            _run_naive(args, cfg, params)


if __name__ == "__main__":
    main()
