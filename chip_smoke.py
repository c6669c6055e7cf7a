"""Chip smoke test: the main path, once, on a TPU, at published widths.

    python chip_smoke.py             # one chip: serving, then MBProx training
    python chip_smoke.py --chips 4   # four chips: the MBProx data-mesh step
                                     # and its CPU comparison, nothing else

One process, run from the repo root. With no argument it builds seeded
smollm-135m at its published widths (30 layers, d_model 576, 9/3 heads of
64, d_ff 1536, vocab 49152) and

  1. serves 8 requests (prompts of 128-1024 tokens, 64 new tokens each)
     through `ServingEngine`, the engine `launch/serve.py` builds, with
     logprobs on. Every chosen token's logprob is checked against a
     float32 forward pass (`lm.forward` at highest matmul precision, fed
     the prompt plus the generated tokens), which shares none of the
     paged serving path;
  2. takes a few MBProx steps through `launch/train.py`'s `train()`; every
     loss must be finite.

With `--chips 4` it runs only the paper's distributed step, the MBProx
`local` variant (`shard_map` over the data axis with `pmean`), on a 4-chip
data mesh, and the same four shards on four virtual CPU devices of the same
process; losses and parameter updates must agree within stated bounds.

It refuses to run without a TPU: no CPU fallback. Earlier lines report
compile seconds, compilation-cache hits, peak device memory and counts;
none of them is a speed. The last line is one JSON object naming the
device. Any failure exits nonzero.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
SEED = 0
ARCH = "smollm-135m"

# serving phase
N_REQUESTS, SLOTS, MAX_NEW = 8, 8, 64
PROMPT_LENS = (128, 1024)
# Chosen-token logprob, engine (bf16 activations, paged path) against the
# float32 reference. bf16 keeps 8 significant bits, a relative rounding of
# 2^-8 ~ 0.004 per operation; over 30 residual layers that grows to a few
# percent of the final hidden state, and the random-init logits have a
# spread of about 0.5 nats, so an honest difference is a few hundredths
# of a nat. A wrong position, block or KV head attends to another context
# and moves a logprob by about the logit spread, 5x this bound.
LOGPROB_TOL = 0.1

# training phase
TRAIN_STEPS, TRAIN_BATCH, TRAIN_SEQ = 3, 8, 128

# four-chip phase: the TPU and the CPU round the bf16 activations at
# different places. At published widths cut to 8 layers, on the CPU, bf16
# against float32 activations moves the losses by 1.5e-5 (relative) and
# the 3-step parameter update by 2.5% (relative L2); two bf16 runs that
# round independently differ by up to sqrt(2) of that, and it grows
# slowly with depth (1.7% at 2 layers). One data shard of four replaced by
# another moves the update by 59%, but the loss only by 8e-4: at random
# init the loss sits near ln(vocab) whatever the data, so the loss bound
# catches gross faults and the update bound catches a wrong shard.
FOUR_CHIP_STEPS, FOUR_CHIP_LR = 3, 3e-3
LOSS_RTOL = 1e-3
UPDATE_RTOL = 0.15


class CompileLog:
    """Counts compilations, persistent-cache hits and misses, and the
    backend compile seconds, through JAX's monitoring events."""

    def __init__(self):
        self.hits = self.misses = self.compiles = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def snapshot(self):
        return (self.compiles, self.seconds, self.hits, self.misses)

    def report(self, phase, since, wall):
        c, s, h, m = (a - b for a, b in zip(self.snapshot(), since))
        print(f"[{phase}] compiles {c}, backend compile seconds {s:.1f}, "
              f"persistent cache hits {h}, misses {m}, "
              f"phase wall seconds {wall:.1f} (set-up and compile "
              f"included; not a speed)", flush=True)


def tpu_devices_or_exit(chips: int):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devices[0].platform!r}); this check has no CPU fallback")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devices)}")
    return devices


def device_bytes(device, key="peak_bytes_in_use"):
    """One figure of the device's memory_stats() (bytes)."""
    return device.memory_stats()[key]


# ----------------------------------------------------------------------------
# phase 1: serving
# ----------------------------------------------------------------------------

def serve_phase(cfg, params):
    from repro.models import lm
    from repro.serving import Request, SamplingParams, ServingEngine

    n_requests, max_new, tol = N_REQUESTS, MAX_NEW, LOGPROB_TOL
    rng = np.random.default_rng(SEED)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, n_requests)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    engine = ServingEngine(params, cfg, num_slots=SLOTS, block_size=16,
                           max_seq_len=int(lens.max()) + max_new + 1)
    reqs = [Request(rid=i, prompt=p,
                    sampling=SamplingParams(max_new_tokens=max_new,
                                            logprobs=1))
            for i, p in enumerate(prompts)]
    done = sorted(engine.run(reqs), key=lambda c: c.rid)
    if len(done) != n_requests:
        raise SystemExit(f"serving: {len(done)} of {n_requests} requests "
                         f"completed")
    for c in done:
        if c.finish_reason not in ("length", "stop") or not len(c.tokens):
            raise SystemExit(f"serving: request {c.rid} ended "
                             f"{c.finish_reason!r} with {len(c.tokens)} "
                             f"tokens")
    n_tok = sum(len(c.tokens) for c in done)
    print(f"[serve] {len(done)} requests, prompts {lens.min()}-{lens.max()} "
          f"tokens ({int(lens.sum())} in all), {n_tok} tokens generated, "
          f"finish reasons {sorted({c.finish_reason for c in done})}",
          flush=True)

    # float32 reference, teacher-forced on prompt + generated tokens;
    # one padded length so it compiles once (causal: the right padding
    # changes nothing at earlier positions)
    ref_cfg = dataclasses.replace(cfg, dtype="float32")
    width = int(lens.max()) + max_new

    @jax.jit
    def reference(params, tokens, start, chosen):
        logits, _ = lm.forward(params, ref_cfg, {"tokens": tokens[None]})
        lp = jax.nn.log_softmax(logits[0].astype(jnp.float32), axis=-1)
        lp = jax.lax.dynamic_slice_in_dim(lp, start, max_new, axis=0)
        return (jnp.take_along_axis(lp, chosen[:, None], axis=1)[:, 0],
                jnp.argmax(lp, axis=-1))

    first_diff, all_diff, matched = [], [], 0
    with jax.default_matmul_precision("highest"):
        for c, p in zip(done, prompts):
            n = len(c.tokens)
            seq = np.zeros(width, np.int32)
            seq[:len(p)] = p
            seq[len(p):len(p) + n] = c.tokens
            chosen = np.zeros(max_new, np.int32)
            chosen[:n] = c.tokens
            ref_lp, ref_arg = jax.device_get(reference(
                params, jnp.asarray(seq), jnp.int32(len(p) - 1),
                jnp.asarray(chosen)))
            diff = np.abs(np.asarray(c.logprobs, np.float64)
                          - ref_lp[:n].astype(np.float64))
            first_diff.append(float(diff[0]))
            all_diff.append(float(diff.max()))
            matched += int((ref_arg[:n] == c.tokens).sum())
    print(f"[serve] chosen-token logprob vs float32 forward: first token "
          f"max |diff| {max(first_diff):.6f}, all {n_tok} tokens max "
          f"|diff| {max(all_diff):.6f} (tolerance {tol})", flush=True)
    print(f"[serve] greedy tokens equal to the float32 argmax: {matched} of "
          f"{n_tok} ({matched / n_tok:.4f}); random-init logits are nearly "
          f"flat, so this share is reported, not required", flush=True)
    if not max(first_diff) <= tol or not max(all_diff) <= tol:
        raise SystemExit(f"serving: logprob difference {max(all_diff)} "
                         f"exceeds {tol}")


# ----------------------------------------------------------------------------
# phase 2: MBProx training steps
# ----------------------------------------------------------------------------

def train_phase():
    from repro.launch.train import train

    _, losses = train(ARCH, TRAIN_STEPS, optimizer="mbprox",
                      batch_size=TRAIN_BATCH, seq_len=TRAIN_SEQ,
                      log_every=1, seed=SEED)
    print(f"[train] mbprox losses {[float(x) for x in losses]}", flush=True)
    if len(losses) != TRAIN_STEPS or not np.all(np.isfinite(losses)):
        raise SystemExit(f"training: losses not all finite: {losses}")


# ----------------------------------------------------------------------------
# --chips 4: the MBProx `local` step on a data mesh vs the same shards on CPU
# ----------------------------------------------------------------------------

def _run_mbprox_on(devices, cfg, params, batches, lr):
    """Runs the steps on a (len(devices), 1) data mesh; returns the
    parameters, still on the devices, and the losses."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch import steps as steps_lib

    mesh = jax.make_mesh((len(devices), 1), ("data", "model"),
                         devices=devices)
    step_fn, inner_opt, _ = steps_lib.make_mbprox_train_step(cfg, mesh)
    step_fn = jax.jit(step_fn)
    params = jax.device_put(params, NamedSharding(mesh, P()))
    opt_state = inner_opt.init(params)
    batch_sharding = NamedSharding(mesh, P(None, "data"))
    losses = []
    with jax.set_mesh(mesh):
        for batch in batches:
            params, opt_state, metrics = step_fn(
                params, opt_state, jax.device_put(batch, batch_sharding),
                jnp.float32(lr))
            losses.append(float(metrics["loss"]))
    return params, losses


def four_chip_phase(chip_devices, cpu_devices, cfg):
    from repro.data.synthetic import TokenStream
    from repro.launch.train import make_batch
    from repro.models import lm

    with jax.default_device(cpu_devices[0]):
        params0 = jax.device_get(lm.init_params(jax.random.PRNGKey(SEED),
                                                cfg))
        stream = TokenStream(vocab_size=cfg.vocab_size, seq_len=TRAIN_SEQ,
                             seed=SEED)
        batches = [jax.device_get(make_batch(
            cfg, stream, jax.random.fold_in(jax.random.PRNGKey(SEED + 1), s),
            TRAIN_BATCH, 2)) for s in range(FOUR_CHIP_STEPS)]

    t0 = time.perf_counter()
    chip_params, chip_losses = _run_mbprox_on(chip_devices, cfg, params0,
                                              batches, FOUR_CHIP_LR)
    print(f"[4 chips] mbprox losses {chip_losses} "
          f"({time.perf_counter() - t0:.1f} s with compile; not a speed)",
          flush=True)
    # read while the replicated parameters are still live on the chips
    in_use = [device_bytes(d, "bytes_in_use") for d in chip_devices]
    print(f"[4 chips] bytes_in_use per device {in_use}, "
          f"peak_bytes_in_use per device "
          f"{[device_bytes(d) for d in chip_devices]}", flush=True)
    chip_params = jax.device_get(chip_params)
    t0 = time.perf_counter()
    cpu_params, cpu_losses = _run_mbprox_on(cpu_devices, cfg, params0,
                                            batches, FOUR_CHIP_LR)
    cpu_params = jax.device_get(cpu_params)
    print(f"[4 cpu devices] mbprox losses {cpu_losses} "
          f"({time.perf_counter() - t0:.1f} s)", flush=True)

    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(chip_losses,
                                                       cpu_losses))
    num = den = 0.0
    for p0, pc, pr in zip(jax.tree.leaves(params0),
                          jax.tree.leaves(chip_params),
                          jax.tree.leaves(cpu_params)):
        d_chip = np.asarray(pc, np.float64) - np.asarray(p0, np.float64)
        d_cpu = np.asarray(pr, np.float64) - np.asarray(p0, np.float64)
        num += float(((d_chip - d_cpu) ** 2).sum())
        den += float((d_cpu ** 2).sum())
    update_rel = (num / den) ** 0.5 if den else float("inf")
    print(f"[4 chips vs 4 cpu devices] max loss rel diff {loss_rel:.3e} "
          f"(bound {LOSS_RTOL}); parameter-update rel L2 diff "
          f"{update_rel:.3e} (bound {UPDATE_RTOL})", flush=True)
    if not np.all(np.isfinite(chip_losses)):
        raise SystemExit(f"4 chips: losses not finite: {chip_losses}")
    if not (min(in_use) > 0 and max(in_use) < 2 * min(in_use)):
        raise SystemExit(f"4 chips: work not spread over the devices: "
                         f"{in_use}")
    if not (loss_rel <= LOSS_RTOL and update_rel <= UPDATE_RTOL):
        raise SystemExit("4 chips: mesh result disagrees with the CPU "
                         "shards beyond the stated bounds")


# ----------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4: run only the MBProx data-mesh step on four "
                         "chips and its CPU comparison")
    args = ap.parse_args(argv)
    if args.chips == 4:
        # four virtual CPU devices for the comparison; must precede the
        # first backend initialization
        jax.config.update("jax_num_cpu_devices", 4)
    devices = tpu_devices_or_exit(args.chips)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.configs import get_config
    from repro.models import lm
    from repro.runtime.compile_cache import enable_compile_cache

    print(f"[device] {devices[0].platform} {devices[0].device_kind} x "
          f"{len(devices)}; compile cache {enable_compile_cache()}",
          flush=True)
    log = CompileLog()
    cfg = get_config(ARCH)

    if args.chips == 4:
        since, t0 = log.snapshot(), time.perf_counter()
        four_chip_phase(devices[:4], jax.devices("cpu")[:4], cfg)
        log.report("4 chips", since, time.perf_counter() - t0)
    else:
        since, t0 = log.snapshot(), time.perf_counter()
        params = lm.init_params(jax.random.PRNGKey(SEED), cfg)
        serve_phase(cfg, params)
        del params
        log.report("serve", since, time.perf_counter() - t0)
        print(f"[serve] peak_bytes_in_use {device_bytes(devices[0])}",
              flush=True)

        since, t0 = log.snapshot(), time.perf_counter()
        train_phase()
        log.report("train", since, time.perf_counter() - t0)
        print(f"[train] peak_bytes_in_use {device_bytes(devices[0])}",
              flush=True)

    d = devices[0]
    print(json.dumps({"ok": True, "device": {"platform": d.platform,
                                             "kind": d.device_kind,
                                             "count": len(devices)}}))


if __name__ == "__main__":
    main()
