"""Tiny cells for the CPU tests: the benchmark's own drivers, at a size a
test run holds, with the chip check skipped."""
from __future__ import annotations

import os
import sys
import tempfile

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
        "vocab_size": 256, "block_pattern": ["attn"], "mlp_kind": "swiglu",
        "tie_embeddings": True, "rope_theta": 10000.0, "norm_eps": 1e-6,
        "attn_chunk": 16, "dtype": "float32", "param_dtype": "float32"}

OPEN = {"loop": "open", "rate": 40.0, "block": 8,
        "prompt": {"median": 20, "sigma": 0.5, "min": 8, "max": 40},
        "output": {"median": 8, "sigma": 0.4, "min": 4, "max": 12},
        "sampling": {"temperature": 0.7, "top_p": 0.95},
        "greedy_every": 2, "logprobs": 1}

CLOSED = {"loop": "closed", "clients": 3, "per_client": 400, "block": 3,
          "prime_steps": 20,
          "prompt": {"median": 40, "sigma": 0.3, "min": 20, "max": 60},
          "output": {"median": 6, "sigma": 0.3, "min": 4, "max": 8},
          "greedy_every": 1, "logprobs": 1}

ENGINE = {"num_slots": 4, "block_size": 8, "max_seq_len": 72,
          "max_logprobs": 1}

# smollm2-code's shape at a test's size: multi-head (KV = H), weights
# stored in bfloat16, a closed loop replaying one order of sizes whose
# prompts take two to four prefill chunks, and a pool smaller than lanes x
# max_seq_len, so that admission sometimes waits for blocks
MHA = dict(TINY, n_heads=4, n_kv_heads=4, head_dim=20,
           param_dtype="bfloat16")

LONG_CLOSED = {"loop": "closed", "clients": 4, "per_client": 400,
               "block": 8, "sizes_seed": 0, "prime_steps": 12,
               "prompt": {"median": 64, "sigma": 0.35, "min": 36,
                          "max": 100},
               "output": {"median": 6, "sigma": 0.3, "min": 4, "max": 8},
               "sampling": {"temperature": 0.7, "top_p": 0.95},
               "greedy_every": 2, "logprobs": 1}

LONG_ENGINE = {"num_slots": 4, "block_size": 8, "max_seq_len": 112,
               "num_blocks": 41, "max_logprobs": 1, "prefill_chunk": 32}


def serve_ctx(mix=OPEN, engine=ENGINE, seed=7, seconds=1.5, trace=False,
              limits=None, model=None):
    import common
    spec = dict(model or TINY)
    cell = {"driver": "serve", "engine": dict(engine),
            "check": {"greedy": 3, "sampled": 2,
                      "limits": limits or {"gap_max": 1e-3,
                                           "lp_err_max": 1e-3}}}
    name = "tiny-serve"
    bench = {"end_to_end": [
        {"name": "output_tok_s", "unit": "tokens/s"},
        {"name": "ttft_p95_ms", "unit": "ms"},
        {"name": "tpot_p95_ms", "unit": "ms"},
        {"name": "setup_s", "unit": "s"}],
        "per_layer": [{"name": "lane_occupancy", "unit": "%"},
                      {"name": "kv_pool_use", "unit": "%"}]}
    import jax
    devs = jax.devices()
    return {"bench": bench, "workload": {"name": name, "chips": 1},
            "cell": cell, "config": {"model": spec}, "traffic": dict(mix),
            "seed": seed, "seconds": seconds, "trace": trace,
            "trace_seconds": 0.5, "devices": devs,
            "log": common.CompileLog(),
            "scratch": tempfile.mkdtemp(prefix="chipbench-test-"),
            "t0": __import__("time").perf_counter(),
            "memory": lambda: common.device_record(devs)}


TRAIN_MIX = {"loop": "train", "seq_len": 16, "n_micro": 2,
             "micro_batch_per_chip": 2, "lr": 0.05}


def train_ctx(seed=3, seconds=1.0, limits=None):
    import jax

    import common
    devs = jax.devices()[:4]
    cell = {"driver": "train",
            "check": {"limits": limits or {"loss_gap": 1e-3,
                                           "state1_gap": 1e-3,
                                           "change3_gap": 1e-3}}}
    bench = {"end_to_end": [{"name": "train_tok_s", "unit": "tokens/s"},
                            {"name": "setup_s", "unit": "s"}],
             "per_layer": []}
    return {"bench": bench, "workload": {"name": "tiny-train", "chips": 4},
            "cell": cell, "config": {"model": dict(TINY)},
            "traffic": dict(TRAIN_MIX), "seed": seed, "seconds": seconds,
            "trace": False, "trace_seconds": 0.5, "devices": devs,
            "log": common.CompileLog(),
            "scratch": tempfile.mkdtemp(prefix="chipbench-test-"),
            "t0": __import__("time").perf_counter(),
            "memory": lambda: common.device_record(devs)}
