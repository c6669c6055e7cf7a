"""Serve a small LM two ways: the legacy fixed-batch generate() path,
and the continuous-batching engine with per-request SamplingParams and
streaming completions (greedy + sampled lanes in one batch).

    PYTHONPATH=src python examples/serve_lm.py --arch recurrentgemma-2b \
        --reduced
(recurrent archs demonstrate O(1)-state decode; attention archs the KV
cache path. Published widths by default, like launch/serve.py; --reduced
serves the small same-family variant sized for CPU runs.)
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import generate
from repro.models import lm
from repro.serving import Request, SamplingParams, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--gen", type=int, default=24)
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    if cfg.frontend == "audio":
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, 8, cfg.n_codebooks), 0,
                                     cfg.vocab_size)
    else:
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, 8), 0, cfg.vocab_size)
    with jax.set_mesh(make_host_mesh()):
        t0 = time.time()
        toks = generate(params, cfg, prompts, args.gen, temperature=0.8)
        dt = time.time() - t0
        print(f"{args.arch}: generated {toks.shape} tokens in {dt:.2f}s "
              f"(legacy fixed-batch path)")
        print("sample:", toks[0][:12])

        if cfg.frontend != "none":
            return                      # engine serves text LMs only
        engine = ServingEngine(params, cfg, num_slots=2, block_size=8,
                               max_seq_len=8 + args.gen + 1)
        requests = [
            Request(rid=0, prompt=np.asarray(prompts[0]),
                    max_new_tokens=args.gen),          # greedy lane
            Request(rid=1, prompt=np.asarray(prompts[1]),
                    sampling=SamplingParams(temperature=0.8, top_k=50,
                                            seed=7, logprobs=True,
                                            max_new_tokens=args.gen)),
        ]
        print("streaming (greedy + sampled lanes in one batch):")
        for ev in engine.stream(requests):
            if ev.done:
                c = ev.completion
                print(f"  req {c.rid} done ({c.finish_reason}): "
                      f"{len(c.tokens)} tokens"
                      + (f", mean logprob {c.logprobs.mean():.2f}"
                         if c.logprobs is not None else ""))
            else:
                print(f"  req {ev.rid} += {ev.tokens}")


if __name__ == "__main__":
    main()
