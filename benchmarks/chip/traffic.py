"""The one traffic generator. A mix is a data file, `traffic/<mix>.json`;
this module turns it and a seed into requests. It imports nothing of the
program, so the yardstick stays put when the program's own generators in
`serving/engine.py` change.

Lengths are lognormal, clipped, and the open loop's gaps between arrivals
are exponential (Poisson arrivals), as in `serving/engine.py`. One
difference keeps runs comparable across seeds: the sizes and gaps are
stratified. Each consecutive block of `block` requests takes the quantiles
(i + 0.5) / block of the distributions, and the seed only shuffles them
within the block and draws the token ids. So every seed offers the same
work, in another order and with other content, and any whole number of
blocks holds the same multiset of sizes. A mix with `sizes_seed` replays
one sequence of sizes: their order (and an open loop's gaps) is drawn from
that fixed seed, so every run serves the same sizes in the same order, and
the run's seed draws the token ids and sampling streams alone. A closed
loop's window holds a few tens of requests, so a seed that reordered the
sizes would change the window's work.

Keys of a serving mix:
  loop        "open" (arrivals on a schedule) or "closed" (each client
              sends its next request when its last one completes)
  rate        open loop: requests per second
  clients     closed loop: requests kept in flight
  per_client  closed loop: requests drawn per client per 10 s of window
  prime_steps closed loop: engine steps the clients run before the window
  block       stratification block (requests)
  sizes_seed  optional: a fixed seed for the sizes' order (replay)
  prompt      {"median", "sigma", "min", "max"}: prompt tokens
  output      {"median", "sigma", "min", "max"}: new tokens per request
  sampling    {"temperature", "top_p"} for sampled requests
  greedy_every  every n-th request decodes greedily (1: all of them)
  logprobs    chosen-token logprobs asked of the server (0 or 1)
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_STD_NORMAL = NormalDist()


def _lognormal_quantiles(spec: dict, n: int) -> np.ndarray:
    qs = [(i + 0.5) / n for i in range(n)]
    vals = [spec["median"] * math.exp(spec["sigma"] * _STD_NORMAL.inv_cdf(q))
            for q in qs]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def _exponential_quantiles(rate: float, n: int) -> np.ndarray:
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])


def requests(mix: dict, seed: int, n: int, vocab_size: int) -> list:
    """n requests of the mix, as plain dicts: rid, prompt (int32 array),
    max_new, arrival (seconds from the window's start; open loop only),
    temperature, top_p, seed (the request's own sampling stream), logprobs.
    The same (mix, seed, n) always gives the same requests."""
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(int(mix["sizes_seed"]))
             if "sizes_seed" in mix else rng)
    block = int(mix["block"])
    n_blocks = -(-n // block)
    plens, olens, gaps = [], [], []
    p_q = _lognormal_quantiles(mix["prompt"], block)
    o_q = _lognormal_quantiles(mix["output"], block)
    g_q = (_exponential_quantiles(mix["rate"], block)
           if mix["loop"] == "open" else None)
    for _ in range(n_blocks):
        plens.append(order.permutation(p_q))
        olens.append(order.permutation(o_q))
        if g_q is not None:
            gaps.append(order.permutation(g_q))
    plens = np.concatenate(plens)[:n]
    olens = np.concatenate(olens)[:n]
    arrivals = (np.cumsum(np.concatenate(gaps)[:n]) if gaps
                else np.full(n, np.nan))
    every = int(mix.get("greedy_every", 1))
    samp = mix.get("sampling", {})
    out = []
    for i in range(n):
        greedy = i % every == 0
        out.append({
            "rid": i,
            "prompt": rng.integers(0, vocab_size, int(plens[i]),
                                   dtype=np.int64).astype(np.int32),
            "max_new": int(olens[i]),
            "arrival": float(arrivals[i]),
            "temperature": 0.0 if greedy else float(samp["temperature"]),
            "top_p": 1.0 if greedy else float(samp.get("top_p", 1.0)),
            "seed": int(rng.integers(0, 2**31 - 1)),
            "logprobs": int(mix.get("logprobs", 0)),
        })
    return out


def count_for(mix: dict, seconds: float) -> int:
    """How many requests a run of `seconds` draws: for the open loop, the
    whole blocks that cover the window; for the closed loop, `per_client`
    requests per client for every 10 s of window (default 4), plus two,
    which the mix sets above what a client can finish."""
    block = int(mix["block"])
    if mix["loop"] == "open":
        n = math.ceil(mix["rate"] * seconds * 1.05) + block
    else:
        per = int(mix.get("per_client", 4))
        n = int(mix["clients"]) * (per * math.ceil(seconds / 10.0) + 2)
    return -(-n // block) * block
