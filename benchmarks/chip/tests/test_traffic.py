"""The traffic generator is a function of (mix, seed): the same seed gives
the same requests, and every seed offers the same work."""
import collections

import numpy as np
import pytest

import cells
import common
import traffic


def _key(reqs):
    return [(r["rid"], r["prompt"].tobytes(), r["max_new"],
             repr(r["arrival"]), r["temperature"], r["seed"]) for r in reqs]


def test_same_seed_same_requests():
    for mix in (cells.OPEN, cells.CLOSED):
        a = traffic.requests(mix, 2**33 + 5, 48, 256)
        b = traffic.requests(mix, 2**33 + 5, 48, 256)
        assert _key(a) == _key(b)
        c = traffic.requests(mix, 2**33 + 6, 48, 256)
        assert _key(a) != _key(c)


@pytest.mark.parametrize("name", ["chat-poisson", "code-closed"])
def test_every_seed_offers_the_same_work(name):
    """The same multiset of sizes and gaps from every seed."""
    mix = common.traffic(name)
    n = 4 * mix["block"]
    sizes = []
    for seed in (1, 99, 2**32 + 17):
        reqs = traffic.requests(mix, seed, n, 49152)
        sizes.append((collections.Counter(len(r["prompt"]) for r in reqs),
                      collections.Counter(r["max_new"] for r in reqs),
                      sorted(np.diff([0.0] + [r["arrival"] for r in reqs]))))
        assert sum(r["temperature"] == 0.0 for r in reqs) == n // 4
    for s in sizes[1:]:
        assert s[0] == sizes[0][0] and s[1] == sizes[0][1]
        np.testing.assert_allclose(s[2], sizes[0][2])


def test_lengths_stay_in_range_and_near_the_median():
    for mix in (common.traffic("chat-poisson"), cells.CLOSED):
        reqs = traffic.requests(mix, 3, 2 * mix["block"], 50000)
        p = [len(r["prompt"]) for r in reqs]
        o = [r["max_new"] for r in reqs]
        assert mix["prompt"]["min"] <= min(p) and max(p) <= mix["prompt"]["max"]
        assert mix["output"]["min"] <= min(o) and max(o) <= mix["output"]["max"]
        assert abs(np.median(p) / mix["prompt"]["median"] - 1) < 0.1
        assert abs(np.median(o) / mix["output"]["median"] - 1) < 0.1


def test_open_loop_rate_and_counts():
    mix = common.traffic("chat-poisson")
    n = traffic.count_for(mix, 10.0)
    assert n % mix["block"] == 0 and n >= mix["rate"] * 10
    reqs = traffic.requests(mix, 4, n, 49152)
    per_block = reqs[mix["block"] - 1]["arrival"]
    assert abs(per_block * mix["rate"] / mix["block"] - 1) < 0.05
    closed = cells.CLOSED
    n = traffic.count_for(closed, 10.0)
    assert n % closed["block"] == 0
    assert n == closed["clients"] * (closed["per_client"] + 2)


def test_sizes_seed_replays_one_order():
    """With `sizes_seed` every run seed gets the same sizes in the same
    order, and other token ids and sampling streams; a mix without the key
    draws what it drew before the key existed."""
    mix = common.traffic("code-closed")
    runs = [traffic.requests(mix, s, 3 * mix["block"], 49152)
            for s in (5, 2**33 + 1)]
    for key in ("max_new", "temperature", "top_p"):
        assert [r[key] for r in runs[0]] == [r[key] for r in runs[1]]
    assert [len(r["prompt"]) for r in runs[0]] == \
        [len(r["prompt"]) for r in runs[1]]
    assert _key(runs[0]) != _key(runs[1])
    plain = dict(mix)
    del plain["sizes_seed"]
    rng = np.random.default_rng(9)
    p_q = traffic._lognormal_quantiles(plain["prompt"], mix["block"])
    first = traffic.requests(plain, 9, mix["block"], 49152)
    assert [len(r["prompt"]) for r in first] == list(rng.permutation(p_q))
