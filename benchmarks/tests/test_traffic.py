"""The generator: every seed gets the same sizes in another order."""
import json
import os

import numpy as np
import pytest

from benchmarks import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
CHAT = json.load(open(os.path.join(HERE, "..", "traffic",
                                   "chat_closed.json")))


def take(seed, n):
    reqs = traffic.Requests(CHAT, 1000, seed)
    return [next(reqs) for _ in range(n)]


def test_the_pool_has_the_distributions_median_and_limits():
    turns = traffic.length_pool(CHAT["turn_tokens"], CHAT["pool"])
    assert min(turns) >= 32 and max(turns) <= 1408
    assert abs(np.median(turns) - 192) <= 4
    outs = traffic.length_pool(CHAT["output_tokens"], CHAT["pool"])
    assert min(outs) >= 16 and max(outs) <= 256
    assert abs(np.median(outs) - 64) <= 2


def test_the_pool_reaches_the_clips_and_fits_the_engine():
    turns = traffic.length_pool(CHAT["turn_tokens"], CHAT["pool"])
    outs = traffic.length_pool(CHAT["output_tokens"], CHAT["pool"])
    assert (turns[0], turns[-1]) == (32, 1408)
    assert (outs[0], outs[-1]) == (16, 256)


def test_any_group_in_a_row_has_one_length_of_every_slice():
    n, strata = CHAT["pool"], CHAT["strata"]
    per = n // strata
    turns = traffic.length_pool(CHAT["turn_tokens"], n)
    outs = traffic.length_pool(CHAT["output_tokens"], n)
    prefix = CHAT["shared_prefix_tokens"]
    reqs = take(11, 2 * n)
    for g in range(0, 2 * n, strata):
        group = reqs[g:g + strata]
        t = sorted(len(p) - prefix for p, _ in group)
        o = sorted(m for _, m in group)
        assert all(t[j] in turns[per * j:per * (j + 1)]
                   and o[j] in outs[per * j:per * (j + 1)]
                   for j in range(strata)), (g, t, o)


def test_a_pool_is_a_whole_number_of_groups():
    with pytest.raises(ValueError, match="whole number"):
        traffic.Requests(dict(CHAT, pool=24), 1000, 1)


def test_a_key_that_nothing_reads_is_refused():
    with pytest.raises(ValueError, match="think_time_s"):
        traffic.known(dict(CHAT, think_time_s=0.5), set(CHAT), "traffic")
    with pytest.raises(ValueError, match="mean"):
        traffic.length_pool(dict(CHAT["turn_tokens"], mean=3), 4)


def test_same_seed_same_requests():
    a, b = take(2**31 + 77, 70), take(2**31 + 77, 70)
    assert all(np.array_equal(p, q) and m == n
               for (p, m), (q, n) in zip(a, b))


def test_seeds_permute_one_set_of_sizes():
    n = CHAT["pool"]
    a, b = take(1, 2 * n), take(2, 2 * n)
    sizes = lambda rs: sorted((len(p), m) for p, m in rs)
    assert sizes(a[:n]) == sizes(b[:n]) == sizes(a[n:]) == sizes(b[n:])
    assert [len(p) for p, _ in a[:n]] != [len(p) for p, _ in b[:n]]
    prefix = CHAT["shared_prefix_tokens"]
    assert all(np.array_equal(p[:prefix], a[0][0][:prefix]) for p, _ in a)
    assert not np.array_equal(a[0][0][:prefix], b[0][0][:prefix])


def test_every_request_fits_the_engine():
    limit = CHAT["engine"]["max_len"]
    assert all(len(p) + m <= limit for p, m in take(3, CHAT["pool"]))


def test_training_tokens_are_fresh_and_seeded():
    spec = {"seqlen": 16}
    a = traffic.token_batches(spec, 100, 5)
    b = traffic.token_batches(spec, 100, 5)
    first, second = next(a), next(a)
    assert np.array_equal(first, next(b)) and not np.array_equal(first, second)
    assert first.dtype == np.int32 and first.shape == (16,)
