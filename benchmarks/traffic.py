"""The one general generator: a traffic file's parameters and ``--seed``
in, inputs out. A traffic mix is data (``traffic/<name>.json``); what is
drawn from it is decided here and nowhere else.

Every seed gets the same set of sizes in another order. Lengths are not
sampled afresh per run: a fixed pool of ``pool`` lengths stands for the
distribution (its quantiles at ``(i + 0.5) / pool``, so the pool has the
distribution's median and tails by construction), and the seed only
orders the pool, cycle after cycle, and draws the token values. So two
runs differ in order and content, never in the amount of work on offer.

A window rarely ends where a cycle does, so a cycle is made of groups of
``strata`` requests, each group holding one turn length and one output
length from each of ``strata`` equal slices of its distribution: any
``strata`` requests in a row are a fair sample of the whole pool.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


# the pairing of turn lengths with output lengths is part of the pool, the
# same for every run of every cell
PAIRING_SEED = 0


def known(given, keys, what):
    """Refuse a key that nothing reads: a parameter that is silently
    ignored makes the file, and the cell's ``why``, say what is not run."""
    unknown = sorted(set(given) - set(keys))
    if unknown:
        raise ValueError(f"{what}: unknown key(s) {unknown}; "
                         f"known: {sorted(keys)}")


def length_pool(spec, n):
    """``n`` whole lengths at the quantiles ``(i + 0.5) / n`` of a
    lognormal with ``median`` and ``sigma``, clipped to ``[min, max]``."""
    known(spec, {"distribution", "median", "sigma", "min", "max"}, "lengths")
    if spec["distribution"] != "lognormal":
        raise ValueError(f"unknown length distribution "
                         f"{spec['distribution']!r}; known: 'lognormal'")
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    return [int(min(max(round(spec["median"] * math.exp(spec["sigma"] * q)),
                        spec["min"]), spec["max"])) for q in z]


def groups(turns, outs, strata):
    """The sorted pools as ``len(turns) / strata`` groups of ``strata``
    (turn, output) pairs. Slice ``j`` of a pool is its ``j``-th run of
    ``len / strata`` neighbours; group ``r`` takes the ``r``-th lowest
    turn of every slice and pairs it with the ``r``-th lowest output of
    another slice, chosen once by ``PAIRING_SEED`` (the same rank, so
    that the groups differ little in prompt tokens per output token:
    that ratio sets the rate). Every length is used once, and every
    group has one of each slice of both pools."""
    per, rem = divmod(len(turns), strata)
    if rem or not per:
        raise ValueError(f"a pool of {len(turns)} is no whole number of "
                         f"groups of {strata}")
    pairing = np.random.default_rng(PAIRING_SEED)
    out = []
    for r in range(per):
        other = pairing.permutation(strata)
        out.append([(turns[per * j + r], outs[per * other[j] + r])
                    for j in range(strata)])
    return out


def token_batches(traffic, vocab, seed):
    """An endless stream of single sequences for the training loader:
    ``seqlen`` uniform random token ids each, fresh every time."""
    rng = np.random.default_rng([seed, 1])
    while True:
        yield rng.integers(0, vocab, (traffic["seqlen"],)).astype(np.int32)


class Requests:
    """An endless, seeded stream of serving requests
    ``(prompt ids, max_new_tokens)``.

    A prompt is the ``shared_prefix_tokens`` every request of the run
    starts with, then a turn of its own. Turn and output lengths come in
    pairs from the fixed pool, in ``pool / strata`` groups that are the
    same for every run; ``seed`` orders the groups of each cycle and the
    requests of each group, and draws the tokens."""

    KEYS = {"shared_prefix_tokens", "pool", "strata", "turn_tokens",
            "output_tokens"}

    def __init__(self, traffic, vocab, seed):
        self.groups = groups(length_pool(traffic["turn_tokens"],
                                         traffic["pool"]),
                             length_pool(traffic["output_tokens"],
                                         traffic["pool"]),
                             traffic["strata"])
        self.vocab = vocab
        self.rng = np.random.default_rng([seed, 2])
        self.prefix = self._tokens(traffic["shared_prefix_tokens"])
        self.order = []

    def _tokens(self, n):
        return self.rng.integers(0, self.vocab, (n,)).astype(np.int32)

    def __iter__(self):
        return self

    def __next__(self):
        if not self.order:
            for g in self.rng.permutation(len(self.groups)):
                group = self.groups[g]
                self.order += [group[i]
                               for i in self.rng.permutation(len(group))]
        turn, out = self.order.pop()
        return np.concatenate([self.prefix, self._tokens(turn)]), out

    def sample(self, spec):
        """The few requests of the reference check: ``count`` prompts of
        ``min``..``max`` tokens in all, ``new_tokens`` to generate."""
        known(spec, {"count", "min_prompt", "max_prompt", "new_tokens"},
              "check")
        lens = np.linspace(spec["min_prompt"], spec["max_prompt"],
                           spec["count"]).astype(int)
        return [(np.concatenate([self.prefix,
                                 self._tokens(n - len(self.prefix))]),
                 spec["new_tokens"]) for n in lens]
