"""The one generator of serving traffic.  A mix is a file of parameters.

Variance is designed out of the traffic, not averaged out of the result.
An open-loop mix offers every seed the same arrivals: the same instants
with the same (prompt, output) lengths, drawn once from the mix's own
``pattern_seed``; the seed draws the tokens (and the weights).  Bursts of
arrivals decide the tail of the time to first token, so instants that
moved with the seed moved the p90 by a quarter (PR 24's first sets).  A
backlog mix offers the same multiset of lengths in a seeded order.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Arrival:
    rid: int
    due: float              # seconds from the start of the window
    prompt_len: int
    new_tokens: int
    sampled: bool = False   # due inside the part of the window that counts


def grid(n, lo, hi, levels):
    """``n`` lengths on the quantile grid of a log-uniform law on
    [lo, hi], held to ``levels`` distinct values so that the program's
    shape-keyed programs are few enough to warm up."""
    u = (np.arange(n) + 0.5) / n
    level = np.minimum((u * levels).astype(int), levels - 1)
    return np.rint(lo * (hi / lo) ** ((level + 0.5) / levels)).astype(int)


def pairs(n, mix):
    """The mix's fixed multiset of ``n`` (prompt, output) lengths.  Which
    output goes with which prompt is a property of the mix, not the seed."""
    p = grid(n, **mix["prompt"])
    o = grid(n, **mix["output"])
    return list(zip(p.tolist(),
                    o[np.random.default_rng(n).permutation(n)].tolist()))


def _span(rng, rid0, n, lengths, t0, t1):
    due = np.sort(rng.uniform(t0, t1, n))
    order = rng.permutation(n)
    return [Arrival(rid0 + i, float(due[i]), *lengths[order[i]])
            for i in range(n)]


def open_loop(mix, seconds):
    """Arrivals of a Poisson process conditioned on its count: ``round(rate
    x length)`` points uniform on each of lead-in, window and drain.  Only
    those due inside the window are sampled."""
    rng = np.random.default_rng(mix["pattern_seed"])
    rate = mix["rate_rps"]
    out = []
    for t0, t1 in ((-mix["lead_in_s"], 0.0), (0.0, seconds),
                   (seconds, seconds + mix["drain_s"])):
        n = round(rate * (t1 - t0))
        out += _span(rng, len(out), n, pairs(n, mix), t0, t1)
    for a in out:
        a.sampled = 0.0 <= a.due < seconds
    return out


def backlog(mix, seed):
    """An endless queue for a closed cell: the multiset of ``block`` pairs
    again and again, each pass in an order of its own."""
    rng = np.random.default_rng(seed)
    lengths = pairs(mix["block"], mix)
    rid = 0
    while True:
        for i in rng.permutation(len(lengths)):
            yield Arrival(rid, 0.0, *lengths[i])
            rid += 1


def prompt_lengths(mix):
    """Every distinct prompt length the mix can offer: what a warm-up has
    to cover."""
    return sorted(set(grid(mix["prompt"]["levels"], **mix["prompt"]).tolist()))


def tokens(seed, rid, n, vocab):
    """The prompt of request ``rid``: ``n`` token ids from the seed."""
    return np.random.default_rng([seed, rid]).integers(
        0, vocab, n).tolist()
