"""Request arithmetic on plain time stamps (no JAX here)."""

from __future__ import annotations

import math


def percentile(values, q):
    """The ``q``-th percentile by linear interpolation between the order
    statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def request_stats(arrivals, submitted, admitted, token_times, finished):
    """Per-request samples of the sampled arrivals, and who failed.

    ``submitted``, ``admitted``: ``{rid: t}``; ``token_times``: ``{rid: [t
    of each token]}``; ``finished``: ``{rid: (t, reason)}``; all on the
    clock whose zero is the start of the window, as ``Arrival.due`` is.  A
    request counts from when it was **due**, not from when the generator
    got round to it.  One that did not finish with its stated number of
    tokens is a failure and gives no sample.
    """
    out = {"ttft_s": [], "tpot_s": [], "queue_wait_s": [],
           "submit_late_s": [], "token_gap_s": []}
    failed = []
    for a in arrivals:
        if not a.sampled:
            continue
        times = token_times.get(a.rid, [])
        reason = finished.get(a.rid, (None, "unfinished"))[1]
        if reason != "length" or len(times) != a.new_tokens:
            failed.append((a.rid, reason, len(times)))
            continue
        out["ttft_s"].append(times[0] - a.due)
        if len(times) > 1:
            out["tpot_s"].append((times[-1] - times[0]) / (len(times) - 1))
        out["queue_wait_s"].append(admitted[a.rid] - a.due)
        out["submit_late_s"].append(submitted[a.rid] - a.due)
        out["token_gap_s"] += [b - a_ for a_, b in zip(times, times[1:])]
    return out, failed
