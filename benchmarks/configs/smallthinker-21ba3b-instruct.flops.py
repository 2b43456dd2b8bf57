"""Operations and bytes the new kernel of smallthinker-21ba3b-instruct's
training step requires, from shapes alone, whatever implements it: the
grouped causal flash attention over rows, forward and backward, with a
window or without.  Recomputed operations and masked positions do not
count.  ``chip_smoke.py``'s ``band_flash_timing`` leg turns them into
shares of the roofline with ``harness/peaks.py``'s numbers; a per-layer
metric of the window layers' calls needs a reader that counts a band, which
is a ``benchmark`` PR's (``PERF.md``, section 7).  The full layer's
backward is the harness's own ``flops.flash_bwd`` count.
"""


def visible_pairs(positions, window=None):
    """(row, key) pairs a causal attention over ``positions`` rows allows,
    each row over itself and the ``window - 1`` before it (all before it
    without a window)."""
    s = positions
    w = min(window or s, s)
    return w * (w + 1) // 2 + (s - w) * w


def _rows_bytes(heads, kv_heads, head_dim, positions, itemsize):
    """One pass over q, k, v and the output rows of one sequence."""
    return 2 * (heads + kv_heads) * head_dim * positions * itemsize


def band_flash_fwd(heads, kv_heads, head_dim, positions, window=None,
                   itemsize=2):
    """(operations, bytes) of the forward: for every query head and visible
    (row, key) pair a score and a weighted sum over ``head_dim`` (two
    products, a multiply and an add each); q and the output of ``heads``
    heads, K and V of ``kv_heads``, read or written once."""
    return (4 * heads * head_dim * visible_pairs(positions, window),
            _rows_bytes(heads, kv_heads, head_dim, positions, itemsize))


def band_flash_bwd(heads, kv_heads, head_dim, positions, window=None,
                   itemsize=2):
    """(operations, bytes) of the backward: five products a visible pair
    (the scores recomputed, dV, dP, dQ, dK); q, k, v, the output and its
    cotangent read and dq, dk, dv written once."""
    return (10 * heads * head_dim * visible_pairs(positions, window),
            2 * _rows_bytes(heads, kv_heads, head_dim, positions, itemsize))
