"""Operations and bytes the new kernels of command-a-plus-05-2026's serving
path require, from shapes and from the counts the engine's
``serving.decode.dispatch`` span carries (``context_tokens``,
``window_tokens``) alone, whatever implements them.  Recomputed operations
and masked positions do not count.  ``chip_smoke.py``'s
``window_flash_timing`` and ``grouped_decode_timing`` legs turn them into
shares of the roofline with ``harness/peaks.py``'s numbers; a per-layer
metric needs a reader that takes a prefill's bucket from the traced
admissions and a tick's counts from its span, which is a ``benchmark``
PR's (``PERF.md``, section 7).
"""


def visible_pairs(positions, window=None):
    """(row, key) pairs a causal attention over ``positions`` rows allows,
    each row over itself and the ``window - 1`` before it (all before it
    without a window)."""
    s = positions
    w = min(window or s, s)
    return w * (w + 1) // 2 + (s - w) * w


def window_flash(heads, kv_heads, head_dim, positions, window=None,
                 itemsize=2):
    """(operations, bytes) of a causal grouped attention forward over one
    sequence of ``positions``: for every query head and visible (row, key)
    pair a score and a weighted sum over ``head_dim``, a multiply and an
    add each; q and the output of ``heads`` heads, K and V of ``kv_heads``,
    read or written once."""
    return (4 * heads * head_dim * visible_pairs(positions, window),
            2 * (heads + kv_heads) * head_dim * positions * itemsize)


def grouped_decode(heads, kv_heads, head_dim, positions, itemsize=2):
    """(operations, bytes) of the tick's grouped attention over ``positions``
    cached positions in all (sequences, ticks and layers together; a window
    layer reads ``min(context, window)`` of a row, the full layer
    ``context``): every query head's score and weighted sum a position, and
    every cached K and V row of the ``kv_heads`` read once.  Queries and
    outputs are a rounding error beside the cache."""
    return (4 * heads * head_dim * positions,
            2 * kv_heads * head_dim * positions * itemsize)
