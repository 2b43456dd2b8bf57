"""Operations and bytes the new kernels of glm-5.2's serving path require,
from shapes and from the counts the engine's ``serving.decode.dispatch`` span
carries (``selected_tokens``, ``context_tokens``) alone, whatever implements
them.  Recomputed operations do not count.  ``chip_smoke.py``'s
``sparse_attention_timing`` and ``indexer_timing`` legs turn them into shares
of the roofline with ``harness/peaks.py``'s numbers; a per-layer metric needs
a reader, which is the next ``benchmark`` issue's (``PERF.md``, section 7).
"""


def sparse_attention(heads, kv_lora_rank, qk_rope_head_dim, selected_tokens,
                     itemsize=2):
    """(operations, bytes) of absorbed attention over ``selected_tokens``
    latent records in all (sequences, ticks and layers together): every
    head's score over ``kv_lora_rank + qk_rope_head_dim`` numbers and its
    weighted sum over ``kv_lora_rank``, a multiply and an add each; every
    selected record read once.  Queries and outputs are a rounding error
    beside the records."""
    width = kv_lora_rank + qk_rope_head_dim
    return (2 * heads * (width + kv_lora_rank) * selected_tokens,
            width * itemsize * selected_tokens)


def indexer_scores(index_n_heads, index_head_dim, context_tokens,
                   itemsize=2):
    """(operations, bytes) of the indexer's scoring of ``context_tokens``
    cached positions: a dot product of ``index_head_dim`` a head and
    position; every cached index key read once.  The ReLU and the weighted
    sum over heads are one operation a head beside ``2 * index_head_dim``."""
    return (2 * index_n_heads * index_head_dim * context_tokens,
            index_head_dim * itemsize * context_tokens)


def topk_select(context_tokens, itemsize=4):
    """(operations, bytes) of an exact top-k's input: every score read once
    and compared at least once; what a selection does beyond that is its
    implementation's."""
    return context_tokens, itemsize * context_tokens
