"""Operations and bytes the algorithms require, from shapes alone.
Recomputed operations do not count."""


def train_flops_per_token(n_params, layers, hidden, seq, causal=False):
    """Forward and backward of a dense transformer, per token: 6 per
    parameter (every parameter sits in one matmul; a tied embedding counts
    once, as the output head) plus attention's QK^T and PV, 2 * 2 * seq *
    hidden forward per layer, three times that with the backward, half of
    it where the mask is causal."""
    attention = 12 * layers * hidden * seq
    return 6 * n_params + (attention // 2 if causal else attention)


def flash_bwd(batch, heads, seq, head_dim, causal=False, itemsize=2):
    """(operations, bytes) of one attention backward: five seq x seq x
    head_dim matmuls (recomputed scores, dV, dP, dQ, dK); reads q, k, v, o,
    do and writes dq, dk, dv once each."""
    ops = 5 * 2 * batch * heads * seq * seq * head_dim
    return (ops // 2 if causal else ops,
            8 * batch * heads * seq * head_dim * itemsize)


def paged_decode(heads, head_dim, context_tokens, itemsize=2):
    """(operations, bytes) of paged decode attention over ``context_tokens``
    cached positions in all (sequences, ticks and layers together): QK^T
    and PV per position, and every cached K and V row read once.  The
    queries and outputs are a rounding error beside the cache."""
    return (4 * heads * head_dim * context_tokens,
            2 * heads * head_dim * context_tokens * itemsize)


def roofline_share(ops, nbytes, seconds, peak):
    """Percent of the least time the chip could take (the larger of
    operations over peak FLOP/s and bytes over peak bytes/s) in the time
    the kernel took."""
    least = max(ops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
    return 100.0 * least / seconds
