"""Context parallelism: ring attention + all-to-all (Ulysses) sequence
parallelism — BEYOND-REFERENCE long-context support.

The reference has NO context parallelism (SURVEY §2.3: no ring
attention, no Ulysses anywhere in apex; its fused softmax caps at seq 4K
and fmha at 512; Megatron SP only reshards norm/dropout regions).  This
module is the documented parity-plus extension the survey calls for:
sequences sharded over a ``context`` mesh axis with attention computed
across the full global sequence, scaling sequence length with the mesh.

Two mechanisms (both differentiable end-to-end, both tested to
loss+grad parity against serial attention):

* :func:`ring_attention` — KV chunks rotate around the ICI ring via
  ``lax.ppermute`` while each device's queries stay resident; partial
  attention per chunk is merged with the streaming-softmax (running
  max / sum-exp) recombination, so memory is O(s_local * s_local) per
  step and the full (s_global x s_global) score matrix never exists.
  Causality is enforced through global positions, so chunks entirely in
  the future contribute nothing.  Autodiff through the
  ``scan``+``ppermute`` yields the backward ring automatically (the
  transpose of a rotation is the reverse rotation).

* :func:`ulysses_attention` — DeepSpeed-Ulysses resharding:
  ``all_to_all`` swaps the sequence shard for a HEAD shard, every device
  runs the Pallas flash kernel over the FULL sequence for its head
  slice, and a second ``all_to_all`` swaps back.  Cost is two
  all-to-alls; heads must divide the axis size.

Call either inside ``shard_map`` with the sequence dim sharded
contiguously over ``axis_name`` (rank r holds rows
``[r*s_local, (r+1)*s_local)``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from apex_tpu.ops.flash_attention import flash_attention

__all__ = ["ring_attention", "ulysses_attention"]

_f32 = jnp.float32
_NEG = -1e30


def ring_attention(q, k, v, axis_name: str = "context", causal: bool = False,
                   softmax_scale=None, remat: bool = True):
    """Exact global attention over a ring-sharded sequence.

    Args:
      q, k, v: ``(batch, heads, s_local, head_dim)`` — this device's
        sequence shard.
      axis_name: mesh axis the sequence is sharded over.
      causal: apply the global causal mask.
      remat: recompute each ring step's chunk scores in backward instead
        of saving them (memory ∝ one chunk instead of n chunks).

    Returns ``(batch, heads, s_local, head_dim)`` — attention of local
    queries over the GLOBAL key/value sequence.
    """
    n = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, h, sl, d = q.shape
    scale = softmax_scale if softmax_scale is not None else d ** -0.5
    if n == 1:
        return flash_attention(q, k, v, causal=causal,
                               softmax_scale=softmax_scale)

    qf = q.astype(_f32)
    rows = jnp.arange(sl)

    def chunk_scores(kc, chunk_id):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, kc.astype(_f32)) * scale
        if causal:
            g_q = rank * sl + rows                       # global query rows
            g_k = chunk_id * sl + rows                   # global key cols
            valid = g_k[None, :] <= g_q[:, None]
            s = jnp.where(valid[None, None], s, _NEG)
        return s

    def combine(m, l, acc, kc, vc, chunk_id):
        s = chunk_scores(kc, chunk_id)
        m_chunk = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_chunk)
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vc.astype(_f32))
        return m_new, l_new, acc_new

    if remat:
        combine = jax.checkpoint(combine)

    def step(carry, t):
        m, l, acc, kc, vc = carry
        m, l, acc = combine(m, l, acc, kc, vc, (rank - t) % n)
        # rotate KV one hop around the ring (device i -> i+1), so next
        # step this device holds chunk (rank - t - 1) mod n
        perm = [(i, (i + 1) % n) for i in range(n)]
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return (m, l, acc, kc, vc), None

    from apex_tpu.utils.collectives import ensure_varying

    # initial accumulators are constants (device-invariant); the loop
    # makes them varying over the ring axis, so the carry must start
    # varying for scan's type check (JAX 0.9 vma tracking)
    m0, l0, acc0 = ensure_varying(
        (jnp.full((b, h, sl, 1), _NEG, _f32),
         jnp.zeros((b, h, sl, 1), _f32),
         jnp.zeros((b, h, sl, d), _f32)), axis_name)
    # n-1 (combine, rotate) steps, then the last combine WITHOUT the
    # rotation — collectives in a scan body are never DCE'd, so a full
    # n-step scan would pay one dead KV ppermute pair per call
    (m, l, acc, kc, vc), _ = jax.lax.scan(
        step, (m0, l0, acc0, k, v), jnp.arange(n - 1))
    m, l, acc = combine(m, l, acc, kc, vc, (rank - (n - 1)) % n)
    # fully-masked rows (none exist with causal self-attention, but keep
    # the kernel's l==0 guard semantics)
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.astype(q.dtype)


def ulysses_attention(q, k, v, axis_name: str = "context",
                      causal: bool = False, softmax_scale=None,
                      block_q: int = 128, block_k: int = 128):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses).

    Reshards ``(b, h, s/n, d)`` → ``(b, h/n, s, d)`` with one
    ``all_to_all``, runs the Pallas flash kernel over the full sequence
    locally (so the MXU-optimized kernel does all the math), and
    reshards back.  ``heads`` must be divisible by the axis size.
    """
    n = jax.lax.axis_size(axis_name)
    b, h, sl, d = q.shape
    if n == 1:
        return flash_attention(q, k, v, causal=causal,
                               softmax_scale=softmax_scale,
                               block_q=block_q, block_k=block_k)
    if h % n:
        raise ValueError(
            f"heads ({h}) must be divisible by the context axis size ({n})")

    def to_seq(x):
        # (b, h, sl, d) -> (b, h/n, n*sl, d): split heads over the axis,
        # concatenate the gathered sequence chunks
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def to_heads(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    out = flash_attention(to_seq(q), to_seq(k), to_seq(v), causal=causal,
                          softmax_scale=softmax_scale, block_q=block_q,
                          block_k=block_k)
    return to_heads(out)
