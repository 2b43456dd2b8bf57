"""NCCL-P2P halo exchange surface — TPU rebuild of
``apex/contrib/nccl_p2p/`` (``nccl_p2p.py`` + ``nccl_p2p_cuda.cu``).

The reference wraps ``ncclSend``/``ncclRecv`` pairs into
``left_right_halo_exchange``: every rank sends its left output halo to
the left neighbor and its right output halo to the right neighbor, and
receives the neighbors' halos back.  On TPU the transport is
``lax.ppermute`` over an ICI mesh axis — same wire pattern, compiled as
a collective-permute; edge ranks receive zeros (the reference leaves
edge buffers untouched and masks them in the caller).

Call inside ``shard_map`` with ``axis_name`` in scope.
"""

from __future__ import annotations

import jax

__all__ = ["left_right_halo_exchange", "left_right_halo_exchange_inplace",
           "get_unique_nccl_id", "init_nccl_comm"]


def left_right_halo_exchange(left_output_halo, right_output_halo,
                             axis_name: str = "spatial"):
    """Send left/right halos to the respective neighbors.

    Returns ``(left_input_halo, right_input_halo)``: what THIS device
    receives from its left and right neighbor (zeros at the edges) —
    reference ``nccl_p2p.left_right_halo_exchange``.
    """
    n = jax.lax.axis_size(axis_name)
    right_from_left = [(i, i + 1) for i in range(n - 1)]   # i -> i+1
    left_from_right = [(i + 1, i) for i in range(n - 1)]   # i -> i-1
    # my RIGHT output halo travels right: arrives as neighbor's LEFT input
    left_input_halo = jax.lax.ppermute(right_output_halo, axis_name,
                                       right_from_left)
    # my LEFT output halo travels left: arrives as neighbor's RIGHT input
    right_input_halo = jax.lax.ppermute(left_output_halo, axis_name,
                                        left_from_right)
    return left_input_halo, right_input_halo


def left_right_halo_exchange_inplace(left_output_halo, right_output_halo,
                                     left_input_halo, right_input_halo,
                                     axis_name: str = "spatial"):
    """Reference in-place variant; functional JAX has no aliasing, so the
    received halos are returned (the in-place buffers are ignored)."""
    del left_input_halo, right_input_halo
    return left_right_halo_exchange(left_output_halo, right_output_halo,
                                    axis_name)


def get_unique_nccl_id(n: int = 1):
    """Reference bootstrap helper; meaningless on TPU (the mesh IS the
    communicator).  Kept so call sites import cleanly."""
    return [0] * n


def init_nccl_comm(nccl_id=None, rank=None, world_size=None):
    """No-op: XLA collectives need no communicator objects."""
    return None
