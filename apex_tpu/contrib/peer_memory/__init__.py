"""Halo exchange — TPU rebuild of ``apex/contrib/peer_memory/``
(``peer_memory.py`` + ``peer_memory_cuda.cu``) and
``apex/contrib/nccl_p2p/`` (the two transports behind
``apex/contrib/bottleneck/halo_exchangers.py``).

The reference moves spatial halo rows between neighboring GPUs through
CUDA-IPC peer mappings or NCCL P2P.  On TPU neighbors are ICI neighbors
and the transport is ``lax.ppermute`` (XLA collective-permute), which is
the hardware remote-DMA path — no pool/registration machinery needed, so
``PeerMemoryPool`` reduces to the exchanger itself.

Use inside ``shard_map`` with the spatial axis sharded over
``axis_name``.  Devices at the global edges receive zeros (ppermute's
missing-source semantics), which matches zero padding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["halo_exchange_1d", "PeerHaloExchanger1d", "PeerMemoryPool"]


def halo_exchange_1d(x, halo, axis_name, dim=1):
    """Exchange ``halo`` slices of axis ``dim`` with both mesh neighbors;
    returns ``x`` extended by the received halos (zeros at the ends)."""
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        pad = [(0, 0)] * x.ndim
        pad[dim] = (halo, halo)
        return jnp.pad(x, pad)
    down = [(i, i + 1) for i in range(n - 1)]     # i's bottom -> i+1's top
    up = [(i + 1, i) for i in range(n - 1)]       # i's top -> i-1's bottom
    bottom = jax.lax.slice_in_dim(x, x.shape[dim] - halo, x.shape[dim],
                                  axis=dim)
    top = jax.lax.slice_in_dim(x, 0, halo, axis=dim)
    halo_top = jax.lax.ppermute(bottom, axis_name, down)
    halo_bottom = jax.lax.ppermute(top, axis_name, up)
    return jnp.concatenate([halo_top, x, halo_bottom], axis=dim)


class PeerHaloExchanger1d:
    """Surface parity with ``halo_exchangers.HaloExchangerPeer`` /
    ``HaloExchangerNCCL``: exchanger bound to a mesh axis."""

    def __init__(self, axis_name, halo=1, dim=1):
        self.axis_name = axis_name
        self.halo = int(halo)
        self.dim = int(dim)

    def __call__(self, x, halo=None):
        return halo_exchange_1d(x, self.halo if halo is None else halo,
                                self.axis_name, self.dim)


class PeerMemoryPool:
    """The reference's IPC buffer pool has no TPU analogue (ppermute is
    bufferless); kept as the factory the bottleneck surface expects."""

    def __init__(self, static_size=0, dynamic_size=0, peer_ranks=None,
                 axis_name="spatial"):
        del static_size, dynamic_size, peer_ranks
        self.axis_name = axis_name

    def exchanger(self, halo=1, dim=1):
        return PeerHaloExchanger1d(self.axis_name, halo, dim)
