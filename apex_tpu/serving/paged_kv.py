"""Paged (block) KV cache with ref-counted copy-on-write prefix sharing.

vLLM-style memory management for the serving engine: instead of one
contiguous ``max_seq`` row per request (``inference.KVCache``), KV lives
in a pool of fixed-size blocks

    ``(num_blocks, layers, parts, block_size, width)``

whose trailing axes are the model's to define (``GPTModel.cache_record``;
``docs/source/pool_record.md``): the plain block caches K and V, 2 parts of
``kv_heads * head_dim`` numbers; latent attention one part, its compressed
latent and rotary key, and, in a second array of the pool over the layers
that own a sparse-attention indexer, their index keys (``data`` is then a
tuple of arrays that share the block axis, and every write, copy, export
and import below treats it leaf by leaf).  (A part of a token's record is
one lane-dense row: with ``head_dim`` 64 as the
minor dimension the TPU's default layout put the block axis in the
lanes, and every decode step relaid the whole pool for its kernel and
back.)  Each request owns an ordered *block table* mapping logical
position ``p`` to ``(table[p // block_size], p % block_size)``.  Admission
allocates ``ceil(len / block_size)`` blocks instead of a whole row, so
memory fragments by at most one block per request and short requests no
longer pin ``max_seq`` worth of HBM.

Prefix sharing: full blocks of prompt tokens are keyed in a radix trie
(node key = the block's token tuple).  A new request whose prompt starts
with an already-cached block chain *shares* those blocks (refcount + 1)
instead of recomputing and rewriting them — a fleet of requests carrying
the same system prompt stores its KV exactly once.  Sharing is safe
bitwise because post-RoPE K/V for a token depends only on the token ids
at and before it (verified by the engine parity tests across prompt
buckets).  The trie itself holds one reference per cached block, so
blocks outlive the request that produced them and are reclaimed lazily:
when the free list runs dry, least-recently-matched leaves are evicted.

Copy-on-write: writes must only ever target blocks with refcount 1.  The
serve loop guarantees this structurally (shared blocks are always *full*
prefix blocks; appends go to the exclusive tail), and :meth:`fork` +
:meth:`ensure_writable` expose the general mechanism for parallel
sampling — a forked sequence shares everything until its first divergent
write, which copies just the written block.

Block 0 is reserved as the *garbage block*: inactive decode-batch rows
point their entire table at it, so their (mathematically garbage) writes
can never corrupt a live block.

Window storage (``GPTModel.window_record``): a model whose window layers
attend to their last ``window`` positions alone keeps those layers' K and V
in an array of its own, ``window_data`` ``(window blocks, window layers, 2,
block_size, width)``: a ring of ``band = ceil(window / block_size) + 1``
blocks a slot, slot ``s`` owning blocks ``1 + s * band`` on and logical
block ``j`` lying at ``j mod band`` of its ring (:meth:`window_table_row`).
The blocks a tick reads, those of ``[position - window + 1, position]``,
are at most ``band`` consecutive ones, so no two of them meet in the ring;
there is nothing to allocate or return.  :meth:`advance_window` counts the
band a sequence holds and the blocks it leaves behind, and an admission
writes only the blocks of the prompt's last ``window`` positions
(:meth:`write_window_kv`).  Such a pool neither publishes nor reuses
prefixes (a shared prefix would have to carry blocks the window has already
left), and a sequence of it cannot be forked.

Bookkeeping is host-side (python ints and lists, like ``KVCache``); the
pool array is functional and reassigned on every device write.  Every
such write is a jitted program that takes the pool donated and updates
it in place, with a shape that follows the prompt's bucket or a power of
two of blocks, never a request's own length: an eager ``.at[].set()``
first copied the whole pool.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_context_kv(data, kv, ids, context_len):
    """A prefill's KV into the pool ``data``, in place.

    ``kv`` is ``(layers, parts, s, kv_heads, head_dim)``, or the prefill's
    ``(layers, parts, 1, s, kv_heads, head_dim)`` as it came (for a pool
    of several arrays, a tuple with one such entry an array); the cast, the
    merge of the heads into the row and the move of the blocks to the
    front all happen here.  ``ids`` ``(ceil(s / block_size),)`` names the
    block that takes each ``block_size`` rows of ``kv``; the garbage
    block 0 stands for every block that must not be written (a shared
    prefix in front, the bucket's padding behind), so an index may
    repeat.  In the block that holds position ``context_len - 1`` the
    rows from ``context_len`` on keep what they held.  The program's
    shape is ``kv``'s and nothing else: ``context_len`` is data.  (The
    name is what ``kv_write_time_share.ttft`` finds the module by.)
    """
    n = ids.shape[0]

    def write(data, kv):
        _, lyr, two, bs, width = data.shape
        rows = kv.astype(data.dtype).reshape(lyr, two, -1, width)
        rows = jnp.pad(rows, ((0, 0), (0, 0), (0, n * bs - rows.shape[2]),
                              (0, 0)))
        new = rows.reshape(lyr, two, n, bs, width).transpose(2, 0, 1, 3, 4)
        # past the context only the last block's rows reach a live block
        old = data[ids[(context_len - 1) // bs]]
        pos = jnp.arange(n * bs).reshape(n, 1, 1, bs, 1)
        return data.at[ids].set(jnp.where(pos < context_len, new, old[None]))

    return jax.tree.map(write, data, kv)


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_window_kv(data, kv, ids, start, context_len):
    """A prefill's window layers' KV into the window storage ``data``, in
    place: ``kv`` as :func:`scatter_context_kv` takes it, of which the
    ``ids.shape[0]`` blocks from logical block ``start`` on are written, to
    ``ids`` (0, the garbage block, for a block the sequence does not hold).
    ``start`` and ``context_len`` are data; the program's shape is ``kv``'s
    and the number of blocks a sequence's band spans."""
    bs, n = data.shape[3], ids.shape[0]
    s = kv.shape[-3]
    if s % bs:
        kv = jnp.pad(kv, [(0, 0)] * (kv.ndim - 3)
                     + [(0, bs - s % bs), (0, 0), (0, 0)])
    rows = jax.lax.dynamic_slice_in_dim(kv, start * bs, n * bs, kv.ndim - 3)
    return scatter_context_kv(data, rows, ids, context_len - start * bs)


@functools.partial(jax.jit, donate_argnums=(0,))
def scatter_blocks(arr, ids, blocks):
    """``arr[ids] = blocks`` in place, for the pool (each of its arrays)
    or its scales."""
    return jax.tree.map(lambda a, b: a.at[ids].set(b.astype(a.dtype)),
                        arr, blocks)


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_block(arr, src, dst):
    """``arr[dst] = arr[src]`` in place (copy-on-write), every array of
    the pool alike."""
    return jax.tree.map(lambda a: a.at[dst].set(a[src]), arr)


@functools.partial(jax.jit, donate_argnums=(0,))
def fill_block(arr, bid, value):
    """``arr[bid] = value`` in place (the int8 pool's zero-on-alloc)."""
    return arr.at[bid].set(value.astype(arr.dtype))


def _padded_blocks(ids, *payloads):
    """``ids`` and each payload's leading axis padded to a power of two
    by repeating the last block, so that a store of any number of blocks
    meets one of a few compiled shapes; the repeats write the same rows
    to the same block."""
    ids = np.asarray(ids, np.int32)
    pad = (1 << max(len(ids) - 1, 0).bit_length()) - len(ids)
    if pad == 0:
        return (ids, *payloads)
    return tuple(
        jax.tree.map(lambda a: np.pad(
            a, [(0, pad)] + [(0, 0)] * (np.ndim(a) - 1), mode="edge"), p)
        for p in (ids, *payloads))


class _TrieNode:
    """One cached full block: ``key`` is the block's token tuple, keyed
    under the parent (so the path from the root spells the prefix)."""

    __slots__ = ("key", "block", "parent", "children", "stamp")

    def __init__(self, key: Tuple[int, ...], block: int,
                 parent: "_TrieNode"):
        self.key = key
        self.block = block
        self.parent = parent
        self.children: Dict[Tuple[int, ...], _TrieNode] = {}
        self.stamp = 0


class PagedSequence:
    """A request's view of the pool: its block table and valid length.

    ``block_ids[i]`` backs logical positions ``[i*bs, (i+1)*bs)``;
    ``shared_tokens`` is the prefix length served from the trie at
    acquire time (those blocks arrived with KV already written).
    """

    __slots__ = ("block_ids", "num_tokens", "shared_tokens", "window_span")

    def __init__(self, block_ids: List[int], num_tokens: int,
                 shared_tokens: int):
        self.block_ids = block_ids
        self.num_tokens = num_tokens
        self.shared_tokens = shared_tokens
        # window storage: the logical blocks (first, last) of the band
        self.window_span: Optional[Tuple[int, int]] = None


class PagedKVCache:
    """Block pool + block tables + prefix trie for paged decode."""

    def __init__(self, num_blocks: int, block_size: int, layers: int,
                 kv_heads: int, head_dim: int, dtype=jnp.bfloat16,
                 share_prefixes: bool = True, registry=None,
                 name: str = "pool0", record=None, window=None,
                 window_slots: int = 0):
        """``record``: the model's ``cache_record()``, one ``(layers,
        parts, width[, dtype])`` an array of the pool; it stands in for
        ``layers``, ``kv_heads`` and ``head_dim``, which describe one array
        of K and V.  ``window``: the model's ``window_record()``, ``(layers,
        parts, width, window)``, and ``window_slots`` the sequences that run
        at once: the window storage holds the garbage block and a ring of
        :attr:`window_band` blocks for each."""
        if num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "reserved garbage block)")
        if block_size < 1:
            raise ValueError("block_size must be positive")
        record = record or ((layers, 2, kv_heads * head_dim),)
        # (layers, parts, width) in the cache's dtype, or with a dtype of
        # its own as a fourth entry
        arrays = tuple(jnp.zeros((num_blocks, *spec[:2], block_size, spec[2]),
                                 *spec[3:] or (dtype,)) for spec in record)
        # one array stays bare: the plain model's programs take it as such
        self.data = arrays[0] if len(arrays) == 1 else arrays
        self.block_size = block_size
        self.share_prefixes = share_prefixes
        self.name = name
        # block 0 is reserved: never allocated, never freed
        self._ref = np.zeros((num_blocks,), np.int32)
        self._ref[0] = 1
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._root = _TrieNode((), 0, None)  # sentinel; holds no block
        self._clock = 0
        self.prefix_hit_tokens = 0
        self.prefix_lookup_tokens = 0
        self.evicted_blocks = 0
        self.cow_copies = 0
        self._g_free = self._g_used = self._g_shared = None
        self._c_hits = self._c_evict = self._c_cow = None
        self.window_data, self.window = None, None
        self._g_window = self._c_window = None
        if window is not None:
            layers, parts, width, self.window = window
            self.window_data = jnp.zeros(
                (1 + window_slots * self.window_band, layers, parts,
                 block_size, width), dtype)
            self.window_blocks_held = self.window_released = 0
            self.share_prefixes = False     # see the module's docstring
            if registry is not None:
                self._g_window = registry.gauge(
                    "serving_window_blocks_held",
                    "window blocks the sequences hold: each its band alone",
                    ["cache"])
                self._c_window = registry.counter(
                    "serving_window_blocks_released_total",
                    "window blocks left behind the window", ["cache"])
        if registry is not None:
            self._g_free = registry.gauge(
                "serving_paged_blocks_free", "free pool blocks", ["cache"])
            self._g_used = registry.gauge(
                "serving_paged_blocks_used", "allocated pool blocks",
                ["cache"])
            self._g_shared = registry.gauge(
                "serving_paged_blocks_shared",
                "blocks referenced more than once (prefix sharing / COW)",
                ["cache"])
            self._c_hits = registry.counter(
                "serving_paged_prefix_hit_tokens_total",
                "prompt tokens served from the prefix trie", ["cache"])
            self._c_evict = registry.counter(
                "serving_paged_evicted_blocks_total",
                "cached prefix blocks reclaimed under memory pressure",
                ["cache"])
            self._c_cow = registry.counter(
                "serving_paged_cow_total", "copy-on-write block copies",
                ["cache"])
            registry.gauge(
                "serving_paged_token_bytes",
                "bytes one cached token costs over all layers: the "
                "model's record", ["cache"]).set(self.token_bytes,
                                                 cache=self.name)
        self._update_gauges()

    # -- accounting ----------------------------------------------------------

    @property
    def num_blocks(self) -> int:
        return jax.tree.leaves(self.data)[0].shape[0]

    @property
    def usable_blocks(self) -> int:
        return self.num_blocks - 1          # minus the garbage block

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.usable_blocks - len(self._free)

    @property
    def shared_blocks(self) -> int:
        return int(np.sum(self._ref[1:] > 1))

    @property
    def block_bytes(self) -> int:
        return sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize
                   for a in jax.tree.leaves(self.data))

    @property
    def token_bytes(self) -> int:
        """Bytes one cached token costs, all layers, parts and arrays (and,
        for the int8 pool, its share of the block's scales)."""
        return self.block_bytes // self.block_size

    def free_bytes(self) -> int:
        return self.free_blocks * self.block_bytes

    def used_bytes(self) -> int:
        return self.used_blocks * self.block_bytes

    def occupancy(self) -> float:
        return self.used_blocks / self.usable_blocks

    def stats(self) -> Dict[str, Any]:
        return {"free_blocks": self.free_blocks,
                "used_blocks": self.used_blocks,
                "shared_blocks": self.shared_blocks,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "prefix_lookup_tokens": self.prefix_lookup_tokens,
                "evicted_blocks": self.evicted_blocks,
                "cow_copies": self.cow_copies,
                "token_bytes": self.token_bytes}

    def _update_gauges(self) -> None:
        if self._g_free is not None:
            self._g_free.set(self.free_blocks, cache=self.name)
            self._g_used.set(self.used_blocks, cache=self.name)
            self._g_shared.set(self.shared_blocks, cache=self.name)
        if self._g_window is not None:
            self._g_window.set(self.window_blocks_held, cache=self.name)

    # -- window storage --------------------------------------------------------

    @property
    def window_band(self) -> int:
        """Window blocks a sequence holds at most, its ring's size:
        ``ceil(window / block_size) + 1`` (the band's first block may hold
        positions before it, its last the tick's own)."""
        return self.blocks_for(self.window) + 1

    def _band(self, position: int) -> Tuple[int, int]:
        """The logical blocks (first, last) of ``[position - window + 1,
        position]``."""
        bs = self.block_size
        return max(position - self.window + 1, 0) // bs, position // bs

    def _hold_window(self, seq: PagedSequence,
                     span: Optional[Tuple[int, int]]) -> int:
        """Count ``span`` (None: no band) as ``seq``'s band; the blocks
        left behind."""
        old = seq.window_span
        for sign, held in ((-1, old), (1, span)):
            if held is not None:
                self.window_blocks_held += sign * (held[1] - held[0] + 1)
        seq.window_span = span
        return 0 if old is None or span is None else span[0] - old[0]

    def advance_window(self, seq: PagedSequence, position: int) -> int:
        """Before the tick that writes ``position``: its band is the blocks
        of ``[position - window + 1, position]``.  Returns the blocks left
        behind the window since the last tick."""
        released = self._hold_window(seq, self._band(position))
        self.window_released += released
        if self._c_window is not None and released:
            self._c_window.inc(released, cache=self.name)
        self._update_gauges()
        return released

    def window_table_row(self, slot: int, max_blocks: int) -> np.ndarray:
        """Slot ``slot``'s window table: logical block ``j`` at block ``1 +
        slot * band + j mod band``, its ring."""
        band = self.window_band
        return (1 + slot * band + np.arange(max_blocks) % band).astype(
            np.int32)

    def write_window_kv(self, slot: int, kv, context_len: int) -> int:
        """Install a prefill's window layers' KV (``kv`` as
        :meth:`write_context_kv` takes it) into slot ``slot``'s ring, in
        place (:func:`scatter_window_kv`): the blocks of the prompt's last
        ``window`` positions, and at most a ring's worth of blocks around
        them, which the ring holds apart.  Returns the blocks of the prompt's
        band."""
        nb = self.blocks_for(kv.shape[-3])
        n = min(nb, self.window_band)
        lo, hi = self._band(context_len - 1)
        start = min(lo, nb - n)
        ids = self.window_table_row(slot, start + n)[start:]
        self.window_data = scatter_window_kv(
            self.window_data, kv, ids, np.int32(start),
            np.int32(context_len))
        return hi - lo + 1

    # -- block-level plumbing ------------------------------------------------

    def blocks_for(self, n_tokens: int) -> int:
        return -(-n_tokens // self.block_size)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _alloc_block(self) -> int:
        bid = self._free.pop()
        self._ref[bid] = 1
        return bid

    def _deref(self, bid: int) -> None:
        self._ref[bid] -= 1
        if self._ref[bid] == 0:
            self._free.append(bid)

    def _reserve(self, n: int) -> bool:
        """Make ``n`` blocks available, evicting cached prefixes LRU-first
        if the free list is short.  False when even a fully-drained trie
        cannot supply them."""
        while len(self._free) < n:
            if not self._evict_one():
                return False
        return True

    def _evict_one(self) -> bool:
        """Drop the least-recently-matched trie *leaf* whose block is held
        only by the trie.  Leaf-first ordering means a parent is never
        reclaimed under a live child (a sequence using the child also
        refs the parent, so the parent is never trie-only first)."""
        victim = None
        stack = [self._root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if (node is not self._root and not node.children
                    and self._ref[node.block] == 1):
                if victim is None or node.stamp < victim.stamp:
                    victim = node
        if victim is None:
            return False
        del victim.parent.children[victim.key]
        self._deref(victim.block)
        self.evicted_blocks += 1
        if self._c_evict is not None:
            self._c_evict.inc(cache=self.name)
        return True

    def flush_prefixes(self) -> int:
        """Drop the ENTIRE prefix trie at once (degradation-ladder
        level 2: shed cached state before shedding requests).  Every
        trie node's reference is released — blocks still held by live
        sequences survive until those release; trie-only blocks return
        to the free list immediately.  Returns the number of trie nodes
        dropped."""
        dropped = 0
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            self._deref(node.block)
            dropped += 1
        self._root.children = {}
        self.evicted_blocks += dropped
        if self._c_evict is not None and dropped:
            self._c_evict.inc(dropped, cache=self.name)
        self._update_gauges()
        return dropped

    # -- sequence lifecycle --------------------------------------------------

    def acquire(self, tokens: Sequence[int]) -> Optional[PagedSequence]:
        """Claim blocks for a context of ``tokens``.

        Matches the longest full-block prefix in the trie (capped so at
        least one context token is left for the caller to actually run —
        a fully-cached context would yield no logits to sample from),
        then allocates fresh exclusive blocks for the rest.  Returns
        None when the pool cannot supply them even after eviction; the
        caller is expected to requeue and retry.  Shared blocks already
        hold their KV — :meth:`write_context_kv` skips them.
        """
        n = len(tokens)
        if n < 1:
            raise ValueError("cannot acquire an empty context")
        bs = self.block_size
        shared: List[int] = []
        if self.share_prefixes:
            node = self._root
            for i in range((n - 1) // bs):
                child = node.children.get(tuple(tokens[i * bs:(i + 1) * bs]))
                if child is None:
                    break
                child.stamp = self._tick()
                shared.append(child.block)
                node = child
        shared_tokens = len(shared) * bs
        fresh_needed = self.blocks_for(n - shared_tokens)
        if not self._reserve(fresh_needed):
            return None
        blocks = shared + [self._alloc_block() for _ in range(fresh_needed)]
        for bid in shared:
            self._ref[bid] += 1
        seq = PagedSequence(blocks, n, shared_tokens)
        if self.window is not None:
            # the band an admission writes: the prompt's last positions
            self._hold_window(seq, self._band(n - 1))
        self.prefix_hit_tokens += shared_tokens
        self.prefix_lookup_tokens += n
        if self._c_hits is not None and shared_tokens:
            self._c_hits.inc(shared_tokens, cache=self.name)
        self._update_gauges()
        return seq

    def register_prefix(self, seq: PagedSequence,
                        tokens: Sequence[int]) -> None:
        """Publish ``seq``'s full context blocks into the trie so later
        requests with the same prompt prefix share them.  Call after the
        blocks' KV is written.  Each newly-published node takes one trie
        reference, which is what keeps the KV alive after ``seq``
        finishes."""
        if not self.share_prefixes:
            return
        bs = self.block_size
        node = self._root
        for i in range(len(tokens) // bs):
            key = tuple(tokens[i * bs:(i + 1) * bs])
            child = node.children.get(key)
            if child is None:
                child = _TrieNode(key, seq.block_ids[i], node)
                node.children[key] = child
                self._ref[seq.block_ids[i]] += 1
            child.stamp = self._tick()
            node = child
        self._update_gauges()

    def release(self, seq: PagedSequence) -> None:
        """Drop ``seq``'s references.  Trie-published blocks stay cached
        (the trie holds its own reference); exclusive blocks return to
        the free list."""
        for bid in seq.block_ids:
            self._deref(bid)
        if self.window is not None:
            self._hold_window(seq, None)
        seq.block_ids = []
        seq.num_tokens = 0
        self._update_gauges()

    def ensure_capacity(self, seq: PagedSequence, n_tokens: int) -> bool:
        """Grow ``seq``'s table to cover ``n_tokens`` logical positions
        (fresh exclusive blocks).  False when the pool is exhausted."""
        need = self.blocks_for(n_tokens) - len(seq.block_ids)
        if need <= 0:
            return True
        if not self._reserve(need):
            return False
        seq.block_ids.extend(self._alloc_block() for _ in range(need))
        self._update_gauges()
        return True

    def ensure_writable(self, seq: PagedSequence, block_index: int) -> int:
        """Copy-on-write: make ``seq.block_ids[block_index]`` exclusively
        owned before a write.  No-op (refcount already 1) on the normal
        serve path; a forked sequence pays one block copy here.  Returns
        the (possibly new) block id; raises MemoryError when the pool
        cannot supply the copy."""
        bid = seq.block_ids[block_index]
        if self._ref[bid] == 1:
            return bid
        if not self._reserve(1):
            raise MemoryError("pool exhausted during copy-on-write")
        new = self._alloc_block()
        self.data = copy_block(self.data, bid, new)
        self._ref[bid] -= 1
        seq.block_ids[block_index] = new
        self.cow_copies += 1
        if self._c_cow is not None:
            self._c_cow.inc(cache=self.name)
        self._update_gauges()
        return new

    def fork(self, seq: PagedSequence) -> Optional[PagedSequence]:
        """Clone ``seq`` sharing every block (parallel sampling: n
        continuations of one prompt).  Writers must call
        :meth:`ensure_writable` on the tail block before appending —
        that is where the copy-on-write actually triggers."""
        if self.window is not None:
            raise NotImplementedError(
                "fork of a sequence with window storage: its window blocks "
                "are its slot's ring, which each tick overwrites behind the "
                "window, so two sequences cannot share them")
        for bid in seq.block_ids:
            self._ref[bid] += 1
        self._update_gauges()
        return PagedSequence(list(seq.block_ids), seq.num_tokens,
                             seq.shared_tokens)

    # -- KV movement ---------------------------------------------------------

    def write_context_kv(self, seq: PagedSequence, kv,
                         context_len: int) -> int:
        """Install prefilled KV into ``seq``'s *exclusive* blocks, in
        place (:func:`scatter_context_kv`); returns how many blocks were
        written.

        ``kv``: ``(layers, parts, s, kv_heads, head_dim)`` for one
        sequence, or ``(layers, parts, 1, s, kv_heads, head_dim)`` as a
        prefill returns it, one such entry for each array of the pool
        (``s`` may be bucket-padded beyond ``context_len``).  The
        shared prefix ``[0, seq.shared_tokens)`` is skipped — those
        blocks already hold bitwise-identical KV from the prefill that
        published them, which is precisely the dedup win.
        """
        first = seq.shared_tokens // self.block_size   # block-aligned
        end = self.blocks_for(context_len)
        if end <= first:
            return 0
        ids = np.zeros(
            (self.blocks_for(jax.tree.leaves(kv)[0].shape[-3]),), np.int32)
        ids[first:end] = seq.block_ids[first:end]
        self.data = scatter_context_kv(self.data, kv, ids,
                                       np.int32(context_len))
        return end - first

    def table_row(self, seq: Optional[PagedSequence],
                  max_blocks: int) -> np.ndarray:
        """``seq``'s block table padded with the garbage block (0) —
        also the whole row for an inactive slot, so stray decode writes
        land in garbage instead of a live block."""
        row = np.zeros((max_blocks,), np.int32)
        if seq is not None:
            row[:len(seq.block_ids)] = seq.block_ids
        return row

    # -- cross-pool handoff (serving.disagg) ----------------------------------

    kind = "paged"                  # handoff compatibility tag

    def export_blocks(self, block_ids: Sequence[int]) -> Dict[str, Any]:
        """Snapshot ``block_ids``'s raw storage as host arrays — the
        payload a prefill→decode KV handoff ships.  Keys are
        storage-kind-specific; :meth:`import_blocks` on a pool of the
        same :attr:`kind` installs them bitwise."""
        ids = np.asarray(block_ids, np.int32)
        return {"data": jax.tree.map(lambda a: np.asarray(a[ids]),
                                     self.data)}

    def import_blocks(self, block_ids: Sequence[int],
                      payload: Dict[str, Any]) -> None:
        """Install a :meth:`export_blocks` payload into ``block_ids``
        (exclusively owned blocks of THIS pool), in place; a payload's
        keys name the pool's arrays."""
        if not len(block_ids):
            return
        ids, *blocks = _padded_blocks(block_ids, *payload.values())
        for name, rows in zip(payload, blocks):
            setattr(self, name, scatter_blocks(getattr(self, name), ids,
                                               rows))


class QuantizedPagedKVCache(PagedKVCache):
    """Int8 scale-per-block paged KV cache (EQuARX idiom applied to
    storage): the pool array holds int8 with one f32 scale per
    ``(block, layer, k/v, head)``, cutting KV bytes ~4x vs f32 (~2x vs
    bf16) — roughly double the concurrent users per chip, and the same
    factor off every cross-pool handoff.

    All bookkeeping (refcounts, trie, COW, eviction) is inherited
    unchanged; only storage semantics differ:

    * ``dtype`` becomes the COMPUTE dtype (what dequantization yields
      into the attention gather path); the pool itself is always int8.
    * **Zero-on-alloc invariant**: a block is zeroed (scale reset to
      1.0) when allocated, so positions beyond a sequence's valid
      length are exact zeros.  Whole-block requantization on append is
      then deterministic — a reused block's stale data can never leak
      into a fresh sequence's scale — which is what keeps the quantized
      stream reproducible across replicas with different allocation
      histories (the disaggregated handoff's bitwise guarantee).
    * Copy-on-write copies the scales alongside the block.
    * Shared (refcount > 1) blocks are never requantized — writers only
      ever touch exclusive blocks (the same structural guarantee COW
      relies on), so a published prefix block's quantization is frozen
      and prefix sharing stays bitwise.
    """

    kind = "paged_int8"

    def __init__(self, num_blocks: int, block_size: int, layers: int,
                 kv_heads: int, head_dim: int, dtype=jnp.bfloat16,
                 share_prefixes: bool = True, registry=None,
                 name: str = "pool0", record=None):
        if record is not None and tuple(record) != (
                (layers, 2, kv_heads * head_dim),):
            raise NotImplementedError(
                "the int8 pool keeps one scale a block, part and head of K "
                "and V: it has no form for a record of "
                f"{tuple(record)} (int8 latent records: a scale a block "
                "and position, which the sparse gather would have to read "
                "beside each record)")
        # before the base: its gauges read block_bytes, scales and all
        self.scales = jnp.ones((num_blocks, layers, 2, kv_heads),
                               jnp.float32)
        super().__init__(num_blocks, block_size, layers, kv_heads,
                         head_dim, dtype=jnp.int8,
                         share_prefixes=share_prefixes,
                         registry=registry, name=name)
        self.compute_dtype = jnp.dtype(dtype)
        if registry is not None:
            ref_bytes = (int(np.prod(self.data.shape[1:]))
                         * self.compute_dtype.itemsize)
            registry.gauge(
                "serving_kv_quant_compression_ratio",
                "quantized block bytes (incl. scales) over the compute-"
                "dtype block bytes", ["cache"]).set(
                    self.block_bytes / ref_bytes, cache=self.name)

    @property
    def block_bytes(self) -> int:
        scale_bytes = int(np.prod(self.scales.shape[1:])) * 4
        return (int(np.prod(self.data.shape[1:]))
                * self.data.dtype.itemsize + scale_bytes)

    def _alloc_block(self) -> int:
        bid = super()._alloc_block()
        # zero-on-alloc: see the class docstring
        self.data = fill_block(self.data, bid, np.int8(0))
        self.scales = fill_block(self.scales, bid, np.float32(1))
        return bid

    def ensure_writable(self, seq: PagedSequence, block_index: int) -> int:
        old = seq.block_ids[block_index]
        new = super().ensure_writable(seq, block_index)
        if new != old:
            self.scales = copy_block(self.scales, old, new)
        return new

    def write_context_kv(self, seq: PagedSequence, kv,
                         context_len: int) -> int:
        """One-shot per-block quantization of a monolithic prefill's
        KV.  NOTE: this quantizes each block over its final contents in
        one pass, whereas chunked prefill / decode requantize per
        appended token — the two paths are each deterministic but not
        bitwise-equal to each other, so engines that need bitwise
        migration on a quantized cache run chunked prefill everywhere
        (enforced by ``PagedInferenceEngine``)."""
        from apex_tpu.ops.flash_attention import quantize_kv_blocks

        bs = self.block_size
        start = seq.shared_tokens        # block-aligned by construction
        if context_len <= start:
            return 0
        if kv.ndim == 6:
            kv = kv[:, :, 0]
        ids = seq.block_ids[start // bs:self.blocks_for(context_len)]
        sl = np.zeros((kv.shape[0], kv.shape[1], len(ids) * bs,
                       *kv.shape[3:]), np.float32)
        sl[:, :, :context_len - start] = np.asarray(
            kv[:, :, start:context_len], np.float32)
        lyr, two = sl.shape[0], sl.shape[1]
        blocks = sl.reshape(lyr, two, len(ids), bs, *sl.shape[3:]
                            ).transpose(2, 0, 1, 3, 4, 5)
        padded, blocks = _padded_blocks(ids, blocks)
        q8, sc = quantize_kv_blocks(jnp.asarray(blocks))
        self.data = scatter_blocks(self.data, padded,
                                   q8.reshape(*q8.shape[:4], -1))
        self.scales = scatter_blocks(self.scales, padded, sc)
        return len(ids)

    def export_blocks(self, block_ids: Sequence[int]) -> Dict[str, Any]:
        ids = np.asarray(block_ids, np.int32)
        return {"data": np.asarray(self.data[ids]),
                "scales": np.asarray(self.scales[ids])}
