"""apex_tpu.serving: paged KV cache, paged engine, scheduler, router.

The serving tier's correctness contract:

* the block pool's allocator/refcount/trie bookkeeping is exact (block
  counts, prefix sharing, LRU eviction, copy-on-write);
* paged decode attention equals the contiguous decode path BITWISE on
  the jnp route (same reference math over a gathered pool) and within
  kernel tolerance under forced-Pallas interpret mode;
* the paged engine's outputs are token-identical to the contiguous
  engine for greedy AND seeded stochastic sampling — with prefix
  sharing on, with chunked prefill, with speculative decoding, and
  across a ``preempt()`` requeue;
* the router places by load, sheds when every replica is overloaded,
  and honors SLO burn-rate pressure.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import (InferenceEngine, Request, SamplingParams)
from apex_tpu.models.gpt import GPTConfig, GPTModel
from apex_tpu.observability.slo import SLOMonitor, SLOTarget
from apex_tpu.ops.flash_attention import (
    flash_attention_chunk_paged,
    flash_attention_decode_paged,
    flash_attention_decode_reference,
    gather_paged_kv,
    scatter_paged_kv,
)
from apex_tpu.serving import (PagedInferenceEngine, PagedKVCache,
                              RequestShed, Router, SpeculativeConfig,
                              TickScheduler)
from apex_tpu.utils import set_force_pallas
from apex_tpu.utils.profiling import ServingMetrics


def tiny_cfg(**kw):
    base = dict(vocab_size=32, hidden_size=16, num_layers=2,
                num_attention_heads=2, max_seq_len=16)
    base.update(kw)
    return GPTConfig(**base)


@pytest.fixture(scope="module")
def tiny():
    model = GPTModel(tiny_cfg())
    return model, model.init_params(jax.random.PRNGKey(0))


def _clone(req: Request) -> Request:
    return dataclasses.replace(req)


def _mixed_requests(vocab=32):
    """Greedy + seeded-stochastic (temp / top-k / top-p) in one batch —
    the full sampling surface the parity guarantee covers."""
    return [
        Request(0, [1, 2, 3, 4, 5], max_new_tokens=6),
        Request(1, [1, 2, 3, 9], max_new_tokens=5, seed=7,
                sampling=SamplingParams(temperature=0.8, top_k=5)),
        Request(2, [1, 2, 3, 4, 5, 6, 7], max_new_tokens=4, seed=3,
                sampling=SamplingParams(temperature=1.1, top_p=0.9)),
        Request(3, [4, 4, 4], max_new_tokens=5, seed=11,
                sampling=SamplingParams(temperature=1.0, top_k=8,
                                        top_p=0.8)),
    ]


def _run(engine, reqs):
    for r in reqs:
        engine.submit(_clone(r))
    return {r.request_id: (r.tokens, r.finish_reason)
            for r in engine.run()}


# -- block pool --------------------------------------------------------------

class TestPagedKVCache:
    def _pool(self, blocks=9, bs=4, **kw):
        return PagedKVCache(blocks, bs, layers=2, kv_heads=2, head_dim=4,
                            dtype=jnp.float32, **kw)

    def test_accounting_and_reserved_block(self):
        p = self._pool()
        assert p.usable_blocks == 8 and p.free_blocks == 8
        seq = p.acquire([1] * 10)                # 3 blocks
        assert p.used_blocks == 3 and p.free_blocks == 5
        assert p.free_bytes() == 5 * p.block_bytes
        assert p.occupancy() == pytest.approx(3 / 8)
        p.release(seq)
        assert p.used_blocks == 0 and p.free_blocks == 8
        # block 0 is the garbage block: never handed out
        assert 0 not in seq.block_ids

    def test_prefix_sharing_stores_shared_blocks_once(self):
        p = self._pool(blocks=17)
        sysp = [1, 2, 3, 4, 5, 6, 7, 8]          # 2 full blocks
        a = p.acquire(sysp + [9])
        p.register_prefix(a, sysp + [9])
        b = p.acquire(sysp + [10])
        # b reuses a's two full prefix blocks, allocates only its tail
        assert b.shared_tokens == 8
        assert b.block_ids[:2] == a.block_ids[:2]
        assert p.used_blocks == 3 + 1            # NOT 3 + 3
        assert p.shared_blocks == 2
        assert p.prefix_hit_tokens == 8

    def test_prefix_cap_leaves_one_token_to_compute(self):
        p = self._pool()
        ctx = [1, 2, 3, 4, 5, 6, 7, 8]
        a = p.acquire(ctx)
        p.register_prefix(a, ctx)
        b = p.acquire(ctx)                       # fully cached context
        # capped at (n-1)//bs blocks: the last token stays uncached so
        # admission still has logits to sample from
        assert b.shared_tokens == 4

    def test_trie_retention_and_lru_eviction(self):
        p = self._pool(blocks=5, bs=4)           # 4 usable
        a = p.acquire([1] * 8)                   # 2 blocks
        p.register_prefix(a, [1] * 8)
        p.release(a)
        assert p.used_blocks == 2                # trie retains the KV
        # demand for 4 blocks forces LRU leaf eviction of the cached pair
        b = p.acquire([9] * 16)
        assert b is not None and len(b.block_ids) == 4
        assert p.evicted_blocks == 2
        assert p.acquire([5] * 4) is None        # truly exhausted

    def test_fork_copy_on_write(self):
        p = self._pool()
        a = p.acquire([1, 2, 3, 4, 5])
        b = p.fork(a)
        assert b.block_ids == a.block_ids
        tail = len(a.block_ids) - 1
        shared_id = a.block_ids[tail]
        new = p.ensure_writable(b, tail)
        assert new != shared_id and b.block_ids[tail] == new
        assert a.block_ids[tail] == shared_id    # a untouched
        assert p.cow_copies == 1
        # exclusive block: writable in place, no copy
        assert p.ensure_writable(a, tail) == shared_id
        assert p.cow_copies == 1

    def test_copy_on_write_copies_the_block_and_nothing_else(self, rng):
        """The forked writer's new block holds the shared block's rows;
        every other block, the shared one too, holds what it held."""
        p = self._pool()
        p.data = jnp.asarray(rng.randn(*p.data.shape), jnp.float32)
        a = p.acquire([1, 2, 3, 4, 5])
        b = p.fork(a)
        before = np.asarray(p.data)
        tail = len(a.block_ids) - 1
        new = p.ensure_writable(b, tail)
        after = np.asarray(p.data)
        np.testing.assert_array_equal(after[new], before[a.block_ids[tail]])
        others = [i for i in range(p.num_blocks) if i != new]
        np.testing.assert_array_equal(after[others], before[others])

    def test_rows_are_lane_dense(self):
        """A token's K or V is ONE row of ``kv_heads * head_dim``: the
        minor dimension the TPU keeps row-major (a minor dimension of
        ``head_dim`` 64 put the block axis in the lanes)."""
        p = PagedKVCache(9, 8, layers=3, kv_heads=16, head_dim=64)
        assert p.data.shape == (9, 3, 2, 8, 1024)
        assert p.block_bytes == 3 * 2 * 8 * 1024 * 2

    @pytest.mark.parametrize("context_len,shared_blocks,s", [
        (8, 0, 16), (11, 0, 16), (3, 0, 16), (14, 2, 16),
        # padded to a bucket well beyond the context
        (8, 0, 32), (11, 0, 32), (3, 0, 8), (14, 2, 32),
        # a length that is no multiple of the block: padded in the program
        (11, 0, 14), (14, 2, 15), (3, 0, 3)])
    @pytest.mark.parametrize("as_prefilled", [False, True])
    def test_write_context_kv_round_trip(self, rng, context_len,
                                         shared_blocks, s, as_prefilled):
        """Prefilled ``(layers, 2, s, heads, head_dim)`` KV (or the
        prefill's own ``(layers, 2, 1, s, heads, head_dim)``) comes back
        from the pool's rows position for position: whole blocks, a
        ragged tail, a context inside one block, and a shared prefix
        that the write must skip.  Outside the sequence's own blocks and
        the garbage block nothing moves, the last block's rows past the
        context keep what they held, and the pool is what a plain numpy
        write of the same rows leaves."""
        p = self._pool(blocks=17)
        p.data = jnp.asarray(rng.randn(*p.data.shape), jnp.float32)
        toks = list(range(1, context_len + 1))
        if shared_blocks:
            first = p.acquire(toks[:shared_blocks * 4 + 1])
            p.register_prefix(first, toks[:shared_blocks * 4 + 1])
        seq = p.acquire(toks)
        assert seq.shared_tokens == shared_blocks * 4
        before = np.asarray(p.data)
        kv = rng.randn(2, 2, s, 2, 4).astype(np.float32)
        written = p.write_context_kv(
            seq, jnp.asarray(kv[:, :, None] if as_prefilled else kv),
            context_len)
        assert written == len(seq.block_ids) - shared_blocks
        after = np.asarray(p.data)
        tbl = jnp.asarray(p.table_row(seq, 4)[None])
        for li in range(2):
            for which in range(2):
                got = gather_paged_kv(p.data, li, which, tbl, heads=2)
                np.testing.assert_array_equal(
                    np.asarray(got[0, seq.shared_tokens:context_len]),
                    kv[li, which, seq.shared_tokens:context_len])
        # (a) the shared prefix's blocks and every block of others
        own = set(seq.block_ids[shared_blocks:])
        for bid in range(1, p.num_blocks):
            if bid not in own:
                np.testing.assert_array_equal(after[bid], before[bid])
        # (b) the last block past the context
        rem = context_len % 4
        if rem:
            np.testing.assert_array_equal(
                after[seq.block_ids[-1]][:, :, rem:],
                before[seq.block_ids[-1]][:, :, rem:])
        # (c) a plain write of the same rows
        want = before.copy()
        for pos in range(seq.shared_tokens, context_len):
            want[seq.block_ids[pos // 4], :, :, pos % 4] = \
                kv[:, :, pos].reshape(2, 2, 8)
        np.testing.assert_array_equal(after[1:], want[1:])

    def test_context_write_traces_once_per_bucket(self, monkeypatch):
        """The serving cell's 16 prompt lengths, each padded to its
        power-of-two bucket as the engine pads it, through one pool: the
        write is traced once for each bucket and never for a length, a
        number of blocks, a tail or a shared prefix."""
        import json
        import os
        from apex_tpu.serving import paged_kv
        from benchmarks.harness import traffic
        with open(os.path.join(
                os.path.dirname(__file__), os.pardir, "benchmarks",
                "traffic", "shortreply-steady.json")) as f:
            lengths = traffic.prompt_lengths(json.load(f))
        assert len(lengths) == 16
        traces = []
        body = paged_kv.scatter_context_kv.__wrapped__

        def counted(data, kv, ids, context_len):
            traces.append(kv.shape)
            return body(data, kv, ids, context_len)

        monkeypatch.setattr(paged_kv, "scatter_context_kv",
                            jax.jit(counted, donate_argnums=(0,)))
        p = PagedKVCache(1025, 8, layers=1, kv_heads=1, head_dim=8,
                         dtype=jnp.float32)
        buckets = set()
        for rnd in range(2):                     # the second round shares
            for n in lengths:
                bucket = 8
                while bucket < n:
                    bucket *= 2
                buckets.add(bucket)
                toks = [n] * (n - rnd) + [0] * rnd
                seq = p.acquire(toks)
                assert bool(seq.shared_tokens) == bool(rnd)
                kv = jnp.full((1, 2, 1, bucket, 1, 8), float(n))
                assert p.write_context_kv(seq, kv, n) == \
                    p.blocks_for(n) - seq.shared_tokens // 8
                p.register_prefix(seq, toks)
                p.release(seq)
        assert buckets == {32, 64, 128, 256, 512}
        assert sorted(shape[3] for shape in traces) == sorted(buckets)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_export_import_round_trip(self, rng, dtype):
        """The handoff's payload is the blocks' rows as they lie:
        exported, imported into other block ids of another pool, and
        read back bitwise through the gather."""
        src = self._pool(blocks=9)
        dst = self._pool(blocks=9)
        src.data = jnp.asarray(rng.randn(*src.data.shape), dtype)
        dst.data = dst.data.astype(dtype)
        payload = src.export_blocks([2, 5, 7])
        assert payload["data"].shape == (3,) + src.data.shape[1:]
        dst.import_blocks([1, 3, 4], payload)
        a = gather_paged_kv(src.data, 1, 0, jnp.asarray([[2, 5, 7]]), 2)
        b = gather_paged_kv(dst.data, 1, 0, jnp.asarray([[1, 3, 4]]), 2)
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert bool(jnp.all(dst.data[2] == 0))       # untouched

    def test_gauges_exported(self):
        from apex_tpu.observability import MetricsRegistry
        reg = MetricsRegistry()
        p = self._pool(registry=reg)
        p.acquire([1] * 10)
        text = reg.prometheus()
        assert "serving_paged_blocks_used" in text
        assert 'cache="pool0"' in text


# -- paged attention kernels -------------------------------------------------

class TestPagedAttention:
    LAYER = 1                                    # of 3: the middle one

    def _paged(self, rng, b=3, nb=4, bs=8, h=2, d=16, pool_blocks=32,
               lens=None):
        """A whole pool ``(blocks, 3 layers, 2, bs, h*d)`` with distinct
        values everywhere, so a read of the wrong layer or of K for V
        cannot pass.  Tables never name block 0: the pool reserves it,
        and a table that starts with it is a slot without a request."""
        pool = jnp.asarray(rng.randn(pool_blocks, 3, 2, bs, h * d),
                           jnp.float32)
        tables = jnp.asarray(
            1 + rng.choice(pool_blocks - 1, size=(b, nb), replace=False)
            .reshape(b, nb), jnp.int32)
        q = jnp.asarray(rng.randn(b, h, d), jnp.float32)
        lens = jnp.asarray([1, 17, nb * bs] if lens is None else lens,
                           jnp.int32)
        return q, pool, tables, lens

    def _gathered(self, pool, tbl, h):
        return (gather_paged_kv(pool, self.LAYER, 0, tbl, h),
                gather_paged_kv(pool, self.LAYER, 1, tbl, h))

    def test_gather_layout(self, rng):
        q, pool, tbl, lens = self._paged(rng)
        b, nb = tbl.shape
        bs, h = pool.shape[3], q.shape[1]
        for kv, g in enumerate(self._gathered(pool, tbl, h)):
            for i in range(b):
                for p in (0, 9, nb * bs - 1):
                    np.testing.assert_array_equal(
                        np.asarray(g[i, p]).reshape(-1),
                        np.asarray(pool[int(tbl[i, p // bs]), self.LAYER,
                                        kv, p % bs]))

    def test_jnp_path_bitwise_vs_reference(self, rng):
        """Off-TPU the paged decode IS the contiguous reference over a
        gathered pool — equality is exact, not approximate."""
        q, pool, tbl, lens = self._paged(rng)
        out = flash_attention_decode_paged(q, pool, self.LAYER, tbl, lens)
        ref = flash_attention_decode_reference(
            q, *self._gathered(pool, tbl, q.shape[1]), lens)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_pallas_interpret_matches_reference(self, rng):
        q, pool, tbl, lens = self._paged(rng, h=4, d=32)
        self._kernel_vs_reference(q, pool, tbl, lens)

    def _kernel_vs_reference(self, q, pool, tbl, lens):
        """The kernel against the reference on the gathered cache; a row
        whose table starts with block 0 holds no request and reads as
        zeros."""
        ref = flash_attention_decode_reference(
            q, *self._gathered(pool, tbl, q.shape[1]), lens)
        ref = jnp.where((tbl[:, 0] == 0)[:, None, None], 0.0, ref)
        set_force_pallas(True)
        try:
            fn = lambda *a: flash_attention_decode_paged(  # noqa: E731
                a[0], a[1], self.LAYER, a[2], a[3])
            # the kernel, not the gather path the toy widths take
            assert "pallas_call" in str(
                jax.make_jaxpr(fn)(q, pool, tbl, lens))
            out = fn(q, pool, tbl, lens)
        finally:
            set_force_pallas(None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
        return out

    @pytest.mark.parametrize("h,d", [(2, 64), (4, 32), (2, 128), (16, 64)])
    @pytest.mark.parametrize("bs", [8, 16])
    def test_lane_dense_kernel_ragged(self, rng, bs, h, d):
        """The in-place kernel at lane-dense widths: lengths of one
        token, mid-block, a block's edge, one past it and a full table,
        on layer 1 of 3 with K and V told apart."""
        nb = 4
        lens = [1, bs - 3, bs, bs + 1, nb * bs]
        q, pool, tbl, lens = self._paged(rng, b=5, nb=nb, bs=bs, h=h, d=d,
                                         pool_blocks=24, lens=lens)
        self._kernel_vs_reference(q, pool, tbl, lens)

    @pytest.mark.parametrize("bs", [8, 16])
    def test_lane_dense_kernel_garbage_rows(self, rng, bs):
        """Inactive slots point their whole table at the garbage block
        and carry a stale length: the kernel reads nothing for them and
        writes zeros, and the live rows stay exact."""
        q, pool, tbl, lens = self._paged(rng, b=4, nb=3, bs=bs, h=2, d=64,
                                         pool_blocks=16,
                                         lens=[2 * bs + 1, 1, bs, 5])
        tbl = tbl.at[1].set(0).at[3].set(0)
        out = self._kernel_vs_reference(q, pool, tbl, lens)
        assert not np.asarray(out[jnp.asarray([1, 3])]).any()

    # what the kernel's loop over a row's live blocks has to get right;
    # ``group`` is the number of blocks one step of it brings to VMEM
    _WALKS = {
        "one_full_row_among_rows_of_one_block":
            lambda group, bs, nb: [1, nb * bs, 1, bs, 1],
        "a_group_less_one_position":
            lambda group, bs, nb: [group * bs - 1] * 2,
        "a_group_exactly":
            lambda group, bs, nb: [group * bs] * 2,
        "a_group_and_one_position":
            lambda group, bs, nb: [group * bs + 1] * 2,
        "two_groups_around_their_edge":
            lambda group, bs, nb: [2 * group * bs - 1, 2 * group * bs,
                                   2 * group * bs + 1],
        "every_slot_empty":
            lambda group, bs, nb: [0, 1, bs + 3, nb * bs],
        "empty_rows_between_live_ones":
            lambda group, bs, nb: [5, 1, nb * bs - 2, 1, 1, group * bs + 2],
        "blocks_descending_and_shared":
            lambda group, bs, nb: [nb * bs, nb * bs - bs - 1, 3 * bs],
    }

    @pytest.mark.parametrize("bs", [8, 16])
    @pytest.mark.parametrize("case", list(_WALKS))
    def test_kernel_walks_the_live_blocks(self, rng, case, bs):
        """Ragged and empty rows at a table far wider than most rows
        need (20 entries, two and a half groups), on layer 1 of 3 with K
        told from V: each row against the reference on its gathered
        cache, an empty row against zeros."""
        from apex_tpu.ops.flash_attention import _PAGED_GROUP as group
        nb = 2 * group + 4
        lens = self._WALKS[case](group, bs, nb)
        q, pool, tbl, lens = self._paged(
            rng, b=len(lens), nb=nb, bs=bs, h=2, d=64,
            pool_blocks=1 + len(lens) * nb, lens=lens)
        if case == "every_slot_empty":
            tbl = jnp.zeros_like(tbl)
        elif case == "empty_rows_between_live_ones":
            tbl = tbl.at[1].set(0).at[3].set(0).at[4].set(0)
        elif case == "blocks_descending_and_shared":
            # one row's blocks in falling order, the next two sharing a
            # prefix of it, as forked or prefix-cached sequences do
            down = jnp.sort(tbl[0])[::-1]
            tbl = tbl.at[0].set(down).at[1].set(down).at[2, :2].set(down[:2])
        out = self._kernel_vs_reference(q, pool, tbl, lens)
        assert bool(jnp.all(jnp.isfinite(out)))
        if case == "every_slot_empty":
            assert not np.asarray(out).any()

    def test_toy_width_takes_gather_path_under_pallas(self, rng):
        """Rows narrower than a 128-lane register are not the kernel's:
        forced Pallas still answers, bitwise, through the gather."""
        q, pool, tbl, lens = self._paged(rng)            # h*d = 32
        ref = flash_attention_decode_paged(q, pool, self.LAYER, tbl, lens)
        set_force_pallas(True)
        try:
            out = flash_attention_decode_paged(q, pool, self.LAYER, tbl,
                                               lens)
        finally:
            set_force_pallas(None)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    @pytest.mark.parametrize("lead", [(3,), (2, 4)])
    def test_scatter_is_the_gathers_inverse(self, rng, lead):
        """``scatter_paged_kv`` writes one decode token per row, or a
        chunk of them, where ``gather_paged_kv`` reads them, and touches
        nothing else; the merged-row update the TPU takes and the
        ``(heads, head_dim)`` view taken off it write the same bytes."""
        h, d, bs = 2, 16, 8
        pool = jnp.asarray(rng.randn(16, 3, 2, bs, h * d), jnp.float32)
        n = int(np.prod(lead))
        bids = jnp.asarray(rng.choice(16, size=n, replace=False)
                           .reshape(lead), jnp.int32)
        offs = jnp.asarray(rng.randint(0, bs, size=lead), jnp.int32)
        x = jnp.asarray(rng.randn(*lead, h, d), jnp.float32)
        out = scatter_paged_kv(pool, self.LAYER, 1, bids, offs, x)
        set_force_pallas(True)
        try:
            rows = scatter_paged_kv(pool, self.LAYER, 1, bids, offs, x)
        finally:
            set_force_pallas(None)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(rows))
        g = gather_paged_kv(out, self.LAYER, 1, bids.reshape(n, 1), h)
        np.testing.assert_array_equal(
            np.asarray(g[jnp.arange(n), offs.reshape(n)]),
            np.asarray(x.reshape(n, h, d)))
        untouched = np.ones(pool.shape, bool)
        untouched[np.asarray(bids).reshape(n), self.LAYER, 1,
                  np.asarray(offs).reshape(n)] = False
        np.testing.assert_array_equal(np.asarray(out)[untouched],
                                      np.asarray(pool)[untouched])

    def test_chunk_matches_per_position_decode(self, rng):
        b, nb, bs, h, d, c = 2, 3, 8, 2, 16, 4
        pool = jnp.asarray(rng.randn(16, 3, 2, bs, h * d), jnp.float32)
        tbl = jnp.asarray(rng.choice(16, size=(b, nb), replace=False)
                          .reshape(b, nb), jnp.int32)
        q = jnp.asarray(rng.randn(b, h, c, d), jnp.float32)
        qpos = jnp.asarray([[3, 4, 5, 6], [10, 11, 12, 13]], jnp.int32)
        out = flash_attention_chunk_paged(q, pool, self.LAYER, tbl, qpos)
        gk, gv = self._gathered(pool, tbl, h)
        for j in range(c):
            ref = flash_attention_decode_reference(
                q[:, :, j], gk, gv, qpos[:, j] + 1)
            np.testing.assert_allclose(np.asarray(out[:, :, j]),
                                       np.asarray(ref),
                                       rtol=1e-5, atol=1e-5)


# -- tick scheduler ----------------------------------------------------------

class TestTickScheduler:
    def test_budget_split_and_caps(self):
        s = TickScheduler(token_budget=32, min_chunk=4, max_chunk=16)
        plan = s.plan(8, [(0, 100), (1, 100)])
        # 8 decode tokens leave 24: head gets max_chunk, next the rest
        assert plan.chunks == {0: 16, 1: 8} and plan.decode

    def test_head_progress_guarantee(self):
        s = TickScheduler(token_budget=8, min_chunk=4, max_chunk=16)
        plan = s.plan(8, [(0, 100), (1, 100)])   # decode exceeds budget
        assert plan.chunks == {0: 4}             # head still advances

    def test_speculative_cost_accounting(self):
        s = TickScheduler(token_budget=32, min_chunk=4, max_chunk=16)
        assert s.plan(4, [(0, 100)], spec_tokens=3).chunks == {0: 16}
        assert s.plan(7, [(0, 100)], spec_tokens=3).chunks == {0: 4}

    def test_validation(self):
        with pytest.raises(ValueError):
            TickScheduler(token_budget=0)
        with pytest.raises(ValueError):
            TickScheduler(min_chunk=8, max_chunk=4)


# -- paged engine parity -----------------------------------------------------

class TestPagedEngine:
    def _ref(self, tiny, reqs, **kw):
        model, params = tiny
        return _run(InferenceEngine(model, params, max_slots=4,
                                    cache_dtype=jnp.float32, **kw), reqs)

    def test_decode_logits_bitwise(self, tiny):
        """Below the token level: the paged decode step's logits are
        BITWISE the contiguous decode step's, prompt through decode."""
        self._assert_decode_logits_bitwise(*tiny)

    def test_decode_logits_bitwise_learned_positions(self):
        """The same without the rotation (GPT-2's learned positions, the
        benchmark's serving configuration)."""
        model = GPTModel(tiny_cfg(rotary=False))
        self._assert_decode_logits_bitwise(
            model, model.init_params(jax.random.PRNGKey(0)))

    def _assert_decode_logits_bitwise(self, model, params):
        base = InferenceEngine(model, params, max_slots=2,
                               cache_dtype=jnp.float32)
        paged = PagedInferenceEngine(model, params, max_slots=2,
                                     block_size=4,
                                     cache_dtype=jnp.float32)
        prompts = [[1, 2, 3, 4, 5], [7, 8, 9]]
        for i, pr in enumerate(prompts):
            base.submit(Request(i, pr, max_new_tokens=8))
            paged.submit(Request(i, pr, max_new_tokens=8))
        base._evict_expired(); base._admit()
        paged._evict_expired(); paged._admit()
        for _ in range(5):
            n = base.cache.slots
            toks = np.zeros((n,), np.int32)
            pos = np.zeros((n,), np.int32)
            for s, st in base._active.items():
                toks[s], pos[s] = st.next_token, st.position
                assert paged._grow(s, st.position + 1)
            bl, bi, base.cache.data = base._decode(
                base.params, jnp.asarray(toks), base.cache.data,
                jnp.asarray(pos))
            pl, pi, paged.pool.data = paged._decode_paged(
                paged.params, jnp.asarray(toks), paged.pool.data,
                jnp.asarray(paged._tables), jnp.asarray(pos))
            np.testing.assert_array_equal(
                np.asarray(bl).view(np.uint32),
                np.asarray(pl).view(np.uint32))
            # the ids the ticks picked, and the logits as a host array
            np.testing.assert_array_equal(np.asarray(bi), np.asarray(pi))
            base._advance_slots(sorted(base._active), np.asarray(bi),
                                np.asarray(bl))
            paged._advance_slots(sorted(paged._active), np.asarray(pi),
                                 np.asarray(pl))

    def test_token_parity_greedy_and_seeded(self, tiny):
        model, params = tiny
        reqs = _mixed_requests()
        ref = self._ref(tiny, reqs)
        out = _run(PagedInferenceEngine(model, params, max_slots=4,
                                        block_size=4,
                                        cache_dtype=jnp.float32), reqs)
        assert out == ref

    def test_prefix_sharing_parity_and_block_savings(self, tiny):
        model, params = tiny
        sysp = [1, 2, 3, 4, 5, 6, 7, 8]
        reqs = [Request(i, sysp + [9 + i], max_new_tokens=3)
                for i in range(4)]
        shared = PagedInferenceEngine(model, params, max_slots=4,
                                      block_size=4,
                                      cache_dtype=jnp.float32)
        unshared = PagedInferenceEngine(model, params, max_slots=4,
                                        block_size=4, share_prefixes=False,
                                        cache_dtype=jnp.float32)
        for r in reqs:
            shared.submit(_clone(r)); unshared.submit(_clone(r))
        shared.step(); unshared.step()
        # the 2-block system prompt is stored ONCE, not once per request
        assert shared.pool.shared_blocks == 2
        assert shared.pool.used_blocks == unshared.pool.used_blocks - 6
        a = {r.request_id: r.tokens for r in shared.run()}
        b = {r.request_id: r.tokens for r in unshared.run()}
        assert a == b == {r.request_id: self._ref(tiny, [r])[
            r.request_id][0] for r in reqs}

    def test_chunked_prefill_parity(self, tiny):
        model, params = tiny
        reqs = _mixed_requests()
        ref = self._ref(tiny, reqs)
        out = _run(PagedInferenceEngine(
            model, params, max_slots=4, block_size=4,
            cache_dtype=jnp.float32, chunked_prefill=True,
            scheduler=TickScheduler(token_budget=8, min_chunk=2,
                                    max_chunk=4)), reqs)
        assert out == ref

    def test_speculative_parity_and_perfect_draft_accepts(self, tiny):
        model, params = tiny
        reqs = _mixed_requests()
        ref = self._ref(tiny, reqs)
        eng = PagedInferenceEngine(
            model, params, max_slots=4, block_size=4,
            cache_dtype=jnp.float32,
            speculative=SpeculativeConfig(model, params, num_tokens=2))
        out = _run(eng, reqs)
        assert out == ref
        # draft == target => every greedy proposal matches the canonical
        # stream; stochastic rows share the (seed, index) keys too
        assert eng.spec_proposed > 0
        assert eng.spec_accept_rate == 1.0

    def test_speculative_with_chunked_prefill_parity(self, tiny):
        model, params = tiny
        reqs = _mixed_requests()
        out = _run(PagedInferenceEngine(
            model, params, max_slots=4, block_size=4,
            cache_dtype=jnp.float32, chunked_prefill=True,
            speculative=SpeculativeConfig(model, params, num_tokens=3)),
            reqs)
        assert out == self._ref(tiny, reqs)

    def test_speculative_config_validation(self, tiny):
        model, params = tiny
        with pytest.raises(ValueError):
            SpeculativeConfig(model, params, num_tokens=0)
        other = GPTModel(tiny_cfg(vocab_size=64))
        with pytest.raises(ValueError):
            SpeculativeConfig(other, params).validate_against(model)

    def test_block_size_must_divide_max_seq(self, tiny):
        model, params = tiny
        with pytest.raises(ValueError):
            PagedInferenceEngine(model, params, block_size=5)

    def test_kv_gauges_exported(self, tiny):
        model, params = tiny
        eng = PagedInferenceEngine(model, params, max_slots=2,
                                   block_size=4)
        eng.submit(Request(0, [1, 2, 3], max_new_tokens=2))
        eng.step()
        text = eng.metrics.registry.prometheus()
        assert "serving_kv_free_bytes" in text
        assert "serving_paged_blocks_used" in text


# -- preemption x paged cache (resilience satellite) -------------------------

class TestPagedPreemption:
    def test_preempt_releases_blocks_and_resumes_token_identical(
            self, tiny):
        model, params = tiny
        reqs = [Request(i, [1 + i, 2, 3, 4, 5], max_new_tokens=8)
                for i in range(2)]
        ref = _run(InferenceEngine(model, params, max_slots=2,
                                   cache_dtype=jnp.float32), reqs)
        eng = PagedInferenceEngine(model, params, max_slots=2,
                                   block_size=4, cache_dtype=jnp.float32)
        for r in reqs:
            eng.submit(_clone(r))
        eng.step(); eng.step()
        held = {b for s in eng._seqs.values() for b in s.block_ids}
        before = eng.pool.used_blocks
        assert eng.preempt() == 2
        assert eng.active_requests == 0
        # exclusive blocks returned; only trie-retained prefix blocks
        # (ref held by the trie alone, so not "shared") may remain
        assert eng.pool.used_blocks < before
        # resume: re-acquired tables may differ, tokens must not
        out = {r.request_id: (r.tokens, r.finish_reason)
               for r in eng.run()}
        assert out == ref
        assert held  # sanity: the engine really was holding blocks

    def test_pool_pressure_preempts_victim_and_recovers(self, tiny):
        """An undersized pool forces mid-decode preemption of the most
        recently admitted request; everything still completes with the
        contiguous engine's exact tokens."""
        model, params = tiny
        reqs = [Request(i, [1 + i, 2, 3, 4, 5], max_new_tokens=8)
                for i in range(3)]
        ref = _run(InferenceEngine(model, params, max_slots=3,
                                   cache_dtype=jnp.float32), reqs)
        eng = PagedInferenceEngine(model, params, max_slots=3,
                                   block_size=4, num_blocks=7,
                                   cache_dtype=jnp.float32)
        for r in reqs:
            eng.submit(_clone(r))
        out = {r.request_id: (r.tokens, r.finish_reason)
               for r in eng.run(max_steps=500)}
        assert out == ref
        assert eng.metrics.requeued > 0          # pressure really hit


# -- the tick picks its tokens on the device --------------------------------------

PICKED = "serving_tokens_picked_on_device_total"
FETCHED = "serving_logit_rows_fetched_total"
ENGINES = {
    "ring": lambda model, params, **kw: InferenceEngine(
        model, params, max_slots=4, cache_dtype=jnp.float32, **kw),
    "paged": lambda model, params, **kw: PagedInferenceEngine(
        model, params, max_slots=4, block_size=4, cache_dtype=jnp.float32,
        **kw),
}


def _tick_of(engine):
    """The engine's jitted tick and abstract arguments to lower it on."""
    if hasattr(engine, "pool"):
        ints = jnp.zeros((engine.max_slots,), jnp.int32)
        return engine._decode_paged, (engine.params, ints, engine.pool.data,
                                      jnp.asarray(engine._tables), ints)
    ints = jnp.zeros((engine.cache.slots,), jnp.int32)
    return engine._decode, (engine.params, ints, engine.cache.data, ints)


def _metric_pattern(name):
    """The module-name pattern of one of the benchmark's metric files."""
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                        "metrics", name + ".json")
    with open(path) as f:
        return json.load(f)["args"]["pattern"]


class TestDevicePick:
    """A greedy row's token is the arg-max the tick's program took; the ids
    alone cross to the host, and a sampler's row is fetched for it alone."""

    @pytest.mark.parametrize("kind", list(ENGINES))
    def test_greedy_traffic_fetches_no_row(self, tiny, kind):
        engine = ENGINES[kind](*tiny)
        reqs = [Request(i, [1 + i, 2, 3, 4 + i], max_new_tokens=3 + i)
                for i in range(5)]              # five over four slots
        out = _run(engine, reqs)
        tokens = sum(len(toks) for toks, _ in out.values())
        assert tokens == sum(r.max_new_tokens for r in reqs)
        registry = engine.metrics.registry
        assert registry.get(FETCHED).value() == 0
        assert registry.get(PICKED).value() == tokens

    @pytest.mark.parametrize("kind", list(ENGINES))
    def test_mixed_batch_fetches_the_samplers_rows_alone(self, tiny, kind):
        from apex_tpu.observability import Tracer
        import json
        tracer = Tracer()
        engine = ENGINES[kind](*tiny, tracer=tracer)
        reqs = _mixed_requests()
        out = _run(engine, reqs)
        greedy = sum(len(out[r.request_id][0]) for r in reqs
                     if r.sampling.greedy)
        sampled = sum(len(out[r.request_id][0]) for r in reqs
                      if not r.sampling.greedy)
        assert greedy == 6 and sampled == 14
        registry = engine.metrics.registry
        assert registry.get(PICKED).value() == greedy
        assert registry.get(FETCHED).value() == sampled
        # the span says how many of a tick's rows came home: every sampled
        # token but each request's first, which its admission picked
        rows = [e["args"]["host_rows"]
                for e in json.loads(tracer.to_json())["traceEvents"]
                if e["ph"] == "X" and e["name"] == "serving.sample"]
        assert sum(rows) == sampled - 3 and max(rows) == 3

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("case", ["ties", "neg_inf", "nan"])
    def test_device_pick_is_numpys_argmax(self, dtype, case):
        """First maximum on exact ties, index 0 of a row of ``-inf``, the
        first NaN: the tick's pick and the admission's, both dtypes."""
        from apex_tpu.inference.engine import _picking, first_token_id
        rows = np.random.RandomState(0).randn(6, 40).astype(np.float32)
        rows = np.array(jnp.asarray(rows, dtype))       # rounded once
        if case == "ties":
            rows[0, [7, 3, 29]] = rows[0].max() + 1     # three-way tie
            rows[1, :] = 0.5                            # the whole row
            rows[2, [39, 38]] = 9.0
        elif case == "neg_inf":
            rows[0, :] = -np.inf
            rows[1, :17] = -np.inf
            rows[2, 5] = np.inf
        else:
            rows[0, 11] = np.nan
            rows[1, [30, 4]] = np.nan                   # the first of two
            rows[2, 0] = np.inf
            rows[2, 9] = np.nan                         # NaN beats +inf
        want = np.argmax(rows, axis=-1)
        logits, ids, state = jax.jit(_picking(lambda x, c: (x, c)))(
            jnp.asarray(rows), jnp.zeros(()))
        assert ids.dtype == jnp.int32 and ids.shape == (6,)
        np.testing.assert_array_equal(np.asarray(ids), want)
        np.testing.assert_array_equal(
            np.asarray(logits).view(np.uint8), rows.view(np.uint8))
        prefill = jnp.asarray(rows)[None]               # (1, rows, vocab)
        for position in range(6):
            got = first_token_id(prefill, position)
            assert got.dtype == jnp.int32 and got.shape == ()
            assert int(got) == want[position]

    def test_benchmarks_readers_find_the_programs(self, tiny):
        """What ``benchmarks/`` reads of the programs: the tick's module is
        named after ``decode_step_paged`` and the prefill's after
        ``prefill``, ``_prefill`` returns ``(logits, kv)`` with logits
        ``(1, bucket, vocab)``, the tick ``(logits, int32[slots], pool)``,
        and the first token's pick compiles once a bucket."""
        import re
        from apex_tpu.inference.engine import first_token_id
        model, params = tiny
        engine = ENGINES["paged"](model, params)
        for program, args, pattern in (
                (*_tick_of(engine), _metric_pattern("decode_time_share.tpot")),
                (engine._prefill, (params, jnp.zeros((1, 8), jnp.int32)),
                 _metric_pattern("prefill_time_share.ttft"))):
            name = re.search(r"module @(\S+)", program.lower(*args).as_text())
            assert re.search(pattern, name.group(1)), name.group(1)
        tick, args = _tick_of(ENGINES["ring"](model, params))
        assert "module @jit_decode_step " in tick.lower(*args).as_text()
        logits, kv = engine._prefill(params, jnp.zeros((1, 8), jnp.int32))
        assert logits.shape == (1, 8, model.cfg.vocab_size)
        tick, args = _tick_of(engine)
        logits, ids, pool = jax.eval_shape(tick, *args)
        assert logits.shape == (4, model.cfg.vocab_size)
        assert (ids.shape, ids.dtype) == ((4,), jnp.int32)
        assert pool.shape == engine.pool.data.shape
        # two prompt lengths of the bucket of 8: one program
        engine.submit(Request(0, [1, 2, 3, 4, 5], max_new_tokens=2))
        engine.run()
        compiled = first_token_id._cache_size()
        engine.submit(Request(1, [9, 8, 7, 6, 5, 4, 3], max_new_tokens=2))
        engine.submit(Request(2, [2, 2, 2, 2, 2, 2], max_new_tokens=2))
        assert {r.request_id for r in engine.run()} == {0, 1, 2}
        assert first_token_id._cache_size() == compiled

    @pytest.mark.parametrize("kind", list(ENGINES))
    def test_a_samplers_failure_in_a_tick_spares_the_greedy_rows(
            self, tiny, kind):
        """A sampling config that raises on the tick's row is quarantined
        there (``finish_reason == "error"``, what it had streamed kept)
        while the greedy rows of the same tick advance."""
        engine = ENGINES[kind](*tiny)
        reqs = [Request(0, [1, 2, 3], max_new_tokens=6),
                Request(1, [4, 5, 6, 7], max_new_tokens=6, seed=5,
                        sampling=SamplingParams(temperature=0.9, top_k=4)),
                Request(2, [8, 9], max_new_tokens=6)]
        want = _run(ENGINES[kind](*tiny), reqs)
        for r in reqs:
            engine.submit(_clone(r))
        engine.step()                    # three admissions and one tick
        (poisoned,) = [st.request for st in engine._active.values()
                       if st.request.request_id == 1]
        # passes SamplingParams' own check, detonates in the sampler
        poisoned.sampling = SamplingParams(temperature=0.9, top_k=2.5)
        fetched = engine.metrics.registry.get(FETCHED).value()
        engine.step()
        assert engine.metrics.registry.get(FETCHED).value() == fetched + 1
        assert sorted(st.request.request_id
                      for st in engine._active.values()) == [0, 2]
        assert all(len(st.generated) == 3
                   for st in engine._active.values())
        out = {r.request_id: r for r in engine.run()}
        assert out[1].finish_reason == "error" and out[1].error
        assert out[1].tokens == want[1][0][:2]
        for rid in (0, 2):
            assert (out[rid].tokens, out[rid].finish_reason) == want[rid]
        assert engine.metrics.summary()["errors"] == 1


# -- router ------------------------------------------------------------------

class _StubEngine:
    """Router-surface stub: queue/active/metrics without device work."""

    def __init__(self, depth=0, active=0, slo=None, max_queue=None):
        self._q = depth
        self._a = active
        self.metrics = ServingMetrics(slo=slo)
        self.max_queue = max_queue
        self.submitted = []

    @property
    def queue_depth(self):
        return self._q

    @property
    def active_requests(self):
        return self._a

    def submit(self, request):
        from apex_tpu.inference.engine import QueueFull
        if self.max_queue is not None and self._q >= self.max_queue:
            raise QueueFull("full")
        self.submitted.append(request)
        self._q += 1


class TestRouter:
    def test_places_least_loaded(self):
        a, b = _StubEngine(depth=3, active=2), _StubEngine(depth=0,
                                                           active=1)
        r = Router([a, b], max_queue_depth=8)
        assert r.submit(Request(0, [1, 2])) == 1
        assert b.submitted and not a.submitted

    def test_sheds_when_all_queues_deep(self):
        r = Router([_StubEngine(depth=8), _StubEngine(depth=9)],
                   max_queue_depth=8)
        with pytest.raises(RequestShed):
            r.submit(Request(0, [1, 2]))
        assert r.shed_requests == 1

    def test_burn_rate_sheds_backlogged_replica(self):
        t = [0.0]

        def clock():
            t[0] += 0.01
            return t[0]

        def burning(depth):
            slo = SLOMonitor([SLOTarget("ttft", 0.1, objective=0.9)],
                             clock=clock)
            for _ in range(50):
                slo.observe("ttft", 5.0)         # every event bad
            return _StubEngine(depth=depth, slo=slo)

        # burn = 1.0 / (1 - 0.9) = 10x on both replicas
        r = Router([burning(1), burning(2)], max_queue_depth=8,
                   burn_threshold=5.0, burn_window_s=60.0)
        with pytest.raises(RequestShed):
            r.submit(Request(0, [1, 2]))
        # an IDLE burning replica still accepts (stale burn, empty queue)
        r2 = Router([burning(0)], max_queue_depth=8, burn_threshold=5.0)
        assert r2.submit(Request(1, [1, 2])) == 0

    def test_queue_full_falls_through_to_next_replica(self):
        a = _StubEngine(depth=0, max_queue=0)    # accepts then raises
        b = _StubEngine(depth=5)
        r = Router([a, b], max_queue_depth=8)
        assert r.submit(Request(0, [1, 2])) == 1

    def test_end_to_end_multi_replica_drain(self, tiny):
        model, params = tiny
        reps = [PagedInferenceEngine(model, params, max_slots=2,
                                     block_size=4,
                                     cache_dtype=jnp.float32)
                for _ in range(2)]
        router = Router(reps, max_queue_depth=8)
        reqs = [Request(i, [1 + i % 3, 2, 3], max_new_tokens=3)
                for i in range(6)]
        for r in reqs:
            router.submit(_clone(r))
        out = router.run()
        assert sorted(r.request_id for r in out) == list(range(6))
        ref = _run(InferenceEngine(model, params, max_slots=2,
                                   cache_dtype=jnp.float32), reqs)
        assert {r.request_id: (r.tokens, r.finish_reason)
                for r in out} == ref

    def test_validation(self):
        with pytest.raises(ValueError):
            Router([])
        with pytest.raises(ValueError):
            Router([_StubEngine()], max_queue_depth=0)


# -- loadgen (importable surface) --------------------------------------------

class TestLoadgen:
    def test_overload_run_sheds_and_reports(self):
        import importlib
        import os
        import sys
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        try:
            loadgen = importlib.import_module("loadgen")
        finally:
            sys.path.pop(0)
        import argparse
        ns = argparse.Namespace(
            requests=12, rate=1e9, overload=True, replicas=2,
            max_slots=2, max_queue=64, max_queue_depth=2,
            burn_threshold=14.4, burn_window_s=60.0, ttft_slo_s=0.5,
            block_size=4, chunked=False, token_budget=32, seed=0,
            min_prompt=4, pareto_shape=2.5, max_new=3,
            shared_prefix_prob=0.5, shared_prefix_len=8,
            num_prefixes=2, vocab=32, hidden=16, layers=2, heads=2,
            max_seq=32)
        report = loadgen.run_loadgen(ns)
        assert report["shed"] > 0                # shedding engaged
        assert report["served"] == 12 - report["shed"]
        assert report["served"] > 0
        assert report["ttft_p99_s"] >= report["ttft_p50_s"] >= 0.0
        assert 0.0 <= report["prefix_hit_rate"] <= 1.0


# -- the loop names its phases (serving.* spans) ------------------------------

ADMIT_SPANS = ("serving.admit.request", "serving.admit.prefill",
               "serving.admit.kv_write", "serving.admit.first_token")
PHASES = ("serving.decode.grow", "serving.decode.inputs",
          "serving.decode.launch")      # the dispatch's three, in order
TICK_SPANS = ("serving.step", "serving.evict", "serving.admit",
              "serving.decode.dispatch", "serving.decode.wait",
              "serving.sample") + PHASES
STALLED = "serving.decode.stalled"     # only where a reply was in flight
CHILDREN = {   # span -> the span that must contain it on the thread
    "serving.evict": "serving.step", "serving.admit": "serving.step",
    "serving.admit.request": "serving.admit",
    "serving.admit.prefill": "serving.admit.request",
    "serving.admit.kv_write": "serving.admit.request",
    "serving.admit.first_token": "serving.admit.request",
    "serving.prefill_chunk": "serving.step",
    "serving.decode.dispatch": "serving.step",
    "serving.decode.grow": "serving.decode.dispatch",
    "serving.decode.inputs": "serving.decode.dispatch",
    "serving.decode.launch": "serving.decode.dispatch",
    STALLED: "serving.admit.request",
    "serving.decode.wait": "serving.step", "serving.sample": "serving.step"}


def _profiled_spans(tmp_path, engine, reqs):
    """Run ``reqs`` under the profiler; returns (tokens, the thread's
    ``serving.*`` events as (name, start, end, stats))."""
    import glob
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = _run(engine, reqs)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    threads = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("serving.")]
                if evs:
                    threads.append(evs)
    (events,) = threads            # one thread drives the engine
    return out, events


def _assert_nested(events):
    """Every span lies inside a span of its parent's name."""
    for name, s, e, _ in events:
        parent = CHILDREN.get(name)
        if parent is not None:
            assert any(n == parent and ps <= s and e <= pe
                       for n, ps, pe, _ in events), name


class TestServingSpans:
    def _engine(self, tiny, **kw):
        model, params = tiny
        return PagedInferenceEngine(model, params, max_slots=4,
                                    block_size=4, cache_dtype=jnp.float32,
                                    **kw)

    def test_profiler_sees_every_span_nested_with_request_ids(
            self, tiny, tmp_path):
        reqs = _mixed_requests()
        ref = _run(self._engine(tiny), reqs)
        out, events = _profiled_spans(tmp_path, self._engine(tiny), reqs)
        assert out == ref               # tokens are what they were
        names = {n for n, _, _, _ in events}
        assert names == set(TICK_SPANS + ADMIT_SPANS) | {STALLED}
        _assert_nested(events)
        admitted = [st for n, _, _, st in events
                    if n == "serving.admit.request"]
        assert all(float(st["queue_wait_ms"]) >= 0 for st in admitted)
        assert sorted(int(st["request_id"]) for st in admitted) \
            == [0, 1, 2, 3]
        for st, req in zip(sorted(admitted,
                                  key=lambda st: int(st["request_id"])),
                           reqs):
            assert int(st["prompt_len"]) == len(req.prompt)
            assert "shared_tokens" in st
        counts = [int(st["admitted"]) for n, _, _, st in events
                  if n == "serving.admit"]
        assert sum(counts) == 4
        batches = [int(st["batch"]) for n, _, _, st in events
                   if n == "serving.decode.dispatch"]
        assert batches and max(batches) <= 4
        ticks = sum(n == "serving.step" for n, _, _, _ in events)
        # nine spans a tick, five an admission: none per token or row
        assert len(events) <= 9 * ticks + 5 * len(reqs)

    def test_kv_write_span_says_what_it_wrote(self, tiny, tmp_path):
        """``serving.admit.kv_write`` carries the bucket the prompt was
        padded to and the blocks really written: the prompt's own at
        blocks of 4, less the prefix it found cached, never the garbage
        entries that fill the bucket."""
        reqs = _mixed_requests()
        _, events = _profiled_spans(tmp_path, self._engine(tiny), reqs)
        wrote, want, shared = {}, {}, 0
        for n, _, _, st in events:
            if n == "serving.admit.request":
                plen = int(st["prompt_len"])
                want[plen] = (max(8, 1 << (plen - 1).bit_length()),
                              -(-plen // 4) - int(st["shared_tokens"]) // 4)
                shared += int(st["shared_tokens"])
            elif n == "serving.admit.kv_write":
                wrote[plen] = (int(st["bucket"]), int(st["blocks"]))
        assert wrote == want and len(wrote) == len(reqs)
        assert shared                   # one prompt found a block cached

    def test_chunked_prefill_opens_prefill_chunk_spans(self, tiny, tmp_path):
        reqs = _mixed_requests()
        kw = dict(chunked_prefill=True, scheduler=TickScheduler(
            token_budget=8, min_chunk=2, max_chunk=4))
        ref = _run(self._engine(tiny, **kw), reqs)
        out, events = _profiled_spans(tmp_path, self._engine(tiny, **kw),
                                      reqs)
        assert out == ref
        _assert_nested(events)
        chunks = [st for n, _, _, st in events
                  if n == "serving.prefill_chunk"]
        assert {int(st["request_id"]) for st in chunks} == {0, 1, 2, 3}
        assert all(1 <= int(st["tokens"]) <= 4 for st in chunks)
        # a chunked admission does no device work of its own
        assert not {n for n, _, _, _ in events} & set(ADMIT_SPANS[1:])

    def test_speculative_round_opens_the_three_decode_spans(
            self, tiny, tmp_path):
        model, params = tiny
        spec = SpeculativeConfig(model, params, num_tokens=2)
        reqs = _mixed_requests()
        ref = _run(self._engine(tiny, speculative=spec), reqs)
        out, events = _profiled_spans(
            tmp_path, self._engine(tiny, speculative=spec), reqs)
        assert out == ref
        _assert_nested(events)
        per = {n: sum(m == n for m, _, _, _ in events) for n in TICK_SPANS}
        assert per["serving.decode.dispatch"] == per["serving.decode.wait"] \
            == per["serving.sample"] > 0

    def test_contiguous_engine_opens_the_same_spans(self, tiny, tmp_path):
        model, params = tiny
        reqs = _mixed_requests()
        make = lambda: InferenceEngine(model, params, max_slots=4,  # noqa
                                       cache_dtype=jnp.float32)
        ref = _run(make(), reqs)
        out, events = _profiled_spans(tmp_path, make(), reqs)
        assert out == ref
        # the ring has nothing to grow
        assert {n for n, _, _, _ in events} == set(
            TICK_SPANS + ADMIT_SPANS) - {"serving.decode.grow"} | {STALLED}
        _assert_nested(events)

    def test_no_profiler_no_tracer_records_nothing(self, tiny, monkeypatch):
        """With neither a profiler session nor a ``tracer=`` a run grows
        no tracing list: no Tracer exists to record into, and a span is
        the profiler's own annotation object."""
        from apex_tpu.observability import spans

        def boom(*a, **k):
            raise AssertionError("a Tracer was built or written to")
        monkeypatch.setattr(spans.Tracer, "__init__", boom)
        monkeypatch.setattr(spans.Tracer, "_record", boom)
        engine = self._engine(tiny)
        assert engine.trace.tracer is None
        reqs = _mixed_requests()
        out = _run(engine, reqs)
        assert set(out) == {0, 1, 2, 3}
        with engine._span("serving.step") as sp:
            assert type(sp) is jax.profiler.TraceAnnotation

    def test_tracer_gets_the_same_spans_as_chrome_events(self, tiny):
        import json
        from apex_tpu.observability import Tracer
        reqs = _mixed_requests()
        ref = _run(self._engine(tiny), reqs)
        tracer = Tracer()
        out = _run(self._engine(tiny, tracer=tracer), reqs)
        assert out == ref
        doc = json.loads(tracer.to_json())
        host = [e for e in doc["traceEvents"]
                if e["ph"] == "X" and e["name"].startswith("serving.")]
        assert {e["name"] for e in host} == set(
            TICK_SPANS + ADMIT_SPANS) | {STALLED}
        events = [(e["name"], e["ts"], e["ts"] + e["dur"],
                   e.get("args", {})) for e in host]
        _assert_nested(events)
        assert sorted(a["request_id"] for n, _, _, a in events
                      if n == "serving.admit.request") == [0, 1, 2, 3]
        # depth follows the nesting on the thread
        depth = {}
        for n, _, _, a in events:
            depth.setdefault(n, set()).add(a.get("depth", 1))
        assert depth["serving.step"] == {1}
        assert depth["serving.admit"] == {2}
        assert depth["serving.admit.request"] == {3}
        assert depth[STALLED] == {4}
        # the first admission found the engine idle, the others a reply
        assert depth["serving.admit.kv_write"] == {4, 5}
        assert depth["serving.decode.launch"] == {3}
        # the per-request rows of RequestTracer share the file
        assert any(e["ph"] == "b" and e.get("cat") == "request"
                   for e in doc["traceEvents"])

    def _maker(self, tiny, kind):
        """``tracer -> engine`` of one kind, four slots each."""
        model, params = tiny
        if kind == "contiguous":
            return lambda tr: InferenceEngine(
                model, params, max_slots=4, cache_dtype=jnp.float32,
                tracer=tr)
        kw = {"speculative": SpeculativeConfig(model, params, num_tokens=2)} \
            if kind == "speculative" else {}
        return lambda tr: self._engine(tiny, tracer=tr, **kw)

    @staticmethod
    def _counted(make_engine):
        """An engine whose Tracer's clock counts its own calls, so that a
        span's edges say how many clock calls apart two things were;
        returns it with a reader of its ``serving.*`` events so far as
        ``(name, start, end, args)`` in opening order."""
        import itertools
        from apex_tpu.observability import Tracer
        calls = itertools.count()
        tracer = Tracer(clock=lambda: float(next(calls)))
        engine = make_engine(tracer)

        def events():
            return sorted(
                ((e["name"], round(e["ts"] / 1e6),
                  round((e["ts"] + e["dur"]) / 1e6), e.get("args", {}))
                 for e in tracer.events
                 if e["ph"] == "X" and e["name"].startswith("serving.")),
                key=lambda ev: ev[1])
        return engine, events

    @pytest.mark.parametrize("kind", ["paged", "speculative", "contiguous"])
    def test_dispatch_is_tiled_by_its_phases(self, tiny, kind):
        """In every tick ``serving.decode.grow``, ``.inputs`` and
        ``.launch`` lie inside ``serving.decode.dispatch`` in that order
        and cover it but for one clock call at each edge (the ring has
        nothing to grow; a speculative round's proposals, between grow and
        inputs, open no span and read no clock)."""
        make = self._maker(tiny, kind)
        engine, events = self._counted(make)
        reqs = _mixed_requests()
        assert _run(engine, reqs) == _run(make(None), reqs)
        evs = events()
        ticks = [ev for ev in evs if ev[0] == "serving.decode.dispatch"]
        assert ticks
        want = PHASES[1:] if kind == "contiguous" else PHASES
        for _, s, e, _ in ticks:
            inside = [ev for ev in evs if s < ev[1] and ev[2] < e]
            assert tuple(ev[0] for ev in inside) == want
            assert inside[0][1] == s + 1 and inside[-1][2] == e - 1
            gaps = [b[1] - a[2] for a, b in zip(inside, inside[1:])]
            assert gaps == [1] * (len(want) - 1)
            if kind != "contiguous":
                assert inside[0][3]["preempted"] == 0

    @pytest.mark.parametrize("kind", ["paged", "contiguous"])
    def test_stalled_opens_for_admissions_that_find_a_reply_in_flight(
            self, tiny, kind):
        """``serving.decode.stalled`` says whose admission held up how
        many decoding sequences: over the admission's own interval, never
        on an idle engine."""
        engine, events = self._counted(self._maker(tiny, kind))
        reqs = _mixed_requests() + [Request(4, [7, 8], max_new_tokens=2)]
        engine.submit(_clone(reqs[0]))
        engine.step()                   # admitted on an idle engine
        engine.submit(_clone(reqs[1]))
        engine.step()                   # finds request 0 decoding
        engine.submit(_clone(reqs[2]))
        engine.submit(_clone(reqs[3]))
        engine.step()                   # 2 finds two, 3 finds three
        assert len(engine.run()) == 4
        engine.submit(_clone(reqs[4]))  # idle again
        assert len(engine.run()) == 5
        evs = events()
        stalled = [ev for ev in evs if ev[0] == STALLED]
        assert [(a["request_id"], a["sequences"])
                for _, _, _, a in stalled] == [(1, 1), (2, 2), (3, 3)]
        admits = {a["request_id"]: (s, e, a) for n, s, e, a in evs
                  if n == "serving.admit.request"}
        assert sorted(admits) == [0, 1, 2, 3, 4]
        assert all(a["queue_wait_ms"] >= 0 for _, _, a in admits.values())
        for _, s, e, a in stalled:
            rs, re_, _ = admits[a["request_id"]]
            # the admission's interval but for a clock call at each edge
            assert (s, e) == (rs + 1, re_ - 1)
            # the wait for the prefill is inside it
            assert any(n == "serving.admit.first_token" and s < fs and fe < e
                       for n, fs, fe, _ in evs)

    def test_a_slot_still_prefilling_is_not_stalled(self, tiny):
        """Under chunked prefill a slot that has not finished its prompt
        is not decoding: an admission beside it holds up nobody."""
        make = lambda tr: self._engine(  # noqa: E731
            tiny, tracer=tr, chunked_prefill=True, scheduler=TickScheduler(
                token_budget=8, min_chunk=2, max_chunk=4))
        engine, events = self._counted(make)
        reqs = _mixed_requests()
        for r in reqs[:2]:
            engine.submit(_clone(r))
        engine.step()                   # both admitted, both prefilling
        assert not [ev for ev in events() if ev[0] == STALLED]
        while engine._prefilling:
            engine.step()
        engine.submit(_clone(reqs[2]))  # now two replies are in flight
        assert len(engine.run()) == 3
        assert [(a["request_id"], a["sequences"]) for n, _, _, a in events()
                if n == STALLED] == [(2, 2)]

    def test_decode_dispatch_counts_the_live_blocks(self, tiny):
        """``live_blocks`` on every ``serving.decode.dispatch`` is what
        the tick's kernel walks: over the rows whose table holds a
        request, ``ceil((position + 1) / block_size)``, counted here
        from the arrays the device program was handed."""
        import json
        from apex_tpu.observability import Tracer
        tracer = Tracer()
        engine = self._engine(tiny, tracer=tracer)
        bs = engine.pool.block_size
        program, handed = engine._decode_paged, []

        def spy(params, tokens, pool, tables, positions):
            live = np.asarray(tables)[:, 0] != 0
            handed.append((int(live.sum()), int(np.sum(
                np.asarray(positions)[live] // bs + 1))))
            return program(params, tokens, pool, tables, positions)

        engine._decode_paged = spy
        out = _run(engine, _mixed_requests())
        assert set(out) == {0, 1, 2, 3}
        ticks = [e["args"] for e in json.loads(tracer.to_json())["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "serving.decode.dispatch"]
        assert ticks and all("live_blocks" in a for a in ticks)
        assert [(a["batch"], a["live_blocks"]) for a in ticks
                if a["batch"]] == handed
        # rows grow past a block's edge during the run
        assert max(blocks for _, blocks in handed) > 4
