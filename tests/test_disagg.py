"""apex_tpu.serving.disagg: disaggregated prefill/decode serving with
a quantized paged KV cache.

The subsystem's correctness contract:

* ``export_kv()``/``adopt_kv()`` move a request between engines WITH
  its KV blocks, and the resumed stream is TOKEN-BITWISE the
  uninterrupted single-engine run — greedy and seeded sampling, f32
  and int8 storage alike (paged attention only ever gathers block
  storage, and the payload is a literal copy of it);
* the int8 scale-per-block cache stays within a pinned numeric
  tolerance of the f32 cache and agrees with it greedily on the CI
  configs; round-trip error is bounded by half a quantization step;
* prefix-shared blocks survive quantization: published trie blocks are
  never requantized (COW copies scales), so sharers decode bitwise;
* the DisaggregatedFleet serves token-bitwise vs a single-pool
  reference — including a prefill replica killed mid-handoff (death
  migration re-prefills the parked work) and a lost channel transfer
  (re-prefill fallback on the decode pool) — with an exactly-once
  response ledger and int8 handoffs under 0.3x the f32 bytes;
* the per-pool capacity controller sizes prefill vs decode on
  TTFT-burn vs TPOT-burn and never flaps (``audit() == []``);
* the degradation ladder acts on the DECODE pool's burn in a
  disaggregated fleet, not fleet-wide occupancy.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import QueueFull, Request, SamplingParams
from apex_tpu.models.gpt import GPTConfig, GPTModel
from apex_tpu.observability import FleetCollector, Tracer
from apex_tpu.observability.slo import SLOMonitor, SLOTarget
from apex_tpu.ops.flash_attention import (dequantize_kv_blocks,
                                          gather_paged_kv_quant,
                                          quantize_kv_blocks)
from apex_tpu.resilience import Fault, FaultInjector, PoolCapacityController
from apex_tpu.serving import (DegradationLadder, DisaggregatedFleet,
                              KvChannel, PagedInferenceEngine,
                              PagedKVCache, QuantizedPagedKVCache,
                              ServingFault, ServingFaultInjector,
                              VirtualClock)
from apex_tpu.utils.profiling import ServingMetrics

# int8 scale-per-block decode must stay this close to the f32 cache on
# the CI config (measured worst |dlogits| is ~5e-4; 10x margin)
QUANT_LOGITS_TOL = 5e-3


def tiny_cfg(**kw):
    base = dict(vocab_size=32, hidden_size=16, num_layers=2,
                num_attention_heads=2, max_seq_len=32)
    base.update(kw)
    return GPTConfig(**base)


@pytest.fixture(scope="module")
def tiny():
    model = GPTModel(tiny_cfg())
    return model, model.init_params(jax.random.PRNGKey(0))


def _clone(req: Request) -> Request:
    return dataclasses.replace(req)


def _mixed_requests():
    return [
        Request(0, [1, 2, 3, 4, 5], max_new_tokens=6),
        Request(1, [1, 2, 3, 9], max_new_tokens=5, seed=7,
                sampling=SamplingParams(temperature=0.8, top_k=5)),
        Request(2, [1, 2, 3, 4, 5, 6, 7], max_new_tokens=4, seed=3,
                sampling=SamplingParams(temperature=1.1, top_p=0.9)),
        Request(3, [4, 4, 4], max_new_tokens=5, seed=11),
    ]


def _engine(model, params, clock, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("chunked_prefill", True)
    return PagedInferenceEngine(model, params, max_slots=4, block_size=4,
                                metrics=ServingMetrics(clock),
                                clock=clock, **kw)


def _drain(engine, clock, dt=0.01):
    while engine.step():
        clock.advance(dt)
    return {r.request_id: (r.tokens, r.finish_reason)
            for r in engine.completed}


def _reference(model, params, reqs, **kw):
    clock = VirtualClock()
    ref = _engine(model, params, clock, **kw)
    for r in reqs:
        ref.submit(_clone(r))
    return _drain(ref, clock)


def _prefill_all(pf, clock, n, dt=0.01):
    """Step a prefill_only engine until n handoffs are parked.

    (``step()`` keeps returning True while parked slots occupy
    ``_active`` — termination is the handoff count, not idleness.)
    """
    for _ in range(200):
        if len(pf.handoffs_ready()) >= n:
            return pf.handoffs_ready()
        pf.step()
        clock.advance(dt)
    raise AssertionError("prefill never parked %d handoffs" % n)


def _disagg(model, params, *, n_prefill=2, n_decode=2, quant=None,
            **fleet_kw):
    clock = VirtualClock()
    pf = [_engine(model, params, clock, kv_quant=quant,
                  prefill_only=True) for _ in range(n_prefill)]
    dc = [_engine(model, params, clock, kv_quant=quant)
          for _ in range(n_decode)]
    fleet = DisaggregatedFleet(pf, dc, clock=clock, **fleet_kw)
    return fleet, clock


def _run_fleet(fleet, clock, max_steps=400, dt=0.01):
    for _ in range(max_steps):
        busy = fleet.step()
        clock.advance(dt)
        if not busy and fleet.pending == 0:
            break
    return {r.request_id: (r.tokens, r.finish_reason)
            for r in fleet.completed}


# -- quantized cache ---------------------------------------------------------

class TestQuantizedCache:
    def test_round_trip_error_bound(self):
        """|x - dequant(quant(x))| <= scale/2 = amax/254 per
        (block, layer, k/v, head) group — the textbook symmetric-int8
        bound, asserted exactly."""
        rng = np.random.RandomState(0)
        blocks = jnp.asarray(rng.randn(5, 2, 2, 8, 3, 16) * 3.0,
                             jnp.float32)
        q8, scales = quantize_kv_blocks(blocks)
        deq = dequantize_kv_blocks(q8, scales)
        err = jnp.abs(deq - blocks)
        bound = scales[..., None, :, None] * 0.5 + 1e-7
        assert bool(jnp.all(err <= bound))
        amax = jnp.max(jnp.abs(blocks), axis=(-3, -1))
        np.testing.assert_allclose(np.asarray(scales),
                                   np.asarray(amax) / 127.0, rtol=1e-6)

    def test_all_zero_block_is_exact(self):
        q8, scales = quantize_kv_blocks(jnp.zeros((2, 1, 2, 4, 2, 8)))
        assert bool(jnp.all(scales == 1.0))      # never divide by zero
        assert bool(jnp.all(dequantize_kv_blocks(q8, scales) == 0.0))

    def test_pool_compression_and_zero_on_alloc(self):
        f32 = PagedKVCache(8, 4, layers=2, kv_heads=2, head_dim=16,
                           dtype=jnp.float32)
        q = QuantizedPagedKVCache(8, 4, layers=2, kv_heads=2,
                                  head_dim=16, dtype=jnp.float32)
        assert q.kind == "paged_int8" and f32.kind == "paged"
        # int8 data + f32 scale per (layer, k/v, head): well under 0.3x
        assert q.block_bytes < 0.3 * f32.block_bytes
        # zero-on-alloc: a reused block comes back clean
        q.data = q.data.at[:].set(7)
        q.scales = q.scales.at[:].set(9.0)
        seq = q.acquire([1, 2, 3, 4, 5])
        for bid in seq.block_ids:
            assert bool(jnp.all(q.data[bid] == 0))
            assert bool(jnp.all(q.scales[bid] == 1.0))

    def test_export_import_blocks_bitwise(self):
        src = QuantizedPagedKVCache(8, 4, layers=2, kv_heads=2,
                                    head_dim=8)
        dst = QuantizedPagedKVCache(8, 4, layers=2, kv_heads=2,
                                    head_dim=8)
        rng = np.random.RandomState(1)
        src.data = jnp.asarray(rng.randint(-127, 128, src.data.shape),
                               jnp.int8)
        src.scales = jnp.asarray(rng.rand(*src.scales.shape),
                                 jnp.float32)
        payload = src.export_blocks([2, 5])
        dst.import_blocks([1, 3], payload)
        assert bool(jnp.all(dst.data[1] == src.data[2]))
        assert bool(jnp.all(dst.data[3] == src.data[5]))
        assert bool(jnp.all(dst.scales[1] == src.scales[2]))
        # a payload round-trips through host bytes unchanged
        assert payload["data"].dtype == np.int8

    @pytest.mark.parametrize("n", [1, 3, 5])
    def test_import_of_any_number_of_blocks(self, n):
        """A handoff of ``n`` blocks is stored through a power of two of
        them (the last repeated): the ``n`` arrive bitwise, scales too,
        and no other block of the pool moves."""
        rng = np.random.RandomState(n)
        src = QuantizedPagedKVCache(8, 4, layers=2, kv_heads=2, head_dim=8)
        dst = QuantizedPagedKVCache(8, 4, layers=2, kv_heads=2, head_dim=8)
        for pool in (src, dst):
            pool.data = jnp.asarray(
                rng.randint(-127, 128, pool.data.shape), jnp.int8)
            pool.scales = jnp.asarray(rng.rand(*pool.scales.shape),
                                      jnp.float32)
        before = np.asarray(dst.data), np.asarray(dst.scales)
        ids_src, ids_dst = [2, 5, 7, 1, 6][:n], [1, 3, 4, 6, 7][:n]
        dst.import_blocks(ids_dst, src.export_blocks(ids_src))
        np.testing.assert_array_equal(np.asarray(dst.data)[ids_dst],
                                      np.asarray(src.data)[ids_src])
        np.testing.assert_array_equal(np.asarray(dst.scales)[ids_dst],
                                      np.asarray(src.scales)[ids_src])
        rest = [i for i in range(8) if i not in ids_dst]
        np.testing.assert_array_equal(np.asarray(dst.data)[rest],
                                      before[0][rest])
        np.testing.assert_array_equal(np.asarray(dst.scales)[rest],
                                      before[1][rest])

    def test_copy_on_write_takes_the_scales_along(self):
        rng = np.random.RandomState(7)
        pool = QuantizedPagedKVCache(8, 4, layers=2, kv_heads=2, head_dim=8)
        a = pool.acquire([1, 2, 3, 4, 5])
        pool.data = jnp.asarray(
            rng.randint(-127, 128, pool.data.shape), jnp.int8)
        pool.scales = jnp.asarray(rng.rand(*pool.scales.shape), jnp.float32)
        b = pool.fork(a)
        shared = a.block_ids[-1]
        before = np.asarray(pool.data), np.asarray(pool.scales)
        new = pool.ensure_writable(b, len(b.block_ids) - 1)
        assert new != shared
        # zero-on-alloc ran first, then the copy: the shared block's rows
        np.testing.assert_array_equal(np.asarray(pool.data[new]),
                                      before[0][shared])
        np.testing.assert_array_equal(np.asarray(pool.scales[new]),
                                      before[1][shared])
        others = [i for i in range(8) if i != new]
        np.testing.assert_array_equal(np.asarray(pool.data)[others],
                                      before[0][others])
        np.testing.assert_array_equal(np.asarray(pool.scales)[others],
                                      before[1][others])

    def test_quant_insert_into_lane_dense_rows(self, tiny):
        """The int8 pool's rows are ``heads * head_dim`` wide and its
        scales stay per head: a token inserted by the decode path comes
        back within half a quantization step, in its own layer, K apart
        from V, and the block's other layer is not touched."""
        model, _ = tiny
        attn = model.layers[0].attention
        pool = QuantizedPagedKVCache(8, 4, layers=2, kv_heads=2,
                                     head_dim=8, dtype=jnp.float32)
        assert pool.data.shape == (8, 2, 2, 4, 16)
        assert pool.scales.shape == (8, 2, 2, 2)
        rng = np.random.RandomState(4)
        k = jnp.asarray(rng.randn(2, 2, 8) * 2.0, jnp.float32)
        v = jnp.asarray(rng.randn(2, 2, 8) * 2.0, jnp.float32)
        bids, offs = jnp.asarray([3, 6]), jnp.asarray([1, 2])
        data, scales = attn._quant_insert(pool.data, pool.scales, 1,
                                          bids, offs, k, v)
        assert data.shape == pool.data.shape and data.dtype == jnp.int8
        for kv_i, want in enumerate((k, v)):
            got = gather_paged_kv_quant(data, scales, 1, kv_i,
                                        bids[:, None])
            for row in range(2):
                step = scales[bids[row], 1, kv_i][:, None]     # (h, 1)
                err = jnp.abs(got[row, offs[row]] - want[row])
                assert bool(jnp.all(err <= step * 0.5 + 1e-7))
        assert bool(jnp.all(data[:, 0] == 0))
        assert bool(jnp.all(scales[:, 0] == 1.0))

    def test_write_context_kv_quantizes_per_head(self):
        rng = np.random.RandomState(5)
        pool = QuantizedPagedKVCache(8, 4, layers=2, kv_heads=2,
                                     head_dim=8, dtype=jnp.float32)
        seq = pool.acquire(list(range(1, 11)))
        kv = jnp.asarray(rng.randn(2, 2, 12, 2, 8), jnp.float32)
        kv = kv.at[:, :, :, 1].multiply(50.0)     # a hot head
        pool.write_context_kv(seq, kv, 10)
        tbl = jnp.asarray(pool.table_row(seq, 3)[None])
        for li in range(2):
            for kv_i in range(2):
                got = gather_paged_kv_quant(pool.data, pool.scales, li,
                                            kv_i, tbl)[0, :10]
                err = jnp.abs(got - kv[li, kv_i, :10])
                # the cold head keeps its own, fifty times finer, step
                assert float(err[:, 0].max()) < 0.02
                assert float(err[:, 1].max()) < 1.0

    def test_quant_requires_chunked_prefill_and_no_spec(self, tiny):
        model, params = tiny
        clock = VirtualClock()
        with pytest.raises(ValueError, match="chunked"):
            _engine(model, params, clock, kv_quant="int8",
                    chunked_prefill=False)
        with pytest.raises(ValueError, match="kv_quant"):
            PagedInferenceEngine(model, params, kv_quant="fp4")


# -- quantized decode quality ------------------------------------------------

class TestQuantDecodeQuality:
    @pytest.mark.slow
    def test_logits_within_pinned_tolerance(self, tiny):
        """The quantized chunk path's logits vs the f32 paged path,
        token-position by token-position, within QUANT_LOGITS_TOL."""
        model, params = tiny
        rng = np.random.RandomState(0)
        toks = rng.randint(1, 32, (1, 16)).astype(np.int32)
        bs = 4
        f = PagedKVCache(16, bs, layers=2, kv_heads=2, head_dim=8,
                         dtype=jnp.float32)
        q = QuantizedPagedKVCache(16, bs, layers=2, kv_heads=2,
                                  head_dim=8, dtype=jnp.float32)
        sf, sq = f.acquire(list(toks[0])), q.acquire(list(toks[0]))
        pos = np.arange(16, dtype=np.int32)[None]
        wo = (np.arange(16, dtype=np.int32) % bs)[None]
        wb_f = np.asarray([sf.block_ids[p // bs] for p in range(16)],
                          np.int32)[None]
        wb_q = np.asarray([sq.block_ids[p // bs] for p in range(16)],
                          np.int32)[None]
        lf, _ = model.decode_chunk(
            params, jnp.asarray(toks), f.data,
            jnp.asarray(f.table_row(sf, 8)[None]), jnp.asarray(pos),
            jnp.asarray(wb_f), jnp.asarray(wo))
        lq, _, _ = model.decode_chunk_quant(
            params, jnp.asarray(toks), q.data, q.scales,
            jnp.asarray(q.table_row(sq, 8)[None]), jnp.asarray(pos),
            jnp.asarray(wb_q), jnp.asarray(wo))
        err = float(jnp.max(jnp.abs(lf.astype(jnp.float32)
                                    - lq.astype(jnp.float32))))
        assert err <= QUANT_LOGITS_TOL

    def test_greedy_agreement_vs_f32_engine(self, tiny):
        """Greedy streams from the int8 engine match the f32 engine on
        the CI config (the acceptance gate for quantized serving)."""
        model, params = tiny
        reqs = [Request(i, [1 + i, 2, 3 + i, 4], max_new_tokens=6)
                for i in range(4)]
        want = _reference(model, params, reqs)
        got = _reference(model, params, reqs, kv_quant="int8")
        assert got == want

    def test_quant_stream_is_deterministic(self, tiny):
        """Same workload, two independent int8 engines: identical
        streams (zero-on-alloc makes requantization reproducible
        across allocation histories)."""
        model, params = tiny
        reqs = _mixed_requests()
        a = _reference(model, params, reqs, kv_quant="int8")
        b = _reference(model, params, reqs, kv_quant="int8")
        assert a == b


# -- engine handoff primitives -----------------------------------------------

class TestHandoffPrimitives:
    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_export_adopt_kv_resumes_bitwise(self, tiny, quant):
        """Prefill on a prefill_only engine, ship KV, decode elsewhere:
        bitwise the single-engine streams, greedy and seeded."""
        model, params = tiny
        reqs = _mixed_requests()
        want = _reference(model, params, reqs, kv_quant=quant)
        clock = VirtualClock()
        pf = _engine(model, params, clock, kv_quant=quant,
                     prefill_only=True)
        dc = _engine(model, params, clock, kv_quant=quant)
        for r in reqs:
            pf.submit(_clone(r))
        ready = _prefill_all(pf, clock, len(reqs))
        assert len(ready) == len(reqs)
        for _slot, rid in ready:
            handoff = pf.export_kv(rid)
            assert handoff.kv_len == len(handoff.kv_tokens)
            dc.adopt_kv(handoff)
        assert pf.handoffs_ready() == [] and pf.active_requests == 0
        got = _drain(dc, clock)
        assert got == want

    def test_export_kv_validation(self, tiny):
        model, params = tiny
        clock = VirtualClock()
        pf = _engine(model, params, clock, prefill_only=True)
        with pytest.raises(KeyError):
            pf.export_kv("nope")
        # mid-prefill: KV incomplete, must re-prefill instead
        pf.submit(Request(0, list(range(1, 21)), max_new_tokens=2))
        pf.step()           # first chunk only (token budget)
        if 0 in pf._prefilling:
            with pytest.raises(ValueError, match="mid-prefill"):
                pf.export_kv(0)

    def test_adopt_kv_rejects_mismatches(self, tiny):
        model, params = tiny
        clock = VirtualClock()
        pf = _engine(model, params, clock, prefill_only=True)
        pf.submit(Request(0, [1, 2, 3, 4, 5], max_new_tokens=4))
        _prefill_all(pf, clock, 1)
        handoff = pf.export_kv(0)
        # kind mismatch: bf16->int8 install is not bitwise-possible
        quant = _engine(model, params, clock, kv_quant="int8")
        with pytest.raises(ValueError, match="kind"):
            quant.adopt_kv(handoff)
        # block geometry mismatch
        other = PagedInferenceEngine(
            model, params, max_slots=2, block_size=8,
            metrics=ServingMetrics(clock), clock=clock,
            chunked_prefill=True, cache_dtype=jnp.float32)
        with pytest.raises(ValueError, match="block_size"):
            other.adopt_kv(handoff)
        # the handoff is still installable where the tags match
        dc = _engine(model, params, clock)
        dc.adopt_kv(handoff)
        assert dc.active_requests == 1

    def test_adopt_kv_queuefull_when_no_slot(self, tiny):
        model, params = tiny
        clock = VirtualClock()
        pf = _engine(model, params, clock, prefill_only=True)
        for i in range(3):
            pf.submit(Request(i, [1 + i, 2, 3], max_new_tokens=3))
        _prefill_all(pf, clock, 3)
        dc = PagedInferenceEngine(
            model, params, max_slots=2, block_size=4,
            metrics=ServingMetrics(clock), clock=clock,
            chunked_prefill=True, cache_dtype=jnp.float32)
        handoffs = [pf.export_kv(rid) for _, rid in pf.handoffs_ready()]
        dc.adopt_kv(handoffs[0])
        dc.adopt_kv(handoffs[1])
        with pytest.raises(QueueFull):
            dc.adopt_kv(handoffs[2])
        # the handoff is host state — still installable after a drain
        _drain(dc, clock)
        dc.adopt_kv(handoffs[2])
        got = _drain(dc, clock)
        assert 2 in got

    def test_prefix_shared_blocks_survive_quantization(self, tiny):
        """Two requests sharing a block-aligned prefix on an int8 pool:
        the trie shares quantized blocks (never requantized once
        published) and both streams match the unshared runs."""
        model, params = tiny
        prefix = [5, 6, 7, 8]                    # exactly one block
        reqs = [Request(0, prefix + [1, 2], max_new_tokens=4),
                Request(1, prefix + [3], max_new_tokens=4)]
        want = _reference(model, params, reqs, kv_quant="int8")
        clock = VirtualClock()
        pf = _engine(model, params, clock, kv_quant="int8",
                     prefill_only=True)
        dc = _engine(model, params, clock, kv_quant="int8")
        # sequential: request 0's published prefix is live in the trie
        # (on BOTH pools) when request 1 arrives
        for n, r in enumerate(reqs):
            pf.submit(_clone(r))
            _prefill_all(pf, clock, n + 1)
        for _slot, rid in pf.handoffs_ready():
            dc.adopt_kv(pf.export_kv(rid))
        assert dc.pool.prefix_hit_tokens >= len(prefix)  # shared install
        got = _drain(dc, clock)
        assert got == want


# -- the disaggregated fleet -------------------------------------------------

class TestDisaggregatedFleet:
    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_fleet_matches_single_pool_reference(self, tiny, quant):
        model, params = tiny
        reqs = _mixed_requests()
        want = _reference(model, params, reqs, kv_quant=quant)
        fleet, clock = _disagg(model, params, quant=quant)
        for r in reqs:
            fleet.submit(_clone(r))
        got = _run_fleet(fleet, clock)
        assert got == want
        assert fleet.pending == 0
        assert fleet.handoffs == len(reqs) and fleet.fallbacks == 0
        assert fleet.duplicate_responses == 0

    def test_pool_validation(self, tiny):
        model, params = tiny
        clock = VirtualClock()
        ordinary = _engine(model, params, clock)
        parked = _engine(model, params, clock, prefill_only=True)
        with pytest.raises(ValueError, match="prefill_only"):
            DisaggregatedFleet([ordinary], [ordinary], clock=clock)
        with pytest.raises(ValueError, match="decode-pool"):
            DisaggregatedFleet([parked], [parked], clock=clock)

    def test_prefill_replica_killed_mid_handoff(self, tiny):
        """Kill a prefill replica while it still holds parked and
        mid-prefill work: death migration re-prefills on the peer, the
        handoff ships from there, and every stream is bitwise the
        single-pool run — exactly once."""
        model, params = tiny
        reqs = _mixed_requests()
        want = _reference(model, params, reqs)
        inj = ServingFaultInjector([
            ServingFault(2, 0, "replica_crash", duration=10 ** 6)])
        fleet, clock = _disagg(model, params, prefill_injector=inj,
                               prefill_kw=dict(suspect_after=1,
                                               dead_after=2),
                               handoff_retry_ticks=4)
        for r in reqs:
            fleet.submit(_clone(r))
        got = _run_fleet(fleet, clock)
        assert got == want
        assert fleet.pending == 0 and fleet.duplicate_responses == 0
        assert inj.log       # the crash actually fired
        # nothing was answered twice, nothing lost
        assert sorted(got) == sorted(r.request_id for r in reqs)

    def test_lost_handoff_falls_back_to_reprefill(self, tiny):
        """Exhaust the channel's retries on the first transfer: the
        request re-prefills on the decode pool — slower, still
        bitwise, never lost."""
        model, params = tiny
        reqs = _mixed_requests()
        want = _reference(model, params, reqs)
        ch = KvChannel(fault_injector=FaultInjector(
            [Fault(step=s, kind="dcn_fault") for s in range(1, 40)]),
            max_retries=0)
        fleet, clock = _disagg(model, params, channel=ch)
        for r in reqs:
            fleet.submit(_clone(r))
        got = _run_fleet(fleet, clock)
        assert got == want
        assert fleet.fallbacks >= 1
        assert fleet.fallbacks + fleet.handoffs == len(reqs)
        assert ch.lost_handoffs == fleet.fallbacks

    def test_int8_handoff_bytes_under_030x_f32(self, tiny):
        """The series the CI leg gates: int8 handoffs ship < 0.3x the
        f32 bytes for the same workload."""
        model, params = tiny
        reqs = _mixed_requests()
        sizes = {}
        for quant in (None, "int8"):
            fleet, clock = _disagg(model, params, quant=quant)
            for r in reqs:
                fleet.submit(_clone(r))
            _run_fleet(fleet, clock)
            assert fleet.handoffs == len(reqs)
            sizes[quant] = fleet.channel.handoff_bytes
        assert sizes["int8"] < 0.30 * sizes[None]

    def test_flow_chain_stitches_across_pools(self, tiny):
        """One Perfetto arrow chain per request: prefill hop →
        kv_handoff → decode hop, continuity-checked over the merged
        timeline."""
        from apex_tpu.observability import FlightRecorder

        model, params = tiny
        clock = VirtualClock()
        tracers = {"p0": Tracer(clock=clock, id_tag="p0"),
                   "d0": Tracer(clock=clock, id_tag="d0"),
                   "router": Tracer(clock=clock, id_tag="router")}
        pf = [_engine(model, params, clock, prefill_only=True,
                      tracer=tracers["p0"])]
        dc = [_engine(model, params, clock, tracer=tracers["d0"])]
        fleet = DisaggregatedFleet(pf, dc, clock=clock,
                                   tracer=tracers["router"],
                                   recorder=FlightRecorder(clock=clock))
        for r in _mixed_requests():
            fleet.submit(_clone(r))
        _run_fleet(fleet, clock)
        fc = FleetCollector()
        for name, tr in tracers.items():
            fc.add_replica(name, tracer=tr)
        cont = fc.continuity()
        assert not cont["broken"] and not cont["orphans"]
        assert len(cont["complete"]) == 4
        for tid, chain in cont["chains"].items():
            assert "kv_handoff" in chain["phases"]
            # the chain spans both pools
            assert {"p0", "d0"} <= set(chain["replicas"])


# -- per-pool capacity -------------------------------------------------------

def _slo_engine(model, params, clock, **kw):
    slo = SLOMonitor(
        [SLOTarget("ttft", 0.5, objective=0.9),
         SLOTarget("token_latency", 0.5, objective=0.9)], clock=clock)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("chunked_prefill", True)
    return PagedInferenceEngine(model, params, max_slots=4, block_size=4,
                                metrics=ServingMetrics(clock, slo=slo),
                                clock=clock, **kw)


class TestPoolCapacity:
    def _stack(self, tiny, n_prefill=3, n_decode=2, **ctl_kw):
        model, params = tiny
        clock = VirtualClock()
        pf = [_slo_engine(model, params, clock, prefill_only=True)
              for _ in range(n_prefill)]
        dc = [_slo_engine(model, params, clock)
              for _ in range(n_decode)]
        fleet = DisaggregatedFleet(pf, dc, clock=clock)
        ctl_kw.setdefault("burn_high", 2.0)
        ctl_kw.setdefault("burn_low", 0.5)
        ctl_kw.setdefault("confirm_ticks", 2)
        ctl_kw.setdefault("cooldown_s", 1.0)
        ctl = PoolCapacityController(
            {"prefill": fleet.prefill, "decode": fleet.decode},
            lambda pool: _slo_engine(model, params, clock,
                                     prefill_only=(pool == "prefill")),
            clock=clock, **ctl_kw)
        return fleet, ctl, clock

    def test_manual_shift_two_phase_and_audit_clean(self, tiny):
        fleet, ctl, clock = self._stack(tiny)
        assert ctl.split == {"prefill": 3, "decode": 2}
        ctl.request_shift("to_decode")
        for _ in range(60):
            fleet.step()
            ctl.tick()
            clock.advance(0.05)
            if ctl.stats["shifts"] == 1 and not ctl.shifting:
                break
        assert ctl.split == {"prefill": 2, "decode": 3}
        assert ctl.audit() == []
        # the reshaped fleet still serves, with handoffs intact
        for r in _mixed_requests():
            fleet.submit(_clone(r))
        got = _run_fleet(fleet, clock)
        assert len(got) == 4 and fleet.pending == 0

    def test_burn_driven_shift_requires_confirmation(self, tiny):
        """One hot tick must not move a chip; confirm_ticks of
        sustained TPOT burn (with a calm donor) must."""
        fleet, ctl, clock = self._stack(tiny)
        dec = [e for _, e in fleet.decode._live()]
        # one hot tick: below confirm_ticks, no shift
        for e in dec:
            for _ in range(20):
                e.metrics.slo.observe("token_latency", 5.0)
        ctl.tick()
        assert ctl.stats["shifts"] == 0 and not ctl.shifting
        # sustained burn: the controller commits exactly one shift
        for _ in range(30):
            for e in dec:
                for _ in range(5):
                    e.metrics.slo.observe("token_latency", 5.0)
            fleet.step()
            ctl.tick()
            clock.advance(0.05)
            if ctl.stats["shifts"] == 1 and not ctl.shifting:
                break
        assert ctl.stats["shifts"] == 1
        assert ctl.split == {"prefill": 2, "decode": 3}
        assert ctl.audit() == []

    def test_shifts_never_flap(self, tiny):
        """A long oscillating-burn run: every committed shift started
        outside the hysteresis band and after cooldown —
        ``audit() == []`` — and the min-replica floor holds."""
        fleet, ctl, clock = self._stack(tiny, cooldown_s=0.5)
        rng = np.random.RandomState(0)
        for t in range(120):
            hot = (t // 20) % 2 == 0             # flips every 20 ticks
            pool = fleet.decode if hot else fleet.prefill
            metric = "token_latency" if hot else "ttft"
            for _, e in pool._live():
                for _ in range(4):
                    e.metrics.slo.observe(
                        metric, 5.0 + float(rng.rand()))
            fleet.step()
            ctl.tick()
            clock.advance(0.05)
        assert ctl.audit() == []
        split = ctl.split
        assert split["prefill"] >= 1 and split["decode"] >= 1
        assert split["prefill"] + split["decode"] == 5

    def test_floor_blocks_donation(self, tiny):
        fleet, ctl, clock = self._stack(tiny, n_prefill=1, n_decode=1,
                                        min_replicas=1)
        ctl.request_shift("to_decode")
        for _ in range(10):
            fleet.step()
            ctl.tick()
            clock.advance(0.05)
        # the only prefill replica is the floor: nothing moved
        assert ctl.split == {"prefill": 1, "decode": 1}
        assert ctl.stats["shifts"] == 0

    def test_validation(self, tiny):
        model, params = tiny
        clock = VirtualClock()
        fleet, ctl, _ = self._stack(tiny)
        with pytest.raises(ValueError):
            PoolCapacityController(
                {"prefill": fleet.prefill}, lambda p: None, clock=clock)
        with pytest.raises(ValueError):
            PoolCapacityController(
                {"a": fleet.prefill, "b": fleet.decode},
                lambda p: None, burn_high=1.0, burn_low=2.0, clock=clock)
        with pytest.raises(ValueError, match="to_"):
            ctl.request_shift("decode")


# -- the ladder's per-pool burn source ---------------------------------------

class TestLadderBurnSource:
    def test_ladder_follows_decode_pool_not_fleet_max(self, tiny):
        """Prefill pool burning TTFT alone must NOT trip the ladder
        (its L2 actions flush the DECODE cache); decode-pool TPOT burn
        must."""
        model, params = tiny
        clock = VirtualClock()
        pf = [_slo_engine(model, params, clock, prefill_only=True)]
        dc = [_slo_engine(model, params, clock)]
        ladder = DegradationLadder(thresholds=(1.0, 2.0, 4.0),
                                   step_down_s=0.5)
        fleet = DisaggregatedFleet(pf, dc, clock=clock, ladder=ladder)
        assert ladder.burn_source is not None    # auto-wired to decode
        # prefill-pool burn only: ladder stays at 0
        for _ in range(40):
            pf[0].metrics.slo.observe("ttft", 5.0)
        fleet.step()
        assert ladder.level == 0
        # decode-pool burn: ladder escalates
        for _ in range(40):
            dc[0].metrics.slo.observe("token_latency", 5.0)
        fleet.step()
        assert ladder.level > 0

    def test_explicit_burn_source_wins(self, tiny):
        model, params = tiny
        clock = VirtualClock()
        ladder = DegradationLadder(thresholds=(1.0, 2.0, 4.0),
                                   burn_source=lambda: 100.0)
        pf = [_slo_engine(model, params, clock, prefill_only=True)]
        dc = [_slo_engine(model, params, clock)]
        DisaggregatedFleet(pf, dc, clock=clock, ladder=ladder)
        assert ladder.burn_source() == 100.0     # not overwritten
